"""Model operations a grid cell requires, from the configuration's shapes.

Counted per grid call and cell, then divided by the rounds: what the
algorithm needs, not what the engine computes. Only the round's
participants count: the engine runs every client's local work and masks
the rest, and that masked work is not required. Nothing recomputed counts.

Per cell and call (N clients, n samples each, S participants a round, B the
minibatch of one query, R rounds = B1 FedAvg + 1 selection + B2 SGD):

* FedAvg round: S clients x local_steps x inner_batch queries, B gradient
  samples each.
* SGD round: S clients x K queries, B gradient samples each.
* Selection row: two candidates x N clients x selection_k queries, B
  forward samples each.
* UCB probe (every row, when the traffic has the policy): N queries of B
  forward samples.
* Evaluation: the global loss over the first ``eval_per_client`` samples
  of each of the N clients (all n where the configuration names none)
  after every row, and once more for the final suboptimality.
"""
from __future__ import annotations


def per_cell_round(config: dict, traffic: dict, family) -> float:
    n, per = config["num_clients"], config["per_client"]
    m = config["method"]
    rounds = int(traffic["rounds"])
    b1 = max(1, int(round(m["local_fraction"] * rounds)))
    b2 = max(1, rounds - b1 - 1)
    s = max(1, int(round(traffic["participation"] * n)))
    batch = config.get("oracle_batch", config.get("batch"))
    fwd, grad = family.forward_flops(config), family.grad_flops(config)
    local = m["local"]
    total = b1 * s * local["local_steps"] * local["inner_batch"] * batch * grad
    total += b2 * s * m["global"]["k"] * batch * grad
    total += 2 * n * m["selection_k"] * batch * fwd
    if traffic.get("policy"):
        total += (b1 + 1 + b2) * n * batch * fwd
    total += (b1 + 1 + b2 + 1) * n * config.get("eval_per_client", per) * fwd
    return total / rounds
