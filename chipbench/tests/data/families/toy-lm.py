"""A family for the CPU tests that declares a kernel of its own layers: the
embedding table's gradient, one [vocab, hidden] call per FedAvg local step.
The program has no token family, so this one builds no problem."""
from __future__ import annotations


def kernel_calls(config: dict, traffic: dict, cells: int) -> list:
    rounds = int(traffic["rounds"])
    m = config["method"]
    b1 = max(1, int(round(m["local_fraction"] * rounds)))
    return [("toy_embed_grad", cells, config["vocab"], config["hidden"],
             b1 * m["local"]["local_steps"])]
