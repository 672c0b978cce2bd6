"""Which Pallas kernels one grid call drives, at which shapes, how often.

Derived from the configuration and the traffic mix, following the engine's
round paths on a TPU (where every kernel runs under Mosaic):

* a QSGD downlink compresses the broadcast leaf by leaf as [1, d] rows;
* a QSGD uplink compresses every client's payload as [N, d] rows per leaf
  (the engine computes all N clients and masks the non-participants);
* with uplink error feedback the round aggregates, updates the residuals
  and applies the step in one ``aggregate_apply`` pass per leaf;
* without it FedAvg averages the reconstructions and SGD its gradients
  through ``weighted_mean_over_clients`` per leaf (SGD on a flat vector
  goes through ``chain_aggregate`` instead);
* the selection row runs no kernel.

Under ``vmap`` one kernel call covers every cell on the device.
"""
from __future__ import annotations


def leaf_widths(config: dict) -> list:
    if "arch" in config:
        arch = config["arch"]
        widths = {}
        for i, (a, b) in enumerate(zip(arch[:-1], arch[1:])):
            widths[f"w{i}"] = a * b
            widths[f"b{i}"] = b
        return [widths[k] for k in sorted(widths)]
    return [config["dim"]]


def per_call(config: dict, traffic: dict, cells: int) -> list:
    """[(kernel, cells, rows, width, calls)] for one grid call on one device
    holding ``cells`` cells."""
    n = config["num_clients"]
    m = config["method"]
    rounds = int(traffic["rounds"])
    b1 = max(1, int(round(m["local_fraction"] * rounds)))
    b2 = max(1, rounds - b1 - 1)
    widths = leaf_widths(config)
    flat = len(widths) == 1
    up, down = traffic["uplink"], traffic["downlink"]
    out = []
    for w in widths:
        if down["compressor"] == "qsgd":
            out.append(("qsgd_dequantize", cells, 1, w, b1 + b2))
        if up["compressor"] == "qsgd":
            out.append(("qsgd_dequantize", cells, n, w, b1 + b2))
        if up.get("error_feedback"):
            out.append(("aggregate_apply", cells, n, w, b1 + b2))
        else:
            out.append(("weighted_mean_over_clients", cells, n, w, b1))
            out.append(("chain_aggregate" if flat
                        else "weighted_mean_over_clients", cells, n, w, b2))
    return out
