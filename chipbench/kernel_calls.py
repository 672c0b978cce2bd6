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
* the selection row runs no kernel;
* the kernels a model's own layers call are the family's to declare, in
  an optional ``kernel_calls(config, traffic, cells)`` of
  ``families/<family>.py`` returning tuples as ``per_call`` does.

Under ``vmap`` one kernel call covers every cell on the device.
"""
from __future__ import annotations

import math

from chipbench.harness import load_module


def leaf_widths(config: dict) -> list:
    """Sizes of the parameter leaves, in ``jax.tree`` flattening order (the
    order in which the engine compresses them), from the shapes of the
    reference's ``init``."""
    import jax
    import jax.numpy as jnp

    model = load_module("reference", config["family"])
    shapes = jax.eval_shape(lambda k: model.init(config, k, jnp.float32),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    return [math.prod(leaf.shape) for leaf in jax.tree.leaves(shapes)]


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
    declared = getattr(load_module("families", config["family"]),
                       "kernel_calls", None)
    if declared is not None:
        out.extend(declared(config, traffic, cells))
    return out
