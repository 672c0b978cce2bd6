"""mnist-2nn on the program: a vision-family ProblemSpec with the
configuration's layer widths, and the model's operation counts."""
from __future__ import annotations

import jax.numpy as jnp

from chipbench.reference import mlp as model


def build_problem(config: dict, data: dict, x0):
    """The program's problem: ``_vision_apply`` takes its depth from the
    params pytree, so any number of hidden layers runs unchanged."""
    from repro.data import spec as spec_lib

    consts = {k: jnp.asarray(0.0, jnp.float32) for k in spec_lib.CONST_KEYS}
    consts["mu"] = jnp.asarray(config["l2"], jnp.float32)
    consts["beta"] = jnp.asarray(10.0, jnp.float32)
    return spec_lib.ProblemSpec(
        family=spec_lib.FAMILY_VISION, num_clients=config["num_clients"],
        dim=int(config["params"]), batch=int(config["batch"]),
        arch=tuple(config["arch"]), name=config["name"],
        data=dict(features=data["features"],
                  labels=model.labels(data["classes"])),
        consts=consts, x0=x0,
        x_star={k: jnp.zeros_like(v) for k, v in x0.items()})


def forward_flops(config: dict) -> int:
    """Operations of one sample's forward pass: 2 in x out per layer."""
    arch = config["arch"]
    return 2 * sum(a * b for a, b in zip(arch[:-1], arch[1:]))


def grad_flops(config: dict) -> int:
    """One sample's gradient: forward, weight gradients (2 in x out per
    layer) and input gradients of every layer but the first, whose input
    is data."""
    arch = config["arch"]
    layers = list(zip(arch[:-1], arch[1:]))
    return (2 * forward_flops(config)
            + 2 * sum(a * b for a, b in layers[1:]))


def dtype(config: dict):
    return jnp.dtype(config["dtype"])
