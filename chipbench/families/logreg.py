"""mnist-logreg on the program: the logreg ProblemSpec over the benchmark's
population, and the model's operation counts."""
from __future__ import annotations

import jax.numpy as jnp

from chipbench.reference import logreg as model


def build_problem(config: dict, data: dict, x0):
    """The program's problem for this configuration (no host F* solve:
    timing does not need F*, so ``history`` holds the raw loss)."""
    from repro.data import spec as spec_lib

    del x0  # the paper starts logistic regression at zero, as the spec does
    return spec_lib.logreg_spec(
        None, features=data["features"],
        labels=model.labels(data["classes"]),
        l2=float(config["l2"]),
        oracle_batch_frac=config["oracle_batch"] / config["per_client"],
        solve_f_star=False, name=config["name"])


def forward_flops(config: dict) -> int:
    """Operations of one sample's forward pass: z = x . w."""
    return 2 * config["dim"]


def grad_flops(config: dict) -> int:
    """One sample's gradient: the forward pass and x (sigma(z) - y)."""
    return 4 * config["dim"]


def dtype(config: dict):
    return jnp.dtype(config["dtype"])
