"""Plain reference of the mnist-logreg model: L2 logistic regression.

    loss(w; X, y) = mean_j [max(z_j, 0) - z_j y_j + log(1 + exp(-|z_j|))]
                    + (l2 / 2) ||w||^2,          z = X w

Labels are the even/odd bit of the digit class. Parameters are one flat
[d] vector, initialised at zero (FedChain, App. I.1).
"""
from __future__ import annotations

import jax.numpy as jnp


def labels(classes):
    """Binary targets from digit classes: odd -> 1, even -> 0."""
    return (classes % 2).astype(jnp.float32)


def arrays(data: dict, dtype):
    """(inputs [N, n, d], targets [N, n]) in the reference's precision."""
    return (data["features"].astype(dtype),
            labels(data["classes"]).astype(dtype))


def init(config: dict, key, dtype):
    del key
    return jnp.zeros((config["dim"],), dtype)


def loss(w, X, y, l2):
    z = X @ w
    per = jnp.maximum(z, 0.0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
    return jnp.mean(per) + 0.5 * l2 * jnp.sum(w * w)


def batch(config: dict) -> int:
    return int(config["oracle_batch"])
