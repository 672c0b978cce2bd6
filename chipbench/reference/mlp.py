"""Plain reference of the mnist-2nn model: an MLP with ReLU.

    h_0 = x,  h_{l+1} = relu(h_l W_l + b_l)  (no ReLU after the last layer)
    loss(params; X, y) = -mean_j log softmax(h_L)_{j, y_j}
                         + (l2 / 2) sum over every leaf of ||leaf||^2

Parameters are a dict {w0, b0, w1, b1, ...}; weights start N(0, 1/fan_in)
and biases at zero, drawn from the seed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def labels(classes):
    return classes.astype(jnp.int32)


def arrays(data: dict, dtype):
    """(inputs [N, n, d] in the reference's precision, integer classes
    [N, n])."""
    return data["features"].astype(dtype), labels(data["classes"])


def init(config: dict, key, dtype):
    arch = config["arch"]
    keys = jax.random.split(key, len(arch) - 1)
    params = {}
    for i, (a, b) in enumerate(zip(arch[:-1], arch[1:])):
        params[f"w{i}"] = (jax.random.normal(keys[i], (a, b), jnp.float32)
                           * (1.0 / a) ** 0.5).astype(dtype)
        params[f"b{i}"] = jnp.zeros((b,), dtype)
    return params


def forward(params, X):
    n = len(params) // 2
    h = X
    for i in range(n):
        h = h @ params[f"w{i}"] + params[f"b{i}"]
        if i < n - 1:
            h = jnp.maximum(h, 0.0)
    return h


def loss(params, X, y, l2):
    logits = forward(params, X)
    shifted = logits - jnp.max(logits, axis=-1, keepdims=True)
    logp = shifted - jnp.log(jnp.sum(jnp.exp(shifted), axis=-1,
                                     keepdims=True))
    nll = -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))
    reg = 0.5 * l2 * sum(jnp.sum(p * p) for p in jax.tree.leaves(params))
    return nll + reg


def batch(config: dict) -> int:
    return int(config["batch"])
