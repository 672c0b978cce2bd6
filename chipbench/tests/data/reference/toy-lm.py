"""A next-token model for the CPU tests: an embedding table and an output
projection.

    logits = embed[x] @ head
    loss(params; X, Y) = -mean log softmax(logits)[Y]
                         + (l2 / 2) sum over every leaf of ||leaf||^2

Its inputs and targets are the population's token ids, shifted by one, and
stay int32 in every precision."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def arrays(data: dict, dtype):
    del dtype  # token ids are never cast
    tokens = data["tokens"]
    return tokens[..., :-1], tokens[..., 1:]


def init(config: dict, key, dtype):
    vocab, hidden = config["vocab"], config["hidden"]
    k_embed, k_head = jax.random.split(key)
    return {"embed": (0.1 * jax.random.normal(k_embed, (vocab, hidden),
                                              jnp.float32)).astype(dtype),
            "head": (jax.random.normal(k_head, (hidden, vocab), jnp.float32)
                     / hidden ** 0.5).astype(dtype)}


def loss(params, X, y, l2):
    logits = params["embed"][X] @ params["head"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))
    reg = 0.5 * l2 * sum(jnp.sum(p * p) for p in jax.tree.leaves(params))
    return nll + reg


def batch(config: dict) -> int:
    return int(config["batch"])
