"""A next-token model on a frozen base for the CPU tests: a frozen
embedding table and output projection, and a trained rank-r adapter on the
hidden state.

    h = embed[x],  h' = h + (h a) b,  logits = h' head
    loss(params; X, Y, frozen) = -mean log softmax(logits)[Y]
                                 + (l2 / 2) (||a||^2 + ||b||^2)

``frozen(config, key)`` makes {embed, head} in one jitted call on the
device, in the configuration's ``frozen_dtype``; ``init`` makes the adapter
{a, b}, a ~ N(0, 1/hidden) and b = 0 as LoRA starts it. Inputs and targets
are the population's token ids, shifted by one, and stay int32."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def arrays(data: dict, dtype):
    del dtype  # token ids are never cast
    tokens = data["tokens"]
    return tokens[..., :-1], tokens[..., 1:]


def frozen(config: dict, key):
    vocab, hidden = config["vocab"], config["hidden"]
    dtype = jnp.dtype(config["frozen_dtype"])

    @jax.jit
    def make(key):
        k_embed, k_head = jax.random.split(key)
        return {"embed": 0.1 * jax.random.normal(k_embed, (vocab, hidden),
                                                 dtype),
                "head": jax.random.normal(k_head, (hidden, vocab), dtype)
                / jnp.asarray(hidden ** 0.5, dtype)}

    return make(key)


def init(config: dict, key, dtype):
    hidden, rank = config["hidden"], config["rank"]
    return {"a": (jax.random.normal(key, (hidden, rank), jnp.float32)
                  / hidden ** 0.5).astype(dtype),
            "b": jnp.zeros((rank, hidden), dtype)}


def loss(params, X, y, l2, frozen):
    h = frozen["embed"][X]
    h = h + (h @ params["a"]) @ params["b"]
    logp = jax.nn.log_softmax(h @ frozen["head"], axis=-1)
    nll = -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))
    reg = 0.5 * l2 * sum(jnp.sum(p * p) for p in jax.tree.leaves(params))
    return nll + reg


def batch(config: dict) -> int:
    return int(config["batch"])
