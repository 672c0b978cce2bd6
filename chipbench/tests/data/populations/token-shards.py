"""A token population for the CPU tests.

``make(config, key)`` returns ``{"tokens": [N, n, seq + 1] int32}``: every
sequence starts at a random id and steps by 1 or 2 (mod ``vocab``), the
step drawn per token, so the next token depends on the last."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make(config: dict, key) -> dict:
    n, per = config["num_clients"], config["per_client"]
    seq, vocab = config["seq"], config["vocab"]
    k_start, k_step = jax.random.split(key)
    start = jax.random.randint(k_start, (n, per, 1), 0, vocab, jnp.int32)
    steps = jax.random.randint(k_step, (n, per, seq), 1, 3, jnp.int32)
    walk = jnp.concatenate([jnp.zeros_like(start), jnp.cumsum(steps, -1)],
                           axis=-1)
    return {"tokens": (start + walk) % vocab}
