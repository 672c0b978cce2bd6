"""A float population for the CPU tests: Gaussian blobs, one mean per
class, in a layout of its own.

``make(config, key)`` returns ``{"features": [N, n, dim] float32,
"classes": [N, n] int32}``; client i draws every sample's class from
{i mod C, (i + 1) mod C}, so the clients differ as the MNIST shards do."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make(config: dict, key) -> dict:
    n, per = config["num_clients"], config["per_client"]
    d, c = config["dim"], config["classes"]
    k_mean, k_class, k_noise = jax.random.split(key, 3)
    means = jax.random.normal(k_mean, (c, d), jnp.float32) / d ** 0.5
    pick = jax.random.bernoulli(k_class, 0.5, (n, per)).astype(jnp.int32)
    classes = (jnp.arange(n, dtype=jnp.int32)[:, None] + pick) % c
    noise = jax.random.normal(k_noise, (n, per, d), jnp.float32)
    return {"features": means[classes] + 0.5 * noise / d ** 0.5,
            "classes": classes}
