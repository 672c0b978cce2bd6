"""The ``mnist-shards`` client population, made on the device from a seed.

A configuration names its population with a ``population`` key; one that
names none runs on this one. ``make(config, key)`` returns
``{"features": [N, n, side^2] float32, "classes": [N, n] int32}``.

This is the benchmark's data, not the program's: a later change to the
program's data modules cannot change what the cells run on. It follows the
recipe of McMahan et al. 2017 (arXiv:1602.05629, section 3) for the MNIST
pathological non-IID split, with synthetic images in place of MNIST pixels:

* each class has a smooth prototype, a sum of 4 x 4 low-frequency sine
  modes with N(0, 1) weights, scaled as a whole to [0, 1];
* a sample is its class prototype plus N(0, noise^2) per pixel, clipped to
  [0, 1]; pixels are then centred on their population mean;
* samples are sorted by label, cut into ``clients * shards_per_client``
  equal shards, and each client is dealt ``shards_per_client`` shards at
  random (MNIST: 200 shards of 300, two per client, 100 clients x 600).

The whole population is one jitted call, so set-up never builds it on the
host or copies it over.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=(
    "num_clients", "per_client", "side", "classes", "shards_per_client",
    "noise"))
def make_population(key, *, num_clients: int, per_client: int, side: int,
                    classes: int, shards_per_client: int, noise: float):
    """Returns (features [N, n, side^2] float32, labels [N, n] int32)."""
    k_freq, k_noise, k_deal = jax.random.split(key, 3)
    d = side * side
    total = num_clients * per_client
    if total % classes or total % (num_clients * shards_per_client):
        raise ValueError("clients x samples must split evenly into classes "
                         "and shards")
    per_class = total // classes
    freq = jax.random.normal(k_freq, (classes, 4, 4), jnp.float32)
    xs = jnp.linspace(0.0, 1.0, side, dtype=jnp.float32)
    modes = jnp.sin(jnp.pi * jnp.arange(1, 5, dtype=jnp.float32)[:, None]
                    * xs[None, :])  # [4, side]
    protos = jnp.einsum("cij,ia,jb->cab", freq, modes, modes,
                        precision=jax.lax.Precision.HIGHEST)
    protos = (protos - protos.min()) / (protos.max() - protos.min() + 1e-9)
    eps = noise * jax.random.normal(k_noise, (classes, per_class, d),
                                    jnp.float32)
    pixels = jnp.clip(protos.reshape(classes, 1, d) + eps, 0.0, 1.0)
    pixels = pixels.reshape(total, d)
    pixels = pixels - jnp.mean(pixels, axis=0, keepdims=True)
    labels = jnp.repeat(jnp.arange(classes, dtype=jnp.int32), per_class)
    n_shards = num_clients * shards_per_client
    size = total // n_shards
    deal = jax.random.permutation(k_deal, n_shards).reshape(
        num_clients, shards_per_client)
    idx = (deal[:, :, None] * size
           + jnp.arange(size, dtype=jnp.int32)).reshape(num_clients, -1)
    return pixels[idx], labels[idx]


def make(config: dict, key) -> dict:
    """The population a configuration file describes."""
    features, classes = make_population(
        key, num_clients=config["num_clients"],
        per_client=config["per_client"], side=config["side"],
        classes=config["classes"],
        shards_per_client=config["shards_per_client"],
        noise=float(config["noise"]))
    return {"features": features, "classes": classes}
