"""Production mesh definitions (TPU v5e target).

Functions, not module-level constants, so importing this module never touches
jax device state (device count is locked at first jax init).

The sweep-grid meshes live in ``repro.dist.mesh``; these are the
model-parallel (data × model) meshes of the serving/training scaffold.
Every axis is ``AxisType.Auto``: operands are placed explicitly through
``shard_map`` / ``NamedSharding``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis ``Auto`` (jax defaults to
    ``Explicit``)."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def make_debug_mesh(data: int = 2, model: int = 2, *, pod: int = 0):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def data_shards(mesh) -> int:
    """Number of data-parallel shards (= MoE routing groups, FL client slots)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("pod", 1) * sizes.get("data", 1)


# Per-chip peaks keyed by ``device_kind`` as JAX reports it. Source: Google
# Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM,
# 1,600 Gbit/s ICI per chip = 4 links × 50 GB/s).
_V5E = {"peak_flops_bf16": 197e12, "hbm_bw": 819e9, "ici_link_bw": 50e9}
PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}
# the chip the production meshes above are sized for
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def hardware_constants(device_kind: str):
    """Roofline peaks of one chip of ``device_kind`` (FLOP/s, B/s, B/s per
    ICI link). A kind outside ``PEAKS`` is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
