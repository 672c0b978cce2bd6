"""Backend-dispatching wrappers for the aggregate kernel.

TPU: the Pallas kernel. CPU: interpret-mode Pallas when ``force_pallas`` or
``REPRO_FORCE_PALLAS=1`` (tests / kernel-path debugging), else the jnp
reference (XLA:CPU can't lower Mosaic).

These wrappers are the *fused aggregation path* exercised by the main
experiment loop: the flat-vector algorithms (``core.algorithms.sgd/saga/
ssnm/fedavg/scaffold/asg``) route their server updates here, so the
quadratic/theory benchmarks hit the same kernel entry points as
``benchmarks.kernels_bench``.
"""
from __future__ import annotations

import os

import jax

from repro.kernels.aggregate import ref
from repro.kernels.aggregate.aggregate import aggregate_apply as _fused_kernel
from repro.kernels.aggregate.aggregate import chain_aggregate as _kernel
from repro.kernels.aggregate.aggregate import mean_over_clients as _mean_kernel


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _force_pallas_env() -> bool:
    return os.environ.get("REPRO_FORCE_PALLAS", "0") not in ("0", "", "false")


def kernel_path(force_pallas: bool = False) -> str:
    """Which path the wrappers below take: ``"mosaic"`` (compiled Pallas on
    TPU), ``"interpret"`` (interpret-mode Pallas) or ``"ref"`` (jnp)."""
    if _on_tpu():
        return "mosaic"
    if force_pallas or _force_pallas_env():
        return "interpret"
    return "ref"


def chain_aggregate(x, g, c_i, c, weights=None, *, lr: float, force_pallas: bool = False):
    import jax.numpy as jnp

    if weights is None:
        weights = jnp.full((g.shape[0],), 1.0 / g.shape[0], jnp.float32)
    if _on_tpu():
        return _kernel(x, g, c_i, c, weights, lr=lr)
    if force_pallas or _force_pallas_env():
        return _kernel(x, g, c_i, c, weights, lr=lr, interpret=True)
    return ref.chain_aggregate_ref(x, g, c_i, c, lr=lr, weights=weights)


def mean_over_clients(t, *, force_pallas: bool = False):
    if _on_tpu():
        return _mean_kernel(t)
    if force_pallas or _force_pallas_env():
        return _mean_kernel(t, interpret=True)
    return ref.mean_over_clients_ref(t)


def use_fused_aggregate(force_pallas: bool = False) -> bool:
    """Whether comm rounds should take the fused aggregate-apply path —
    kernel backends only (TPU, or forced Pallas interpret mode). The jnp
    reference backend keeps the historical unfused sequence so default CPU
    runs stay bitwise unchanged."""
    return _on_tpu() or force_pallas or _force_pallas_env()


def aggregate_apply(x, agg_rows, comp, delta_in, res, m, w, *,
                    force_pallas: bool = False):
    """Fused aggregate + error-feedback + server apply; see
    ``aggregate.aggregate_apply`` for the math. Returns (x_new, res_new)."""
    if _on_tpu():
        return _fused_kernel(x, agg_rows, comp, delta_in, res, m, w)
    if force_pallas or _force_pallas_env():
        return _fused_kernel(x, agg_rows, comp, delta_in, res, m, w,
                             interpret=True)
    return ref.aggregate_apply_ref(x, agg_rows, comp, delta_in, res, m, w)
