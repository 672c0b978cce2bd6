"""Pallas TPU kernel for the fused FedChain server aggregation.

    out = x − lr · ( Σ_i w_i·(g_i − c_i) + c )

One kernel pass fuses the client reduction, control-variate correction and
the server step — on TPU this keeps the [S, D] client buffers in HBM and
streams [S, BLOCK_D] tiles through VMEM exactly once (the XLA default would
materialize the [S, D] difference tensor).

Layout. Every operand is 2-D at the ``pallas_call`` boundary: [D] vectors
ride as [1, D] rows and [S] weights/masks as [S, 1] columns, so each
block's last two dims are either full array dims or a 128-multiple
``BLOCK_D`` — legal for Mosaic bare AND after ``vmap`` prepends a batch
axis (which it does for every sweep cell). The client reduction is a
broadcast multiply + ``sum`` over the sublane axis (Mosaic has no
[S, D] × [S] contraction), written identically in ``chain_aggregate`` and
``aggregate_apply`` so the two reduce in the same order.

Grid: (D // BLOCK_D,). ``block_d_for`` sizes BLOCK_D from the cohort S so
the double-buffered working set stays inside the scoped VMEM budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_D = 2048
LANES = 128
# bytes of double-buffered blocks a kernel may stage: half of v5e's 16 MiB
# default scoped VMEM, leaving the rest to in-kernel temporaries
VMEM_BLOCK_BUDGET = 8 * 1024 * 1024


def block_d_for(s: int, row_tiles: int, *, cap: int = BLOCK_D) -> int:
    """Largest power-of-two multiple of 128 lanes (≤ ``cap``) such that
    ``row_tiles`` f32 [S, BD] tiles plus two [1, BD] rows, double-buffered,
    fit ``VMEM_BLOCK_BUDGET``."""
    fit = VMEM_BLOCK_BUDGET // (2 * 4 * (row_tiles * s + 2))
    bd = LANES
    while bd * 2 <= min(cap, fit):
        bd *= 2
    return bd


def pad_cols(a, pad: int):
    """Zero-pad the last axis of a 2-D operand by ``pad`` columns."""
    return jnp.pad(a, ((0, 0), (0, pad))) if pad else a


def row(v):
    """[D] → [1, D] (the lane-major layout of a vector operand)."""
    return v.reshape(1, -1)


def col(v):
    """[S] → [S, 1] (per-client scalars as a sublane column)."""
    return v.reshape(-1, 1)


def _weighted_sum(w, t):
    """Σ_i w_i·t_i over the client (sublane) axis: [S, 1] × [S, BD] → [1, BD]."""
    return jnp.sum(w * t, axis=0, keepdims=True)


def _agg_kernel(w_ref, x_ref, g_ref, ci_ref, c_ref, o_ref, *, lr: float):
    g = g_ref[...].astype(jnp.float32)  # [S, BD]
    ci = ci_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)  # [S, 1]
    upd = _weighted_sum(w, g - ci) + c_ref[...].astype(jnp.float32)
    o_ref[...] = (x_ref[...].astype(jnp.float32) - lr * upd).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("lr", "interpret", "block_d"))
def chain_aggregate(x, g, c_i, c, weights, *, lr: float, interpret: bool = False,
                    block_d: int | None = None):
    """x: [D]; g, c_i: [S, D]; c: [D]; weights: [S]. Returns [D]."""
    d = x.shape[0]
    s = g.shape[0]
    bd = min(block_d or block_d_for(s, 3), d)
    pad = (-d) % bd
    x, c = pad_cols(row(x), pad), pad_cols(row(c), pad)
    g, c_i = pad_cols(g, pad), pad_cols(c_i, pad)
    dp = x.shape[1]
    vec = pl.BlockSpec((1, bd), lambda j: (0, j))
    rows = pl.BlockSpec((s, bd), lambda j: (0, j))

    out = pl.pallas_call(
        functools.partial(_agg_kernel, lr=lr),
        grid=(dp // bd,),
        in_specs=[
            pl.BlockSpec((s, 1), lambda j: (0, 0)),  # weights: whole column
            vec,  # x tile
            rows,  # g tile
            rows,  # c_i tile
            vec,  # c tile
        ],
        out_specs=vec,
        out_shape=jax.ShapeDtypeStruct((1, dp), x.dtype),
        interpret=interpret,
        name="chain_aggregate",
    )(col(weights), x, g, c_i, c)
    return out[0, :d]


def _agg_apply_kernel(w_ref, m_ref, x_ref, a_ref, di_ref, co_ref, rs_ref,
                      xo_ref, ro_ref):
    a = a_ref[...].astype(jnp.float32)  # [S, BD] wire rows
    w = w_ref[...].astype(jnp.float32)  # [S, 1]
    upd = _weighted_sum(w, a)
    xo_ref[...] = (x_ref[...].astype(jnp.float32) - upd).astype(xo_ref.dtype)
    m = m_ref[...].astype(jnp.float32)  # [S, 1]
    di = di_ref[...].astype(jnp.float32)
    co = co_ref[...].astype(jnp.float32)
    rs = rs_ref[...].astype(jnp.float32)
    ro_ref[...] = (m * (di - co) + (1.0 - m) * rs).astype(ro_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block_d"))
def aggregate_apply(x, agg_rows, comp, delta_in, res, m, w, *,
                    interpret: bool = False, block_d: int | None = None):
    """Fused aggregate + error-feedback + server apply over one round.

        x_new   = x − Σ_i w_i·a_i
        res_new = m·(Δ_in − C(Δ_in)) + (1 − m)·res

    x: [D]; agg_rows (wire rows a_i), comp (C(Δ_in)), delta_in (Δ_in), res:
    [S, D]; m (participation mask rows), w (step-folded aggregation
    weights): [S]. One pass streams the [S, D] client rows through VMEM —
    the per-block working set is 5 [S, BD] tiles (4 in, 1 out) + 2 [1, BD]
    rows, and XLA never materializes the masked residual/update
    intermediates in HBM. The weighted sum is ``chain_aggregate``'s, so
    the SGD comm round is bitwise identical fused vs unfused; the residual
    expression is ``uplink``'s, term for term. Returns
    ``(x_new [D], res_new [S, D])``.
    """
    d = x.shape[0]
    s = agg_rows.shape[0]
    bd = min(block_d or block_d_for(s, 5), d)
    pad = (-d) % bd
    x = pad_cols(row(x), pad)
    agg_rows, comp, delta_in, res = (
        pad_cols(t, pad) for t in (agg_rows, comp, delta_in, res))
    dp = x.shape[1]
    vec = pl.BlockSpec((1, bd), lambda j: (0, j))
    rows = pl.BlockSpec((s, bd), lambda j: (0, j))
    whole = pl.BlockSpec((s, 1), lambda j: (0, 0))

    x_new, res_new = pl.pallas_call(
        _agg_apply_kernel,
        grid=(dp // bd,),
        in_specs=[
            whole,  # w
            whole,  # m
            vec,  # x tile
            rows,  # agg_rows tile
            rows,  # delta_in tile
            rows,  # comp tile
            rows,  # res tile
        ],
        out_specs=(vec, rows),
        out_shape=(
            jax.ShapeDtypeStruct((1, dp), x.dtype),
            jax.ShapeDtypeStruct((s, dp), res.dtype),
        ),
        interpret=interpret,
        name="aggregate_apply",
    )(col(w), col(m), x, agg_rows, delta_in, comp, res)
    return x_new[0, :d], res_new[:, :d]


def _mean_kernel(t_ref, o_ref):
    o_ref[...] = jnp.mean(t_ref[...].astype(jnp.float32), axis=0,
                          keepdims=True).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block_d"))
def mean_over_clients(t, *, interpret: bool = False, block_d: int | None = None):
    """Mean over the leading client axis of a [C, ...] tensor."""
    c = t.shape[0]
    flat = t.reshape(c, -1)
    d = flat.shape[1]
    bd = min(block_d or block_d_for(c, 1), d)
    pad = (-d) % bd
    flat = pad_cols(flat, pad)
    dp = flat.shape[1]
    out = pl.pallas_call(
        _mean_kernel,
        grid=(dp // bd,),
        in_specs=[pl.BlockSpec((c, bd), lambda j: (0, j))],
        out_specs=pl.BlockSpec((1, bd), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, dp), t.dtype),
        interpret=interpret,
        name="mean_over_clients",
    )(flat)
    return out[0, :d].reshape(t.shape[1:])
