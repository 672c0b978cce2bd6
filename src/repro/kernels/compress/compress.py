"""Pallas TPU kernels for uplink compression and masked aggregation.

``qsgd_dequantize`` is elementwise over [S, D] with a per-row norm and a
scalar level count; one pass streams [S, BLOCK_D] tiles through VMEM
(quantize and dequantize fused, so the int lattice never hits HBM).
``weighted_mean_over_clients`` is the masked-aggregate primitive: the whole
[S, 1] weight column is staged per grid step next to each [S, BLOCK_D] tile.
Operand layout and block sizing are ``aggregate.chain_aggregate``'s: [S]
columns, [D] rows and a levels scalar as [1, 1], so every block stays legal
for Mosaic under ``vmap``.

Both take runtime operands only — levels and weights are data, so comm
config changes never retrace a compiled caller.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.aggregate.aggregate import block_d_for, col, pad_cols


def _qsgd_kernel(lv_ref, n_ref, v_ref, u_ref, o_ref):
    lv = jnp.maximum(lv_ref[...].astype(jnp.float32), 1.0)  # [1, 1]
    safe = jnp.maximum(n_ref[...].astype(jnp.float32), 1e-30)  # [S, 1]
    v = v_ref[...].astype(jnp.float32)  # [S, BD]
    scaled = jnp.abs(v) / safe * lv
    lo = jnp.floor(scaled)
    q = lo + jnp.where(u_ref[...].astype(jnp.float32) < scaled - lo, 1.0, 0.0)
    o_ref[...] = (jnp.sign(v) * safe * (q / lv)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block_d"))
def qsgd_dequantize(v, u, norms, levels, *, interpret: bool = False,
                    block_d: int | None = None):
    """v, u: [S, D]; norms: [S]; levels: scalar array. Returns [S, D]."""
    s, d = v.shape
    bd = min(block_d or block_d_for(s, 3), d)
    pad = (-d) % bd  # padded zeros quantize to zero and are sliced off below
    v, u = pad_cols(v, pad), pad_cols(u, pad)
    dp = v.shape[1]
    rows = pl.BlockSpec((s, bd), lambda j: (0, j))

    out = pl.pallas_call(
        _qsgd_kernel,
        grid=(dp // bd,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda j: (0, 0)),  # levels: whole scalar
            pl.BlockSpec((s, 1), lambda j: (0, 0)),  # norms: whole column
            rows,  # v tile
            rows,  # u tile
        ],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct((s, dp), v.dtype),
        interpret=interpret,
        name="qsgd_dequantize",
    )(jnp.reshape(levels, (1, 1)), col(norms), v, u)
    return out[:, :d]


def _wmean_kernel(w_ref, t_ref, o_ref):
    w = w_ref[...].astype(jnp.float32)  # [S, 1]
    t = t_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.mean(w * t, axis=0, keepdims=True).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block_d"))
def weighted_mean_over_clients(t, w, *, interpret: bool = False,
                               block_d: int | None = None):
    """meanᵢ wᵢ·tᵢ over the leading axis of t: [S, D] × [S] → [D]."""
    s, d = t.shape
    bd = min(block_d or block_d_for(s, 1), d)
    pad = (-d) % bd
    t = pad_cols(t, pad)
    dp = t.shape[1]
    out = pl.pallas_call(
        _wmean_kernel,
        grid=(dp // bd,),
        in_specs=[
            pl.BlockSpec((s, 1), lambda j: (0, 0)),  # weights: whole column
            pl.BlockSpec((s, bd), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bd), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, dp), t.dtype),
        interpret=interpret,
        name="weighted_mean_over_clients",
    )(col(w), t)
    return out[0, :d]
