"""Kernel micro-benchmarks: the Pallas kernels through their ``ops``
dispatchers (compiled by Mosaic on TPU, interpret mode elsewhere) vs the
jnp reference.

Each kernel row is named by the path that actually ran
(``kernels/<kernel>/<mosaic|interpret>``); derived = max |Δ| vs the
reference, computed at ``highest`` matmul precision. Wall numbers name the
backend they came from: interpret-mode times say nothing about the chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timed
from repro.kernels.aggregate import ops as agg_ops
from repro.kernels.aggregate.ref import (aggregate_apply_ref,
                                         chain_aggregate_ref)
from repro.kernels.flash_attention import ops as attn_ops
from repro.kernels.flash_attention.ref import attention_ref


def _ref(fn):
    with jax.default_matmul_precision("highest"):
        return timed(fn)


def main(quick: bool = True):
    rows = []
    key = jax.random.PRNGKey(0)
    path = agg_ops.kernel_path(force_pallas=True)

    # aggregate
    s, d = 8, 1 << 16
    k_x, k_g, k_ci, k_c = jax.random.split(key, 4)
    x = jax.random.normal(k_x, (d,))
    g = jax.random.normal(k_g, (s, d))
    ci = jax.random.normal(k_ci, (s, d))
    c = jax.random.normal(k_c, (d,))
    w = jnp.full((s,), 1.0 / s)
    ref, us_ref = _ref(lambda: chain_aggregate_ref(x, g, ci, c, lr=0.1, weights=w))
    out, us_k = timed(lambda: agg_ops.chain_aggregate(
        x, g, ci, c, w, lr=0.1, force_pallas=True))
    err = float(jnp.max(jnp.abs(out - ref)))
    rows.append(emit("kernels/chain_aggregate/ref", us_ref, f"d={d}"))
    rows.append(emit(f"kernels/chain_aggregate/{path}", us_k, f"err={err:.1e}"))

    # fused aggregate-apply (EF round: masked weighted mean + residual
    # update + server step in one pass)
    keys = jax.random.split(key, 6)
    agg = jax.random.normal(keys[0], (s, d))
    delta_in = jax.random.normal(keys[1], (s, d))
    comp = jax.random.normal(keys[2], (s, d))
    res = jax.random.normal(keys[3], (s, d))
    m = (jax.random.uniform(keys[4], (s,)) < 0.5).astype(jnp.float32)
    wf = jax.random.uniform(keys[5], (s,)) / s
    ref_xr, us_ref3 = _ref(
        lambda: aggregate_apply_ref(x, agg, comp, delta_in, res, m, wf))
    out_xr, us_k3 = timed(
        lambda: agg_ops.aggregate_apply(x, agg, comp, delta_in, res, m, wf,
                                        force_pallas=True))
    err3 = max(float(jnp.max(jnp.abs(o - r)))
               for o, r in zip(out_xr, ref_xr))
    rows.append(emit("kernels/aggregate_apply/ref", us_ref3, f"d={d}"))
    rows.append(emit(f"kernels/aggregate_apply/{path}", us_k3,
                     f"err={err3:.1e}"))

    # flash attention
    b, s2, h, kv, hd = 1, 512, 4, 2, 64
    k_q, k_k, k_v = jax.random.split(key, 3)
    q = jax.random.normal(k_q, (b, s2, h, hd), jnp.float32)
    k2 = jax.random.normal(k_k, (b, s2, kv, hd), jnp.float32)
    v2 = jax.random.normal(k_v, (b, s2, kv, hd), jnp.float32)
    ref2, us_ref2 = _ref(lambda: attention_ref(q, k2, v2, causal=True))
    out2, us_k2 = timed(lambda: attn_ops.attention(q, k2, v2, causal=True,
                                                   force_pallas=True))
    err2 = float(jnp.max(jnp.abs(out2 - ref2)))
    rows.append(emit("kernels/flash_attention/ref", us_ref2, f"s={s2}"))
    rows.append(emit(f"kernels/flash_attention/{path}", us_k2,
                     f"err={err2:.1e}"))
    return rows


if __name__ == "__main__":
    main()
