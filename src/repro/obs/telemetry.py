"""Layer 1: in-scan round taps — the ``Telemetry`` spec and tap computation.

A ``Telemetry`` instance is a STRUCTURAL executor-cache-key dimension,
exactly like the named donate tuples (rule R4): every executor body appends
it to its cache key, so runs with different tap sets compile distinct
executors, and ``telemetry=None`` (the default everywhere) leaves today's
cache keys, jaxprs, and outputs bitwise identical — the tap code is never
traced on the None path.

All taps are pure in-trace functions of values the round body already holds
(no host callbacks, no side effects — R1/R2-clean by construction) built on
the batch-invariant ``tree_math`` reductions, so the vmapped and sharded
engines emit bitwise-identical diagnostics. Each round contributes one
scalar per enabled tap; ``lax.scan`` stacks them into ``[R]`` leaves of the
``diagnostics`` dict riding beside the usual outputs (grid sweeps add the
cell axes in front, like ``history``).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro.core import tree_math as tm
from repro.obs.profile import scope


@dataclasses.dataclass(frozen=True)
class Telemetry:
    """Which per-round diagnostics the executors emit as extra scan outputs.

    Only ``grad_norm`` adds real work (one extra full gradient per round);
    every other tap is a cheap reduction of values the round already
    computed, which is what keeps the taps-on warm path inside the
    ``BENCH_obs.json`` overhead gate. A tap only appears in the diagnostics
    dict when the executor family actually has its input (e.g. no
    ``participation`` on the plain, comm-free runner), so the tap pytree
    structure is a pure function of (telemetry, executor family).
    """

    update_norm: bool = True     # ‖x_r − x_{r−1}‖ of the server iterate
    grad_norm: bool = False      # ‖∇F(x_eval)‖ — one extra global gradient
    residual_norms: bool = True  # EF residual norms on all three CommPlan legs
    participation: bool = True   # Σ mask — clients participating this round
    leg_bits: bool = True        # per-round uplink/downlink bits in the taps
    policy_summary: bool = True  # PolicyState summaries (selection executors)
    stage_index: bool = True     # active chain stage id (chain executors)


@scope("taps")
def round_taps(tel: Telemetry, *, problem=None, prev_x=None, new_x=None,
               x_eval=None, comm=None, mask=None, pstate=None,
               stage=None, bits_up=None, bits_down=None) -> dict:
    """One round's diagnostics dict (scalar leaves, in-trace only).

    Callers pass whatever their round body holds; disabled or unavailable
    taps are simply absent. The uplink and momentum CommPlan legs share the
    per-client residual tables (``CommState.residual`` — the momentum leg
    runs the same EF kernels on the same tables), so their norms coincide;
    both are emitted so the three legs are always individually named. With
    error feedback off the residual tables are ``[N, 0]`` and the norms are
    exactly 0.0 — no trace-time branching.
    """
    taps = {}
    if tel.update_norm and prev_x is not None:
        taps["update_norm"] = tm.tree_norm(tm.tree_sub(new_x, prev_x))
    if tel.grad_norm and problem is not None and x_eval is not None:
        taps["grad_norm"] = tm.tree_norm(problem.global_grad(x_eval))
    if tel.residual_norms and comm is not None:
        up_norm = tm.tree_norm(comm.residual)
        taps["residual_up_norm"] = up_norm
        taps["residual_mom_norm"] = up_norm
        taps["residual_down_norm"] = tm.tree_norm(comm.down_residual)
    if tel.participation and mask is not None:
        taps["participation"] = jnp.sum(mask)
    if tel.leg_bits and bits_up is not None:
        taps["bits_up"] = bits_up
        taps["bits_down"] = bits_down
    if tel.policy_summary and pstate is not None:
        taps["policy_t"] = pstate.t
        taps["policy_count_max"] = jnp.max(pstate.counts)
        taps["policy_value_mean"] = jnp.mean(pstate.values)
    if tel.stage_index and stage is not None:
        taps["stage"] = jnp.asarray(stage, jnp.int32)
    return taps
