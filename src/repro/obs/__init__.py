"""Run-telemetry subsystem: in-scan round taps, the executor event log, and
the program's profiler spans and round-body scopes.

The telemetry model, in one page
================================

**Round taps (values).** Layer 1 (``repro.obs.telemetry``) lives INSIDE the
compiled executors: a frozen ``Telemetry`` spec makes the
runner/chain/sweep/dist scan bodies emit a per-round diagnostics dict as
extra ``lax.scan`` outputs — update/gradient norms, the error-feedback
residual norms of all three ``CommPlan`` legs, participation counts,
per-leg bits, policy-state summaries, and the active chain stage. Every tap
is a pure in-trace reduction of values the round body already holds
(batch-invariant ``tree_math`` ops, so the vmapped and sharded engines
agree bitwise); no host callbacks, no trace-time side effects. ``Telemetry``
is a STRUCTURAL cache-key dimension: a taps-on run compiles its own
executor and ``telemetry=None`` (the default) traces today's jaxprs. The
taps-on warm path is gated by ``BENCH_obs.json`` in
``benchmarks/check_regression.py``.

**Events (host log).** Layer 2 (``repro.obs.events``) is a JSONL recorder
hooked beside the executor cache: ``compile`` events (one per top-level
executor call that moved ``runner.TRACE_COUNTS``: family, trace tags, wall
seconds, donation tuple, optional jaxpr const bytes), ``cache``
hit/miss/put/evict events, benchmark ``phase`` events and training
``metric`` events. ``python -m repro.obs report`` summarizes a log.
``runner.TRACE_COUNTS`` is the one trace-time counter; the old mirror
counter ``TRACE_EVENTS`` is gone.

**Time (profiler).** Layer 3 (``repro.obs.profile``) marks where time goes
in a ``jax.profiler`` trace. Host spans (``annotate``), opened by the sweep
host path on every grid call:

* ``repro.sweep.run`` — all of ``core.sweep.run``; argument ``call``, the
  process's grid-call sequence number. Read by chipbench's
  ``host_ms_per_grid`` reconciliation.
* ``repro.sweep.operands`` — per-seed keys, the η array, the initial comm
  state, the η schedule, the problem/policy operand stacks.
* ``repro.sweep.masks`` — the comm mask schedule (``CommPlan.round_masks``
  per cell) or a policy grid's selection keys. Read by
  ``host_masks_ms_per_grid``.
* ``repro.sweep.executor`` — the cached executor lookup (or build).
* ``repro.sweep.dispatch`` — the executor call, up to its (asynchronous)
  return. Read by ``host_dispatch_ms_per_grid``.

The four children open in this order inside ``repro.sweep.run`` on the
vmapped grid family, the policy grid family and the sharded grid
(``dist.grid``). Round-body scopes (``scope``, a ``jax.named_scope`` from
the fixed vocabulary ``SCOPES``) name the part of the round each device op
belongs to through its ``op_name`` metadata; they change no computation:

* ``sample`` — the Fisher–Yates client sampler, a selection policy's pick;
* ``local`` — client computation: ``grad_k``, FedAvg's local steps and
  their ``vmap``, the other algorithms' client phases (``round_local_us``);
* ``downlink`` / ``uplink`` — the comm legs: compression, error feedback,
  the fused aggregate-apply kernel (``round_comm_us``);
* ``server`` — aggregation and the server update on the unfused path;
* ``select`` — the chain's stage handoff and Lemma H.2 selection;
* ``eval`` — the per-round (and final) ``F(x) − F*`` (``round_eval_us``);
* ``taps`` — the round taps above.

A trace reader gives each op the first scope in its name stack;
``device_unscoped_share`` reads the share of busy time under none. Spans
and scopes cost nothing while no profiler runs: a span is a flag check,
a scope exists only in op metadata.
"""
from repro.obs.events import (
    EventRecorder, emit, install, recording, uninstall,
)
from repro.obs.profile import (
    SCOPES, annotate, phase, run_manifest, scope, write_manifest,
)
from repro.obs.telemetry import Telemetry, round_taps

__all__ = [
    "EventRecorder", "SCOPES", "Telemetry", "annotate", "emit", "install",
    "phase", "recording", "round_taps", "run_manifest", "scope",
    "uninstall", "write_manifest",
]
