"""Single-compile round executors for federated algorithms.

The round loop is one ``jax.lax.scan`` over a per-round *schedule*: PRNG keys
plus a stepsize multiplier ``eta_scale[r]`` applied to the state's base η each
round. Stepsize decay (the paper's "M-" variants, App. I.1) is therefore pure
data — the same compiled executor runs constant-η and decayed-η schedules.

Problems are executor OPERANDS. Every executor takes a leading ``spec``
argument (a ``repro.data.spec.ProblemSpec`` pytree — arrays only, family
dispatch is static metadata): the cache key is the spec's *structural*
identity (family tag + static fields + leaf shapes/dtypes), never the
instance, so re-running any same-shaped problem — a whole ζ/σ grid of them —
reuses ONE compile. Legacy hand-closure problems (``FederatedProblem`` with
``spec=None``) still run: their executors close over the problem and are
keyed by an id-reuse-safe weak token; callers pass ``spec=None``.

The executor cache holds ``(key, fn)`` ONLY. Spec-path entries capture no
problem data at all (the spec rides in as an argument), so evicting or
caching an executor never pins client data shards; tokens for legacy
problems are weak references.

State protocol (audited in ``algorithms.base``): every algorithm state is a
NamedTuple carrying ``.x`` (server iterate), ``.eta`` (base stepsize — the
executor owns annealing and restores the base after every round) and ``.r``
(round counter). ``round`` must pass ``eta`` through unchanged.

``method_executor_body`` stacks SEVERAL method instances with matching state
structure (e.g. SGD at three ``mu_avg`` values, FedAvg at two local-step
counts) into one executor: the per-round dispatch is a ``lax.switch`` over
the method index — an operand — so the sweep engine vmaps methods × seeds ×
stepsizes through one compile (``core.sweep.run_method_sweep``).

``TRACE_COUNTS`` increments once per executor *trace* (a Python side effect
inside the traced body) — tests assert single-compile behaviour with it.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import warnings
import weakref
from typing import Optional

import jax
import jax.numpy as jnp

from repro.obs.profile import scope

# The jitted executors donate their call-private operands (scan-carry
# state0, per-cell key/mask stacks — see core.sweep's memory model). CPU
# has no donation support, so JAX warns once per call that the donated
# buffers went unused; that is expected on CPU hosts and pure noise here.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


def dealias_donated(donated, *others):
    """Copy any leaf of ``donated`` whose buffer also backs a protected
    array in ``others`` — the call's other operands (XLA refuses to execute
    when a donated buffer appears twice among the arguments) and any
    caller-owned arrays like ``x0`` that the fresh state stores by
    reference (donation would delete them out from under the caller).
    Aliases only arise from object reuse at init time, so object identity
    is the right test; fresh arrays pass through untouched and nothing is
    copied on the common path."""
    seen = set()
    for t in others:
        for leaf in jax.tree.leaves(t):
            if isinstance(leaf, jax.Array):
                seen.add(id(leaf))

    def dealias(leaf):
        if isinstance(leaf, jax.Array):
            if id(leaf) in seen:
                return jnp.array(leaf, copy=True)
            seen.add(id(leaf))
        return leaf

    return jax.tree.map(dealias, donated)

# Trace counter: the executor bodies bump this when (re)traced. A cached,
# single-compile executor leaves the count unchanged on repeated calls.
TRACE_COUNTS: collections.Counter = collections.Counter()


def snapshot_traces() -> dict:
    """A point-in-time copy of ``TRACE_COUNTS`` for later ``trace_deltas``."""
    return dict(TRACE_COUNTS)


def trace_deltas(before: dict) -> dict:
    """TRACE_COUNTS movement since the ``before`` snapshot (nonzero only)."""
    return {k: v - before.get(k, 0) for k, v in TRACE_COUNTS.items()
            if v != before.get(k, 0)}


class _TraceProbe:
    """Exposes ``.deltas`` (the TRACE_COUNTS movement) after the
    ``assert_no_retrace`` block exits."""

    deltas: dict = {}


@contextlib.contextmanager
def assert_no_retrace(traced=(), *, what: str = "with-block"):
    """Assert executor-trace discipline across the block.

    Each counter named in ``traced`` must move by EXACTLY one (the block
    pays that executor's single compile) and every other ``TRACE_COUNTS``
    entry must not move at all. ``traced=()`` is the warm contract: zero
    movement anywhere — re-running an already-compiled grid, swapping
    operands (problems, comm configs, policies) at a fixed structure, or a
    repeat call of any cached executor must all pass it.

    Yields a probe whose ``.deltas`` holds the observed movement at exit,
    for tests that want to report or further inspect the counters.
    """
    probe = _TraceProbe()
    before = dict(TRACE_COUNTS)
    yield probe
    probe.deltas = deltas = trace_deltas(before)
    traced = tuple(traced)
    problems = [f"{name!r} traced {deltas.get(name, 0)} times "
                f"(expected exactly 1)"
                for name in traced if deltas.get(name, 0) != 1]
    extra = {k: v for k, v in deltas.items() if k not in traced}
    if extra:
        problems.append(f"unexpected re-traces: {extra}")
    if problems:
        raise AssertionError(
            f"trace discipline violated across {what}: "
            + "; ".join(problems))


# jaxpr-audit hook (``repro.analysis.jaxpr_audit``): while ``AUDIT_SINK`` is
# a list, every top-level call of a cached executor records
# ``(cache_key, fn, args, kwargs)`` so the audit can re-trace the EXACT
# executor object on its real operands and walk the ClosedJaxpr consts.
# Calls made during tracing (the unjitted bodies run inside jit/vmap with
# Tracer arguments) are skipped — recording them would leak tracers.
AUDIT_SINK: Optional[list] = None

# cache key -> executor fn. A bounded LRU; entries hold NO problem objects
# (spec-path executors take the problem as an operand; legacy closure
# executors capture their problem themselves, which is exactly the lifetime
# the closure path implies).
_EXECUTOR_CACHE: "collections.OrderedDict" = collections.OrderedDict()
_EXECUTOR_CACHE_MAX = 128

# Legacy problems are keyed by a token that is unique per live object and
# never reused while the object is alive: ids are validated through a weak
# reference and the table entry dies with the problem (no strong refs).
_TOKEN_COUNTER = itertools.count()
_PROBLEM_TOKENS: dict = {}


@dataclasses.dataclass
class RunResult:
    state: object  # final algorithm state
    x_hat: object  # algorithm's returned iterate
    history: jnp.ndarray  # [R] F(x̂_r) − F* after each round (of x̂, not x)
    grad_norms: Optional[jnp.ndarray] = None
    bits_up: Optional[jnp.ndarray] = None  # [R] per-round uplink bits (comm)
    bits_down: Optional[jnp.ndarray] = None  # [R] per-round downlink bits
    diagnostics: Optional[dict] = None  # per-round taps ([R] leaves), obs


def _env_key():
    """Trace-time environment baked into compiled executors: a cached
    executor traced under one kernel-dispatch mode must not be served under
    another (``REPRO_FORCE_PALLAS`` is read when the round body traces)."""
    from repro.kernels.aggregate import ops as agg_ops

    return agg_ops._force_pallas_env()


def as_spec(problem):
    """The operand form of a problem: the ProblemSpec itself, a shim's
    ``.spec``, or None for legacy hand-closure problems."""
    if getattr(problem, "is_problem_spec", False):
        return problem
    return getattr(problem, "spec", None)


def _problem_token(problem) -> int:
    pid = id(problem)
    entry = _PROBLEM_TOKENS.get(pid)
    if entry is not None:
        ref, token = entry
        if ref() is problem:
            return token
    token = next(_TOKEN_COUNTER)
    ref = weakref.ref(problem,
                      lambda _, pid=pid: _PROBLEM_TOKENS.pop(pid, None))
    _PROBLEM_TOKENS[pid] = (ref, token)
    return token


def problem_key(problem):
    """The problem's contribution to an executor cache key: structural for
    specs (shapes, never identity), a weak identity token for legacy
    closures."""
    spec = as_spec(problem)
    if spec is not None:
        return ("spec", spec.cache_key())
    return ("closure", _problem_token(problem))


def f_star_operand(problem):
    """The F* the executors subtract. For specs this is the ``f_star``
    CONSTANT LEAF (an operand — 0.0 when unknown, making histories raw
    objective values; the explicit-fallback warning lives in
    ``suboptimality``). For legacy problems it is the baked float."""
    spec = as_spec(problem)
    if spec is not None:
        return spec.f_star_leaf
    return problem.f_star if problem.f_star is not None else 0.0


@scope("eval")
def evaluate(p, x, f_star):
    """F(x) − F*: what every executor records per round (and the sweep
    cells once more at the end)."""
    return p.global_loss(x) - f_star


def _obs_emit(kind, **fields):
    """Forward one cache/compile event to the obs event log — a None-check
    no-op unless ``repro.obs.events`` has a recorder installed."""
    from repro.obs import events as obs_events

    obs_events.emit(kind, **fields)


def _cache_get(key):
    full = (key, _env_key())
    fn = _EXECUTOR_CACHE.get(full)
    if fn is not None:
        _EXECUTOR_CACHE.move_to_end(full)
        _obs_emit("cache", op="hit", family=key[0])
    else:
        _obs_emit("cache", op="miss", family=key[0])
    return fn


def _audit_wrap(key, fn):
    def wrapped(*args, **kwargs):
        concrete = not any(
            isinstance(leaf, jax.core.Tracer)
            for leaf in jax.tree.leaves((args, kwargs)))
        if AUDIT_SINK is not None and concrete:
            AUDIT_SINK.append((key, fn, args, kwargs))
        if concrete:
            from repro.obs import events as obs_events

            if obs_events.RECORDER is not None:
                return obs_events.observed_call(key, fn, args, kwargs)
        return fn(*args, **kwargs)

    return wrapped


def _cache_put(key, fn):
    full = (key, _env_key())
    fn = _audit_wrap(key, fn)
    _EXECUTOR_CACHE[full] = fn
    _EXECUTOR_CACHE.move_to_end(full)
    _obs_emit("cache", op="put", family=key[0])
    while len(_EXECUTOR_CACHE) > _EXECUTOR_CACHE_MAX:
        evicted, _ = _EXECUTOR_CACHE.popitem(last=False)
        _obs_emit("cache", op="evict", family=evicted[0][0])
    return fn


def clear_executor_cache():
    """Drop all cached executors (mainly for tests)."""
    _EXECUTOR_CACHE.clear()


def _bind(problem):
    """(spec, resolve) where ``resolve(spec_op)`` yields the problem an
    executor body should query: the traced spec operand on the spec path, or
    the captured legacy problem (spec_op is then None) on the closure path."""
    spec = as_spec(problem)
    if spec is not None:
        return spec, (lambda spec_op: spec_op)
    return None, (lambda spec_op: problem)


def executor_body(algo, problem, eval_output: bool = True, telemetry=None):
    """The unjitted single-compile executor.

    Returns ``fn(spec, state0, keys, eta_scale) -> (state, history)``
    scanning all rounds at once; ``spec`` is the problem operand (None for
    legacy closure problems), ``keys`` is [R, 2] raw PRNG keys, ``eta_scale``
    is [R] multipliers on the *base* stepsize carried in ``state0.eta``.

    ``telemetry`` (a ``repro.obs.Telemetry``, part of the cache key like
    ``eval_output``) switches the scan output to ``(history, taps)`` where
    ``taps`` is the per-round diagnostics dict; ``None`` traces exactly the
    pre-telemetry jaxpr.
    """
    key = ("body", algo, problem_key(problem), eval_output, telemetry)
    fn = _cache_get(key)
    if fn is not None:
        return fn

    _, resolve = _bind(problem)

    def executor(spec, state0, keys, eta_scale):
        from repro.core.algorithms import base as algo_base
        from repro.obs import telemetry as obs_tel

        p = resolve(spec)
        algo_base.audit_state(state0)  # protocol check, once per trace
        TRACE_COUNTS[f"runner/{algo.name}"] += 1  # trace-time side effect
        f_star = f_star_operand(p)
        base_eta = state0.eta

        def one_round(state, xs):
            k, scale = xs
            st = algo.round(p, state._replace(eta=base_eta * scale), k)
            st = st._replace(eta=base_eta)  # executor owns annealing
            x_eval = algo.output(st) if eval_output else st.x
            sub = evaluate(p, x_eval, f_star)
            if telemetry is None:
                return st, sub
            taps = obs_tel.round_taps(
                telemetry, problem=p, prev_x=state.x, new_x=st.x,
                x_eval=x_eval)
            return st, (sub, taps)

        return jax.lax.scan(one_round, state0, (keys, eta_scale))

    return _cache_put(key, executor)


def executor(algo, problem, eval_output: bool = True, telemetry=None):
    """The jitted, module-cached executor (same signature as the body).

    ``state0`` (argnum 1) is DONATED: it is the scan carry, dead the moment
    the scan starts, so donation-capable backends reuse its buffers for the
    output state instead of copying. Callers must build it fresh per call
    (``run``/``run_with_decay`` do). The donated argnums are part of the
    cache key.
    """
    donate = (1,)
    key = ("jit", algo, problem_key(problem), eval_output, telemetry, donate)
    fn = _cache_get(key)
    if fn is not None:
        return fn
    return _cache_put(key, jax.jit(
        executor_body(algo, problem, eval_output, telemetry),
        donate_argnums=donate))


def comm_executor_body(algo, problem, eval_output: bool = True,
                       telemetry=None):
    """The comm-enabled single-compile executor.

    Returns ``fn(spec, state0, keys, eta_scale, masks) -> (state, (history,
    bits_up, bits_down))``. ``state0`` must carry a ``CommState`` in its
    ``comm`` leaf; ``masks`` is the [R, N] participation schedule — pure scan
    data, like the keys and η multipliers, so comm config (participation
    fraction, compressor, bit-width) never re-traces this executor.

    With ``telemetry`` set the scan emits ``(history, bits_up, bits_down,
    taps)`` — the taps include the EF residual norms of all three CommPlan
    legs and the per-round participation count.
    """
    key = ("comm-body", algo, problem_key(problem), eval_output, telemetry)
    fn = _cache_get(key)
    if fn is not None:
        return fn

    _, resolve = _bind(problem)

    def executor(spec, state0, keys, eta_scale, masks):
        from repro.comm import config as comm_cfg
        from repro.core.algorithms import base as algo_base
        from repro.obs import telemetry as obs_tel

        p = resolve(spec)
        algo_base.audit_state(state0)
        comm_cfg.comm_state_or_error(state0, algo.name)
        TRACE_COUNTS[f"runner-comm/{algo.name}"] += 1
        f_star = f_star_operand(p)
        base_eta = state0.eta

        def one_round(state, xs):
            k, scale, mask = xs
            comm_in = comm_cfg.zero_round_bits(
                state.comm._replace(mask=mask))
            st = algo.round(
                p, state._replace(eta=base_eta * scale, comm=comm_in), k)
            comm = comm_cfg.comm_state_or_error(st, algo.name)
            st = st._replace(eta=base_eta)
            x_eval = algo.output(st) if eval_output else st.x
            sub = evaluate(p, x_eval, f_star)
            if telemetry is None:
                return st, (sub, comm.bits_up, comm.bits_down)
            taps = obs_tel.round_taps(
                telemetry, problem=p, prev_x=state.x, new_x=st.x,
                x_eval=x_eval, comm=comm, mask=mask, bits_up=comm.bits_up,
                bits_down=comm.bits_down)
            return st, (sub, comm.bits_up, comm.bits_down, taps)

        return jax.lax.scan(one_round, state0, (keys, eta_scale, masks))

    return _cache_put(key, executor)


def comm_executor(algo, problem, eval_output: bool = True, telemetry=None):
    """The jitted, module-cached comm executor. ``state0`` is donated like
    the plain executor's (the [R, N] masks are NOT — ``run`` forwards
    user-supplied ``comm_masks`` arrays there)."""
    donate = (1,)
    key = ("comm-jit", algo, problem_key(problem), eval_output, telemetry,
           donate)
    fn = _cache_get(key)
    if fn is not None:
        return fn
    return _cache_put(key, jax.jit(
        comm_executor_body(algo, problem, eval_output, telemetry),
        donate_argnums=donate))


def selection_executor_body(algo, problem, eval_output: bool = True,
                            telemetry=None):
    """The policy-selection single-compile executor.

    Returns ``fn(spec, state0, keys, eta_scale, sel_keys, pparams, pstate0)
    -> ((state, pstate), (history, bits_up, bits_down, masks))``.  Instead
    of a precomputed [R, N] mask schedule, each round's participation mask
    is produced in-scan by ``selection.policies.round_select`` from the
    policy operand ``pparams`` (a ``PolicyParams`` of jnp scalars — the
    policy choice is a ``lax.switch`` index, so swapping policies or their
    hyperparameters never re-traces) and the policy state ``pstate0``
    (``PolicyState`` pytree leaves carried through the scan).  The mask
    feeds the comm ledger unchanged; probing policies additionally bill
    their value-probe uplink via ``policies.probe_bits``.

    With ``telemetry`` set the scan additionally emits the per-round taps
    dict (policy-state summaries included) as a trailing output.
    """
    key = ("sel-body", algo, problem_key(problem), eval_output, telemetry)
    fn = _cache_get(key)
    if fn is not None:
        return fn

    _, resolve = _bind(problem)

    def executor(spec, state0, keys, eta_scale, sel_keys, pparams, pstate0):
        from repro.comm import config as comm_cfg
        from repro.core.algorithms import base as algo_base
        from repro.obs import telemetry as obs_tel
        from repro.selection import policies as pol

        p = resolve(spec)
        algo_base.audit_state(state0)
        comm_cfg.comm_state_or_error(state0, algo.name)
        TRACE_COUNTS[f"runner-sel/{algo.name}"] += 1
        f_star = f_star_operand(p)
        base_eta = state0.eta
        extra_up = pol.probe_bits(pparams, p.num_clients)

        def one_round(carry, xs):
            state, pstate = carry
            k, scale, k_sel = xs
            mask, pstate = pol.round_select(p, state.x, pstate, pparams,
                                            k_sel)
            comm_in = comm_cfg.zero_round_bits(
                state.comm._replace(mask=mask))
            st = algo.round(
                p, state._replace(eta=base_eta * scale, comm=comm_in), k)
            comm = comm_cfg.comm_state_or_error(st, algo.name)
            comm = comm._replace(bits_up=comm.bits_up + extra_up)
            st = st._replace(eta=base_eta, comm=comm)
            x_eval = algo.output(st) if eval_output else st.x
            sub = evaluate(p, x_eval, f_star)
            if telemetry is None:
                return (st, pstate), (sub, comm.bits_up, comm.bits_down,
                                      mask)
            taps = obs_tel.round_taps(
                telemetry, problem=p, prev_x=state.x, new_x=st.x,
                x_eval=x_eval, comm=comm, mask=mask, pstate=pstate,
                bits_up=comm.bits_up, bits_down=comm.bits_down)
            return (st, pstate), (sub, comm.bits_up, comm.bits_down, mask,
                                  taps)

        return jax.lax.scan(one_round, (state0, pstate0),
                            (keys, eta_scale, sel_keys))

    return _cache_put(key, executor)


def method_executor_body(methods, problem, eval_output: bool = True):
    """The multi-method stacked executor (one compile for several methods).

    ``methods`` is a tuple of algorithm instances whose states share one
    pytree structure (e.g. one class at different hyperparameters — SGD at
    several ``mu_avg``, FedAvg at several local-step counts). Returns
    ``fn(spec, state0, keys, eta_scale, midx) -> (state, history)`` where
    ``midx`` selects the method via ``lax.switch`` each round — an operand,
    so the sweep engine vmaps it alongside seeds and stepsizes.
    """
    methods = tuple(methods)
    tag = "+".join(m.name for m in methods)
    key = ("methods-body", methods, problem_key(problem), eval_output)
    fn = _cache_get(key)
    if fn is not None:
        return fn

    _, resolve = _bind(problem)

    def executor(spec, state0, keys, eta_scale, midx):
        from repro.core.algorithms import base as algo_base

        p = resolve(spec)
        algo_base.audit_state(state0)
        TRACE_COUNTS[f"runner-methods/{tag}"] += 1
        f_star = f_star_operand(p)
        base_eta = state0.eta

        def _output(st):
            if not eval_output:
                return st.x
            return jax.lax.switch(
                midx, [lambda s, m=m: m.output(s) for m in methods], st)

        def one_round(state, xs):
            k, scale = xs
            st_in = state._replace(eta=base_eta * scale)
            st = jax.lax.switch(
                midx,
                [lambda args, m=m: m.round(p, args[0], args[1])
                 for m in methods],
                (st_in, k))
            st = st._replace(eta=base_eta)
            sub = evaluate(p, _output(st), f_star)
            return st, sub

        return jax.lax.scan(one_round, state0, (keys, eta_scale))

    return _cache_put(key, executor)


def run(algo, problem, x0, rounds: int, key, *, eval_output: bool = True,
        jit: bool = True, eta=None, comm=None, comm_masks=None,
        telemetry=None):
    """Run ``rounds`` communication rounds; record suboptimality each round.

    ``eta`` overrides the state's base stepsize (used by the sweep engine's
    per-run comparator); ``None`` keeps the algorithm's own initialization.
    ``comm`` (a ``repro.comm.CommConfig``) enables the communication layer:
    compressed uplinks, the per-round participation schedule (``comm_masks``
    overrides the config-derived [R, N] masks) and exact bits accounting in
    the result's ``bits_up``/``bits_down``. ``telemetry`` (a
    ``repro.obs.Telemetry``) additionally returns the per-round taps in the
    result's ``diagnostics`` ([R]-shaped leaves); ``None`` is bitwise
    identical to a run without the telemetry layer.
    """
    spec = as_spec(problem)
    state0 = algo.init_with_eta(problem, x0, eta)
    keys = jax.random.split(key, rounds)
    eta_scale = jnp.ones((rounds,), jnp.float32)
    if comm is not None:
        from repro.comm import config as comm_cfg

        comm_cfg.require_comm_leaf(state0, algo.name)
        n = problem.num_clients
        masks = (comm.round_masks(rounds, n) if comm_masks is None
                 else jnp.asarray(comm_masks, jnp.float32))
        state0 = state0._replace(comm=comm.init_state(n, x0))
        # x0/eta are caller-owned and typically stored BY REFERENCE in the
        # fresh state — they must survive the donation
        state0 = dealias_donated(state0, spec, keys, eta_scale, masks,
                                 x0, eta)
        fn = (comm_executor if jit else comm_executor_body)(
            algo, problem, eval_output, telemetry)
        if telemetry is None:
            state, (history, bits_up, bits_down) = fn(
                spec, state0, keys, eta_scale, masks)
            taps = None
        else:
            state, (history, bits_up, bits_down, taps) = fn(
                spec, state0, keys, eta_scale, masks)
        return RunResult(state=state, x_hat=algo.output(state),
                         history=history, bits_up=bits_up,
                         bits_down=bits_down, diagnostics=taps)
    fn = (executor if jit else executor_body)(algo, problem, eval_output,
                                              telemetry)
    state0 = dealias_donated(state0, spec, keys, eta_scale, x0, eta)
    if telemetry is None:
        state, history = fn(spec, state0, keys, eta_scale)
        taps = None
    else:
        state, (history, taps) = fn(spec, state0, keys, eta_scale)
    return RunResult(state=state, x_hat=algo.output(state), history=history,
                     diagnostics=taps)


def decay_segments(rounds: int, decay_first: float = 0.3):
    """Segment lengths of the App. I.1 decay schedule (sum == rounds).

    Boundaries at ceil(decay_first·R) and every doubling thereof.
    """
    boundaries = []
    b = max(1, int(round(decay_first * rounds)))
    while b < rounds:
        boundaries.append(b)
        b *= 2
    segments = []
    prev = 0
    for b in boundaries:
        segments.append(b - prev)
        prev = b
    segments.append(rounds - prev)
    return segments


def decay_eta_scale(rounds: int, decay_first: float = 0.3,
                    decay_factor: float = 0.5) -> jnp.ndarray:
    """Per-round η multipliers implementing the "M-" stepsize decay."""
    segments = decay_segments(rounds, decay_first)
    scales = []
    for i, seg in enumerate(segments):
        if seg > 0:
            scales.append(jnp.full((seg,), decay_factor**i, jnp.float32))
    return jnp.concatenate(scales) if scales else jnp.zeros((0,), jnp.float32)


def run_with_decay(
    algo, problem, x0, rounds: int, key, *,
    decay_first: float = 0.3, decay_factor: float = 0.5, jit: bool = True,
    eta=None,
):
    """The paper's "M-" stepsize-decay variants (App. I.1): halve η at
    R_decay = decay_first·R and again at every doubling of R_decay.

    Runs through the SAME compiled executor as ``run`` — decay is schedule
    data (``eta_scale``), not a re-traced per-segment loop.
    """
    segments = decay_segments(rounds, decay_first)
    seg_keys = jax.random.split(key, len(segments))
    keys = jnp.concatenate([
        jax.random.split(seg_keys[i], seg)
        for i, seg in enumerate(segments) if seg > 0
    ]) if rounds > 0 else jnp.zeros((0, 2), jnp.uint32)
    eta_scale = decay_eta_scale(rounds, decay_first, decay_factor)

    state0 = algo.init_with_eta(problem, x0, eta)
    # the annealed final stepsize is derived BEFORE the executor call:
    # state0 is donated to the jit, so its buffers must not be read after
    n_applied = sum(1 for seg in segments if seg > 0)
    eta_final = state0.eta * decay_factor**n_applied
    fn = (executor if jit else executor_body)(algo, problem, True)
    spec = as_spec(problem)
    state0 = dealias_donated(state0, spec, keys, eta_scale, x0, eta)
    state, history = fn(spec, state0, keys, eta_scale)
    # final state carries the fully-annealed stepsize, as the segment loop did
    state = state._replace(eta=eta_final)
    return RunResult(state=state, x_hat=algo.output(state), history=history)
