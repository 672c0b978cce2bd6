"""FedChain — the paper's Algorithm 1, plus multi-stage generalizations.

  x̂_1/2 ← A_local(x̂_0)                      (local_fraction · R rounds)
  x̂_1   ← better of {x̂_0, x̂_1/2}            (Lemma H.2 selection, S clients × K samples)
  x̂_2   ← A_global(x̂_1)                     (remaining rounds)

``Chain`` also supports >2 stages (e.g. FedAvg→SCAFFOLD→SGD) and optional
per-stage stepsize decay — the "multistage algorithms" of Fig. 2.

Execution model
---------------
A chain of N stages runs as ONE ``jax.lax.scan`` over a precomputed per-round
schedule: for each round, which stage executes (``stage_id``), whether the
round is a Lemma H.2 selection round (``kind``), whether a stage handoff
(selection + re-init of the incoming stage) happens before it (``hmode``),
and the η decay multiplier (``eta_scale``). Stage switching is a
``lax.switch`` over the per-stage round functions inside the scan body, so a
whole chain — stages, selection rounds, stepsize decay — compiles exactly
once per ``(chain, problem STRUCTURE)``: the problem rides in as a
``ProblemSpec`` operand (see ``repro.data.spec``), so every same-shaped
instance — an entire ζ/σ grid — shares the compile. The executor is cached
at module level (via ``runner``'s cache) and reused across calls, round
budgets and the sweep engine's vmapped grids.

The seed implementation Python-looped over stages with a separate jit per
stage per call; this executor replaces that with schedule data.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import runner as runner_lib
from repro.core import selection
from repro.core import tree_math as tm
from repro.obs.profile import scope

# handoff modes (the transition INTO stage j, applied before its first round)
_H_NONE = 0  # no handoff this round
_H_ANCHOR = 1  # init from the anchor (a costed selection round already ran)
_H_SELECT = 2  # inline selection between anchor and previous stage's output
_H_TAKE = 3  # take the previous stage's output unconditionally


@scope("select")
def _reset_residuals(comm_st, hmd):
    """Zero the error-feedback residuals on a handoff round (``hmd`` > 0).

    They don't survive a stage handoff: the incoming stage's uplink payloads
    have different semantics (iterate deltas vs gradients), and the residual
    mass may belong to a trajectory selection just discarded; the
    server-side downlink residual resets for the same reason (the selection
    broadcast is full-precision, so clients hold the handoff point
    exactly)."""
    return comm_st._replace(
        residual=jax.tree.map(lambda r: jnp.where(hmd > 0, 0.0, r),
                              comm_st.residual),
        down_residual=jax.tree.map(lambda r: jnp.where(hmd > 0, 0.0, r),
                                   comm_st.down_residual))


@dataclasses.dataclass
class ChainResult:
    x_hat: object
    history: jnp.ndarray  # concatenated per-round suboptimality
    switch_rounds: list  # round indices where a stage switch happened
    selected_initial: list  # per switch: True if selection kept the pre-stage point
    bits_up: Optional[jnp.ndarray] = None  # [R] per-round uplink bits (comm)
    bits_down: Optional[jnp.ndarray] = None  # [R] per-round downlink bits
    diagnostics: Optional[dict] = None  # per-round taps ([R] leaves), obs


@dataclasses.dataclass(frozen=True)
class _Schedule:
    """Static per-round schedule for a chain execution.

    Stepsize decay is NOT part of the schedule: η multipliers are an executor
    *operand* (see ``eta_schedule``), so a decay grid reuses one compile.
    """

    stage_id: np.ndarray  # [R] which stage's round (or whose output, kind=1)
    kind: np.ndarray  # [R] 0 = algorithm round, 1 = selection round
    hmode: np.ndarray  # [R] handoff mode before the round (_H_*)
    round_slot: np.ndarray  # [R] index into the stage's key block
    sel_stage: np.ndarray  # [R] selection key index (stage whose k_sel to use)
    budgets: tuple  # per-stage round budgets
    switch_rounds: tuple  # cumulative totals after each stage
    sel_indices: tuple  # round indices carrying a selection decision


@dataclasses.dataclass(frozen=True)
class Chain:
    """A FedChain instantiation: an ordered list of algorithms + fractions."""

    stages: Sequence[object]  # algorithm instances
    fractions: Sequence[float]  # round fractions per stage (sums to <= 1)
    selection_s: int = 0  # 0 => full participation
    selection_k: int = 16
    select_between_stages: bool = True
    selection_costs_round: bool = True
    name: str = "chain"

    def _key(self):
        # name is part of the key: TRACE_COUNTS entries are per-name, so two
        # same-config chains with different names must not share a counter
        return (tuple(self.stages), tuple(self.fractions), self.selection_s,
                self.selection_k, self.select_between_stages,
                self.selection_costs_round, self.name)

    def _fraction_free_key(self):
        """Cache key WITHOUT the round fractions — the fraction-sweep
        executor takes the whole per-round schedule as operands, so chains
        differing only in ``fractions`` share one compile."""
        return (tuple(self.stages), self.selection_s, self.selection_k,
                self.select_between_stages, self.selection_costs_round,
                self.name)

    def budgets(self, rounds: int):
        assert len(self.stages) == len(self.fractions)
        budgets = [max(1, int(round(f * rounds))) for f in self.fractions]
        # spend any rounding surplus/deficit on the last stage
        budgets[-1] += rounds - sum(budgets) - (
            (len(self.stages) - 1)
            if (self.select_between_stages and self.selection_costs_round) else 0
        )
        budgets[-1] = max(1, budgets[-1])
        return budgets

    def _schedule(self, rounds: int) -> _Schedule:
        budgets = self.budgets(rounds)
        n = len(self.stages)
        stage_id, kind, hmode, round_slot, sel_stage = [], [], [], [], []
        switch_rounds, sel_indices = [], []

        for i, b in enumerate(budgets):
            for j in range(b):
                mode = _H_NONE
                if i > 0 and j == 0:
                    if self.select_between_stages and self.selection_costs_round:
                        mode = _H_ANCHOR
                    elif self.select_between_stages:
                        mode = _H_SELECT
                        sel_indices.append(len(stage_id))
                    else:
                        mode = _H_TAKE
                stage_id.append(i)
                kind.append(0)
                hmode.append(mode)
                round_slot.append(j)
                sel_stage.append(max(i - 1, 0))
            if i + 1 < n and self.select_between_stages and self.selection_costs_round:
                sel_indices.append(len(stage_id))
                stage_id.append(i)
                kind.append(1)
                hmode.append(_H_NONE)
                round_slot.append(0)
                sel_stage.append(i)
            switch_rounds.append(len(stage_id))

        return _Schedule(
            stage_id=np.asarray(stage_id, np.int32),
            kind=np.asarray(kind, np.int32),
            hmode=np.asarray(hmode, np.int32),
            round_slot=np.asarray(round_slot, np.int32),
            sel_stage=np.asarray(sel_stage, np.int32),
            budgets=tuple(budgets),
            switch_rounds=tuple(switch_rounds),
            sel_indices=tuple(sel_indices),
        )

    def eta_schedule(self, rounds: int, decay: Optional[dict] = None):
        """Per-round η multipliers [R] — EXECUTOR OPERAND, not schedule
        structure: the paper's "M-" decay (per stage, selection rounds at
        1.0) is data, so sweeping ``decay_factor`` reuses one compile.

        Derived from ``_schedule``'s round enumeration (stage/slot/kind), so
        the multipliers stay aligned with the executor's rounds by
        construction."""
        sched = self._schedule(rounds)
        if decay is None:
            return jnp.ones((len(sched.stage_id),), jnp.float32)
        d_first = decay.get("decay_first", 0.3)
        d_factor = decay.get("decay_factor", 0.5)
        per_stage = [np.asarray(runner_lib.decay_eta_scale(b, d_first, d_factor))
                     for b in sched.budgets]
        out = np.asarray([
            1.0 if k == 1 else per_stage[s][j]
            for s, j, k in zip(sched.stage_id, sched.round_slot, sched.kind)
        ], np.float32)
        return jnp.asarray(out)

    def schedule_len(self, rounds: int) -> int:
        """Rounds the executor actually scans (algorithm + costed selection
        rounds). Constant across ``fractions`` for a fixed stage count —
        what lets a local-fraction grid ride one executor as operands."""
        return len(self._schedule(rounds).stage_id)

    def _derive_keys(self, sched: _Schedule, key):
        """Per-round and per-selection key streams for one schedule.

        Mirrors the seed's derivation: split(key, 2N), stage i's rounds use
        split(keys[2i], budget_i), selections after stage i use keys[2i+1].
        (With decay the seed split stage keys segment-wise; here rounds
        always split once per stage, so decayed-chain streams differ from
        pre-executor results — equivalent in distribution, not bit-for-bit.)
        Pure jax ops: the executors call it on a traced key, the fraction
        sweep calls it host-side per (fraction, seed) so the streams become
        operands — bit-exact with ``Chain.run`` either way.
        """
        n = len(self.stages)
        stage_keys = jax.random.split(key, 2 * n)
        round_keys = jnp.concatenate([
            jax.random.split(stage_keys[2 * i], b)
            for i, b in enumerate(sched.budgets)
        ])
        sel_keys = jnp.stack([stage_keys[2 * i + 1] for i in range(n)])

        # round_keys is indexed per stage block; build the flat [R] view
        offsets = np.concatenate([[0], np.cumsum(sched.budgets)[:-1]])
        flat_idx = jnp.asarray(
            offsets[sched.stage_id] + sched.round_slot, jnp.int32)
        return round_keys[flat_idx], sel_keys[jnp.asarray(sched.sel_stage)]

    def _round_ops(self, problem):
        """The per-round building blocks every chain executor shares:
        selection, stage output/reinit/round dispatch, and the handoff
        transition. All take the resolved problem ``p`` first; stage
        dispatch is a ``lax.switch`` over the static stage tuple, so these
        are schedule-agnostic (the fraction-sweep executor reuses them with
        the schedule as operands)."""
        import types

        stages = tuple(self.stages)
        n = len(stages)
        sel_s = (self.selection_s if self.selection_s > 0
                 else problem.num_clients)
        sel_k = self.selection_k

        @scope("select")
        def _select2(p, anchor, cand, k_sel):
            """Lemma H.2 pick between the anchor and a candidate; True = kept
            the anchor (argmin ties resolve to the anchor, as the seed did)."""
            vals = selection.empirical_values(
                p, [anchor, cand], k_sel, s=sel_s, k=sel_k)
            keep = vals[0] <= vals[1]
            return tm.tree_where(keep, anchor, cand), keep

        def _output(j, states):
            return jax.lax.switch(
                j, [lambda s, i=i: stages[i].output(s[i]) for i in range(n)],
                states)

        def _stage_x(j, states):
            # the active stage's current iterate (what the round broadcasts),
            # NOT its averaged output
            return jax.lax.switch(
                j, [lambda s, i=i: s[i].x for i in range(n)], states)

        def _reinit(p, j, states, x_init):
            """states with slot j re-initialized at x_init, base η preserved."""

            def branch(i):
                def init_i(args):
                    states, x = args
                    st = stages[i].init(p, x)
                    st = st._replace(eta=states[i].eta)
                    return states[:i] + (st,) + states[i + 1:]
                return init_i

            return jax.lax.switch(j, [branch(i) for i in range(n)],
                                  (states, x_init))

        def _round(p, j, states, k_round, scale):
            def branch(i):
                def round_i(args):
                    states, k, scale = args
                    st = states[i]
                    run = stages[i].round(p, st._replace(eta=st.eta * scale), k)
                    run = run._replace(eta=st.eta)
                    return states[:i] + (run,) + states[i + 1:]
                return round_i

            return jax.lax.switch(j, [branch(i) for i in range(n)],
                                  (states, k_round, scale))

        def _round_comm(p, j, states, comm_st, k_round, scale, mask):
            """One stage round with the shared CommState injected into (and
            pulled back out of) the active stage's state; every branch
            returns the ``comm=None`` structure the carry uses."""
            from repro.comm import config as comm_cfg

            def branch(i):
                def round_i(args):
                    states, comm_st, k, scale, mask = args
                    st = states[i]
                    st_in = st._replace(eta=st.eta * scale,
                                        comm=comm_st._replace(mask=mask))
                    out = stages[i].round(p, st_in, k)
                    new_comm = comm_cfg.comm_state_or_error(
                        out, stages[i].name)
                    out = out._replace(eta=st.eta, comm=None)
                    return states[:i] + (out,) + states[i + 1:], new_comm
                return round_i

            return jax.lax.switch(j, [branch(i) for i in range(n)],
                                  (states, comm_st, k_round, scale, mask))

        @scope("select")
        def _handoff(p, states, anchor, sid, hmd, k_sel):
            def do_handoff(args):
                states, anchor = args
                prev_out = _output(jnp.maximum(sid - 1, 0), states)

                def from_anchor(_):
                    return anchor, jnp.asarray(True)

                def with_sel(_):
                    return _select2(p, anchor, prev_out, k_sel)

                def take(_):
                    return prev_out, jnp.asarray(False)

                x_init, kept = jax.lax.switch(
                    hmd - 1, [from_anchor, with_sel, take], None)
                states = _reinit(p, sid, states, x_init)
                return states, x_init, kept

            def no_handoff(args):
                states, anchor = args
                return states, anchor, jnp.asarray(False)

            return jax.lax.cond(
                hmd > 0, do_handoff, no_handoff, (states, anchor))

        return types.SimpleNamespace(
            select2=_select2, output=_output, stage_x=_stage_x,
            reinit=_reinit, round=_round, round_comm=_round_comm,
            handoff=_handoff)

    def _plain_scan_body(self, ops, p, f_star, telemetry=None):
        """The non-comm per-round scan body over operand schedule rows
        ``(k_round, k_sel, sid, knd, hmd, scale)`` — shared by the fixed-
        schedule executor and the fraction-sweep (schedule-as-operand)
        executor. With ``telemetry`` set, the per-round taps dict rides as a
        third scan output (``update_norm`` measures the active stage's own
        movement — the post-handoff iterate before vs after the round)."""
        from repro.obs import telemetry as obs_tel

        def body(carry, xs):
            states, anchor = carry
            k_round, k_sel, sid, knd, hmd, scale = xs
            states, anchor, h_kept = ops.handoff(
                p, states, anchor, sid, hmd, k_sel)
            prev_x = (ops.stage_x(sid, states) if telemetry is not None
                      else None)

            def sel_round(args):
                states, anchor = args
                cand = ops.output(sid, states)
                best, kept = ops.select2(p, anchor, cand, k_sel)
                sub = runner_lib.evaluate(p, best, f_star)
                return states, best, sub, kept

            def alg_round(args):
                states, anchor = args
                states = ops.round(p, sid, states, k_round, scale)
                sub = runner_lib.evaluate(p, ops.output(sid, states), f_star)
                return states, anchor, sub, jnp.asarray(False)

            states, anchor, sub, s_kept = jax.lax.cond(
                knd == 1, sel_round, alg_round, (states, anchor))
            if telemetry is None:
                return (states, anchor), (sub, h_kept | s_kept)
            x_eval = (ops.output(sid, states) if telemetry.grad_norm
                      else None)
            taps = obs_tel.round_taps(
                telemetry, problem=p, prev_x=prev_x,
                new_x=ops.stage_x(sid, states), x_eval=x_eval, stage=sid)
            return (states, anchor), (sub, h_kept | s_kept, taps)

        return body

    # -- executor ----------------------------------------------------------

    def executor_body(self, problem, rounds: int, comm: bool = False,
                      telemetry=None):
        """Unjitted single-scan chain executor.

        Returns ``fn(spec, x0, states0, key, eta_scale) -> (x_hat, history,
        sel_flags)`` where ``spec`` is the PROBLEM OPERAND (a ``ProblemSpec``
        pytree; None for legacy closure problems, which the executor then
        captures), ``states0`` is the tuple of per-stage initial states
        (their ``.eta`` fields carry any sweep stepsize scaling),
        ``eta_scale`` is the [R] per-round η multiplier operand (see
        ``eta_schedule``) and ``sel_flags`` is a [R] bool vector whose
        entries at ``schedule.sel_indices`` record whether selection kept
        the pre-stage anchor. The cache key is the spec's structural
        identity, so a ζ/σ grid of same-shaped problems shares one compile.

        With ``comm=True`` the signature grows ``(…, masks, comm0)`` — the
        [R, N] participation schedule and the initial ``CommState`` — and the
        executor returns ``(x_hat, history, sel_flags, bits_up, bits_down)``.
        One ``CommState`` is carried through the whole chain (residuals and
        bit meters persist across stage handoffs) and injected into the
        active stage's state each round; selection rounds are billed at the
        Lemma H.2 cost (2 candidates down, 1 scalar per candidate up).

        ``telemetry`` (a ``repro.obs.Telemetry``, part of the cache key)
        appends the per-round taps dict — stage index included — as a
        trailing scan output on either variant; ``None`` traces exactly the
        pre-telemetry jaxpr.
        """
        key = ("chain-body", self._key(), runner_lib.problem_key(problem),
               rounds, comm, telemetry)
        fn = runner_lib._cache_get(key)
        if fn is not None:
            return fn

        _, resolve = runner_lib._bind(problem)

        sched = self._schedule(rounds)
        stages = tuple(self.stages)
        ops = self._round_ops(problem)
        sel_s = (self.selection_s if self.selection_s > 0
                 else problem.num_clients)
        stage_id = jnp.asarray(sched.stage_id)
        kind = jnp.asarray(sched.kind)
        hmode = jnp.asarray(sched.hmode)

        if not comm:

            def executor(spec, x0, states0, key, eta_scale):
                from repro.core.algorithms import base as algo_base

                p = resolve(spec)
                for st in states0:
                    algo_base.audit_state(st)  # protocol check, once per trace
                runner_lib.TRACE_COUNTS[f"chain/{self.name}"] += 1
                f_star = runner_lib.f_star_operand(p)
                keys_r, keys_s = self._derive_keys(sched, key)

                (states, _), ys = jax.lax.scan(
                    self._plain_scan_body(ops, p, f_star, telemetry),
                    (states0, x0),
                    (keys_r, keys_s, stage_id, kind, hmode, eta_scale))
                x_hat = stages[-1].output(states[-1])
                if telemetry is None:
                    history, kept_flags = ys
                    return x_hat, history, kept_flags
                history, kept_flags, taps = ys
                return x_hat, history, kept_flags, taps

        else:

            def executor(spec, x0, states0, key, eta_scale, masks, comm0):
                from repro.comm import config as comm_cfg
                from repro.core.algorithms import base as algo_base
                from repro.obs import telemetry as obs_tel

                p = resolve(spec)
                for st in states0:
                    algo_base.audit_state(st)
                runner_lib.TRACE_COUNTS[f"chain-comm/{self.name}"] += 1
                f_star = runner_lib.f_star_operand(p)
                keys_r, keys_s = self._derive_keys(sched, key)
                # selection broadcasts the whole parameter pytree (leaf dims
                # are static under trace)
                sel_up, sel_down = comm_cfg.selection_round_bits(x0, sel_s)

                def body(carry, xs):
                    states, anchor, comm_st = carry
                    k_round, k_sel, sid, knd, hmd, scale, mask = xs
                    comm_st = _reset_residuals(
                        comm_cfg.zero_round_bits(comm_st), hmd)
                    states, anchor, h_kept = ops.handoff(
                        p, states, anchor, sid, hmd, k_sel)
                    prev_x = (ops.stage_x(sid, states)
                              if telemetry is not None else None)

                    def sel_round(args):
                        states, anchor, comm_st = args
                        cand = ops.output(sid, states)
                        best, kept = ops.select2(p, anchor, cand, k_sel)
                        sub = runner_lib.evaluate(p, best, f_star)
                        return states, best, comm_st, sub, kept

                    def alg_round(args):
                        states, anchor, comm_st = args
                        states, comm_st = ops.round_comm(
                            p, sid, states, comm_st, k_round, scale, mask)
                        sub = runner_lib.evaluate(
                            p, ops.output(sid, states), f_star)
                        return states, anchor, comm_st, sub, jnp.asarray(False)

                    states, anchor, comm_st, sub, s_kept = jax.lax.cond(
                        knd == 1, sel_round, alg_round,
                        (states, anchor, comm_st))

                    # Lemma H.2 selections (explicit rounds AND inline
                    # handoffs) bill their candidate broadcasts / value
                    # uplinks on top of whatever the stage round accounted.
                    did_sel = (knd == 1) | (hmd == _H_SELECT)
                    comm_st = comm_st._replace(
                        bits_up=comm_st.bits_up
                        + jnp.where(did_sel, sel_up, 0.0),
                        bits_down=comm_st.bits_down
                        + jnp.where(did_sel, sel_down, 0.0))
                    if telemetry is None:
                        return ((states, anchor, comm_st),
                                (sub, h_kept | s_kept,
                                 comm_st.bits_up, comm_st.bits_down))
                    x_eval = (ops.output(sid, states) if telemetry.grad_norm
                              else None)
                    taps = obs_tel.round_taps(
                        telemetry, problem=p, prev_x=prev_x,
                        new_x=ops.stage_x(sid, states), x_eval=x_eval,
                        comm=comm_st, mask=mask, stage=sid,
                        bits_up=comm_st.bits_up,
                        bits_down=comm_st.bits_down)
                    return ((states, anchor, comm_st),
                            (sub, h_kept | s_kept,
                             comm_st.bits_up, comm_st.bits_down, taps))

                (states, _, _), ys = jax.lax.scan(
                    body, (states0, x0, comm0),
                    (keys_r, keys_s, stage_id, kind, hmode, eta_scale,
                     masks))
                x_hat = stages[-1].output(states[-1])
                if telemetry is None:
                    history, kept_flags, bits_up, bits_down = ys
                    return x_hat, history, kept_flags, bits_up, bits_down
                history, kept_flags, bits_up, bits_down, taps = ys
                return (x_hat, history, kept_flags, bits_up, bits_down,
                        taps)

        return runner_lib._cache_put(key, executor)

    def executor(self, problem, rounds: int, comm: bool = False,
                 telemetry=None):
        """The jitted, module-cached chain executor.

        ``states0`` (argnum 2) is donated — the per-stage scan carry is
        rebuilt fresh by every caller (``init_states``), so its buffers are
        free for the outputs on donation-capable backends. The comm variant
        also donates the initial ``CommState`` (argnum 6, built fresh from
        ``CommConfig.init_state``) but NOT the masks: ``run`` forwards
        user-supplied ``comm_masks`` arrays there. Donated argnums are part
        of the cache key.
        """
        donate = (2, 6) if comm else (2,)
        key = ("chain-jit", self._key(), runner_lib.problem_key(problem),
               rounds, comm, telemetry, donate)
        fn = runner_lib._cache_get(key)
        if fn is not None:
            return fn
        return runner_lib._cache_put(
            key, jax.jit(self.executor_body(problem, rounds, comm, telemetry),
                         donate_argnums=donate))

    def selection_executor_body(self, problem, rounds: int, telemetry=None):
        """The policy-selection chain executor (comm-enabled).

        Returns ``fn(spec, x0, states0, key, eta_scale, sel_keys, pparams,
        pstate0, comm0) -> (x_hat, history, kept_flags, bits_up, bits_down,
        masks, pstate)``.  Like the ``comm=True`` executor but per-round
        participation comes from ``selection.policies.round_select`` instead
        of a precomputed [R, N] mask schedule: the policy (a
        ``PolicyParams`` switch-index operand) sees the ACTIVE stage's
        post-handoff iterate each scheduled round and its ``PolicyState``
        rides the scan carry.  The policy advances on Lemma H.2 selection
        rounds too (one ``sel_keys`` row per scheduled round, so the key
        stream stays aligned with the schedule); probing policies bill
        their value probe every round on top of the stage/selection bits.

        With ``telemetry`` set (part of the cache key) the scan additionally
        emits the per-round taps dict — policy-state summaries and the
        active stage index included — as a trailing output.
        """
        key = ("chain-sel-body", self._key(),
               runner_lib.problem_key(problem), rounds, telemetry)
        fn = runner_lib._cache_get(key)
        if fn is not None:
            return fn

        _, resolve = runner_lib._bind(problem)

        sched = self._schedule(rounds)
        stages = tuple(self.stages)
        ops = self._round_ops(problem)
        sel_s = (self.selection_s if self.selection_s > 0
                 else problem.num_clients)
        stage_id = jnp.asarray(sched.stage_id)
        kind = jnp.asarray(sched.kind)
        hmode = jnp.asarray(sched.hmode)

        def executor(spec, x0, states0, key, eta_scale, sel_keys, pparams,
                     pstate0, comm0):
            from repro.comm import config as comm_cfg
            from repro.core.algorithms import base as algo_base
            from repro.obs import telemetry as obs_tel
            from repro.selection import policies as pol

            p = resolve(spec)
            for st in states0:
                algo_base.audit_state(st)
            runner_lib.TRACE_COUNTS[f"chain-sel/{self.name}"] += 1
            f_star = runner_lib.f_star_operand(p)
            keys_r, keys_s = self._derive_keys(sched, key)
            sel_up, sel_down = comm_cfg.selection_round_bits(x0, sel_s)
            extra_up = pol.probe_bits(pparams, p.num_clients)

            def body(carry, xs):
                states, anchor, comm_st, pstate = carry
                k_round, k_sel, sid, knd, hmd, scale, k_pol = xs
                comm_st = _reset_residuals(
                    comm_cfg.zero_round_bits(comm_st), hmd)
                states, anchor, h_kept = ops.handoff(
                    p, states, anchor, sid, hmd, k_sel)
                prev_x = (ops.stage_x(sid, states) if telemetry is not None
                          else None)
                mask, pstate = pol.round_select(
                    p, ops.stage_x(sid, states), pstate, pparams, k_pol)

                def sel_round(args):
                    states, anchor, comm_st = args
                    cand = ops.output(sid, states)
                    best, kept = ops.select2(p, anchor, cand, k_sel)
                    sub = runner_lib.evaluate(p, best, f_star)
                    return states, best, comm_st, sub, kept

                def alg_round(args):
                    states, anchor, comm_st = args
                    states, comm_st = ops.round_comm(
                        p, sid, states, comm_st, k_round, scale, mask)
                    sub = runner_lib.evaluate(
                        p, ops.output(sid, states), f_star)
                    return states, anchor, comm_st, sub, jnp.asarray(False)

                states, anchor, comm_st, sub, s_kept = jax.lax.cond(
                    knd == 1, sel_round, alg_round,
                    (states, anchor, comm_st))

                did_sel = (knd == 1) | (hmd == _H_SELECT)
                comm_st = comm_st._replace(
                    bits_up=comm_st.bits_up
                    + jnp.where(did_sel, sel_up, 0.0) + extra_up,
                    bits_down=comm_st.bits_down
                    + jnp.where(did_sel, sel_down, 0.0))
                if telemetry is None:
                    return ((states, anchor, comm_st, pstate),
                            (sub, h_kept | s_kept,
                             comm_st.bits_up, comm_st.bits_down, mask))
                x_eval = (ops.output(sid, states) if telemetry.grad_norm
                          else None)
                taps = obs_tel.round_taps(
                    telemetry, problem=p, prev_x=prev_x,
                    new_x=ops.stage_x(sid, states), x_eval=x_eval,
                    comm=comm_st, mask=mask, pstate=pstate, stage=sid,
                    bits_up=comm_st.bits_up, bits_down=comm_st.bits_down)
                return ((states, anchor, comm_st, pstate),
                        (sub, h_kept | s_kept,
                         comm_st.bits_up, comm_st.bits_down, mask, taps))

            (states, _, _, pstate), ys = jax.lax.scan(
                body, (states0, x0, comm0, pstate0),
                (keys_r, keys_s, stage_id, kind, hmode, eta_scale,
                 sel_keys))
            x_hat = stages[-1].output(states[-1])
            if telemetry is None:
                history, kept_flags, bits_up, bits_down, masks = ys
                return (x_hat, history, kept_flags, bits_up, bits_down,
                        masks, pstate)
            history, kept_flags, bits_up, bits_down, masks, taps = ys
            return (x_hat, history, kept_flags, bits_up, bits_down, masks,
                    pstate, taps)

        return runner_lib._cache_put(key, executor)

    def fraction_executor_body(self, problem, rounds: int):
        """The schedule-as-OPERAND chain executor (local-fraction sweeps).

        ``executor_body`` bakes this chain's ``fractions`` into the trace
        twice: the per-stage key derivation and the selection-row indices.
        This variant instead takes the whole per-round schedule as data —

          ``fn(spec, x0, states0, keys_r, keys_s, stage_id, kind, hmode,
          eta_scale) -> (x_hat, history, kept_flags)``

        with ``keys_r``/``keys_s`` the [R, 2] precomputed key streams
        (``_derive_keys`` run host-side) and ``stage_id``/``kind``/``hmode``
        the [R] rows of ``_schedule``. The App. I.2 ``local_fraction``
        tuning grid then rides ONE compile: every fraction of a fixed stage
        tuple has the same schedule LENGTH (``schedule_len``), so a stacked
        fraction axis is just more operand rows — and each row replays the
        exact key streams ``Chain.run``'s executor derives in-trace for the
        corresponding per-fraction chain. Cache key:
        ``_fraction_free_key`` — chains differing only in ``fractions``
        share the compile.
        """
        key = ("chain-frac-body", self._fraction_free_key(),
               runner_lib.problem_key(problem), rounds)
        fn = runner_lib._cache_get(key)
        if fn is not None:
            return fn

        _, resolve = runner_lib._bind(problem)
        stages = tuple(self.stages)
        ops = self._round_ops(problem)

        def executor(spec, x0, states0, keys_r, keys_s, stage_id, kind,
                     hmode, eta_scale):
            from repro.core.algorithms import base as algo_base

            p = resolve(spec)
            for st in states0:
                algo_base.audit_state(st)
            runner_lib.TRACE_COUNTS[f"chain-frac/{self.name}"] += 1
            f_star = runner_lib.f_star_operand(p)

            (states, _), (history, kept_flags) = jax.lax.scan(
                self._plain_scan_body(ops, p, f_star), (states0, x0),
                (keys_r, keys_s, stage_id, kind, hmode, eta_scale))
            x_hat = stages[-1].output(states[-1])
            return x_hat, history, kept_flags

        return runner_lib._cache_put(key, executor)

    def with_local_fraction(self, fraction: float) -> "Chain":
        """This chain with its FIRST stage's round share set to ``fraction``
        (two-stage chains only — the paper's Algo 1 tuning knob)."""
        if len(self.stages) != 2:
            raise ValueError(
                f"local_fraction is the two-stage FedChain knob; this chain "
                f"has {len(self.stages)} stages")
        if not 0.0 < fraction < 1.0:
            raise ValueError(f"local_fraction must be in (0, 1), "
                             f"got {fraction}")
        return dataclasses.replace(
            self, fractions=(fraction, 1.0 - fraction))

    def init_states(self, problem, x0, eta_scale=None):
        """Per-stage initial states; ``eta_scale`` multiplies every stage's
        base stepsize (the sweep engine's batched axis)."""
        states = tuple(a.init(problem, x0) for a in self.stages)
        if eta_scale is not None:
            states = tuple(s._replace(eta=s.eta * eta_scale) for s in states)
        return states

    def run(self, problem, x0, rounds: int, key, *, decay: Optional[dict] = None,
            eta_scale=None, comm=None, comm_masks=None, telemetry=None):
        """Execute the chain for a total budget of ``rounds`` communication
        rounds — a single compiled call regardless of stage count, decay
        schedule, or comm config (decay multipliers, participation masks and
        compressor knobs are all executor operands).

        ``comm`` (a ``repro.comm.CommConfig``) enables compressed uplinks +
        partial participation + bits accounting; ``comm_masks`` overrides the
        config-derived [R, N] schedule. ``telemetry`` (a
        ``repro.obs.Telemetry``) returns the per-round taps in the result's
        ``diagnostics``; ``None`` is bitwise identical to a run without the
        telemetry layer.
        """
        sched = self._schedule(rounds)
        eta_arr = self.eta_schedule(rounds, decay)
        states0 = self.init_states(problem, x0, eta_scale)
        spec = runner_lib.as_spec(problem)
        bits_up = bits_down = taps = None
        if comm is None:
            fn = self.executor(problem, rounds, telemetry=telemetry)
            states0 = runner_lib.dealias_donated(
                states0, spec, x0, key, eta_arr)
            if telemetry is None:
                x_hat, history, kept_flags = fn(
                    spec, x0, states0, key, eta_arr)
            else:
                x_hat, history, kept_flags, taps = fn(
                    spec, x0, states0, key, eta_arr)
        else:
            from repro.comm import config as comm_cfg

            for stage, st in zip(self.stages, states0):
                comm_cfg.require_comm_leaf(st, stage.name)
            n_clients = problem.num_clients
            masks = (comm.round_masks(len(sched.stage_id), n_clients)
                     if comm_masks is None
                     else jnp.asarray(comm_masks, jnp.float32))
            comm0 = comm.init_state(n_clients, x0)
            fn = self.executor(problem, rounds, comm=True,
                               telemetry=telemetry)
            states0 = runner_lib.dealias_donated(
                states0, spec, x0, key, eta_arr, masks)
            comm0 = runner_lib.dealias_donated(
                comm0, spec, x0, states0, key, eta_arr, masks)
            if telemetry is None:
                x_hat, history, kept_flags, bits_up, bits_down = fn(
                    spec, x0, states0, key, eta_arr, masks, comm0)
            else:
                x_hat, history, kept_flags, bits_up, bits_down, taps = fn(
                    spec, x0, states0, key, eta_arr, masks, comm0)
        kept = np.asarray(kept_flags)
        return ChainResult(
            x_hat=x_hat,
            history=history,
            switch_rounds=list(sched.switch_rounds[:-1]),
            selected_initial=[bool(kept[i]) for i in sched.sel_indices],
            bits_up=bits_up,
            bits_down=bits_down,
            diagnostics=taps,
        )


def fedchain(a_local, a_global, *, local_fraction: float = 0.5, **kw) -> Chain:
    """The canonical two-stage FedChain (Algo 1)."""
    name = kw.pop("name", f"{a_local.name}->{a_global.name}")
    return Chain(
        stages=[a_local, a_global],
        fractions=[local_fraction, 1.0 - local_fraction],
        name=name,
        **kw,
    )
