"""Vmapped sweep engine: one compiled call for seeds × stepsizes × problems.

FedChain's experiment grids (Tables 1–4, Fig. 2) repeat the same algorithm
over seeds, stepsizes, heterogeneity levels ζ, noise levels σ and problem
instances. ``run_sweep`` vmaps the single-compile executors from
``runner``/``chain`` over all of these axes and jits the whole grid, so a
P × S × E sweep costs ONE trace + one device dispatch instead of P·S·E
re-traced round loops.

Problems are OPERANDS (``repro.data.spec.ProblemSpec``): sweep functions are
cached per ``(algo-or-chain, problem STRUCTURE, rounds)`` — family tag +
shapes, never instance identity — so repeated sweeps across ζ values, σ
values or fresh instances never re-trace, and the ``problems=`` axis batches
a stacked spec (``spec.stack_specs``) through the same compiled cell.

Stepsize semantics
------------------
* Plain algorithms, ``eta_mode="absolute"`` (default): each grid value is the
  stepsize itself (``state.eta = η``), matching ``runner.run(..., eta=η)``.
* Plain algorithms, ``eta_mode="scale"``: grid values multiply the state's
  own initialized stepsize — use this for algorithms that derive η from
  problem constants (e.g. SSNM's Thm. D.5 stepsize).
* Chains: grid values are always *multipliers* applied to every stage's base
  stepsize (a chain has one η per stage, so an absolute grid is ambiguous),
  matching ``Chain.run(..., eta_scale=m)``.

Because η lives in algorithm state (the uniform state protocol of
``algorithms.base``), batching stepsizes is just a batched ``state.eta`` leaf
— no algorithm code is sweep-aware.

Multi-method stacking
---------------------
``run_method_sweep`` batches SEVERAL method instances whose states share one
pytree structure (SGD at several ``mu_avg``, FedAvg at several local-step
counts, mixed output modes, …) into one compiled call: the method index is
an operand dispatched by ``lax.switch`` inside the executor
(``runner.method_executor_body``), riding the same uniform-state protocol
that batches η — the methods axis is just a stacked state plus an index.
Cost model: stacking trades COMPILES for FLOPs. Because the switch index is
batched, vmap evaluates every branch and selects, so each grid row runs all
M methods' rounds (M× device work vs a per-method loop, which — thanks to
structural executor caching — pays at most M compiles). Stack when traces
dominate (many short cold-path configs); loop per method for long warm
grids.

Communication sweeps
--------------------
``run_sweep(..., comm=CommConfig(...))`` threads the communication subsystem
(``repro.comm``) through every grid cell: uplinks are compressed, per-round
participation masks (one independent [R, N] schedule per seed) ride the scan
as data, and ``SweepResult.bits_up``/``bits_down`` record the exact per-round
wire cost — the suboptimality-vs-bits frontier. All comm knobs are operands:
switching compressor, bit-width or participation fraction reuses the same
compiled grid (``runner.TRACE_COUNTS`` stays flat). Comm composes with the
``problems=`` axis — mask schedules batch per (problem, seed) cell (fold
p·S + s of the config's mask seed) and the ``CommState`` rides the vmapped
state like any other leaf, so a bits-accounted ζ×σ frontier over a whole
problem grid is still ONE compile. Parameters may be arbitrary pytrees
(vision MLPs): the comm layer operates leaf-wise (``repro.comm``).

Decay sweeps: stepsize-decay multipliers are an executor *operand* (PR-2),
so ``run_decay_sweep`` batches a ``decay_factor`` grid through one compile
of the same chain executor ``run_sweep`` uses. Local-fraction sweeps
(``run_fraction_sweep``) go further: the chain's whole per-round schedule —
stage assignment, selection placement, key streams — is an operand
(``Chain.fraction_executor_body``), so the App. I.2 tuning grid rides one
compile too.

Device sharding
---------------
Grid cells are built by the ``make_*_cell`` factories below and batched two
ways from the same cells: the vmapped engine here (a flattened problems ×
seeds cells axis × a dense stepsize axis), or sharded over a ``('grid',)``
device mesh via ``run_sweep(..., mesh=...)`` / ``run_fraction_sweep(...,
mesh=...)`` (``repro.dist.grid``), which partitions the identical cell
stacks across devices with ``shard_map`` — bitwise the same results, one
compile either way.

Memory model
------------
Operand layouts. A ``problems=`` sweep flattens problems × seeds into one
cells axis (c = p·S + s, the ``repro.dist.partition`` contract). Two
operand layouts feed it:

* ``operand_layout="indexed"`` (default) — ONE O(P) stacked spec (and one
  [P, …] x0 stack) rides the call unbatched, plus a per-cell int32 problem
  index ``pidx[c] = c // S``; each cell gathers its own spec leaves
  (``make_indexed_cell``). Spec-operand memory is O(P) regardless of the
  seed count.
* ``operand_layout="stacked"`` — the historical layout: every spec data
  leaf materialized once per cell (``jnp.repeat`` along the cells axis),
  O(P·S) operand memory. Kept as the reference the indexed path is tested
  bitwise against (``benchmarks/memory_bench.py`` measures both).

The in-cell gather is exact (a gather of identical rows), and every
per-cell op is batch-invariant, so the two layouts are BITWISE identical —
on the vmapped engine here and on the sharded one (where the indexed
layout replicates the O(P) stack across shards and shards only ``pidx``).

Donation contract. Every jitted executor donates its call-private operands
(``jax.jit(..., donate_argnums=...)``): the scan-carry state0/states0 in
``runner``/``chain`` executors, and the per-cell key/mask/index/stepsize
stacks here — never ``spec``/``x0`` (caller-owned; donating them would
invalidate the user's arrays on donation-capable backends). Callers of the
cached executors must therefore pass freshly built arrays for the donated
positions — everything ``run_sweep`` constructs per call. On CPU donation
is a no-op (JAX's "donated buffers were not usable" warning is filtered in
``runner``).

Executor cache keys. The executor LRU (``runner._EXECUTOR_CACHE``) keys
every jitted grid on (algo/chain identity, problem STRUCTURE, rounds,
flags, operand layout, donated argnums) plus the Pallas-dispatch env — so
switching layout or donation never silently reuses a stale compile, and
numeric knobs (ζ, σ, compressor, …) never force a new one.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import chain as chain_lib
from repro.core import runner as runner_lib
from repro.core import tree_math as tm
from repro.obs.profile import annotate


@dataclasses.dataclass
class SweepResult:
    """Results over the grid.

    Leading axes are ``[n_seeds, n_etas]``; a ``problems=`` sweep prepends a
    problem axis (``[n_problems, n_seeds, n_etas]``) and a
    ``run_method_sweep`` prepends a method axis (``[n_methods, …]``) —
    ``problems``/``methods`` are set accordingly.
    """

    history: jnp.ndarray  # [..., S, E, R] per-round suboptimality
    final_sub: jnp.ndarray  # [..., S, E] F(x̂) − F* at the end
    x_hat: object  # pytree, leaves [..., S, E, ...]
    seeds: tuple
    etas: tuple
    selected_initial: Optional[jnp.ndarray] = None  # [..., S, E, n_sel]
    bits_up: Optional[jnp.ndarray] = None  # [S, E, R] per-round uplink bits
    bits_down: Optional[jnp.ndarray] = None  # [S, E, R] downlink bits
    problems: Optional[tuple] = None  # problem names along the leading axis
    methods: Optional[tuple] = None  # method names along the leading axis
    diagnostics: Optional[dict] = None  # per-round obs taps, leaves [..., R]

    def cumulative_bits(self):
        """[S, E, R] total (up + down) bits through each round, float64 —
        the x-axis of a cost-vs-accuracy frontier."""
        if self.bits_up is None:
            raise ValueError("not a comm sweep: no bits were accounted")
        per_round = (np.asarray(self.bits_up, np.float64)
                     + np.asarray(self.bits_down, np.float64))
        return np.cumsum(per_round, axis=-1)


def make_algo_cell(algo, problem, rounds: int, eval_output: bool,
                   eta_mode: str, tag: str, telemetry=None):
    """ONE grid cell of a plain-algorithm sweep: ``cell(spec, x0, key, eta)``.

    The vmapped engine below and the sharded engine (``repro.dist.grid``)
    both build their grids from these cell factories, so a sharded sweep
    runs bit-for-bit the same per-cell computation as the single-device one
    — only the batching around the cell differs. ``tag`` names the
    ``TRACE_COUNTS`` entry the cell bumps when traced. A non-None
    ``telemetry`` (``repro.obs.Telemetry``) appends the per-round taps dict
    as a trailing output.
    """

    body = runner_lib.executor_body(algo, problem, eval_output, telemetry)
    _, resolve = runner_lib._bind(problem)
    eta_scale = jnp.ones((rounds,), jnp.float32)

    def cell(spec, x0, key, eta):
        p = resolve(spec)
        runner_lib.TRACE_COUNTS[f"{tag}/{algo.name}"] += 1
        state0 = algo.init(p, x0)
        new_eta = (state0.eta * eta if eta_mode == "scale"
                   else jnp.asarray(eta, jnp.result_type(state0.eta)))
        state0 = state0._replace(eta=new_eta)
        keys = jax.random.split(key, rounds)
        if telemetry is None:
            state, history = body(spec, state0, keys, eta_scale)
        else:
            state, (history, taps) = body(spec, state0, keys, eta_scale)
        x_hat = algo.output(state)
        sub = runner_lib.evaluate(p, x_hat, runner_lib.f_star_operand(p))
        if telemetry is None:
            return x_hat, history, sub
        return x_hat, history, sub, taps

    return cell


def make_algo_comm_cell(algo, problem, rounds: int, eval_output: bool,
                        eta_mode: str, tag: str, telemetry=None):
    """Comm-enabled cell: ``cell(spec, x0, key, eta, masks, comm0)``."""

    body = runner_lib.comm_executor_body(algo, problem, eval_output,
                                         telemetry)
    _, resolve = runner_lib._bind(problem)
    eta_scale = jnp.ones((rounds,), jnp.float32)

    def cell(spec, x0, key, eta, masks, comm0):
        p = resolve(spec)
        runner_lib.TRACE_COUNTS[f"{tag}/{algo.name}"] += 1
        state0 = algo.init(p, x0)
        new_eta = (state0.eta * eta if eta_mode == "scale"
                   else jnp.asarray(eta, jnp.result_type(state0.eta)))
        state0 = state0._replace(eta=new_eta, comm=comm0)
        keys = jax.random.split(key, rounds)
        if telemetry is None:
            state, (history, bits_up, bits_down) = body(
                spec, state0, keys, eta_scale, masks)
        else:
            state, (history, bits_up, bits_down, taps) = body(
                spec, state0, keys, eta_scale, masks)
        x_hat = algo.output(state)
        sub = runner_lib.evaluate(p, x_hat, runner_lib.f_star_operand(p))
        if telemetry is None:
            return x_hat, history, sub, bits_up, bits_down
        return x_hat, history, sub, bits_up, bits_down, taps

    return cell


def make_chain_cell(chain, problem, rounds: int, tag: str, telemetry=None):
    """Chain cell: ``cell(spec, x0, key, mult, eta_scale)``."""

    body = chain.executor_body(problem, rounds, telemetry=telemetry)
    _, resolve = runner_lib._bind(problem)
    sel_idx = jnp.asarray(chain._schedule(rounds).sel_indices, jnp.int32)

    def cell(spec, x0, key, mult, eta_scale):
        p = resolve(spec)
        runner_lib.TRACE_COUNTS[f"{tag}/{chain.name}"] += 1
        states0 = chain.init_states(p, x0, eta_scale=mult)
        if telemetry is None:
            x_hat, history, kept = body(spec, x0, states0, key, eta_scale)
        else:
            x_hat, history, kept, taps = body(spec, x0, states0, key,
                                              eta_scale)
        sub = runner_lib.evaluate(p, x_hat, runner_lib.f_star_operand(p))
        if telemetry is None:
            return x_hat, history, sub, kept[sel_idx]
        return x_hat, history, sub, kept[sel_idx], taps

    return cell


def make_chain_comm_cell(chain, problem, rounds: int, tag: str,
                         telemetry=None):
    """Comm-enabled chain cell:
    ``cell(spec, x0, key, mult, eta_scale, masks, comm0)``."""

    body = chain.executor_body(problem, rounds, comm=True,
                               telemetry=telemetry)
    _, resolve = runner_lib._bind(problem)
    sel_idx = jnp.asarray(chain._schedule(rounds).sel_indices, jnp.int32)

    def cell(spec, x0, key, mult, eta_scale, masks, comm0):
        p = resolve(spec)
        runner_lib.TRACE_COUNTS[f"{tag}/{chain.name}"] += 1
        states0 = chain.init_states(p, x0, eta_scale=mult)
        if telemetry is None:
            x_hat, history, kept, bits_up, bits_down = body(
                spec, x0, states0, key, eta_scale, masks, comm0)
        else:
            x_hat, history, kept, bits_up, bits_down, taps = body(
                spec, x0, states0, key, eta_scale, masks, comm0)
        sub = runner_lib.evaluate(p, x_hat, runner_lib.f_star_operand(p))
        if telemetry is None:
            return x_hat, history, sub, kept[sel_idx], bits_up, bits_down
        return (x_hat, history, sub, kept[sel_idx], bits_up, bits_down,
                taps)

    return cell


def make_chain_fraction_cell(chain, problem, rounds: int, tag: str):
    """Local-fraction-sweep cell over operand schedules:
    ``cell(spec, x0, keys_r, keys_s, stage_id, kind, hmode, eta_scale)``.
    Returns the FULL [R] kept-flags row (selection positions differ per
    fraction, so callers gather them per schedule)."""

    body = chain.fraction_executor_body(problem, rounds)
    _, resolve = runner_lib._bind(problem)

    def cell(spec, x0, keys_r, keys_s, stage_id, kind, hmode, eta_scale):
        p = resolve(spec)
        runner_lib.TRACE_COUNTS[f"{tag}/{chain.name}"] += 1
        states0 = chain.init_states(p, x0)
        x_hat, history, kept = body(spec, x0, states0, keys_r, keys_s,
                                    stage_id, kind, hmode, eta_scale)
        sub = runner_lib.evaluate(p, x_hat, runner_lib.f_star_operand(p))
        return x_hat, history, sub, kept

    return cell


def make_selection_algo_cell(algo, problem, rounds: int, eval_output: bool,
                             eta_mode: str, tag: str, telemetry=None):
    """Policy-selection cell:
    ``cell(spec, x0, pparams, pstate0, key, eta, sel_keys, comm0)``.

    The policy (``PolicyParams``) and its initial state (``PolicyState``)
    are leading operands so the policy-index adapter
    (``make_policy_cell``) can gather them per cell exactly like the
    problem stacks."""

    body = runner_lib.selection_executor_body(algo, problem, eval_output,
                                              telemetry)
    _, resolve = runner_lib._bind(problem)
    eta_scale = jnp.ones((rounds,), jnp.float32)

    def cell(spec, x0, pparams, pstate0, key, eta, sel_keys, comm0):
        p = resolve(spec)
        runner_lib.TRACE_COUNTS[f"{tag}/{algo.name}"] += 1
        state0 = algo.init(p, x0)
        new_eta = (state0.eta * eta if eta_mode == "scale"
                   else jnp.asarray(eta, jnp.result_type(state0.eta)))
        state0 = state0._replace(eta=new_eta, comm=comm0)
        keys = jax.random.split(key, rounds)
        if telemetry is None:
            (state, pstate), (history, bits_up, bits_down, masks) = body(
                spec, state0, keys, eta_scale, sel_keys, pparams, pstate0)
        else:
            (state, pstate), (history, bits_up, bits_down, masks,
                              taps) = body(
                spec, state0, keys, eta_scale, sel_keys, pparams, pstate0)
        x_hat = algo.output(state)
        sub = runner_lib.evaluate(p, x_hat, runner_lib.f_star_operand(p))
        if telemetry is None:
            return x_hat, history, sub, bits_up, bits_down, masks, pstate
        return (x_hat, history, sub, bits_up, bits_down, masks, pstate,
                taps)

    return cell


def make_selection_chain_cell(chain, problem, rounds: int, tag: str,
                              telemetry=None):
    """Policy-selection chain cell:
    ``cell(spec, x0, pparams, pstate0, key, mult, eta_sched, sel_keys,
    comm0)``."""

    body = chain.selection_executor_body(problem, rounds,
                                         telemetry=telemetry)
    _, resolve = runner_lib._bind(problem)
    sel_idx = jnp.asarray(chain._schedule(rounds).sel_indices, jnp.int32)

    def cell(spec, x0, pparams, pstate0, key, mult, eta_sched, sel_keys,
             comm0):
        p = resolve(spec)
        runner_lib.TRACE_COUNTS[f"{tag}/{chain.name}"] += 1
        states0 = chain.init_states(p, x0, eta_scale=mult)
        if telemetry is None:
            x_hat, history, kept, bits_up, bits_down, masks, pstate = body(
                spec, x0, states0, key, eta_sched, sel_keys, pparams,
                pstate0, comm0)
        else:
            (x_hat, history, kept, bits_up, bits_down, masks, pstate,
             taps) = body(
                spec, x0, states0, key, eta_sched, sel_keys, pparams,
                pstate0, comm0)
        sub = runner_lib.evaluate(p, x_hat, runner_lib.f_star_operand(p))
        if telemetry is None:
            return (x_hat, history, sub, kept[sel_idx], bits_up, bits_down,
                    masks, pstate)
        return (x_hat, history, sub, kept[sel_idx], bits_up, bits_down,
                masks, pstate, taps)

    return cell


_OPERAND_LAYOUTS = ("indexed", "stacked")


def check_operand_layout(layout: str) -> str:
    """Validate an ``operand_layout`` value (shared with the sharded
    engine)."""
    if layout not in _OPERAND_LAYOUTS:
        raise ValueError(f"operand_layout must be one of "
                         f"{_OPERAND_LAYOUTS}, got {layout!r}")
    return layout


def make_indexed_cell(cell):
    """O(P) operand adapter around a ``make_*_cell`` cell: the cell's
    leading ``(spec, x0, …)`` operands become ``(spec_stack, x0_stack,
    pidx, …)`` with an in-cell gather of the problem's own leaves.

    Under the engines' batching only ``pidx`` is per-cell (batched /
    shard-sharded) while the stacks ride unbatched (replicated), so spec
    operand memory is O(P) instead of O(P·S). The gather pulls identical
    rows to what the stacked layout materializes per cell, and every
    per-cell op is batch-invariant, so results are bitwise identical.
    """
    def indexed_cell(spec_stack, x0_stack, pidx, *rest):
        spec = jax.tree.map(lambda l: l[pidx], spec_stack)
        x0 = jax.tree.map(lambda l: l[pidx], x0_stack)
        return cell(spec, x0, *rest)

    return indexed_cell


def problem_index_operand(n_probs: int, n_seeds: int) -> jnp.ndarray:
    """The per-cell problem index of the flattened cells axis:
    ``pidx[c] = c // S`` for c = p·S + s (``repro.dist.partition``)."""
    return jnp.arange(n_probs * n_seeds, dtype=jnp.int32) // n_seeds


def build_problem_operands(stacked, x0_stack, keys, n_probs: int,
                           n_seeds: int, layout: str = "indexed"):
    """Materialize the flattened problems × seeds cell operands for the
    vmapped engine (shared with ``benchmarks/memory_bench.py``).

    Returns ``(spec_op, x0_op, pidx, keys_c)``: the indexed layout keeps
    the O(P) stacks and adds an int32 [P·S] problem index; the stacked
    layout repeats every spec/x0 leaf once per seed (O(P·S)) and returns
    ``pidx=None``. ``keys_c`` tiles the per-seed keys per problem either
    way.
    """
    check_operand_layout(layout)
    keys_c = jnp.tile(keys, (n_probs, 1))
    if layout == "stacked":
        spec_op = jax.tree.map(
            lambda l: jnp.repeat(l, n_seeds, axis=0), stacked)
        x0_op = jax.tree.map(
            lambda l: jnp.repeat(l, n_seeds, axis=0), x0_stack)
        return spec_op, x0_op, None, keys_c
    return stacked, x0_stack, problem_index_operand(n_probs, n_seeds), keys_c


def make_policy_cell(cell):
    """O(Q)+O(P) operand adapter around a ``make_selection_*_cell`` cell:
    the cell's leading ``(spec, x0, pparams, pstate0, …)`` operands become
    ``(spec_stack, x0_stack, pol_stack, pst_stack, pidx, qidx, …)`` with
    in-cell gathers — the policies × problems × seeds grid carries ONE
    stacked spec, ONE stacked ``PolicyParams``/``PolicyState`` and two
    int32 per-cell indices (the selection-sweep analogue of
    ``make_indexed_cell``). Both engines batch over this same adapter, so
    sharding stays bitwise."""
    def policy_cell(spec_stack, x0_stack, pol_stack, pst_stack, pidx, qidx,
                    *rest):
        spec = jax.tree.map(lambda l: l[pidx], spec_stack)
        x0 = jax.tree.map(lambda l: l[pidx], x0_stack)
        pparams = jax.tree.map(lambda l: l[qidx], pol_stack)
        pstate0 = jax.tree.map(lambda l: l[qidx], pst_stack)
        return cell(spec, x0, pparams, pstate0, *rest)

    return policy_cell


def policy_index_operands(n_pols: int, n_probs: int, n_seeds: int):
    """Per-cell (qidx, pidx) of the flattened policies × problems × seeds
    cells axis ``c = (q·P + p)·S + s``: ``qidx[c] = c // (P·S)``,
    ``pidx[c] = (c // S) % P``."""
    c = jnp.arange(n_pols * n_probs * n_seeds, dtype=jnp.int32)
    return c // (n_probs * n_seeds), (c // n_seeds) % n_probs


def _sweep_fn_selection_algo(algo, problem, rounds: int, eval_output: bool,
                             eta_mode: str, telemetry=None):
    # donate everything but the problem stacks: the policy stacks, index
    # vectors, keys and comm state are all built fresh per call
    donate = (2, 3, 4, 5, 6, 7, 8, 9)
    key = ("sweep-sel-algo", algo, runner_lib.problem_key(problem), rounds,
           eval_output, eta_mode, telemetry, donate)
    fn = runner_lib._cache_get(key)
    if fn is not None:
        return fn

    cell = make_selection_algo_cell(algo, problem, rounds, eval_output,
                                    eta_mode, "sweep-sel", telemetry)
    pcell = make_policy_cell(cell)
    # (spec, x0, pol, pst, pidx, qidx, key, eta, sel_keys, comm0):
    # inner vmap is the dense η axis, outer the flattened cells axis
    inner = jax.vmap(pcell, in_axes=(None, None, None, None, None, None,
                                     None, 0, None, None))
    grid = jax.vmap(inner, in_axes=(None, None, None, None, 0, 0, 0, None,
                                    0, None))
    return runner_lib._cache_put(key, jax.jit(grid, donate_argnums=donate))


def _sweep_fn_selection_chain(chain, problem, rounds: int, telemetry=None):
    donate = (2, 3, 4, 5, 6, 7, 8, 9, 10)
    key = ("sweep-sel-chain", chain._key(), runner_lib.problem_key(problem),
           rounds, telemetry, donate)
    fn = runner_lib._cache_get(key)
    if fn is not None:
        return fn

    cell = make_selection_chain_cell(chain, problem, rounds, "sweep-sel",
                                     telemetry)
    pcell = make_policy_cell(cell)
    # (spec, x0, pol, pst, pidx, qidx, key, mult, eta_sched, sel_keys, comm0)
    inner = jax.vmap(pcell, in_axes=(None, None, None, None, None, None,
                                     None, 0, None, None, None))
    grid = jax.vmap(inner, in_axes=(None, None, None, None, 0, 0, 0, None,
                                    None, 0, None))
    return runner_lib._cache_put(key, jax.jit(grid, donate_argnums=donate))


def _sweep_fn_algo(algo, problem, rounds: int, eval_output: bool,
                   eta_mode: str, problem_axis: bool = False,
                   layout: str = "indexed", telemetry=None):
    """The seeds × etas grid cell; ``problem_axis`` wraps one more vmap over
    the problem operands — one compiled call for the whole problems × seeds
    × stepsizes grid (O(P) spec memory under the indexed layout)."""
    if problem_axis and layout == "indexed":
        donate = (2, 3, 4)  # pidx, keys, etas — never spec/x0
    else:
        donate = (2, 3)  # keys, etas
    key = ("sweep-algo", algo, runner_lib.problem_key(problem), rounds,
           eval_output, eta_mode, problem_axis,
           layout if problem_axis else None, telemetry, donate)
    fn = runner_lib._cache_get(key)
    if fn is not None:
        return fn

    tag = "sweep-probs" if problem_axis else "sweep"
    cell = make_algo_cell(algo, problem, rounds, eval_output, eta_mode, tag,
                          telemetry)
    # problems × seeds ride ONE flattened cells axis (c = p·S + s) — the
    # same batching structure the sharded engine (repro.dist.grid) runs per
    # shard, so sharding is bitwise. Indexed layout: the O(P) spec/x0
    # stacks ride unbatched and only pidx is per-cell.
    if problem_axis and layout == "indexed":
        icell = make_indexed_cell(cell)
        inner = jax.vmap(icell, in_axes=(None, None, None, None, 0))
        grid = jax.vmap(inner, in_axes=(None, None, 0, 0, None))
    else:
        inner = jax.vmap(cell, in_axes=(None, None, None, 0))
        grid = jax.vmap(inner, in_axes=((0, 0, 0, None) if problem_axis
                                        else (None, None, 0, None)))
    return runner_lib._cache_put(key, jax.jit(grid, donate_argnums=donate))


def _sweep_fn_algo_comm(algo, problem, rounds: int, eval_output: bool,
                        eta_mode: str, problem_axis: bool = False,
                        layout: str = "indexed", telemetry=None):
    if problem_axis and layout == "indexed":
        donate = (2, 3, 4, 5, 6)  # pidx, keys, etas, masks, comm0
    else:
        donate = (2, 3, 4, 5)  # keys, etas, masks, comm0
    key = ("sweep-algo-comm", algo, runner_lib.problem_key(problem), rounds,
           eval_output, eta_mode, problem_axis,
           layout if problem_axis else None, telemetry, donate)
    fn = runner_lib._cache_get(key)
    if fn is not None:
        return fn

    tag = "sweep-comm-probs" if problem_axis else "sweep-comm"
    cell = make_algo_comm_cell(algo, problem, rounds, eval_output, eta_mode,
                               tag, telemetry)
    # masks batch with the cells axis (one independent [R, N] schedule per
    # (problem, seed) cell); the initial CommState is identical across the
    # grid (zeros) so it broadcasts
    if problem_axis and layout == "indexed":
        icell = make_indexed_cell(cell)
        inner = jax.vmap(icell,
                         in_axes=(None, None, None, None, 0, None, None))
        grid = jax.vmap(inner, in_axes=(None, None, 0, 0, None, 0, None))
    else:
        inner = jax.vmap(cell, in_axes=(None, None, None, 0, None, None))
        grid = jax.vmap(inner, in_axes=(
            (0, 0, 0, None, 0, None) if problem_axis
            else (None, None, 0, None, 0, None)))
    return runner_lib._cache_put(key, jax.jit(grid, donate_argnums=donate))


def _sweep_fn_chain(chain, problem, rounds: int, problem_axis: bool = False,
                    layout: str = "indexed", telemetry=None):
    if problem_axis and layout == "indexed":
        donate = (2, 3, 4, 5)  # pidx, keys, mults, eta_sched
    else:
        donate = (2, 3, 4)  # keys, mults, eta_sched
    key = ("sweep-chain", chain._key(), runner_lib.problem_key(problem),
           rounds, problem_axis, layout if problem_axis else None,
           telemetry, donate)
    fn = runner_lib._cache_get(key)
    if fn is not None:
        return fn

    tag = "sweep-probs" if problem_axis else "sweep"
    cell = make_chain_cell(chain, problem, rounds, tag, telemetry)
    if problem_axis and layout == "indexed":
        icell = make_indexed_cell(cell)
        inner = jax.vmap(icell,
                         in_axes=(None, None, None, None, 0, None))
        grid = jax.vmap(inner, in_axes=(None, None, 0, 0, None, None))
    else:
        inner = jax.vmap(cell, in_axes=(None, None, None, 0, None))
        grid = jax.vmap(inner, in_axes=((0, 0, 0, None, None) if problem_axis
                                        else (None, None, 0, None, None)))
    return runner_lib._cache_put(key, jax.jit(grid, donate_argnums=donate))


def _sweep_fn_chain_comm(chain, problem, rounds: int,
                         problem_axis: bool = False,
                         layout: str = "indexed", telemetry=None):
    if problem_axis and layout == "indexed":
        donate = (2, 3, 4, 5, 6, 7)  # pidx, keys, mults, η-sched, masks, comm0
    else:
        donate = (2, 3, 4, 5, 6)
    key = ("sweep-chain-comm", chain._key(), runner_lib.problem_key(problem),
           rounds, problem_axis, layout if problem_axis else None,
           telemetry, donate)
    fn = runner_lib._cache_get(key)
    if fn is not None:
        return fn

    tag = "sweep-comm-probs" if problem_axis else "sweep-comm"
    cell = make_chain_comm_cell(chain, problem, rounds, tag, telemetry)
    if problem_axis and layout == "indexed":
        icell = make_indexed_cell(cell)
        inner = jax.vmap(
            icell, in_axes=(None, None, None, None, 0, None, None, None))
        grid = jax.vmap(inner,
                        in_axes=(None, None, 0, 0, None, None, 0, None))
    else:
        inner = jax.vmap(cell,
                         in_axes=(None, None, None, 0, None, None, None))
        grid = jax.vmap(inner, in_axes=(
            (0, 0, 0, None, None, 0, None) if problem_axis
            else (None, None, 0, None, None, 0, None)))
    return runner_lib._cache_put(key, jax.jit(grid, donate_argnums=donate))


def _sweep_fn_chain_fraction(chain, problem, rounds: int):
    donate = (2, 3, 4, 5, 6, 7)  # every operand row but spec/x0
    key = ("sweep-chain-frac", chain._fraction_free_key(),
           runner_lib.problem_key(problem), rounds, donate)
    fn = runner_lib._cache_get(key)
    if fn is not None:
        return fn

    cell = make_chain_fraction_cell(chain, problem, rounds, "sweep-frac")
    # axes: seeds (outer) × fractions (inner); key streams vary on both,
    # schedule rows on the fraction axis only
    grid = jax.vmap(jax.vmap(cell, in_axes=(None, None, 0, 0, 0, 0, 0, 0)),
                    in_axes=(None, None, 0, 0, None, None, None, None))
    return runner_lib._cache_put(key, jax.jit(grid, donate_argnums=donate))


def _sweep_fn_chain_decay(chain, problem, rounds: int):
    donate = (2, 3)  # keys, eta_scale rows
    key = ("sweep-chain-decay", chain._key(), runner_lib.problem_key(problem),
           rounds, donate)
    fn = runner_lib._cache_get(key)
    if fn is not None:
        return fn


    body = chain.executor_body(problem, rounds)  # SAME executor as run_sweep
    _, resolve = runner_lib._bind(problem)

    def cell(spec, x0, key, eta_scale):
        p = resolve(spec)
        runner_lib.TRACE_COUNTS[f"sweep-decay/{chain.name}"] += 1
        states0 = chain.init_states(p, x0)
        x_hat, history, _ = body(spec, x0, states0, key, eta_scale)
        sub = runner_lib.evaluate(p, x_hat, runner_lib.f_star_operand(p))
        return x_hat, history, sub

    # axes: seeds × decay grids (eta_scale rows)
    grid = jax.vmap(jax.vmap(cell, in_axes=(None, None, None, 0)),
                    in_axes=(None, None, 0, None))
    return runner_lib._cache_put(key, jax.jit(grid, donate_argnums=donate))


def _sweep_fn_methods(methods, problem, rounds: int, eval_output: bool):
    tag = "+".join(m.name for m in methods)
    donate = (2, 3, 4, 5)  # stacked state0, keys, etas, method index
    key = ("sweep-methods", methods, runner_lib.problem_key(problem), rounds,
           eval_output, donate)
    fn = runner_lib._cache_get(key)
    if fn is not None:
        return fn


    body = runner_lib.method_executor_body(methods, problem, eval_output)
    _, resolve = runner_lib._bind(problem)
    eta_scale = jnp.ones((rounds,), jnp.float32)

    def cell(spec, x0, state0, key, eta, midx):
        p = resolve(spec)
        runner_lib.TRACE_COUNTS[f"sweep-methods/{tag}"] += 1
        state0 = state0._replace(eta=state0.eta * eta)  # scale semantics
        keys = jax.random.split(key, rounds)
        state, history = body(spec, state0, keys, eta_scale, midx)
        x_hat = jax.lax.switch(
            midx, [lambda s, m=m: m.output(s) for m in methods], state)
        sub = runner_lib.evaluate(p, x_hat, runner_lib.f_star_operand(p))
        return x_hat, history, sub

    grid = jax.vmap(jax.vmap(cell, in_axes=(None, None, None, None, 0, None)),
                    in_axes=(None, None, None, 0, None, None))
    grid = jax.vmap(grid, in_axes=(None, None, 0, None, None, 0))  # methods
    return runner_lib._cache_put(key, jax.jit(grid, donate_argnums=donate))


def _normalize_x0_stack(x0, stacked, n_probs: int):
    """The ``problems=`` x0 semantics, shared with the sharded engine:
    None -> each spec's own x0; array-likes keep the historical behaviour (a
    [D] point is shared, a [P, ...] stack is per-problem); a params PYTREE
    (vision MLPs) is a shared unbatched point broadcast along the axis."""
    if x0 is None:
        return stacked.x0
    try:
        x0_stack = jnp.asarray(x0)
    except (TypeError, ValueError):
        return tm.tree_broadcast_leading(x0, n_probs)
    if x0_stack.ndim == 1:
        return jnp.broadcast_to(x0_stack, (n_probs,) + x0_stack.shape)
    if x0_stack.shape[0] != n_probs:
        raise ValueError(
            f"x0 leading axis {x0_stack.shape[0]} != number of "
            f"problems {n_probs}")
    return x0_stack


def _resolve_eta_mode(algo_or_chain, eta_mode):
    """Default + validate ``eta_mode`` (shared with the sharded engine)."""
    is_chain = isinstance(algo_or_chain, chain_lib.Chain)
    if eta_mode is None:
        eta_mode = "scale" if is_chain else "absolute"
    if eta_mode not in ("absolute", "scale"):
        raise ValueError(
            f"eta_mode must be 'absolute' or 'scale', got {eta_mode!r}")
    if is_chain and eta_mode != "scale":
        raise ValueError(
            "chains sweep stepsize *multipliers* (one η per stage makes an "
            "absolute grid ambiguous); pass eta_mode='scale' or omit it")
    return eta_mode


def _as_stacked_specs(problems):
    """Normalize the ``problems=`` argument into (stacked spec, names)."""
    from repro.data import spec as spec_lib

    if spec_lib.is_spec(problems):
        return problems, tuple(
            [problems.name] * spec_lib.spec_count(problems))
    specs = []
    for p in problems:
        s = runner_lib.as_spec(p)
        if s is None:
            raise TypeError(
                "problems= entries must be ProblemSpecs (or spec-backed "
                "problems); legacy hand-closure problems cannot batch — "
                "their data lives in Python closures, not operands")
        specs.append(s)
    names = tuple(s.name for s in specs)
    return spec_lib.stack_specs(specs), names


def _split_taps(outs, telemetry):
    """Split the trailing taps element off a grid output tuple when
    telemetry was enabled — ``(outs, taps-or-None)``."""
    if telemetry is None:
        return outs, None
    return outs[:-1], outs[-1]


def _run_grid_sweep(algo_or_chain, problem, x0, rounds: int, *,
                    seeds: Sequence[int], etas: Sequence[float],
                    eta_mode: Optional[str] = None, eval_output: bool = True,
                    decay: Optional[dict] = None, comm=None,
                    problems=None, mesh=None,
                    operand_layout: str = "indexed",
                    telemetry=None) -> SweepResult:
    """The (seed, η) / (problem, seed, η) grid family — see ``run()``.

    The host path opens four spans in turn: ``repro.sweep.operands`` (keys,
    η, the comm state, the η schedule, the problem operands),
    ``repro.sweep.masks`` (the comm mask schedule), ``repro.sweep.executor``
    (the cached executor) and ``repro.sweep.dispatch`` (its call)."""
    if mesh is not None:
        from repro.dist import grid as dist_grid

        return dist_grid.run_sweep_sharded(
            algo_or_chain, problem, x0, rounds, seeds=seeds, etas=etas,
            eta_mode=eta_mode, eval_output=eval_output, decay=decay,
            comm=comm, problems=problems, mesh=mesh,
            operand_layout=operand_layout, telemetry=telemetry)
    is_chain = isinstance(algo_or_chain, chain_lib.Chain)
    eta_mode = _resolve_eta_mode(algo_or_chain, eta_mode)
    check_operand_layout(operand_layout)
    seeds = tuple(int(s) for s in seeds)
    etas = tuple(float(e) for e in etas)
    if not seeds:
        raise ValueError("run_sweep needs at least one seed")
    if decay is not None and not is_chain:
        raise NotImplementedError("decay sweeps: wrap the algorithm in a Chain")
    n_seeds = len(seeds)

    with annotate("repro.sweep.operands"):
        keys = jnp.stack([jax.random.PRNGKey(s) for s in seeds])
        etas_arr = jnp.asarray(etas, jnp.float32)
        if problems is not None:
            stacked, prob_names = _as_stacked_specs(problems)
            n_probs = len(prob_names)
            x0_stack = _normalize_x0_stack(x0, stacked, n_probs)
            # problems × seeds flatten to ONE cells axis, c = p·S + s (the
            # contract of repro.dist.partition): keys tile per problem and
            # the spec rides either as ONE O(P) stack + per-cell problem
            # index (indexed layout, the default) or with every leaf
            # repeated per seed (stacked layout, O(P·S)) — the exact
            # per-cell values the sharded engine partitions over devices,
            # so run_sweep(..., mesh=...) is bitwise identical to this path,
            # and so are the two layouts to each other (module docstring:
            # memory model).
            spec_c, x0_c, pidx, keys = build_problem_operands(
                stacked, x0_stack, keys, n_probs, n_seeds, operand_layout)
            lead = ((spec_c, x0_c) if pidx is None
                    else (spec_c, x0_c, pidx))
            # the executors are keyed on the stack's structure
            structure, x0_one = stacked, tm.tree_index(x0_stack, 0)
            axis = dict(problem_axis=True, layout=operand_layout)
        else:
            prob_names, n_probs = None, 1
            lead = (runner_lib.as_spec(problem), x0)
            structure, x0_one = problem, x0
            axis = {}
        tail = ((algo_or_chain.eta_schedule(rounds, decay),) if is_chain
                else ())
        if comm is not None:
            n_clients = structure.num_clients
            comm0 = comm.init_state(n_clients, x0_one)

    if comm is not None:
        with annotate("repro.sweep.masks"):
            n_sched = (algo_or_chain.schedule_len(rounds) if is_chain
                       else rounds)
            # one independent [R, N] schedule per (problem, seed) cell:
            # cell (p, s) uses the config's fold p·len(seeds) + s (s without
            # a problem axis), so runner.run(..., comm_masks=round_masks(R,
            # N, fold=p*S+s)) reproduces it
            masks = jnp.stack([
                comm.round_masks(n_sched, n_clients, fold=p * n_seeds + s)
                for p in range(n_probs) for s in range(n_seeds)])
        tail += (masks, comm0)

    with annotate("repro.sweep.executor"):
        if is_chain:
            fn = (_sweep_fn_chain if comm is None else _sweep_fn_chain_comm)(
                algo_or_chain, structure, rounds, telemetry=telemetry, **axis)
        else:
            fn = (_sweep_fn_algo if comm is None else _sweep_fn_algo_comm)(
                algo_or_chain, structure, rounds, eval_output, eta_mode,
                telemetry=telemetry, **axis)

    with annotate("repro.sweep.dispatch"):
        outs = fn(*lead, keys, etas_arr, *tail)

    if problems is not None:
        outs = jax.tree.map(
            lambda l: l.reshape((n_probs, n_seeds) + l.shape[1:]), outs)
    outs, taps = _split_taps(outs, telemetry)
    x_hat, history, final, *rest = outs
    kept = rest.pop(0) if is_chain else None
    bits_up, bits_down = rest if comm is not None else (None, None)
    return SweepResult(history=history, final_sub=final, x_hat=x_hat,
                       seeds=seeds, etas=etas, selected_initial=kept,
                       bits_up=bits_up, bits_down=bits_down,
                       problems=prob_names, diagnostics=taps)


def run_method_sweep(methods, problem, x0, rounds: int, *,
                     seeds: Sequence[int], etas: Sequence[float] = (1.0,),
                     eval_output: bool = True) -> SweepResult:
    """Batch SEVERAL methods through one compiled methods × seeds × η call.

    ``methods`` must be plain algorithms (not chains) whose states share one
    pytree structure and leaf shapes on this problem — one class at
    different hyperparameters is the canonical case (SGD at several
    ``mu_avg``, FedAvg at several ``local_steps``). ``etas`` are
    MULTIPLIERS on each method's own base stepsize ("scale" semantics: an
    absolute grid is ambiguous across methods). Results carry the method
    axis first (``history[m, s, e]`` matches ``runner.run(methods[m], …)``
    cell-for-cell) and ``SweepResult.methods`` names it.

    Note the cost model (module docstring): the batched ``lax.switch``
    evaluates every method's round per grid row — ONE compile but M× the
    warm FLOPs of a per-method sweep loop. Prefer stacking when compile
    time dominates; prefer looping ``run_sweep`` per method for long warm
    grids.
    """
    methods = tuple(methods)
    if not methods:
        raise ValueError("run_method_sweep needs at least one method")
    for m in methods:
        if isinstance(m, chain_lib.Chain):
            raise TypeError("run_method_sweep stacks plain algorithms; "
                            "chains batch through run_sweep directly")
    seeds = tuple(int(s) for s in seeds)
    etas = tuple(float(e) for e in etas)
    if not seeds:
        raise ValueError("run_method_sweep needs at least one seed")

    states = [m.init(problem, x0) for m in methods]
    td0 = jax.tree_util.tree_structure(states[0])
    shapes0 = [jnp.shape(l) for l in jax.tree_util.tree_leaves(states[0])]
    for m, st in zip(methods[1:], states[1:]):
        td = jax.tree_util.tree_structure(st)
        shapes = [jnp.shape(l) for l in jax.tree_util.tree_leaves(st)]
        if td != td0 or shapes != shapes0:
            raise TypeError(
                f"method {m.name!r} has a state structure incompatible with "
                f"{methods[0].name!r}: multi-method stacking needs one state "
                f"pytree structure and leaf shapes across all methods "
                f"(same algorithm class at different hyperparameters)")
    state0 = jax.tree.map(lambda *xs: jnp.stack(xs), *states)

    keys = jnp.stack([jax.random.PRNGKey(s) for s in seeds])
    etas_arr = jnp.asarray(etas, jnp.float32)
    midx = jnp.arange(len(methods), dtype=jnp.int32)
    spec = runner_lib.as_spec(problem)

    fn = _sweep_fn_methods(methods, problem, rounds, eval_output)
    x_hat, history, final = fn(spec, x0, state0, keys, etas_arr, midx)
    return SweepResult(history=history, final_sub=final, x_hat=x_hat,
                       seeds=seeds, etas=etas,
                       methods=tuple(m.name for m in methods))


def _run_decay_sweep(chain, problem, x0, rounds: int, *,
                     seeds: Sequence[int], decay_factors: Sequence[float],
                     decay_first: float = 0.3) -> SweepResult:
    """The "M-" ``decay_factor`` grid family — see ``run()``."""
    if not isinstance(chain, chain_lib.Chain):
        raise TypeError("run_decay_sweep takes a Chain (wrap plain "
                        "algorithms in a single-stage Chain)")
    seeds = tuple(int(s) for s in seeds)
    factors = tuple(float(f) for f in decay_factors)
    if not seeds or not factors:
        raise ValueError("run_decay_sweep needs ≥1 seed and ≥1 decay factor")
    keys = jnp.stack([jax.random.PRNGKey(s) for s in seeds])
    eta_rows = jnp.stack([
        chain.eta_schedule(rounds, {"decay_first": decay_first,
                                    "decay_factor": f})
        for f in factors])
    fn = _sweep_fn_chain_decay(chain, problem, rounds)
    x_hat, history, final = fn(runner_lib.as_spec(problem), x0, keys,
                               eta_rows)
    return SweepResult(history=history, final_sub=final, x_hat=x_hat,
                       seeds=seeds, etas=factors)


def fraction_schedule_operands(chain, rounds: int, fractions,
                               seeds, decay: Optional[dict] = None):
    """The operand rows a local-fraction sweep feeds the fraction executor.

    Returns ``(chains, keys_r [S,F,R,2], keys_s [S,F,R,2], stage_id [F,R],
    kind [F,R], hmode [F,R], eta_rows [F,R], sel_indices [F][n_sel])`` —
    per-fraction schedules stacked into operands (every fraction of a fixed
    stage tuple has the same schedule length), with the key streams
    precomputed host-side by the SAME derivation the fixed-schedule executor
    performs in-trace — each row therefore replays ``Chain.run``'s exact
    RNG streams on the corresponding per-fraction chain. Shared by the
    vmapped and sharded fraction sweeps.

    Fractions must leave BOTH stages at least one round inside the fixed
    budget: ``Chain.budgets`` clamps a starved last stage back up to one
    round, which would CHANGE the schedule length and break the stacked
    operand layout — such fractions are rejected up front with the valid
    range for this round budget.
    """
    chains = [chain.with_local_fraction(float(f)) for f in fractions]
    # a costed between-stage selection occupies one scanned round of the
    # total budget; the first stage may take at most rounds − n_sel − 1
    n_sel = ((len(chain.stages) - 1)
             if (chain.select_between_stages and chain.selection_costs_round)
             else 0)
    max_b0 = rounds - n_sel - 1
    for ch in chains:
        b0 = max(1, int(round(ch.fractions[0] * rounds)))
        if b0 > max_b0:
            lo = 0.5 / rounds  # anything rounding to ≥ 1 is fine below
            hi = (max_b0 + 0.49) / rounds
            raise ValueError(
                f"local_fraction {ch.fractions[0]:g} gives the first stage "
                f"{b0} of {rounds} rounds, leaving none for the second "
                f"stage (selection costs {n_sel}); with rounds={rounds} "
                f"sweepable fractions lie in about ({lo:g}, {hi:g}]")
    scheds = [ch._schedule(rounds) for ch in chains]
    n_sched = len(scheds[0].stage_id)
    # backstop only — the budget check above is the real gate
    for ch, sc in zip(chains, scheds):
        if len(sc.stage_id) != n_sched:
            raise AssertionError(
                f"fraction {ch.fractions[0]} produced schedule length "
                f"{len(sc.stage_id)} != {n_sched}")
    stage_id = jnp.asarray(np.stack([s.stage_id for s in scheds]))
    kind = jnp.asarray(np.stack([s.kind for s in scheds]))
    hmode = jnp.asarray(np.stack([s.hmode for s in scheds]))
    eta_rows = jnp.stack([ch.eta_schedule(rounds, decay) for ch in chains])
    per_seed = []
    for s in seeds:
        key = jax.random.PRNGKey(s)
        per_seed.append([ch._derive_keys(sc, key)
                         for ch, sc in zip(chains, scheds)])
    keys_r = jnp.stack([jnp.stack([kr for kr, _ in row]) for row in per_seed])
    keys_s = jnp.stack([jnp.stack([ks for _, ks in row]) for row in per_seed])
    sel_indices = [list(s.sel_indices) for s in scheds]
    return chains, keys_r, keys_s, stage_id, kind, hmode, eta_rows, sel_indices


def gather_selection_flags(kept, sel_indices):
    """[S, F, R] full kept-flags rows → the [S, F, n_sel] selection
    decisions: selection rounds sit at fraction-dependent positions, so
    each fraction's flags are gathered from its own schedule's indices.
    Shared by the vmapped and sharded fraction sweeps."""
    kept_np = np.asarray(kept)
    return jnp.asarray(np.stack(
        [kept_np[:, fi, idx] for fi, idx in enumerate(sel_indices)], axis=1))


def _run_fraction_sweep(chain, problem, x0, rounds: int, *,
                        seeds: Sequence[int], fractions: Sequence[float],
                        decay: Optional[dict] = None,
                        mesh=None) -> SweepResult:
    """The two-stage ``local_fraction`` grid family — see ``run()``."""
    if not isinstance(chain, chain_lib.Chain):
        raise TypeError("run_fraction_sweep takes a Chain")
    seeds = tuple(int(s) for s in seeds)
    fractions = tuple(float(f) for f in fractions)
    if not seeds or not fractions:
        raise ValueError("run_fraction_sweep needs ≥1 seed and ≥1 fraction")
    if mesh is not None:
        from repro.dist import grid as dist_grid

        return dist_grid.run_fraction_sweep_sharded(
            chain, problem, x0, rounds, seeds=seeds, fractions=fractions,
            decay=decay, mesh=mesh)
    if x0 is None:
        spec = runner_lib.as_spec(problem)
        if spec is None:
            raise TypeError("x0=None needs a spec-backed problem "
                            "(uses the spec's own x0)")
        x0 = spec.x0

    (_, keys_r, keys_s, stage_id, kind, hmode, eta_rows,
     sel_indices) = fraction_schedule_operands(
         chain, rounds, fractions, seeds, decay)

    fn = _sweep_fn_chain_fraction(chain, problem, rounds)
    x_hat, history, final, kept = fn(
        runner_lib.as_spec(problem), x0, keys_r, keys_s, stage_id, kind,
        hmode, eta_rows)
    return SweepResult(
        history=history, final_sub=final, x_hat=x_hat, seeds=seeds,
        etas=fractions,
        selected_initial=gather_selection_flags(kept, sel_indices))


@dataclasses.dataclass(frozen=True)
class SweepRequest:
    """One description for every sweep family the engine runs.

    Exactly one grid FAMILY is selected by which axis field is set —
    ``run()`` dispatches on it:

    * none of the below → the (seed, η) grid over ``etas`` (optionally ×
      ``problems``), vmapped through one compiled executor per structure;
    * ``decay_factors`` → the "M-" decay grid (η-scale rows as operands;
      ``decay_first`` sets the undecayed prefix fraction);
    * ``fractions`` → the two-stage chain ``local_fraction`` grid (App. I.2;
      the whole per-round schedule is an operand);
    * ``policies`` → the client-selection grid (policies × problems ×
      seeds × etas through the ``lax.switch`` policy operand).

    Shared operand axes and options, identical across families:

    * ``seeds``: PRNG seeds — cell s uses ``jax.random.PRNGKey(seeds[s])``,
      so any cell is reproducible by the corresponding per-call runner
      (``runner.run`` / ``Chain.run``) with that key.
    * ``etas``: stepsize grid. ``eta_mode`` defaults to "absolute" for
      plain algorithms; chains only accept "scale" (per-stage multipliers)
      — passing "absolute" with a chain is an error, not a silent
      reinterpretation. Decay/fraction families carry their own grid in the
      result's ``etas`` slot instead.
    * ``problems``: a sequence of same-family, same-shaped ``ProblemSpec``s
      (or one pre-stacked spec from ``spec.stack_specs``). Problems × seeds
      flatten to ONE cells axis c = p·S + s; under the default
      ``operand_layout="indexed"`` the call carries ONE O(P) stacked spec
      plus an int32 per-cell index ("stacked" keeps the O(P·S)
      repeated-leaf reference layout, bitwise identical). ``x0`` may be
      None (each problem starts from its spec's own x0), a single shared
      point, or a [P, …] stack.
    * ``comm``: a ``repro.comm.CommPlan`` (or legacy ``CommConfig`` shim)
      enabling compressed uplinks/downlinks, partial participation, and
      the bits ledgers. Cell (p, s) uses the plan's mask schedule with
      ``fold=p·len(seeds)+s`` (``fold=s`` without a problem axis), so
      ``runner.run(..., comm_masks=...)`` reproduces any cell.
    * ``mesh``: a 1-D ``('grid',)`` device mesh (``dist.make_grid_mesh``)
      shard_maps the flattened cells axis — same semantics, same bits,
      bitwise identical results including the ledgers.
    * ``telemetry``: a ``repro.obs.Telemetry`` spec enabling in-scan round
      taps — ``SweepResult.diagnostics`` carries the per-round diagnostics
      dict with the grid's leading axes. A structural cache-key dimension:
      ``telemetry=None`` (the default) reuses today's executors bitwise.
      Supported by the (seed, η) and ``policies`` families; the
      decay/fraction families reject it.

    The legacy entry points (``run_sweep``, ``run_decay_sweep``,
    ``run_fraction_sweep``, ``selection.run_selection_sweep``) are thin
    keyword shims constructing a ``SweepRequest`` and calling ``run()`` —
    same code path, bitwise identical.
    """

    algo_or_chain: object
    problem: object
    x0: object
    rounds: int
    seeds: Sequence[int]
    etas: Sequence[float] = (1.0,)
    # family-selecting axes (at most one)
    decay_factors: Optional[Sequence[float]] = None
    fractions: Optional[Sequence[float]] = None
    policies: Optional[Sequence] = None
    # shared options
    eta_mode: Optional[str] = None
    eval_output: bool = True
    decay: Optional[dict] = None
    decay_first: float = 0.3
    comm: object = None
    problems: object = None
    mesh: object = None
    operand_layout: str = "indexed"
    telemetry: object = None


# grid calls made by this process: the ``call`` argument of each
# ``repro.sweep.run`` span, so a trace's spans group by call
_GRID_CALLS = itertools.count(1)


def run(req: SweepRequest) -> SweepResult:
    """Run the sweep family ``req`` describes — see ``SweepRequest`` for
    the operand axes. Returns a ``SweepResult`` (``SelectionSweepResult``
    for the policy family). The call is one ``repro.sweep.run`` host span
    (argument ``call``, this process's grid-call sequence number)."""
    with annotate("repro.sweep.run", call=next(_GRID_CALLS)):
        families = [name for name, axis in (
            ("decay_factors", req.decay_factors),
            ("fractions", req.fractions),
            ("policies", req.policies)) if axis is not None]
        if len(families) > 1:
            raise ValueError(
                f"SweepRequest selects at most one sweep family; got "
                f"{families} together")
        if req.telemetry is not None and families not in ([], ["policies"]):
            raise ValueError(
                f"telemetry round taps are supported by the (seed, η) and "
                f"policies sweep families, not {families[0]!r}")
        if req.policies is not None:
            from repro.selection import sweep as sel_sweep

            return sel_sweep._run_selection_sweep(
                req.algo_or_chain, req.problem, req.x0, req.rounds,
                policies=req.policies, seeds=req.seeds, etas=req.etas,
                eta_mode=req.eta_mode, comm=req.comm, problems=req.problems,
                eval_output=req.eval_output, mesh=req.mesh,
                telemetry=req.telemetry)
        if req.fractions is not None:
            return _run_fraction_sweep(
                req.algo_or_chain, req.problem, req.x0, req.rounds,
                seeds=req.seeds, fractions=req.fractions, decay=req.decay,
                mesh=req.mesh)
        if req.decay_factors is not None:
            return _run_decay_sweep(
                req.algo_or_chain, req.problem, req.x0, req.rounds,
                seeds=req.seeds, decay_factors=req.decay_factors,
                decay_first=req.decay_first)
        return _run_grid_sweep(
            req.algo_or_chain, req.problem, req.x0, req.rounds,
            seeds=req.seeds, etas=req.etas, eta_mode=req.eta_mode,
            eval_output=req.eval_output, decay=req.decay, comm=req.comm,
            problems=req.problems, mesh=req.mesh,
            operand_layout=req.operand_layout, telemetry=req.telemetry)


def run_sweep(algo_or_chain, problem, x0, rounds: int, *,
              seeds: Sequence[int], etas: Sequence[float],
              eta_mode: Optional[str] = None, eval_output: bool = True,
              decay: Optional[dict] = None, comm=None,
              problems=None, mesh=None,
              operand_layout: str = "indexed",
              telemetry=None) -> SweepResult:
    """Thin keyword shim over ``run()`` for the (seed, η) grid family —
    ``SweepRequest`` documents the operand axes."""
    return run(SweepRequest(
        algo_or_chain=algo_or_chain, problem=problem, x0=x0, rounds=rounds,
        seeds=seeds, etas=etas, eta_mode=eta_mode, eval_output=eval_output,
        decay=decay, comm=comm, problems=problems, mesh=mesh,
        operand_layout=operand_layout, telemetry=telemetry))


def run_decay_sweep(chain, problem, x0, rounds: int, *,
                    seeds: Sequence[int], decay_factors: Sequence[float],
                    decay_first: float = 0.3) -> SweepResult:
    """Thin keyword shim over ``run()`` for the decay-factor grid family —
    ``SweepRequest`` documents the operand axes."""
    return run(SweepRequest(
        algo_or_chain=chain, problem=problem, x0=x0, rounds=rounds,
        seeds=seeds, decay_factors=decay_factors, decay_first=decay_first))


def run_fraction_sweep(chain, problem, x0, rounds: int, *,
                       seeds: Sequence[int], fractions: Sequence[float],
                       decay: Optional[dict] = None,
                       mesh=None) -> SweepResult:
    """Thin keyword shim over ``run()`` for the local-fraction grid family —
    ``SweepRequest`` documents the operand axes."""
    return run(SweepRequest(
        algo_or_chain=chain, problem=problem, x0=x0, rounds=rounds,
        seeds=seeds, fractions=fractions, decay=decay, mesh=mesh))


def best_cell(result: SweepResult):
    """Grid index of the lowest finite final suboptimality —
    ``(seed_idx, eta_idx)``, with a leading problem/method index when the
    sweep had one.

    Raises if every cell diverged — callers must not mistake a nan/inf run
    for a tuned result.
    """
    final = np.asarray(result.final_sub)
    masked = np.where(np.isfinite(final), final, np.inf)
    if not np.isfinite(masked).any():
        raise ValueError(
            f"every sweep cell diverged (no finite final suboptimality) "
            f"over seeds={result.seeds} etas={result.etas}")
    flat = int(np.argmin(masked))
    return np.unravel_index(flat, final.shape)
