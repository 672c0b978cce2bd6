"""The grid axis: sweeps sharded over devices with ``shard_map``.

``run_sweep_sharded`` is the device-mesh twin of ``core.sweep.run_sweep``
(and the target of its ``mesh=`` argument): the flattened problems × seeds
cells are partitioned across the ``grid`` mesh axis, each shard runs ITS
cells through the SAME cell functions the vmapped engine uses
(``core.sweep.make_*_cell`` — one source of truth for per-cell math). On
the CPU backend results come back bitwise identical to the single-device
call. On TPU each shard's program is compiled for its own cell count, and
XLA may order its float reductions differently, so per-round histories and
iterates agree to float tolerance; per-cell RNG streams, masks and the bits
ledgers stay exact.

Anatomy of a sharded sweep
--------------------------
1. ``dist.partition`` flattens cell (p, s) to ``p·S + s``, pads the flat
   axis to a multiple of the grid size by repeating real cells, and keeps
   the identity prefix for unpadding (a property-tested bijection).
2. Every per-cell operand is gathered to a ``[C_pad, ...]`` stack: the
   stacked ``ProblemSpec`` leaves, per-cell x0, per-cell raw PRNG keys
   (``PRNGKey(seeds[s])``, exactly the single-device values), and — under
   ``comm=`` — the per-cell ``[R, N]`` mask schedule derived with the same
   fold ``p·S + s``. The stepsize axis stays dense inside every cell.
3. ``sharding.rules.leading_axis_specs`` (the ``cells`` logical axis) maps
   each stack's leading axis to the ``grid`` mesh axis; replicated operands
   (η grid, chain decay rows, initial ``CommState``) get empty specs.
4. The executor is ``jit(shard_map(vmap(vmap(cell))))``: each shard vmaps
   its local cells × stepsizes, the same nesting as the vmapped engine. No
   collective crosses cells — the grid axis is pure map parallelism, so
   per-cell results (and the in-cell bits accounting) cannot depend on
   placement.

Executors are cached per (algorithm-or-chain, problem STRUCTURE, rounds,
mesh signature) in the same LRU the single-device engine uses, and the
shard_map body is traced ONCE per structure (``runner.TRACE_COUNTS`` moves
by exactly 1 — asserted in the dist tests and the ``dist_scaling``
benchmark).

The fraction sweep (``run_fraction_sweep_sharded``) shards the seeds ×
fractions cells the same way, with the per-fraction schedule rows riding
each cell's shard as operands.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import chain as chain_lib
from repro.core import runner as runner_lib
from repro.core import sweep as sweep_lib
from repro.core import tree_math as tm
from repro.dist import mesh as mesh_lib, partition
from repro.obs.profile import annotate
from repro.sharding import rules as rules_lib


def _require_spec(problem):
    spec = runner_lib.as_spec(problem)
    if spec is None:
        raise TypeError(
            "the sharded sweep needs spec-backed problems (ProblemSpec or a "
            "spec-backed shim): legacy hand-closure problems keep their data "
            "in Python closures, which cannot be placed on a device shard")
    return spec


def _cell_specs(tree, ruleset):
    """PartitionSpecs sharding every leaf's leading cells axis over 'grid'
    (the ``cells`` logical rule of ``sharding.rules``)."""
    return rules_lib.leading_axis_specs(tree, ruleset, "cells")


def _replicated(tree):
    return jax.tree.map(lambda _: P(), tree)


def _gather_cells(tree, idx):
    """Stack per-cell operands: gather ``idx`` along every leaf's leading
    axis (idx indexes the unpadded cell order; repeats implement padding)."""
    return jax.tree.map(lambda l: jnp.take(l, idx, axis=0), tree)


def _indexed_cell_plan(cell, cell_in_axes, replicated_args):
    """Adapt a cell's sharding plan to the indexed O(P) operand layout:
    the leading ``(spec, x0)`` become replicated stacks and a per-cell
    problem index is inserted (sharded with the cells, off the dense
    stepsize axis) — ``core.sweep.make_indexed_cell`` does the in-cell
    gather."""
    icell = sweep_lib.make_indexed_cell(cell)
    in_axes = (None if cell_in_axes is None
               else (None, None, None) + tuple(cell_in_axes[2:]))
    rep_args = (True, True, False) + tuple(replicated_args[2:])
    return icell, in_axes, rep_args


def _sharded_grid_fn(cache_key, mesh, cell, cell_in_axes, replicated_args,
                     donate_argnums=()):
    """Build (or fetch) the sharded grid executor around one sweep cell.

    ``replicated_args`` flags which cell arguments ride replicated
    (everything else is a [C_pad, ...] per-cell stack whose leading axis is
    sharded over ``grid``); the shard body vmaps local cells over the
    non-replicated axis-0s, with an optional inner dense vmap
    (``cell_in_axes``, the stepsize axis — None for flat cell grids).
    ``in_specs`` follow each argument's pytree STRUCTURE, so one cached
    entry lazily assembles a shard_map per operand structure (e.g. comm
    states with/without error-feedback residuals); jit handles shapes.
    ``donate_argnums`` positions are donated to the jit (call-private
    stacks only — never the caller-owned spec/x0) and are part of the
    cache key.
    """
    key = ("dist-grid", cache_key, mesh_lib.mesh_signature(mesh),
           tuple(donate_argnums))
    fn = runner_lib._cache_get(key)
    if fn is not None:
        return fn

    ruleset = rules_lib.RuleSet(mesh)
    outer_axes = tuple(None if rep else 0 for rep in replicated_args)

    def shard_body(*args):
        inner = (cell if cell_in_axes is None
                 else jax.vmap(cell, in_axes=cell_in_axes))
        return jax.vmap(inner, in_axes=outer_axes)(*args)

    compiled: dict = {}

    def jitted_for(args):
        struct = jax.tree_util.tree_structure(args)
        jitted = compiled.get(struct)
        if jitted is None:
            in_specs = tuple(
                _replicated(a) if rep else _cell_specs(a, ruleset)
                for a, rep in zip(args, replicated_args))
            jitted = jax.jit(
                jax.shard_map(shard_body, mesh=mesh, in_specs=in_specs,
                              out_specs=P("grid"), check_vma=False),
                donate_argnums=tuple(donate_argnums))
            compiled[struct] = jitted
        return jitted

    def call(*args):
        return jitted_for(args)(*args)

    # lowerable like the single-device executors (``jax.jit`` objects)
    call.lower = lambda *args: jitted_for(args).lower(*args)
    return runner_lib._cache_put(key, call)


def _unpad_cells(outs, n_cells, lead_shape):
    """Drop padding and restore the grid's leading axes ([P, S] or [S])."""

    def fix(l):
        l = partition.unpad(l, n_cells)
        return l.reshape(tuple(lead_shape) + l.shape[1:])

    return jax.tree.map(fix, outs)


def run_sweep_sharded(algo_or_chain, problem, x0, rounds: int, *,
                      seeds: Sequence[int], etas: Sequence[float], mesh,
                      eta_mode: Optional[str] = None,
                      eval_output: bool = True,
                      decay: Optional[dict] = None, comm=None,
                      problems=None,
                      operand_layout: str = "indexed",
                      telemetry=None) -> "sweep_lib.SweepResult":
    """``core.sweep.run_sweep`` on a ``('grid',)`` device mesh.

    Same arguments, same semantics, same ``SweepResult`` shapes; results,
    per-cell RNG streams and ``bits_up``/``bits_down`` are BITWISE identical
    to the single-device call on a CPU debug mesh (tested); on TPU the float
    results agree to tolerance (module docstring). See the module
    docstring for the sharding anatomy. Under the default
    ``operand_layout="indexed"`` the O(P) stacked spec/x0 ride REPLICATED
    across shards and only the int32 per-cell problem index is sharded
    with the cells; ``operand_layout="stacked"`` keeps the historical
    per-cell gathered copies. The two layouts are bitwise identical
    (``core.sweep``'s memory model).
    """
    is_chain = isinstance(algo_or_chain, chain_lib.Chain)
    eta_mode = sweep_lib._resolve_eta_mode(algo_or_chain, eta_mode)
    sweep_lib.check_operand_layout(operand_layout)
    seeds = tuple(int(s) for s in seeds)
    etas = tuple(float(e) for e in etas)
    if not seeds:
        raise ValueError("run_sweep needs at least one seed")
    if decay is not None and not is_chain:
        raise NotImplementedError(
            "decay sweeps: wrap the algorithm in a Chain")
    n_seeds = len(seeds)

    with annotate("repro.sweep.operands"):
        n_shards = mesh_lib.grid_size(mesh)
        keys = jnp.stack([jax.random.PRNGKey(s) for s in seeds])
        etas_arr = jnp.asarray(etas, jnp.float32)

        # spec/x0 stacked per cell, or replicated
        per_cell = problems is not None
        if per_cell:
            stacked, prob_names = sweep_lib._as_stacked_specs(problems)
            n_probs = len(prob_names)
            x0_stack = sweep_lib._normalize_x0_stack(x0, stacked, n_probs)
            lead_shape = (n_probs, n_seeds)
        else:
            stacked = _require_spec(problem)
            prob_names = None
            n_probs = 1
            lead_shape = (n_seeds,)

        n_cells = n_probs * n_seeds
        src_idx, _ = partition.pad_cells(n_cells, n_shards)
        idx = jnp.asarray(src_idx)
        p_idx, s_idx = partition.cell_coords(n_probs, n_seeds)
        keys_c = keys[jnp.asarray(s_idx)][idx]  # [C_pad, 2]

        indexed = per_cell and operand_layout == "indexed"
        if indexed:
            # O(P) layout: the stacks ride replicated, the in-cell gather
            # is driven by the sharded per-cell problem index
            spec_c, x0_c = stacked, x0_stack
            pidx_c = jnp.asarray(p_idx, jnp.int32)[idx]
        elif per_cell:
            spec_c = _gather_cells(stacked, jnp.asarray(p_idx)[idx])
            x0_c = _gather_cells(x0_stack, jnp.asarray(p_idx)[idx])
        else:
            spec_c, x0_c = stacked, x0  # replicated: the single-device layout

        tail = ((algo_or_chain.eta_schedule(rounds, decay),) if is_chain
                else ())
        if comm is not None:
            n_clients = stacked.num_clients
            comm0 = comm.init_state(
                n_clients, tm.tree_index(x0_stack, 0) if per_cell else x0)

    if comm is not None:
        with annotate("repro.sweep.masks"):
            n_sched = (algo_or_chain.schedule_len(rounds) if is_chain
                       else rounds)
            # per-cell [R, N] schedules; fold p·S + s == s when there is no
            # problems axis — exactly the single-device folds, cell for cell
            masks_flat = jnp.stack([
                comm.round_masks(n_sched, n_clients,
                                 fold=partition.flatten_cell(p, s, n_seeds))
                for p in range(n_probs) for s in range(n_seeds)])
            tail += (masks_flat[idx], comm0)

    rep = not per_cell  # spec/x0 replication flag (stacked layout)
    name_tag = "dist-comm" if comm is not None else "dist"
    if per_cell:
        name_tag += "-probs"
    pkey = runner_lib.problem_key(stacked)
    layout_key = operand_layout if per_cell else None

    # (cell, axes, replication, cache key) of the family; donation covers
    # every call-private stack (keys/masks/η rows/pidx/comm0), never the
    # caller-owned spec/x0
    with annotate("repro.sweep.executor"):
        if is_chain:
            chain = algo_or_chain
            if comm is not None:
                cell = sweep_lib.make_chain_comm_cell(
                    chain, stacked, rounds, name_tag, telemetry)
                axes = (None, None, None, 0, None, None, None)
                reps = (rep, rep, False, True, True, False, True)
                key = ("dist-chain-comm", chain._key(), pkey, rounds,
                       per_cell, layout_key, telemetry)
            else:
                cell = sweep_lib.make_chain_cell(chain, stacked, rounds,
                                                 name_tag, telemetry)
                axes = (None, None, None, 0, None)
                reps = (rep, rep, False, True, True)
                key = ("dist-chain", chain._key(), pkey, rounds, per_cell,
                       layout_key, telemetry)
        elif comm is not None:
            cell = sweep_lib.make_algo_comm_cell(
                algo_or_chain, stacked, rounds, eval_output, eta_mode,
                name_tag, telemetry)
            axes = (None, None, None, 0, None, None)
            reps = (rep, rep, False, True, False, True)
            key = ("dist-algo-comm", algo_or_chain, pkey, rounds,
                   eval_output, eta_mode, per_cell, layout_key, telemetry)
        else:
            cell = sweep_lib.make_algo_cell(
                algo_or_chain, stacked, rounds, eval_output, eta_mode,
                name_tag, telemetry)
            axes = (None, None, None, 0)
            reps = (rep, rep, False, True)
            key = ("dist-algo", algo_or_chain, pkey, rounds, eval_output,
                   eta_mode, per_cell, layout_key, telemetry)
        if indexed:
            cell, axes, reps = _indexed_cell_plan(cell, axes, reps)
            lead = (spec_c, x0_c, pidx_c)
        else:
            lead = (spec_c, x0_c)
        fn = _sharded_grid_fn(key, mesh, cell, cell_in_axes=axes,
                              replicated_args=reps,
                              donate_argnums=tuple(range(2, len(reps))))

    with annotate("repro.sweep.dispatch"):
        outs = fn(*lead, keys_c, etas_arr, *tail)

    outs, taps = sweep_lib._split_taps(
        _unpad_cells(outs, n_cells, lead_shape), telemetry)
    x_hat, history, final, *rest = outs
    kept = rest.pop(0) if is_chain else None
    bits_up, bits_down = rest if comm is not None else (None, None)
    return sweep_lib.SweepResult(
        history=history, final_sub=final, x_hat=x_hat, seeds=seeds,
        etas=etas, selected_initial=kept, bits_up=bits_up,
        bits_down=bits_down, problems=prob_names, diagnostics=taps)


def run_selection_sweep_sharded(algo_or_chain, problem, x0, rounds: int, *,
                                policies, seeds: Sequence[int], mesh,
                                etas: Sequence[float] = (1.0,),
                                eta_mode: Optional[str] = None, comm=None,
                                problems=None, eval_output: bool = True,
                                telemetry=None):
    """``selection.sweep.run_selection_sweep`` with the flattened policies ×
    problems × seeds cells sharded over the ``grid`` mesh axis.

    Both engines consume the SAME host-derived operands
    (``selection.sweep.selection_grid_operands``): here the per-cell index
    vectors (qidx/pidx), raw key rows, and [R, 2] selection-key rows are
    gathered onto their shard while the O(Q) policy stacks and the O(P)
    spec/x0 stacks ride replicated — so per-cell results, masks and the
    bits ledgers are BITWISE identical to the vmapped call.
    """
    from repro.selection import sweep as sel_sweep

    ops = sel_sweep.selection_grid_operands(
        algo_or_chain, problem, x0, rounds, policies=policies, seeds=seeds,
        etas=etas, eta_mode=eta_mode, comm=comm, problems=problems,
        eval_output=eval_output)

    n_cells = ops.n_pols * ops.n_probs * ops.n_seeds
    lead_shape = (ops.n_pols, ops.n_probs, ops.n_seeds)
    src_idx, _ = partition.pad_cells(n_cells, mesh_lib.grid_size(mesh))
    idx = jnp.asarray(src_idx)
    pidx_c = ops.pidx[idx]
    qidx_c = ops.qidx[idx]
    keys_c = ops.keys_c[idx]
    sel_keys_c = ops.sel_keys_c[idx]
    pkey = runner_lib.problem_key(ops.stacked)
    lead = (ops.stacked, ops.x0_stack, ops.pol_stack, ops.pst_stack)

    with annotate("repro.sweep.executor"):
        if ops.is_chain:
            chain = algo_or_chain
            cell = sweep_lib.make_policy_cell(
                sweep_lib.make_selection_chain_cell(
                    chain, ops.stacked, rounds, "dist-sel", telemetry))
            fn = _sharded_grid_fn(
                ("dist-sel-chain", chain._key(), pkey, rounds, telemetry),
                mesh, cell,
                cell_in_axes=(None, None, None, None, None, None, None, 0,
                              None, None, None),
                replicated_args=(True, True, True, True, False, False,
                                 False, True, True, False, True),
                donate_argnums=tuple(range(2, 11)))
            tail = (ops.eta_sched,)
        else:
            cell = sweep_lib.make_policy_cell(
                sweep_lib.make_selection_algo_cell(
                    algo_or_chain, ops.stacked, rounds, eval_output,
                    ops.eta_mode, "dist-sel", telemetry))
            fn = _sharded_grid_fn(
                ("dist-sel-algo", algo_or_chain, pkey, rounds, eval_output,
                 ops.eta_mode, telemetry),
                mesh, cell,
                cell_in_axes=(None, None, None, None, None, None, None, 0,
                              None, None),
                replicated_args=(True, True, True, True, False, False,
                                 False, True, False, True),
                donate_argnums=tuple(range(2, 10)))
            tail = ()

    with annotate("repro.sweep.dispatch"):
        outs = fn(*lead, pidx_c, qidx_c, keys_c, ops.etas_arr, *tail,
                  sel_keys_c, ops.comm0)

    outs, taps = sweep_lib._split_taps(
        _unpad_cells(outs, n_cells, lead_shape), telemetry)
    x_hat, history, final, *rest = outs
    kept = rest.pop(0) if ops.is_chain else None
    bits_up, bits_down, masks, pstate = rest
    return sel_sweep.SelectionSweepResult(
        history=history, final_sub=final, x_hat=x_hat, bits_up=bits_up,
        bits_down=bits_down, masks=masks, policy_state=pstate,
        policies=ops.pol_names, problems=ops.prob_names, seeds=ops.seeds,
        etas=ops.etas, selected_initial=kept, diagnostics=taps)


def run_fraction_sweep_sharded(chain, problem, x0, rounds: int, *,
                               seeds: Sequence[int],
                               fractions: Sequence[float], mesh,
                               decay: Optional[dict] = None
                               ) -> "sweep_lib.SweepResult":
    """``core.sweep.run_fraction_sweep`` with the seeds × fractions cells
    sharded over the ``grid`` mesh axis (cell (s, f) flattens to
    ``s·F + f``; per-cell key streams and schedule rows ride their shard)."""
    if not isinstance(chain, chain_lib.Chain):
        raise TypeError("run_fraction_sweep takes a Chain")
    seeds = tuple(int(s) for s in seeds)
    fractions = tuple(float(f) for f in fractions)
    if not seeds or not fractions:
        raise ValueError("run_fraction_sweep needs ≥1 seed and ≥1 fraction")
    spec = _require_spec(problem)
    if x0 is None:
        x0 = spec.x0

    (_, keys_r, keys_s, stage_id, kind, hmode, eta_rows,
     sel_indices) = sweep_lib.fraction_schedule_operands(
         chain, rounds, fractions, seeds, decay)

    n_seeds, n_fracs = len(seeds), len(fractions)
    n_cells = n_seeds * n_fracs
    src_idx, _ = partition.pad_cells(n_cells, mesh_lib.grid_size(mesh))
    idx = jnp.asarray(src_idx)
    _, f_idx = partition.cell_coords(n_seeds, n_fracs)
    f_c = jnp.asarray(f_idx)[idx]

    keys_r_c = keys_r.reshape((n_cells,) + keys_r.shape[2:])[idx]
    keys_s_c = keys_s.reshape((n_cells,) + keys_s.shape[2:])[idx]
    stage_c, kind_c, hmode_c, eta_c = (
        arr[f_c] for arr in (stage_id, kind, hmode, eta_rows))

    cell = sweep_lib.make_chain_fraction_cell(chain, spec, rounds,
                                              "dist-frac")
    fn = _sharded_grid_fn(
        ("dist-chain-frac", chain._fraction_free_key(),
         runner_lib.problem_key(spec), rounds),
        mesh, cell,
        cell_in_axes=None,  # flat cells axis, no dense inner axis
        replicated_args=(True, True, False, False, False, False, False,
                         False),
        donate_argnums=(2, 3, 4, 5, 6, 7))  # per-cell key/schedule rows
    outs = fn(spec, x0, keys_r_c, keys_s_c, stage_c, kind_c, hmode_c, eta_c)
    x_hat, history, final, kept = _unpad_cells(
        outs, n_cells, (n_seeds, n_fracs))
    return sweep_lib.SweepResult(
        history=history, final_sub=final, x_hat=x_hat, seeds=seeds,
        etas=fractions,
        selected_initial=sweep_lib.gather_selection_flags(kept, sel_indices))
