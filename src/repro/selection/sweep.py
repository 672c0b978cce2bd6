"""Selection sweeps: policies × problems × seeds × stepsizes, one compile.

``run_selection_sweep`` is the subsystem's grid entry point. The policies
axis rides the flattened cells axis exactly like problems and seeds do —
``c = (q·P + p)·S + s`` — with the policy hyperparameters stacked into ONE
``PolicyParams`` pytree (O(Q) operands) gathered per cell by an int32
``qidx``, mirroring the O(P) indexed problem layout. Swapping the policy
list, like swapping problems or seeds, is pure operand data: zero
re-traces.

``mesh=`` routes the identical per-cell computation through the sharded
engine (``repro.dist.grid.run_selection_sweep_sharded``), bitwise identical
cell-for-cell including the bits ledgers — both engines consume the SAME
host-derived operands built by ``selection_grid_operands``.

Communication accounting composes unchanged: the per-round policy mask
feeds the comm ledger exactly like a precomputed schedule row, so
``bits_up``/``bits_down`` follow the closed forms in ``repro.comm.config``
(plus the probe uplink for probing policies). The participation axis is
owned by the POLICY here — the ``comm`` config must keep
``participation=1.0``.
"""
from __future__ import annotations

import dataclasses
import types
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import chain as chain_lib
from repro.core import runner as runner_lib
from repro.core import sweep as sweep_lib
from repro.core import tree_math as tm
from repro.obs.profile import annotate
from repro.selection.policies import SelectionPolicy


@dataclasses.dataclass
class SelectionSweepResult:
    """Grid results with axes [policy, problem, seed, eta, ...].

    ``masks`` is the per-round participation record [Q, P, S, E, R, N]
    emitted by the scan (what the validity/bits tests check);
    ``policy_state`` is the final ``PolicyState`` pytree with [Q, P, S, E]
    leading axes. ``cumulative_bits``/``bits_to_target`` turn histories
    into bits-to-target frontiers.
    """

    history: jnp.ndarray
    final_sub: jnp.ndarray
    x_hat: object
    bits_up: jnp.ndarray
    bits_down: jnp.ndarray
    masks: jnp.ndarray
    policy_state: object
    policies: Tuple[str, ...]
    problems: Tuple[str, ...]
    seeds: Tuple[int, ...]
    etas: Tuple[float, ...]
    selected_initial: Optional[jnp.ndarray] = None
    diagnostics: Optional[dict] = None  # per-round obs taps, [Q,P,S,E,R]

    def cumulative_bits(self) -> np.ndarray:
        """Cumulative up+down bits per round, [Q, P, S, E, R] float64 (the
        meters are exact in float32 per round; the large sums are not)."""
        up = np.asarray(self.bits_up, np.float64)
        down = np.asarray(self.bits_down, np.float64)
        return np.cumsum(up + down, axis=-1)

    def bits_to_target(self, target: float) -> np.ndarray:
        """Bits spent until suboptimality first drops to ``target``,
        [Q, P, S, E] float64; +inf where the run never reaches it."""
        sub = np.asarray(self.history, np.float64)
        cum = self.cumulative_bits()
        hit = sub <= float(target)
        reached = hit.any(axis=-1)
        first = np.argmax(hit, axis=-1)
        bits = np.take_along_axis(cum, first[..., None], axis=-1)[..., 0]
        return np.where(reached, bits, np.inf)

    def frontier(self, targets: Sequence[float]) -> dict:
        """{target: bits_to_target array} over a target grid."""
        return {float(t): self.bits_to_target(t) for t in targets}


def _normalize_policies(policies) -> Tuple[SelectionPolicy, ...]:
    out = []
    for q in policies:
        if isinstance(q, SelectionPolicy):
            out.append(q)
        elif isinstance(q, str):
            out.append(SelectionPolicy(policy=q))
        else:
            raise TypeError(
                f"policies= entries must be SelectionPolicy or policy-name "
                f"strings, got {type(q).__name__}")
    if not out:
        raise ValueError("run_selection_sweep needs at least one policy")
    return tuple(out)


def selection_grid_operands(algo_or_chain, problem, x0, rounds: int, *,
                            policies, seeds, etas, eta_mode, comm, problems,
                            eval_output: bool = True):
    """Host-side operand derivation SHARED by the vmapped and sharded
    engines — both consume these exact per-cell values, which is what makes
    ``mesh=`` bitwise identical. Opens the ``repro.sweep.operands`` span,
    then ``repro.sweep.masks`` around the per-cell selection keys (what
    stands in for the mask schedule)."""
    from repro.comm import config as comm_cfg

    is_chain = isinstance(algo_or_chain, chain_lib.Chain)
    eta_mode = sweep_lib._resolve_eta_mode(algo_or_chain, eta_mode)
    policies = _normalize_policies(policies)
    seeds = tuple(int(s) for s in seeds)
    etas = tuple(float(e) for e in etas)
    if not seeds:
        raise ValueError("run_selection_sweep needs at least one seed")

    if comm is None:
        from repro.comm import CommConfig

        comm = CommConfig()
    if comm.participation < 1.0:
        raise ValueError(
            "run_selection_sweep owns the participation axis through its "
            "policies; pass a CommConfig with participation=1.0 (the "
            "policy's mask replaces the config's mask schedule)")
    stages = algo_or_chain.stages if is_chain else (algo_or_chain,)
    for st in stages:
        comm_cfg.reject_algo_participation(getattr(st, "s", 0), st.name)

    with annotate("repro.sweep.operands"):
        if problems is None:
            spec = runner_lib.as_spec(problem)
            if spec is None:
                raise TypeError(
                    "run_selection_sweep needs spec-backed problems (the "
                    "policy/problem stacks are gathered per cell)")
            from repro.data import spec as spec_lib

            stacked, prob_names = spec_lib.stack_specs([spec]), (spec.name,)
        else:
            stacked, prob_names = sweep_lib._as_stacked_specs(problems)
        n_probs = len(prob_names)
        n_seeds = len(seeds)
        n_pols = len(policies)
        n_clients = int(stacked.num_clients)
        x0_stack = sweep_lib._normalize_x0_stack(x0, stacked, n_probs)

        pol_stack = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[q.params(n_clients) for q in policies])
        pst_stack = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[q.init_state(n_clients) for q in policies])
        qidx, pidx = sweep_lib.policy_index_operands(n_pols, n_probs,
                                                     n_seeds)

        keys = jnp.stack([jax.random.PRNGKey(s) for s in seeds])
        keys_c = jnp.tile(keys, (n_pols * n_probs, 1))
        etas_arr = jnp.asarray(etas, jnp.float32)
        eta_sched = (algo_or_chain.eta_schedule(rounds) if is_chain
                     else None)
        comm0 = comm.init_state(n_clients, tm.tree_index(x0_stack, 0))

    cohort = max(q.clients_per_round(n_clients) for q in policies)
    with sweep_lib.masks_span(n_clients, cohort):
        n_sched = (algo_or_chain.schedule_len(rounds) if is_chain
                   else rounds)
        # selection keys: policy-INDEPENDENT fold p·S + s, so every policy
        # at a given (problem, seed) cell consumes the same randomness
        # (paired comparisons) and the uniform policy replays the comm
        # mask-schedule fold convention exactly
        sel_keys_c = jnp.stack([
            q.sel_keys(n_sched, fold=p * n_seeds + s)
            for q in policies for p in range(n_probs)
            for s in range(n_seeds)])

    return types.SimpleNamespace(
        is_chain=is_chain, eta_mode=eta_mode, policies=policies,
        pol_names=tuple(q.name for q in policies), seeds=seeds, etas=etas,
        stacked=stacked, prob_names=prob_names, x0_stack=x0_stack,
        pol_stack=pol_stack, pst_stack=pst_stack, qidx=qidx, pidx=pidx,
        keys_c=keys_c, sel_keys_c=sel_keys_c, etas_arr=etas_arr,
        eta_sched=eta_sched, comm0=comm0, n_pols=n_pols, n_probs=n_probs,
        n_seeds=n_seeds, n_clients=n_clients, eval_output=eval_output)


def _grid_shape(ops, outs):
    shape = (ops.n_pols, ops.n_probs, ops.n_seeds)
    return jax.tree.map(lambda l: l.reshape(shape + l.shape[1:]), outs)


def run_selection_sweep(algo_or_chain, problem, x0, rounds: int, *,
                        policies, seeds: Sequence[int],
                        etas: Sequence[float] = (1.0,),
                        eta_mode: Optional[str] = None, comm=None,
                        problems=None, eval_output: bool = True,
                        mesh=None, telemetry=None) -> SelectionSweepResult:
    """Thin keyword shim over ``core.sweep.run()`` for the policy grid
    family — ``core.sweep.SweepRequest`` documents the operand axes."""
    return sweep_lib.run(sweep_lib.SweepRequest(
        algo_or_chain=algo_or_chain, problem=problem, x0=x0, rounds=rounds,
        seeds=seeds, etas=etas, policies=tuple(policies),
        eta_mode=eta_mode, comm=comm, problems=problems,
        eval_output=eval_output, mesh=mesh, telemetry=telemetry))


def _run_selection_sweep(algo_or_chain, problem, x0, rounds: int, *,
                         policies, seeds: Sequence[int],
                         etas: Sequence[float] = (1.0,),
                         eta_mode: Optional[str] = None, comm=None,
                         problems=None, eval_output: bool = True,
                         mesh=None, telemetry=None) -> SelectionSweepResult:
    """The policies × problems × seeds × stepsizes grid family, ONE
    compiled call per executor structure (see ``core.sweep.run``).

    ``policies`` is a sequence of ``SelectionPolicy`` (or policy-name
    strings); ``problems`` follows the grid family's semantics (None keeps
    a singleton problem axis from ``problem``). ``comm`` configures the
    compressed ledger (participation must stay 1.0 — the policy owns who
    participates). ``mesh`` shards the flattened cells axis (bitwise
    identical to the vmapped path, including bits_up/bits_down).
    """
    if mesh is not None:
        from repro.dist import grid as dist_grid

        return dist_grid.run_selection_sweep_sharded(
            algo_or_chain, problem, x0, rounds, policies=policies,
            seeds=seeds, etas=etas, eta_mode=eta_mode, comm=comm,
            problems=problems, eval_output=eval_output, mesh=mesh,
            telemetry=telemetry)

    ops = selection_grid_operands(
        algo_or_chain, problem, x0, rounds, policies=policies, seeds=seeds,
        etas=etas, eta_mode=eta_mode, comm=comm, problems=problems,
        eval_output=eval_output)

    with annotate("repro.sweep.executor"):
        if ops.is_chain:
            fn = sweep_lib._sweep_fn_selection_chain(
                algo_or_chain, ops.stacked, rounds, telemetry)
        else:
            fn = sweep_lib._sweep_fn_selection_algo(
                algo_or_chain, ops.stacked, rounds, eval_output,
                ops.eta_mode, telemetry)
    eta_sched = (ops.eta_sched,) if ops.is_chain else ()
    with annotate("repro.sweep.dispatch"):
        outs = fn(ops.stacked, ops.x0_stack, ops.pol_stack, ops.pst_stack,
                  ops.pidx, ops.qidx, ops.keys_c, ops.etas_arr, *eta_sched,
                  ops.sel_keys_c, ops.comm0)
    outs, taps = sweep_lib._split_taps(_grid_shape(ops, outs), telemetry)
    x_hat, history, final, *rest = outs
    kept = rest.pop(0) if ops.is_chain else None
    bits_up, bits_down, masks, pstate = rest
    return SelectionSweepResult(
        history=history, final_sub=final, x_hat=x_hat, bits_up=bits_up,
        bits_down=bits_down, masks=masks, policy_state=pstate,
        policies=ops.pol_names, problems=ops.prob_names, seeds=ops.seeds,
        etas=ops.etas, selected_initial=kept, diagnostics=taps)
