"""Participants-only local work in the comm rounds of FedAvg and SGD.

``base.participant_rows`` computes the round's local work for the clients
the participation mask keeps, in static blocks of B = ⌈N/8⌉ rows, with the
number of blocks read from the mask at run time. The oracle kept here is
the all-N round: ``participant_rows`` replaced by the plain call over every
client's row, which is what the comm rounds computed before. Non-participant
rows carry no signal (weight 0, residual kept), so the iterate, both bit
meters and every residual equal the oracle's.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import CommPlan, Leg
from repro.comm import config as comm_cfg
from repro.core import algorithms as A, runner
from repro.core.algorithms import base
from repro.data import spec as spec_lib

N, N_PER, DIM = 20, 24, 12
B = base.participant_block(N)
ROUNDS = 3

PLANS = {
    "qsgd4ef": CommPlan(uplink=Leg("qsgd", qsgd_bits=4, error_feedback=True),
                        downlink=Leg("qsgd", qsgd_bits=4)),
    "identity": CommPlan(),
}


def _all_rows(fn, cids, keys, mask, fill):
    return fn(cids, keys)


@pytest.fixture(scope="module")
def problem():
    kf, kl = jax.random.split(jax.random.PRNGKey(7))
    features = jax.random.normal(kf, (N, N_PER, DIM), jnp.float32)
    labels = (jax.random.uniform(kl, (N, N_PER)) < 0.5).astype(jnp.float32)
    return spec_lib.logreg_spec(
        jax.random.PRNGKey(0), features=features, labels=labels, l2=1e-2,
        oracle_batch_frac=0.25, solve_f_star=False)


def _algo(name):
    if name == "fedavg":
        return A.FedAvg(eta=0.2, local_steps=3, inner_batch=2)
    return A.SGD(eta=0.2, k=4, mu_avg=1e-2)


def _x0(problem):
    return 0.1 * jax.random.normal(jax.random.PRNGKey(1), (DIM,))


def _state0(algo, problem, plan):
    x0 = _x0(problem)
    state = algo.init(problem, x0)
    comm = plan.init_state(N, x0)
    if comm_cfg.ef_enabled(comm):
        # nonzero residuals, so that keeping them is visible
        comm = comm._replace(residual=0.01 * jax.random.normal(
            jax.random.PRNGKey(2), comm.residual.shape))
    return state._replace(comm=comm)


def _rounds(algo, problem, state, masks):
    """Run one round per mask row; returns the states after each round."""
    step = jax.jit(lambda st, k: algo.round(problem, st, k))
    out = []
    for r, mask in enumerate(masks):
        comm = comm_cfg.zero_round_bits(state.comm._replace(mask=mask))
        state = step(state._replace(comm=comm),
                     jax.random.fold_in(jax.random.PRNGKey(3), r))
        out.append(state)
    return out


def _assert_same(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _check_against_oracle(monkeypatch, algo, problem, plan, masks):
    state0 = _state0(algo, problem, plan)
    got = _rounds(algo, problem, state0, masks)
    with monkeypatch.context() as m:
        m.setattr(base, "participant_rows", _all_rows)
        want = _rounds(algo, problem, state0, masks)
    prev = state0
    for mask, g, w in zip(masks, got, want):
        _assert_same(g.x, w.x)
        _assert_same(g.comm.bits_up, w.comm.bits_up)
        _assert_same(g.comm.bits_down, w.comm.bits_down)
        _assert_same(g.comm.residual, w.comm.residual)
        if comm_cfg.ef_enabled(g.comm):
            out = np.asarray(mask) == 0
            np.testing.assert_array_equal(
                np.asarray(g.comm.residual)[out],
                np.asarray(prev.comm.residual)[out])
        prev = g


@pytest.mark.parametrize("plan", sorted(PLANS))
@pytest.mark.parametrize("participation", [0.1, 0.25, 0.5, 1.0])
@pytest.mark.parametrize("algo_name", ["fedavg", "sgd"])
def test_participant_rounds_match_all_clients(monkeypatch, problem,
                                              algo_name, participation,
                                              plan):
    cfg = PLANS[plan]
    cfg = CommPlan(uplink=cfg.uplink, downlink=cfg.downlink,
                   participation=participation, mask_seed=5)
    masks = cfg.round_masks(ROUNDS, N)
    _check_against_oracle(monkeypatch, _algo(algo_name), problem, cfg, masks)


def _custom_masks():
    """Rows of sums 0, 1, S and N (S the C = 0.25 cohort)."""
    s = CommPlan(participation=0.25).clients_per_round(N)
    rows = [np.zeros(N), np.eye(N)[N - 1], np.zeros(N), np.ones(N)]
    rows[2][np.random.default_rng(0).permutation(N)[:s]] = 1.0
    return jnp.asarray(np.stack(rows), jnp.float32)


@pytest.mark.parametrize("algo_name", ["fedavg", "sgd"])
def test_custom_mask_row_sums_match_all_clients(monkeypatch, problem,
                                                algo_name):
    masks = _custom_masks()
    assert [int(r) for r in masks.sum(axis=1)] == [0, 1, 5, N]
    _check_against_oracle(monkeypatch, _algo(algo_name), problem,
                          PLANS["qsgd4ef"], masks)

    # through the runner's comm executor, as `comm_masks`
    algo, plan = _algo(algo_name), PLANS["qsgd4ef"]

    def run():
        return runner.run(algo, problem, _x0(problem), len(masks),
                          jax.random.PRNGKey(4), comm=plan,
                          comm_masks=masks, jit=False)

    got = run()
    with monkeypatch.context() as m:
        m.setattr(base, "participant_rows", _all_rows)
        want = run()
    for field in ("x_hat", "history", "bits_up", "bits_down"):
        _assert_same(getattr(got, field), getattr(want, field))


@pytest.mark.parametrize("algo_name", ["fedavg", "sgd"])
def test_vmapped_cells_with_differing_trip_counts(monkeypatch, problem,
                                                  algo_name):
    """Under vmap over cells the block loop runs to the largest trip count;
    the extra blocks of the smaller cells leave their results as they are."""
    algo, plan = _algo(algo_name), PLANS["qsgd4ef"]
    masks = _custom_masks()
    state0 = _state0(algo, problem, plan)
    cells = jax.tree.map(lambda l: jnp.stack([l] * len(masks)), state0)
    cells = cells._replace(comm=cells.comm._replace(mask=masks))
    keys = jax.random.split(jax.random.PRNGKey(6), len(masks))

    def go():
        return jax.jit(jax.vmap(lambda st, k: algo.round(problem, st, k)))(
            cells, keys)

    got = go()
    with monkeypatch.context() as m:
        m.setattr(base, "participant_rows", _all_rows)
        want = go()
    _assert_same(got.x, want.x)
    _assert_same(got.comm, want.comm)


def test_fused_ef_round_matches_all_clients(monkeypatch, problem):
    """The fused aggregate-apply path (Pallas in interpret mode here)."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    cfg = PLANS["qsgd4ef"]
    cfg = CommPlan(uplink=cfg.uplink, downlink=cfg.downlink,
                   participation=0.25, mask_seed=5)
    masks = cfg.round_masks(2, N)
    for name in ("fedavg", "sgd"):
        _check_against_oracle(monkeypatch, _algo(name), problem, cfg, masks)


def _gathers_from(jaxpr, shape):
    """Output shapes of every gather (sub-jaxprs included) that reads an
    operand of ``shape``."""
    found = []
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "gather"
                and tuple(eqn.invars[0].aval.shape) == shape):
            found.append(tuple(eqn.outvars[0].aval.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _gathers_from(sub, shape)
    return found


@pytest.mark.parametrize("algo_name", ["fedavg", "sgd"])
def test_oracle_gather_reads_one_block_of_rows(problem, algo_name):
    algo, plan = _algo(algo_name), PLANS["qsgd4ef"]
    state = _state0(algo, problem, plan)
    jaxpr = jax.make_jaxpr(lambda st, k: algo.round(problem, st, k))(
        state, jax.random.PRNGKey(0))
    found = _gathers_from(jaxpr.jaxpr, problem.data["features"].shape)
    assert found, "no gather from the feature shards in the round"
    assert B < N
    assert all(shape[0] == B for shape in found), found


def test_block_size_depends_on_n_alone():
    assert [base.participant_block(n) for n in (1, 8, 9, 20, 100, 1000)] == [
        1, 1, 2, 3, 13, 125]
    assert base.local_rows(100, 10) == 13
    assert base.local_rows(100, 100) == 104
    assert base.local_rows(20, 5) == 6


@pytest.mark.parametrize("engine", ["sweep", "grid", "selection"])
def test_masks_span_reports_cohort_and_local_rows(monkeypatch, problem,
                                                  engine):
    from repro.core import sweep
    from repro.dist import mesh as mesh_lib
    from repro.selection import SelectionPolicy
    from repro.selection.sweep import run_selection_sweep

    seen, annotate = [], sweep.annotate

    def record(name, **args):
        seen.append((name, args))
        return annotate(name, **args)

    monkeypatch.setattr(sweep, "annotate", record)
    algo, x0 = _algo("sgd"), _x0(problem)
    plan = CommPlan(uplink=Leg("qsgd", qsgd_bits=4, error_feedback=True))
    if engine == "selection":
        pols = (SelectionPolicy("uniform", participation=0.1),
                SelectionPolicy("ucb", participation=0.25))
        run_selection_sweep(algo, problem, x0, 2, policies=pols, seeds=(0,),
                            comm=plan)
    else:
        plan = CommPlan(uplink=plan.uplink, participation=0.25)
        mesh = mesh_lib.make_grid_mesh(1) if engine == "grid" else None
        sweep.run_sweep(algo, problem, x0, 2, seeds=(0,), etas=(1.0,),
                        comm=plan, mesh=mesh)
    spans = [args for name, args in seen if name == "repro.sweep.masks"]
    # 5 of 20 participants, in blocks of B = 3 rows
    assert spans == [{"cohort": 5, "local_rows": 6}]
