"""ProblemSpec API guarantees.

(a) spec-built problems reproduce closure-built trajectories BIT-EXACTLY —
    plain, under identity comm, and under QSGD comm (the spec rides in as an
    executor operand; the closure path bakes the same arrays as constants);
(b) a seeds × stepsizes × ζ problem grid compiles each executor exactly once
    (``runner.TRACE_COUNTS``), for a flat algorithm and a FedAvg→SGD chain,
    and matches per-problem sweeps cell-for-cell;
(c) fresh same-shaped instances reuse compiled executors (structural cache
    keys) and the executor cache holds no problem references;
(d) multi-method stacking matches per-method runs through one compile;
(e) logreg F*/x* come from the high-precision Newton solve and unknown-F*
    suboptimality is an explicit (warning) fallback, not a silent 0;
(f) the logreg and vision oracles read a query's rows in one gather under
    a cells × clients × queries vmap, never a whole client shard, and the
    rows are exactly ``features[i][idx]``.
"""
import gc
import weakref

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import CommConfig
from repro.core import algorithms as A, chain, runner, sweep
from repro.data import problems
from repro.data import spec as spec_lib

ZETAS = (0.2, 1.0, 5.0)


def quad_problem(zeta=1.0, sigma=0.2, seed=0):
    return problems.quadratic_problem(
        jax.random.PRNGKey(seed), num_clients=6, dim=12, mu=0.1, beta=1.0,
        zeta=zeta, sigma=sigma, sigma_f=0.05)


def logreg_shim(seed=0):
    rng = np.random.default_rng(seed)
    feats = rng.normal(size=(4, 50, 8)).astype(np.float32)
    labels = (rng.random((4, 50)) > 0.5).astype(np.float32)
    return problems.logreg_problem(
        jax.random.PRNGKey(seed), features=jnp.asarray(feats),
        labels=jnp.asarray(labels), l2=0.1)


# ---------------------------------------------------------------------------
# (a) spec ↔ closure bit-exactness
# ---------------------------------------------------------------------------

# The spec-operand and constant-baked-closure programs are SEPARATE
# compiles: XLA may contract a multiply-add into an FMA in one and not the
# other (the perturbed family's ζ·u + ∇base, logreg's minibatch-gathered
# logits), so those trajectories agree to a few contraction ulps — which
# compound through the iterate over the run — rather than bitwise. The
# pure elementwise quadratic family is bitwise identical.
_ULP = dict(rtol=5e-6, atol=0.0)


@pytest.mark.parametrize("build,exact", [
    (lambda: quad_problem(), True),
    (lambda: problems.general_convex_problem(
        jax.random.PRNGKey(1), num_clients=5, zeta=2.0, sigma=0.1, dim=10),
     False),
    (lambda: logreg_shim(), False),
], ids=["quadratic", "perturbed", "logreg"])
def test_spec_matches_closure_bitexact(build, exact):
    p = build()
    legacy = problems.without_spec(p)
    x0 = p.init_params(jax.random.PRNGKey(0))
    algo = A.SGD(eta=0.3, k=3, mu_avg=p.mu)
    r_spec = runner.run(algo, p.spec, x0, 8, jax.random.PRNGKey(2))
    r_shim = runner.run(algo, p, x0, 8, jax.random.PRNGKey(2))
    check = (np.testing.assert_array_equal if exact
             else lambda a, b: np.testing.assert_allclose(a, b, **_ULP))
    r_clos = runner.run(algo, legacy, x0, 8, jax.random.PRNGKey(2))
    check(np.asarray(r_spec.history), np.asarray(r_clos.history))
    np.testing.assert_array_equal(np.asarray(r_spec.history),
                                  np.asarray(r_shim.history))


@pytest.mark.parametrize("cfg", [
    CommConfig(),  # identity, full participation
    CommConfig(compressor="qsgd", qsgd_bits=4),
], ids=["identity", "qsgd4"])
def test_spec_matches_closure_under_comm(cfg):
    p = quad_problem()
    legacy = problems.without_spec(p)
    x0 = p.init_params(jax.random.PRNGKey(0))
    algo = A.SGD(eta=0.3, k=3, mu_avg=p.mu)
    r_spec = runner.run(algo, p.spec, x0, 6, jax.random.PRNGKey(2), comm=cfg)
    r_clos = runner.run(algo, legacy, x0, 6, jax.random.PRNGKey(2), comm=cfg)
    np.testing.assert_array_equal(np.asarray(r_spec.history),
                                  np.asarray(r_clos.history))
    np.testing.assert_array_equal(np.asarray(r_spec.bits_up),
                                  np.asarray(r_clos.bits_up))


def test_chain_spec_matches_closure_bitexact():
    p = quad_problem()
    legacy = problems.without_spec(p)
    x0 = p.init_params(jax.random.PRNGKey(0))
    ch = chain.fedchain(
        A.FedAvg(eta=0.3, local_steps=3, inner_batch=2),
        A.SGD(eta=0.3, k=3, mu_avg=p.mu), selection_k=4, name="spec-eq-chain")
    r_spec = ch.run(p.spec, x0, 10, jax.random.PRNGKey(3))
    r_clos = ch.run(legacy, x0, 10, jax.random.PRNGKey(3))
    np.testing.assert_array_equal(np.asarray(r_spec.history),
                                  np.asarray(r_clos.history))
    assert r_spec.selected_initial == r_clos.selected_initial


# ---------------------------------------------------------------------------
# (b) the ζ grid: one compile, per-problem equivalence
# ---------------------------------------------------------------------------

def _zeta_specs():
    return [spec_lib.quadratic_spec(
        jax.random.PRNGKey(0), num_clients=6, dim=12, mu=0.1, beta=1.0,
        zeta=z, sigma=0.2, sigma_f=0.05) for z in ZETAS]


def test_zeta_grid_single_compile_flat_algo():
    specs = _zeta_specs()
    algo = A.SGD(eta=0.4, k=3, mu_avg=0.1, name="cc-spec-sgd")
    res = sweep.run_sweep(algo, None, None, 10, seeds=(0, 1),
                          etas=(0.5, 1.0), eta_mode="scale", problems=specs)
    assert res.history.shape == (len(ZETAS), 2, 2, 10)
    assert runner.TRACE_COUNTS["sweep-probs/cc-spec-sgd"] == 1
    assert runner.TRACE_COUNTS["runner/cc-spec-sgd"] == 1
    # repeated grid call and FRESH same-shaped instances: still one compile
    specs2 = [spec_lib.quadratic_spec(
        jax.random.PRNGKey(5), num_clients=6, dim=12, mu=0.1, beta=1.0,
        zeta=z, sigma=0.2, sigma_f=0.05) for z in ZETAS]
    with runner.assert_no_retrace(what="fresh same-shaped problem instances"):
        sweep.run_sweep(algo, None, None, 10, seeds=(0, 1), etas=(0.5, 1.0),
                        eta_mode="scale", problems=specs2)
    # grid cells match per-problem sweeps
    for i, s in enumerate(specs):
        per = sweep.run_sweep(algo, s, s.x0, 10, seeds=(0, 1),
                              etas=(0.5, 1.0), eta_mode="scale")
        np.testing.assert_allclose(np.asarray(res.history[i]),
                                   np.asarray(per.history),
                                   rtol=2e-4, atol=1e-6)


def test_zeta_grid_single_compile_chain():
    specs = _zeta_specs()
    ch = chain.fedchain(
        A.FedAvg(eta=0.3, local_steps=3, inner_batch=2),
        A.SGD(eta=0.3, k=3, mu_avg=0.1), selection_k=4, name="cc-spec-chain")
    res = sweep.run_sweep(ch, None, None, 12, seeds=(0, 1), etas=(0.5, 1.0),
                          problems=specs)
    assert res.history.shape == (len(ZETAS), 2, 2, 12)
    assert res.selected_initial.shape == (len(ZETAS), 2, 2, 1)
    assert runner.TRACE_COUNTS["sweep-probs/cc-spec-chain"] == 1
    assert runner.TRACE_COUNTS["chain/cc-spec-chain"] == 1
    with runner.assert_no_retrace(what="warm chain problems grid"):
        sweep.run_sweep(ch, None, None, 12, seeds=(2, 3), etas=(0.5, 1.0),
                        problems=specs)
    for i, s in enumerate(specs):
        per = sweep.run_sweep(ch, s, s.x0, 12, seeds=(0, 1), etas=(0.5, 1.0))
        np.testing.assert_allclose(np.asarray(res.history[i]),
                                   np.asarray(per.history),
                                   rtol=2e-4, atol=1e-6)


def test_run_no_retrace_across_instances():
    algo = A.SGD(eta=0.35, k=3, mu_avg=0.1, name="cc-spec-fresh")
    p1 = quad_problem(zeta=0.5, seed=0)
    x0 = p1.init_params(None)
    runner.run(algo, p1, x0, 6, jax.random.PRNGKey(0))
    with runner.assert_no_retrace(what="fresh same-shaped problem instances"):
        for seed, zeta in ((1, 1.0), (2, 4.0)):
            p = quad_problem(zeta=zeta, seed=seed)
            runner.run(algo, p, x0, 6, jax.random.PRNGKey(0))


def test_stack_specs_rejects_structural_mismatch():
    a = spec_lib.quadratic_spec(jax.random.PRNGKey(0), dim=8)
    b = spec_lib.quadratic_spec(jax.random.PRNGKey(0), dim=10)
    with pytest.raises(ValueError, match="stack"):
        spec_lib.stack_specs([a, b])
    c = spec_lib.pl_spec(jax.random.PRNGKey(0), dim=8)
    with pytest.raises(ValueError, match="stack"):
        spec_lib.stack_specs([a, c])


def test_base_id_distinguishes_closure_values():
    """Auto-registered bases fingerprint captured values, not just bytecode:
    a parameterized base built in a loop must not silently resolve to the
    first registration."""
    def make(scale):
        def base(x):
            return scale * jnp.sum(x**2)
        return base

    a = spec_lib.base_id_for(make(1.0))
    b = spec_lib.base_id_for(make(2.0))
    assert a != b
    assert spec_lib.base_id_for(make(1.0)) == a  # same value dedupes
    x = jnp.ones((3,))
    assert float(spec_lib._BASE_REGISTRY[b](x)) == pytest.approx(6.0)


def test_problems_axis_rejects_closure_problems():
    p = problems.without_spec(quad_problem())
    algo = A.SGD(eta=0.3, k=2)
    with pytest.raises(TypeError, match="closure"):
        sweep.run_sweep(algo, None, None, 4, seeds=(0,), etas=(0.3,),
                        problems=[p])


# ---------------------------------------------------------------------------
# (c) cache hygiene: structural keys, no pinned problems
# ---------------------------------------------------------------------------

def test_executor_cache_does_not_pin_specs():
    spec = spec_lib.quadratic_spec(jax.random.PRNGKey(3), num_clients=6,
                                   dim=12, zeta=1.0)
    x0 = np.asarray(spec.x0)
    algo = A.SGD(eta=0.3, k=2, name="cc-spec-leak")
    runner.run(algo, spec, jnp.asarray(x0), 4, jax.random.PRNGKey(0))
    ref = weakref.ref(spec)
    del spec
    gc.collect()
    assert ref() is None, ("executor cache (or executors) kept the spec "
                           "alive: problems must be operands, not captures")


def test_legacy_problem_token_is_weak():
    p = problems.without_spec(quad_problem(zeta=0.7, seed=9))
    token_key = runner.problem_key(p)
    assert token_key[0] == "closure"
    pid = id(p)
    assert pid in runner._PROBLEM_TOKENS
    del p
    gc.collect()
    assert pid not in runner._PROBLEM_TOKENS  # entry died with the problem


# ---------------------------------------------------------------------------
# (d) multi-method stacking
# ---------------------------------------------------------------------------

def test_method_sweep_matches_per_method_runs():
    p = quad_problem()
    x0 = p.init_params(None)
    methods = [A.SGD(eta=0.4, k=3, mu_avg=m, name="cc-msgd")
               for m in (0.0, 0.05, 0.1)]
    res = sweep.run_method_sweep(methods, p, x0, 8, seeds=(0, 1))
    assert res.history.shape == (3, 2, 1, 8)
    assert res.methods == ("cc-msgd",) * 3
    assert runner.TRACE_COUNTS["runner-methods/cc-msgd+cc-msgd+cc-msgd"] == 1
    for i, m in enumerate(methods):
        for j, sd in enumerate((0, 1)):
            r = runner.run(m, p, x0, 8, jax.random.PRNGKey(sd))
            np.testing.assert_allclose(np.asarray(res.history[i, j, 0]),
                                       np.asarray(r.history),
                                       rtol=2e-4, atol=1e-6)
    # warm call (same grid shape): no new traces
    with runner.assert_no_retrace(what="warm method grid"):
        sweep.run_method_sweep(methods, p, x0, 8, seeds=(2, 3))


def test_method_sweep_fedavg_local_steps():
    """Different local-step counts are different TRACED loops, but the state
    structure matches — exactly what the lax.switch stacking covers."""
    p = quad_problem()
    x0 = p.init_params(None)
    methods = [A.FedAvg(eta=0.3, local_steps=ls, inner_batch=2,
                        name="cc-mfa") for ls in (2, 5)]
    res = sweep.run_method_sweep(methods, p, x0, 6, seeds=(0,))
    for i, m in enumerate(methods):
        r = runner.run(m, p, x0, 6, jax.random.PRNGKey(0))
        np.testing.assert_allclose(np.asarray(res.history[i, 0, 0]),
                                   np.asarray(r.history),
                                   rtol=2e-4, atol=1e-6)


def test_method_sweep_rejects_mismatched_states():
    p = quad_problem()
    x0 = p.init_params(None)
    with pytest.raises(TypeError, match="state structure"):
        sweep.run_method_sweep(
            [A.SGD(eta=0.3, k=2), A.Scaffold(eta=0.3)], p, x0, 4, seeds=(0,))


# ---------------------------------------------------------------------------
# (e) F*: Newton solve + explicit unknown fallback
# ---------------------------------------------------------------------------

def test_logreg_newton_fstar():
    p = logreg_shim()
    assert p.f_star is not None and p.x_star is not None
    # x* is a stationary point of the exact global objective
    g = p.global_grad(p.x_star)
    assert float(jnp.linalg.norm(g)) < 1e-5
    # F* is the minimum (float32 evaluation may undershoot by ~1e-6)
    assert float(p.global_loss(p.x_star)) == pytest.approx(p.f_star, abs=1e-5)
    w = p.init_params(None)
    assert p.suboptimality(w) > 0
    gd = w - 0.5 * p.global_grad(w)  # one gradient step stays above F*
    assert float(p.suboptimality(gd)) > -1e-5


def test_logreg_suboptimality_reporting_true_gap():
    """Table-2-style reporting: histories are F − F*, not raw loss."""
    p = logreg_shim()
    x0 = p.init_params(None)
    algo = A.SGD(eta=0.5, k=2, mu_avg=p.mu)
    res = runner.run(algo, p, x0, 6, jax.random.PRNGKey(0))
    raw = float(p.global_loss(res.x_hat))
    assert float(res.history[-1]) == pytest.approx(raw - p.f_star, abs=1e-5)


def test_unknown_fstar_warns_not_silent():
    spec = spec_lib.perturbed_spec(
        jax.random.PRNGKey(0), "logcosh", dim=6, zeta=0.5)  # f_star=None
    assert spec.f_star is None
    x = jnp.ones((6,))
    with pytest.warns(UserWarning, match="no known F\\*"):
        spec.suboptimality(x)
    shim = problems.problem_from_spec(spec)
    with pytest.warns(UserWarning, match="no known F\\*"):
        shim.suboptimality(x)


def test_spec_constants_are_leaves():
    """ζ/σ/F* ride as operand leaves: a stacked grid batches them."""
    stacked = spec_lib.stack_specs(_zeta_specs())
    assert stacked.consts["zeta"].shape == (len(ZETAS),)
    np.testing.assert_allclose(np.asarray(stacked.consts["zeta"]),
                               np.asarray(ZETAS), rtol=1e-6)
    assert stacked.x0.shape == (len(ZETAS), 12)
    assert spec_lib.spec_count(stacked) == len(ZETAS)


# ---------------------------------------------------------------------------
# (f) minibatch oracles gather the sampled rows, not whole shards
# ---------------------------------------------------------------------------

# cells × clients × queries; rows per client (_N_PER) matches no other size
_CELLS, _CLIENTS, _QUERIES, _N_PER, _D = 2, 5, 4, 40, 12


def _oracle_logreg():
    rng = np.random.default_rng(0)
    spec = spec_lib.logreg_spec(
        jax.random.PRNGKey(0),
        features=rng.normal(size=(_CLIENTS, _N_PER, _D)).astype(np.float32),
        labels=(rng.random((_CLIENTS, _N_PER)) > 0.5).astype(np.float32),
        l2=0.1, oracle_batch_frac=3 / _N_PER, solve_f_star=False)
    return spec, spec_lib._logreg_batch, spec_lib._logreg_loss_on


def _oracle_vision():
    from repro.data import vision_problem

    rng = np.random.default_rng(0)
    spec = vision_problem.vision_spec_from_shards(
        jax.random.PRNGKey(0),
        rng.normal(size=(_CLIENTS, _N_PER, _D)).astype(np.float32),
        rng.integers(0, 3, size=(_CLIENTS, _N_PER)),
        num_classes=3, hidden=7, batch=3)
    return spec, spec_lib._vision_batch, spec_lib._vision_loss_on


def _nested(fn, query_axes=(None, None, 0)):
    """``fn(x, i, key)`` over cells × clients × queries: x per cell, client
    ids [cells, clients], keys [cells, clients, queries]."""
    per_client = jax.vmap(fn, in_axes=query_axes)
    per_cell = jax.vmap(per_client, in_axes=(None, 0, 0))
    return jax.vmap(per_cell)


def _nested_operands(spec):
    xs = jax.tree.map(lambda v: jnp.stack([v, v + 0.1]), spec.x0)
    ids = jnp.stack([jnp.arange(_CLIENTS), jnp.arange(_CLIENTS)[::-1]])
    keys = jax.random.split(jax.random.PRNGKey(3),
                            _CELLS * _CLIENTS * _QUERIES)
    return xs, ids, keys.reshape(_CELLS, _CLIENTS, _QUERIES, -1)


def _gathers(closed):
    for eqn in closed.jaxpr.eqns:
        if eqn.primitive.name == "gather":
            yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jex_core.ClosedJaxpr):
                    yield from _gathers(sub)


@pytest.mark.parametrize("build", [_oracle_logreg, _oracle_vision],
                         ids=["logreg", "vision"])
def test_oracle_gathers_rows_not_shards(build):
    """A query reads its rows by (client, row) in one gather: no gather in
    the nested-vmap grad oracle slices a client's whole shard."""
    spec, _, _ = build()
    xs, ids, keys = _nested_operands(spec)
    closed = jax.make_jaxpr(
        lambda s, x, i, k: _nested(s.grad_oracle)(x, i, k))(
            spec, xs, ids, keys)
    gathers = list(_gathers(closed))
    assert gathers
    for eqn in gathers:
        assert _N_PER not in tuple(eqn.params["slice_sizes"]), eqn


@pytest.mark.parametrize("build", [_oracle_logreg, _oracle_vision],
                         ids=["logreg", "vision"])
def test_oracle_rows_match_numpy_gather(build):
    """The batch is exactly ``features[i][idx]`` for the oracle's own
    ``idx`` (the rows the chip benchmark's reference replays), and the
    gradient is the loss's gradient on those rows, bit for bit."""
    spec, batch_fn, loss_on = build()
    xs, ids, keys = _nested_operands(spec)
    X, y = jax.jit(_nested(lambda x, i, k: batch_fn(spec, i, k)))(
        xs, ids, keys)
    grads = jax.jit(_nested(spec.grad_oracle))(xs, ids, keys)

    feats = np.asarray(spec.data["features"])
    labels = np.asarray(spec.data["labels"])
    idx = np.asarray(jax.vmap(
        lambda k: jax.random.randint(k, (spec.batch,), 0, _N_PER))(
            keys.reshape(-1, keys.shape[-1])))
    idx = idx.reshape(_CELLS, _CLIENTS, _QUERIES, spec.batch)
    X_ref = np.empty(X.shape, feats.dtype)
    y_ref = np.empty(y.shape, labels.dtype)
    for c, m, q in np.ndindex(_CELLS, _CLIENTS, _QUERIES):
        i = int(ids[c, m])
        X_ref[c, m, q] = feats[i][idx[c, m, q]]
        y_ref[c, m, q] = labels[i][idx[c, m, q]]
    np.testing.assert_array_equal(np.asarray(X), X_ref)
    np.testing.assert_array_equal(np.asarray(y), y_ref)

    grad_on = jax.grad(loss_on, argnums=1)
    grads_ref = jax.jit(_nested(
        lambda x, Xq, yq: grad_on(spec, x, Xq, yq), query_axes=(None, 0, 0)))(
            xs, jnp.asarray(X_ref), jnp.asarray(y_ref))
    for g, g_ref in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_ref)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(g_ref))
