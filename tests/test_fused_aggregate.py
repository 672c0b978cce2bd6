"""The fused aggregate-apply round kernel: interpret-mode Pallas vs the jnp
reference, and ``comm.uplink_fused_apply`` vs the unfused
uplink → participation-scale → server-step sequence it replaces."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import comm as comm_lib
from repro.comm import CommConfig
from repro.core import algorithms as A, runner, tree_math as tm
from repro.core.algorithms import base
from repro.kernels.aggregate import ops as agg_ops
from repro.kernels.aggregate.aggregate import aggregate_apply
from repro.kernels.aggregate.ref import aggregate_apply_ref


def _round_inputs(key, s, d):
    ks = jax.random.split(key, 7)
    x = jax.random.normal(ks[0], (d,))
    agg = jax.random.normal(ks[1], (s, d))
    comp = jax.random.normal(ks[2], (s, d))
    delta_in = jax.random.normal(ks[3], (s, d))
    res = jax.random.normal(ks[4], (s, d))
    m = (jax.random.uniform(ks[5], (s,)) < 0.5).astype(jnp.float32)
    w = jax.random.uniform(ks[6], (s,)) / s
    return x, agg, comp, delta_in, res, m, w


@pytest.mark.parametrize("s,d,block_d", [
    (8, 33, 8),   # multi-block grid with a padded tail block
    (8, 32, 8),   # exact block multiple
    (1, 5, 8),    # single client row, d smaller than one block
    (4, 1, 8),    # scalar-leaf rows ([S, 1] after ravel)
])
def test_aggregate_apply_interpret_matches_ref(s, d, block_d):
    args = _round_inputs(jax.random.PRNGKey(s * 100 + d), s, d)
    x_ref, r_ref = aggregate_apply_ref(*args)
    x_k, r_k = aggregate_apply(*args, interpret=True, block_d=block_d)
    np.testing.assert_allclose(np.asarray(x_k), np.asarray(x_ref),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(r_k), np.asarray(r_ref),
                               rtol=1e-6, atol=1e-6)
    assert x_k.shape == (d,) and r_k.shape == (s, d)


def test_aggregate_apply_masked_rows_keep_residual():
    """m=0 rows must leave their residual untouched and contribute only via
    their (already weighted) aggregate row."""
    s, d = 4, 6
    x, agg, comp, delta_in, res, _, w = _round_inputs(
        jax.random.PRNGKey(3), s, d)
    m = jnp.asarray([1.0, 0.0, 1.0, 0.0])
    _, r_out = aggregate_apply_ref(x, agg, comp, delta_in, res, m, w)
    np.testing.assert_array_equal(np.asarray(r_out[1]), np.asarray(res[1]))
    np.testing.assert_array_equal(np.asarray(r_out[3]), np.asarray(res[3]))
    np.testing.assert_allclose(np.asarray(r_out[0]),
                               np.asarray(delta_in[0] - comp[0]), rtol=1e-6)


def test_ops_dispatch_matches_kernel_and_ref():
    args = _round_inputs(jax.random.PRNGKey(7), 8, 17)
    via_ref = agg_ops.aggregate_apply(*args)
    via_kernel = agg_ops.aggregate_apply(*args, force_pallas=True)
    expect = aggregate_apply_ref(*args)
    for got, want in zip(via_ref, expect):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for got, want in zip(via_kernel, expect):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)


def _ef_comm(n, d, participation_mask=None):
    cfg = CommConfig(compressor="topk", spars_k=2, error_feedback=True)
    comm = cfg.init_state(n, d)
    if participation_mask is not None:
        comm = comm._replace(mask=jnp.asarray(participation_mask, jnp.float32))
    # a warm, nonzero residual table so the EF fold actually matters
    comm = comm._replace(residual=jax.random.normal(
        jax.random.PRNGKey(99), comm.residual.shape) * 0.1)
    return comm


def _unfused_sgd(comm, g_per, cids, key, x, eta):
    g_hat, comm2 = comm_lib.uplink(comm, g_per, cids, key)
    scale = comm_lib.participation_scale(comm2.mask, cids)
    x2 = base.fused_server_step(x, g_hat, eta, weight_scale=scale)
    return x2, comm2


@pytest.mark.parametrize("mask", [None, (1.0, 0.0, 1.0, 1.0, 0.0, 1.0)])
def test_uplink_fused_apply_matches_unfused_sgd_bitwise(mask):
    """The SGD wire format (payload = per-client gradient, no ref): the
    fused round reproduces uplink + participation scale + fused_server_step
    BITWISE — same compression randomness, same einsum fold order."""
    n, d = 6, 24
    comm = _ef_comm(n, d, mask)
    key = jax.random.PRNGKey(1)
    g_per = jax.random.normal(jax.random.PRNGKey(2), (n, d))
    cids = jnp.arange(n)
    x = jax.random.normal(jax.random.PRNGKey(4), (d,))
    eta = jnp.asarray(0.3)
    x_ref, comm_ref = _unfused_sgd(comm, g_per, cids, key, x, eta)
    x_f, comm_f = comm_lib.uplink_fused_apply(comm, g_per, cids, key, x, eta)
    np.testing.assert_array_equal(np.asarray(x_f), np.asarray(x_ref))
    np.testing.assert_array_equal(np.asarray(comm_f.residual),
                                  np.asarray(comm_ref.residual))
    # the interpret-mode kernel path agrees to float tolerance
    x_k, comm_k = comm_lib.uplink_fused_apply(comm, g_per, cids, key, x, eta,
                                              force_pallas=True)
    np.testing.assert_allclose(np.asarray(x_k), np.asarray(x_ref),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(comm_k.residual),
                               np.asarray(comm_ref.residual),
                               rtol=1e-5, atol=1e-6)


def test_uplink_fused_apply_matches_unfused_fedavg():
    """The local-update wire format (ref=x, delta payload, negative η for
    the lerp): fused vs reconstruct-then-lerp to float tolerance."""
    n, d = 6, 24
    comm = _ef_comm(n, d)
    key = jax.random.PRNGKey(5)
    y_final = jax.random.normal(jax.random.PRNGKey(6), (n, d))
    cids = jnp.arange(n)
    x = jax.random.normal(jax.random.PRNGKey(7), (d,))
    server_lr = 0.8
    y_hat, comm_ref = comm_lib.uplink(comm, y_final, cids, key, ref=x)
    scale = comm_lib.participation_scale(comm_ref.mask, cids)
    y_mean = base.client_mean(x, y_hat, weight_scale=scale)
    x_ref = tm.tree_lerp(server_lr, x, y_mean)
    x_f, comm_f = comm_lib.uplink_fused_apply(
        comm, y_final, cids, key, x, -server_lr, ref=x)
    np.testing.assert_allclose(np.asarray(x_f), np.asarray(x_ref),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(comm_f.residual),
                                  np.asarray(comm_ref.residual))


def test_uplink_fused_apply_rejects_non_ef():
    comm = CommConfig(compressor="qsgd", qsgd_bits=4).init_state(4, 8)
    with pytest.raises(ValueError, match="error-feedback"):
        comm_lib.uplink_fused_apply(
            comm, jnp.zeros((4, 8)), jnp.arange(4), jax.random.PRNGKey(0),
            jnp.zeros((8,)), jnp.asarray(0.1))


def test_fused_round_end_to_end_matches_ref_path(monkeypatch):
    """REPRO_FORCE_PALLAS=1 routes SGD's EF round through the fused kernel;
    the full runner history must match the default ref path to float
    tolerance (the env var keys the executor cache, so no stale reuse)."""
    from repro.data import problems

    p = problems.quadratic_problem(
        jax.random.PRNGKey(0), num_clients=8, dim=16, mu=0.1, beta=1.0,
        zeta=1.0, sigma=0.2, sigma_f=0.05)
    cfg = CommConfig(compressor="topk", spars_k=2, error_feedback=True,
                     participation=0.5)
    algo = A.SGD(eta=0.2, k=2, mu_avg=0.1, output_mode="last")
    x0 = p.init_params(jax.random.PRNGKey(0))
    run = lambda: runner.run(  # noqa: E731
        algo, p, x0, 6, jax.random.PRNGKey(0), comm=cfg)
    monkeypatch.delenv("REPRO_FORCE_PALLAS", raising=False)
    ref = run()
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    fused = run()
    assert agg_ops.use_fused_aggregate()  # the env gate is actually on
    np.testing.assert_allclose(np.asarray(fused.history),
                               np.asarray(ref.history), rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(fused.state.comm.residual),
        np.asarray(ref.state.comm.residual), rtol=1e-4, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(fused.state.comm.bits_up),
                                  np.asarray(ref.state.comm.bits_up))


def test_fused_fedavg_round_with_lossy_downlink_matches_ref_path(monkeypatch):
    """Under a lossy downlink FedAvg's clients start at the downlink
    reconstruction x_start ≠ x; the fused EF round must fold the
    reconstruct-then-lerp offset lr·(x_start − x) in exactly as the unfused
    ref path does, so both histories agree to float tolerance."""
    from repro.comm import CommPlan, Leg
    from repro.data import problems

    p = problems.quadratic_problem(
        jax.random.PRNGKey(0), num_clients=8, dim=16, mu=0.1, beta=1.0,
        zeta=1.0, sigma=0.2, sigma_f=0.05)
    plan = CommPlan(uplink=Leg("topk", spars_k=2, error_feedback=True),
                    downlink=Leg("qsgd", qsgd_bits=4), participation=0.5)
    algo = A.FedAvg(eta=0.2, local_steps=2, inner_batch=2)
    run = lambda: runner.run(  # noqa: E731
        algo, p, p.init_params(jax.random.PRNGKey(0)), 6,
        jax.random.PRNGKey(0), comm=plan)
    monkeypatch.delenv("REPRO_FORCE_PALLAS", raising=False)
    ref = run()
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    fused = run()
    np.testing.assert_allclose(np.asarray(fused.history),
                               np.asarray(ref.history), rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(fused.x_hat), np.asarray(ref.x_hat),
                               rtol=1e-4, atol=1e-6)
