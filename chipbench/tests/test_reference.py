"""The plain reference holds one client at a time, with the numbers it gave
when it vectorised every per-client step over all N clients."""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench.reference.fedchain import Reference, uniform_row
from conftest import DATA, small_cell


@pytest.mark.parametrize("shape", [(1, 5), (7, 33), (100, 784)])
def test_uniform_row_is_the_full_draws_row(shape):
    for key in jax.random.split(jax.random.PRNGKey(2**31 + 3), 3):
        full = np.asarray(jax.random.uniform(key, shape, jnp.float32))
        row = jax.jit(uniform_row, static_argnums=1)
        for i in sorted({0, shape[0] // 2, shape[0] - 1}):
            assert np.array_equal(np.asarray(row(key, shape, i)), full[i])


def test_matches_the_vectorised_reference():
    with open(os.path.join(DATA, "vmapped_reference.json")) as f:
        want = json.load(f)
    cell = small_cell("logreg.c01-qsgd4ef")
    cell.setup(want["setup_seed"])
    ref = cell.reference()
    for row in want["replays"]:
        got = ref.run_cell(cell.x0, seed=row["seed"], mult=row["mult"],
                           mask_seed=row["mask_seed"],
                           sel_seed=row["sel_seed"], fold=row["fold"])
        np.testing.assert_allclose(got.history, row["history"], rtol=1e-6)
        np.testing.assert_allclose(np.asarray(got.x_hat), row["x_hat"],
                                   rtol=1e-6, atol=0)


def _reference_2nn(n, k=5):
    """The 2NN reference at 8-512-512-8 (a parameter copy is 1.09 MB), N
    clients of 8 samples, SGD taking ``k`` queries a client, its start."""
    model = harness.load_module("reference", "mlp")
    cfg = harness.load_json("configs", "mnist-2nn")
    method = dict(cfg["method"], **{"global": dict(cfg["method"]["global"],
                                                   k=k)})
    cfg = dict(cfg, num_clients=n, per_client=8, arch=[8, 512, 512, 8],
               method=method)
    data = {"features": jnp.zeros((n, 8, 8), jnp.float32),
            "classes": jnp.zeros((n, 8), jnp.int32)}
    ref = Reference(model, cfg, harness.load_json("traffic", "c01-qsgd4ef"),
                    data)
    return ref, model.init(cfg, jax.random.PRNGKey(0), jnp.float32)


def _temp_bytes(step, *args):
    mem = step.lower(*args).compile().memory_analysis()
    if mem is None:
        pytest.skip("the backend reports no memory analysis")
    return mem.temp_size_in_bytes


def _round_operands(ref, x):
    """Zero downlink reference and residual, zero uplink residuals, a key,
    every client in, stepsize 0.1."""
    zero = jax.tree.map(jnp.zeros_like, x)
    ures = jax.tree.map(lambda l: jnp.zeros((ref.n_clients,) + l.shape), x)
    return (zero, zero, ures, jax.random.PRNGKey(1),
            jnp.ones((ref.n_clients,), jnp.float32), jnp.float32(0.1))


def _fedavg_temp_bytes(n):
    """Temp bytes of one compiled FedAvg round (QSGD-4 + EF up) of the 2NN
    reference: a parameter copy dominates a client's memory, not its
    data."""
    ref, x = _reference_2nn(n)
    return _temp_bytes(ref.j_fedavg, ref.data, x, *_round_operands(ref, x))


def _sgd_temp_bytes(k):
    """Temp bytes of one compiled SGD round (QSGD-4 + EF up) of the 2NN
    reference at 4 clients, ``k`` gradient queries a client."""
    ref, x = _reference_2nn(4, k)
    return _temp_bytes(ref.j_sgd, ref.data, x, x, jnp.float32(1.0),
                       *_round_operands(ref, x))


def test_fedavg_round_holds_one_client_at_a_time():
    small, large = _fedavg_temp_bytes(4), _fedavg_temp_bytes(32)
    assert large < 2 * small, (small, large)


def test_sgd_round_holds_one_query_at_a_time():
    """SGD's K queries are summed one at a time: with each query's
    parameter-sized gradient kept until the mean, 16 queries would hold 16
    copies (1.09 MB each) besides a round's 5.4 MB."""
    one, many = _sgd_temp_bytes(1), _sgd_temp_bytes(16)
    assert many < 2 * one, (one, many)
