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


def _fedavg_temp_bytes(n):
    """Temp bytes of one compiled FedAvg round (QSGD-4 + EF up) of the 2NN
    reference at 8-512-512-8: a parameter copy (1.09 MB) dominates a
    client's memory, not its data."""
    model = harness.load_module("reference", "mlp")
    cfg = dict(harness.load_json("configs", "mnist-2nn"), num_clients=n,
               per_client=8, arch=[8, 512, 512, 8])
    data = {"features": jnp.zeros((n, 8, 8), jnp.float32),
            "classes": jnp.zeros((n, 8), jnp.int32)}
    ref = Reference(model, cfg, harness.load_json("traffic", "c01-qsgd4ef"),
                    data)
    x = model.init(cfg, jax.random.PRNGKey(0), jnp.float32)
    zero = jax.tree.map(jnp.zeros_like, x)
    ures = jax.tree.map(lambda l: jnp.zeros((n,) + l.shape), x)
    mem = ref.j_fedavg.lower(
        ref.data, x, zero, zero, ures, jax.random.PRNGKey(1),
        jnp.ones((n,), jnp.float32), jnp.float32(0.1)).compile(
        ).memory_analysis()
    if mem is None:
        pytest.skip("the backend reports no memory analysis")
    return mem.temp_size_in_bytes


def test_fedavg_round_holds_one_client_at_a_time():
    small, large = _fedavg_temp_bytes(4), _fedavg_temp_bytes(32)
    assert large < 2 * small, (small, large)
