"""A configuration brings what it needs as new files: its population, its
reference model with the arrays it takes, its family's kernel calls, a
kernel's counts and a metric reader. The files here lie under
``tests/data/<kind>/`` and are found by name as if they lay in
``chipbench/<kind>/`` (the ``test_files`` fixture); no existing file of the
benchmark names them."""
from __future__ import annotations

import io
import types

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness, kernel_calls, peaks, roofline
from conftest import small_cell

# a next-token configuration at a CPU test's size
TOY_LM = {
    "name": "toy-lm", "family": "toy-lm", "population": "token-shards",
    "num_clients": 8, "per_client": 16, "seq": 8, "vocab": 32, "hidden": 8,
    "batch": 4, "l2": 1e-4, "dtype": "float32",
    "matmul_precision": "highest",
    "method": {
        "local": {"algo": "fedavg", "eta": 0.5, "local_steps": 2,
                  "inner_batch": 2, "k": 4},
        "global": {"algo": "sgd", "eta": 0.5, "k": 2, "mu_avg": 0.0,
                   "output_mode": "last"},
        "local_fraction": 0.5, "selection_k": 2},
}


def _traffic(rounds=6):
    return dict(harness.load_json("traffic", "c01-qsgd4ef"), rounds=rounds,
                participation=0.25)


def test_cell_runs_on_a_population_it_names(test_files):
    """Set-up, a grid call and the check, through the logreg family, on a
    float population that only the configuration names."""
    base = small_cell("logreg.c01-qsgd4ef")
    cell = harness.Cell(base.name, config=dict(base.config,
                                               population="blobs"),
                        traffic=base.traffic)
    cell.setup(2**31 + 11)
    d = cell.config["dim"]
    blobs = harness.load_module("populations", "blobs").make(
        cell.config, jax.random.split(
            jax.random.PRNGKey(harness.data_seed(2**31 + 11)))[0])
    assert np.array_equal(cell.data["features"], blobs["features"])
    assert cell.data["features"].shape == (20, 60, d)
    call = cell.grid_call(1)
    assert call.ok
    numbers = cell.numbers([call], 2**31 + 11, cell.reference())
    assert numbers and all(v <= cell.limits[k] for k, v in numbers.items()
                           if k in cell.limits), numbers


def test_token_population_replays_on_the_reference(test_files):
    from chipbench.reference.fedchain import Reference

    data = harness.load_module("populations", "token-shards").make(
        TOY_LM, jax.random.PRNGKey(5))
    model = harness.load_module("reference", "toy-lm")
    ref = Reference(model, TOY_LM, _traffic(), data)
    X, Y = ref.data
    assert X.dtype == Y.dtype == jnp.int32
    assert (ref.n_clients, ref.n_per) == (8, 16) and X.shape == (8, 16, 8)
    # a lower precision casts floats, never token ids
    assert Reference(model, TOY_LM, _traffic(), data,
                     dtype=jnp.bfloat16).data[0].dtype == jnp.int32
    key = jax.random.PRNGKey(9)
    rows, targets = jax.jit(ref._query, static_argnums=1)(ref.data, 3, key)
    idx = np.asarray(jax.random.randint(key, (4,), 0, 16))
    assert np.array_equal(rows, np.asarray(X)[3][idx])
    assert np.array_equal(targets, np.asarray(Y)[3][idx])

    x0 = model.init(TOY_LM, jax.random.PRNGKey(1), jnp.float32)
    out = ref.run_cell(x0, seed=2**31 + 3, mult=1.0, mask_seed=4,
                       sel_seed=5, fold=0)
    assert out.history.shape == (6,) and np.isfinite(out.history).all()
    assert out.masks.sum(axis=1).tolist() == [2.0] * 6
    for leaf, start in zip(jax.tree.leaves(out.x_hat), jax.tree.leaves(x0)):
        assert leaf.shape == start.shape and not np.allclose(leaf, start)
    # QSGD-4 up and down on both leaves: 2 participants x (32 + d x 5) bits
    dims = 2 * 32 * 8
    want = np.float32(2 * 2 * (32 + dims // 2 * 5))
    assert out.bits_up[0] == want and out.bits_down[0] == want


def test_declared_kernel_call_gets_a_roofline(test_files):
    """The family's declared call is counted by ``roofline.share`` (read
    through the metric's own reader) on a hand-made traced context; the
    comm kernels follow the reference's parameter tree."""
    traffic = _traffic(rounds=50)
    calls = kernel_calls.per_call(TOY_LM, traffic, 16)
    # the QSGD legs compress embed then head, [32, 8] and [8, 32]
    assert ("qsgd_dequantize", 16, 8, 256, 49) in calls
    assert ("qsgd_dequantize", 16, 1, 256, 49) in calls
    assert calls[-1] == ("toy_embed_grad", 16, 32, 8, 25 * 2)

    traced, kernel_s = 3, 2.5e-4
    cell = types.SimpleNamespace(config=TOY_LM, traffic=traffic, cells=16,
                                 chips=1)
    log = io.StringIO()
    ctx = {"cell": cell, "device": {"kind": "TPU v5 lite"},
           "traced_calls": [None] * traced, "log": log,
           "trace": {"kernel_s": {"toy_embed_grad": kernel_s},
                     "kernel_events": {"toy_embed_grad": 50 * traced}}}
    pk = peaks.peaks("TPU v5 lite")
    flops, nbytes = 16 * 2 * 256, 16 * 4 * 3 * 256
    least = 50 * max(flops / pk["flops"], nbytes / pk["hbm_bytes_per_s"])
    want = 100.0 * least * traced / kernel_s
    read = harness.load_module("metrics", "toy_embed_grad_roofline").read
    assert read(ctx) == want == roofline.share(ctx, "toy_embed_grad")
    assert "expected" not in log.getvalue()
    # a kernel the trace did not see reads nothing
    assert roofline.share(ctx, "qsgd_dequantize") is None
