"""A configuration brings what it needs as new files: its population, its
reference model with the arrays it takes and the frozen weights it makes,
its evaluation slice, its family's kernel calls, a kernel's counts and a
metric reader. The files here lie under
``tests/data/<kind>/`` and are found by name as if they lay in
``chipbench/<kind>/`` (the ``test_files`` fixture); no existing file of the
benchmark names them."""
from __future__ import annotations

import io
import types

import jax
import jax.numpy as jnp
import numpy as np

import pytest

from chipbench import harness, kernel_calls, peaks, roofline
from chipbench.reference.fedchain import Reference
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

# an adapter on a frozen base, evaluated on the first 10 of 16 samples
# (blocks of 4, 4 and a short 2)
TOY_LORA = dict(TOY_LM, name="toy-lora", family="toy-lora", num_clients=4,
                rank=2, eval_per_client=10, frozen_dtype="bfloat16")


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


def _lora_cell(config=TOY_LORA, traffic=None):
    bench = {"workloads": [{"name": "toy-lora.c01", "config": "toy-lora",
                            "traffic": "c01-qsgd4ef", "chips": 1}]}
    return harness.Cell("toy-lora.c01", bench, config=config,
                        traffic=_traffic() if traffic is None else traffic,
                        limits={})


def _replay(ref, x0, seed=2**31 + 21):
    return ref.run_cell(x0, seed=seed, mult=1.0, mask_seed=4, sel_seed=5,
                        fold=0)


def test_frozen_weights_through_setup_calibrate_and_replay(test_files):
    """``Cell.setup`` makes the frozen weights from their own key, beside
    the population and ``x0`` it made before; the problem and the
    reference get them; a new seed's weights go in through
    ``set_population``, as ``calibrate.py`` puts them, and compile
    nothing."""
    seed = 2**31 + 17
    cell = _lora_cell()
    cell.setup(seed)
    model = harness.load_module("reference", "toy-lora")
    key = jax.random.PRNGKey(harness.data_seed(seed))
    k_pop, k_init = jax.random.split(key)
    want = model.frozen(TOY_LORA, jax.random.fold_in(key, harness.FROZEN_TAG))
    assert cell.problem.frozen is cell.frozen
    for name in ("embed", "head"):
        assert cell.frozen[name].dtype == jnp.bfloat16
        assert np.array_equal(cell.frozen[name], want[name])
    tokens = harness.load_module("populations", "token-shards").make(
        TOY_LORA, k_pop)["tokens"]
    assert np.array_equal(cell.data["tokens"], tokens)
    for got, start in zip(jax.tree.leaves(cell.x0), jax.tree.leaves(
            model.init(TOY_LORA, k_init, jnp.float32))):
        assert np.array_equal(got, start)

    # the comm kernels are counted at the adapter's widths, a then b
    assert kernel_calls.leaf_widths(TOY_LORA) == [8 * 2, 2 * 8]
    ref = cell.reference()
    assert {k: v.dtype for k, v in ref.data[2].items()} == {
        "embed": jnp.float32, "head": jnp.float32}
    assert np.array_equal(ref.data[2]["head"],
                          cell.frozen["head"].astype(jnp.float32))
    out = _replay(ref, cell.x0)
    assert out.history.shape == (6,) and np.isfinite(out.history).all()
    for leaf, start in zip(jax.tree.leaves(out.x_hat),
                           jax.tree.leaves(cell.x0)):
        assert leaf.shape == start.shape and not np.allclose(leaf, start)
    # QSGD-4 up and down on the adapter's two leaves: 1 participant a round
    want_bits = np.float32(2 * (32 + 2 * 8 * 5))
    assert out.bits_up[0] == want_bits and out.bits_down[0] == want_bits

    steps = (ref.j_fedavg, ref.j_sgd, ref.j_select, ref.j_loss)
    compiled = [f._cache_size() for f in steps]
    cell.setup(seed + 1)
    ref.set_population(cell.data, cell.frozen)
    again = _replay(ref, cell.x0)
    assert [f._cache_size() for f in steps] == compiled
    assert not np.array_equal(again.history, out.history)


def test_frozen_weights_are_arguments_not_constants(test_files):
    """Every jitted step of a replay, UCB's probe too, takes the frozen
    weights (2 MB a leaf here) as an argument: no step's jaxpr holds a
    constant over 1 MB."""
    config = dict(TOY_LORA, vocab=8192, hidden=64)
    for traffic in (_traffic(), dict(_traffic(), policy={"name": "ucb"})):
        cell = _lora_cell(config, traffic)
        cell.setup(2**31 + 19)
        ref = cell.reference()
        assert all(leaf.nbytes == 2**21 for leaf in ref.data[2].values())
        seen = {}
        for name in ("j_fedavg", "j_sgd", "j_select", "j_loss", "j_ucb"):
            step = getattr(ref, name)

            def record(*args, _step=step, _name=name):
                seen.setdefault(_name, (_step, args))
                return _step(*args)

            setattr(ref, name, record)
        _replay(ref, cell.x0)
        want = {"j_fedavg", "j_sgd", "j_select", "j_loss"}
        assert set(seen) == want | ({"j_ucb"} if traffic["policy"] else set())
        for name, (step, args) in seen.items():
            with jax.default_matmul_precision(ref.precision):
                consts = step.trace(*args).jaxpr.consts
            big = [np.shape(c) for c in consts if np.asarray(c).nbytes > 2**20]
            assert not big, (name, big)


def test_global_loss_reads_the_eval_slice(test_files):
    """The history's loss is the mean over clients of the loss over each
    client's first ``eval_per_client`` samples, whatever blocks it is
    taken in; a slice no client holds is refused."""
    cell = _lora_cell()
    cell.setup(2**31 + 23)
    ref = cell.reference()
    X, Y, frozen = ref.data
    model = harness.load_module("reference", "toy-lora")
    x = jax.tree.map(lambda l: l + 0.1, cell.x0)

    def mean_loss(n):
        with jax.default_matmul_precision("highest"):
            return np.mean([float(model.loss(x, X[c, :n], Y[c, :n], ref.l2,
                                             frozen))
                            for c in range(ref.n_clients)])

    got = float(ref.j_loss(ref.data, x))
    np.testing.assert_allclose(got, mean_loss(10), rtol=1e-6)
    assert abs(got - mean_loss(16)) > 1e-4
    for e in (0, 17):
        with pytest.raises(ValueError, match="eval_per_client"):
            Reference(model, dict(TOY_LORA, eval_per_client=e), _traffic(),
                      cell.data, frozen=cell.frozen)
