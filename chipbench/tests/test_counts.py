"""Operation and byte counts against hand counts."""
from __future__ import annotations

import json
import os

import pytest

from chipbench import flops, harness, kernel_calls


def _config(name):
    with open(os.path.join(harness.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_2nn_forward_and_gradient():
    fam = harness.load_module("families", "mlp")
    cfg = _config("mnist-2nn")
    # 784*200 + 200*200 + 200*10 = 198,800 multiply-adds a sample
    assert fam.forward_flops(cfg) == 2 * 198_800
    # forward + weight gradients + input gradients of layers 2 and 3
    assert fam.grad_flops(cfg) == 4 * 198_800 + 2 * (200 * 200 + 200 * 10)
    assert sum(kernel_calls.leaf_widths(cfg)) == cfg["params"] == 199_210


@pytest.mark.parametrize("eval_per_client", [None, 60])
def test_logreg_per_cell_round(eval_per_client):
    """The evaluation reads every client's first ``eval_per_client``
    samples, all 600 where the configuration names none."""
    fam = harness.load_module("families", "logreg")
    cfg = _config("mnist-logreg")
    if eval_per_client is not None:
        cfg["eval_per_client"] = eval_per_client
    traffic = harness.load_json("traffic", "c01-qsgd4ef")
    d = 784
    fedavg = 25 * 10 * 4 * 4 * 6 * 4 * d   # rounds x S x steps x queries x B
    sgd = 24 * 10 * 16 * 6 * 4 * d
    select = 2 * 100 * 16 * 6 * 2 * d
    evals = 51 * 100 * (eval_per_client or 600) * 2 * d  # 50 rows + final
    want = (fedavg + sgd + select + evals) / 50
    assert flops.per_cell_round(cfg, traffic, fam) == want


def test_2nn_ucb_per_cell_round_counts_the_probe():
    fam = harness.load_module("families", "mlp")
    cfg = _config("mnist-2nn")
    full = dict(harness.load_json("traffic", "c01-qsgd4ef"),
                participation=1.0, uplink={"compressor": "qsgd",
                                           "qsgd_bits": 8})
    ucb = dict(full, participation=0.1, policy={"name": "ucb"})
    f = 2 * 198_800
    probe = 50 * 100 * 10 * f / 50
    local_10 = (25 * 10 * 5 * 1 * 10 + 24 * 10 * 5 * 10) * fam.grad_flops(
        cfg) / 50
    local_100 = 10 * local_10
    base_ucb = flops.per_cell_round(cfg, ucb, fam)
    base_full = flops.per_cell_round(cfg, full, fam)
    assert base_ucb - probe - local_10 == base_full - local_100


def test_kernel_costs():
    q = harness.load_module("kernels", "qsgd_dequantize").cost
    assert q(2, 3, 5) == (2 * 11 * 15, 2 * 4 * (3 * 15 + 3 + 1))
    a = harness.load_module("kernels", "aggregate_apply").cost
    assert a(1, 100, 784) == (6 * 78_400 + 784 + 100,
                              4 * (5 * 78_400 + 2 * 784 + 200))
    w = harness.load_module("kernels", "weighted_mean_over_clients").cost
    assert w(16, 100, 156_800) == (16 * (2 * 15_680_000 + 156_800),
                                   16 * 4 * (15_680_000 + 100 + 156_800))


def test_kernel_calls_follow_the_traffic():
    cfg = _config("mnist-logreg")
    calls = kernel_calls.per_call(
        cfg, harness.load_json("traffic", "c01-qsgd4ef"), 16)
    assert ("aggregate_apply", 16, 100, 784, 49) in calls
    assert ("qsgd_dequantize", 16, 1, 784, 49) in calls
    assert ("qsgd_dequantize", 16, 100, 784, 49) in calls
    c1 = dict(harness.load_json("traffic", "c01-qsgd4ef"), participation=1.0,
              uplink={"compressor": "qsgd", "qsgd_bits": 8},
              downlink={"compressor": "qsgd", "qsgd_bits": 8})
    mlp = kernel_calls.per_call(_config("mnist-2nn"), c1, 16)
    assert {k for k, *_ in mlp} == {"qsgd_dequantize",
                                    "weighted_mean_over_clients"}
    assert sum(n for k, _, _, _, n in mlp
               if k == "weighted_mean_over_clients") == 6 * 49


@pytest.mark.parametrize("name, widths", [
    ("mnist-logreg", [784]),
    # b0, b1, b2, w0, w1, w2: the 2NN's dict leaves in sorted order
    ("mnist-2nn", [200, 200, 10, 784 * 200, 200 * 200, 200 * 10]),
])
def test_leaf_widths_from_the_parameter_tree(name, widths):
    assert kernel_calls.leaf_widths(_config(name)) == widths
