"""The trace reduction on small traces whose numbers are counted by hand."""
from __future__ import annotations

import json
import os

import pytest

from chipbench import trace

MS = 1_000_000  # ns


def test_busy_idle_and_kernel_sums():
    devices = {
        "/device:TPU:0": [
            (0 * MS, 2 * MS, "fusion.1"),
            (1 * MS, 3 * MS, "qsgd_dequantize.2"),   # overlaps fusion.1
            (5 * MS, 6 * MS, "qsgd_dequantize"),
            (8 * MS, 12 * MS, "aggregate_apply.7"),  # clipped at 10 ms
        ],
        "/device:TPU:1": [(0 * MS, 10 * MS, "fusion.3")],
    }
    spans = [(0, 4 * MS, "chipbench.sweep_run"),
             (4 * MS, 7 * MS, "chipbench.fetch")]
    r = trace.reduce(devices, spans, (0, 10 * MS),
                     ["qsgd_dequantize", "aggregate_apply"])
    # chip 0 busy 0-3, 5-6, 8-10 = 6 ms; chip 1 busy 10 ms
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.008)
    assert r["idle_share"] == pytest.approx(0.4)
    assert r["kernel_s"]["qsgd_dequantize"] == pytest.approx(0.003)
    assert r["kernel_events"] == {"qsgd_dequantize": 2, "aggregate_apply": 1}
    # the aggregate_apply event counts whole (4 ms) though the window clips
    assert r["kernel_s"]["aggregate_apply"] == pytest.approx(0.004)
    # idle on chip 0: 3-5 ms inside sweep_run/fetch (midpoint 4 ms: fetch),
    # 6-8 ms after both spans
    assert r["idle_gaps"] == [["fetch", pytest.approx(0.002)],
                              ["between_calls", pytest.approx(0.002)]]
    names = dict(r["device_ops"])
    assert names["fusion"] == pytest.approx(0.012)


def test_recorded_trace_excerpt():
    """An excerpt of a real v5e trace (one grid call's first events)."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "trace_excerpt.json")
    if not os.path.exists(path):
        pytest.skip("no recorded excerpt")
    with open(path) as f:
        rec = json.load(f)
    devices = {k: [tuple(e) for e in v] for k, v in rec["devices"].items()}
    spans = [tuple(s) for s in rec["spans"]]
    r = trace.reduce(devices, spans, tuple(rec["window"]), rec["kernels"])
    for key, want in rec["expect"].items():
        got = r[key]
        if isinstance(want, dict):
            for k, v in want.items():
                assert got[k] == pytest.approx(v, rel=1e-9)
        else:
            assert got == pytest.approx(want, rel=1e-9)


def test_hlo_instruction_names():
    op = ('%qsgd_dequantize.3 = f32[4,4,100,784]{3,2,1,0} custom-call('
          'f32[1,1] %a), custom_call_target="tpu_custom_call"')
    assert trace.base_name(op) == "qsgd_dequantize"
    assert not trace.is_container(op)
    loop = '%while.50 = (s32[], f32[4,784]) while((s32[], f32[4,784]) %t)'
    assert trace.base_name(loop) == "while" and trace.is_container(loop)
    fused = ('%fusion.1 = f32[4] fusion(f32[4] %x), kind=kLoop, '
             'calls=%fused_computation.191')
    assert trace.base_name(fused) == "fusion"
    assert not trace.is_container(fused)


def test_containers_do_not_count_as_busy():
    loop = '%while.5 = (s32[]) while((s32[]) %t), body=%b'
    devices = {"/device:TPU:0": [(0, 10 * MS, loop),
                                 (1 * MS, 2 * MS, "%fusion.2 = f32[] fusion()"),
                                 (5 * MS, 7 * MS, "%add.1 = f32[] add()")]}
    r = trace.reduce(devices, [], (0, 10 * MS))
    assert r["busy_s"] == pytest.approx(0.003)
    assert [n for n, _ in r["device_ops"]] == ["add", "fusion"]
