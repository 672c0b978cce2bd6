"""Every name in BENCHMARK.json resolves to its file; unknown names fail."""
from __future__ import annotations

import json
import os

import pytest

from chipbench import harness


def test_every_entry_resolves(bench):
    for cfg in bench["configs"]:
        assert os.path.isfile(os.path.join(harness.ROOT, cfg["file"]))
    for w in bench["workloads"]:
        cell = harness.Cell(w["name"], bench)
        assert cell.config["name"] == w["config"]
        assert cell.chips == w["chips"]
        harness.load_json("limits", w["name"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        mod = harness.load_module("metrics", m["name"])
        assert callable(mod.read)
        assert mod.UNIT == m["unit"]
        if "layer" in m:
            assert mod.LAYER == m["layer"] and mod.MOVES == m["moves"]


@pytest.mark.parametrize("kind, ext", [
    ("configs", ".json"), ("traffic", ".json"), ("limits", ".json"),
    ("metrics", ".py"), ("kernels", ".py"), ("families", ".py"),
    ("populations", ".py"), ("reference", ".py")])
def test_unknown_name_is_an_error(kind, ext):
    load = harness.load_json if ext == ".json" else harness.load_module
    with pytest.raises(LookupError):
        load(kind, "no-such-name")


def test_unknown_population_is_an_error(bench):
    entry = next(c for c in bench["configs"])
    with open(os.path.join(harness.ROOT, entry["file"])) as f:
        config = dict(json.load(f), population="no-such-name")
    with pytest.raises(LookupError):
        harness.Cell(bench["workloads"][0]["name"], bench, config=config)


def test_unknown_workload_is_an_error(bench):
    with pytest.raises(LookupError):
        harness.workload("no-such-cell", bench)


def test_metrics_follow_workloads_key(bench):
    for w in bench["workloads"]:
        names = {m["name"] for m in harness.cell_metrics(w["name"], bench,
                                                         True)}
        for m in bench["per_layer"]:
            listed = m.get("workloads")
            assert (m["name"] in names) == (listed is None
                                            or w["name"] in listed)
        e2e = {m["name"] for m in harness.cell_metrics(w["name"], bench,
                                                       False)}
        assert "setup_s" in e2e and len(e2e) >= 2


def test_seeds_are_fresh_per_call_and_fixed_per_seed():
    a = harness.call_seeds(2**31 + 7, 1, 4)
    assert a == harness.call_seeds(2**31 + 7, 1, 4)
    b = harness.call_seeds(2**31 + 7, 2, 4)
    assert set(a[0]).isdisjoint(b[0]) and a[1] != b[1]
    assert all(0 <= s < 2**31 for s in a[0])
