"""The program-trace reduction: scopes of device ops, program host spans,
idle gaps named by the innermost span, on hand-counted traces and on a
small CPU run."""
from __future__ import annotations

import glob
import io
import json
import os

import pytest

from chipbench import harness, program_trace as pt, trace

MS = 1_000_000  # ns


def test_scope_of_name_stacks():
    base = "jit(grid)/vmap(jit(grid))/while/body/closed_call"
    assert pt.scope_of(base + "/local/vmap(local)/mul") == "local"
    assert pt.scope_of(base + "/vmap(local)/sin") == "local"
    assert pt.scope_of(base + "/cond/branch_1_fun/uplink/add") == "uplink"
    # the first scope in the stack owns the op
    assert pt.scope_of(base + "/select/eval/reduce_sum") == "select"
    assert pt.scope_of(base + "/server/transpose(jvp(eval))/mul") == "server"
    # a jitted function's name and a primitive's are not scopes
    assert pt.scope_of("jit(local)/select_n") == "none"
    assert pt.scope_of(base + "/dynamic_update_slice") == "none"
    assert pt.scope_of(None) == "none"


def test_ops_without_a_name_stack_take_their_containers_scope():
    """A gather XLA turned into a loop: the while op carries the name stack,
    the fusions of its body carry none."""
    stacks = {
        "while.63": "jit(cell)/while/body/closed_call/local/vmap()/gather",
        "while.1": "jit(cell)/while",
        "add.7": "jit(cell)/while/body/closed_call/eval/add",
    }
    events = [
        (0, 100, "%while.1 = (s32[]) while((s32[]) %t), body=%b1"),
        (10, 60, "%while.63 = (s32[]) while((s32[]) %u), body=%b2"),
        (10, 30, "%bitcast_dynamic-update-slice_fusion.14 = f32[] fusion()"),
        (30, 60, "%bitcast_dynamic-update-slice_fusion.14 = f32[] fusion()"),
        (60, 70, "%add.7 = f32[] add()"),
        (70, 80, "%copy.2 = f32[] copy()"),
        (120, 130, "%copy.3 = f32[] copy()"),
    ]
    ops, found = pt.assign_scopes(events, lambda t, instr: stacks.get(instr))
    assert found == 3
    assert [op[3] for op in ops] == ["none", "local", "local", "local",
                                     "eval", "none", "none"]


def test_vocabulary_is_the_programs():
    from repro.obs import SCOPES

    assert pt.SCOPES == SCOPES


def test_scopes_host_spans_and_gap_labels():
    """Two devices; overlapping, clipped and container ops; a call whose
    program spans nest inside the benchmark's span."""
    loop = "%while.5 = (s32[]) while((s32[]) %t), body=%b"
    devices = {
        "/device:TPU:0": [
            (0 * MS, 10 * MS, loop, "none"),          # container: no time
            (0 * MS, 2 * MS, "%fusion.1 = f32[] fusion()", "local"),
            (1 * MS, 3 * MS, "%fusion.2 = f32[] fusion()", "uplink"),
            (5 * MS, 6 * MS, "%add.1 = f32[] add()", "eval"),
            (8 * MS, 12 * MS, "%copy.3 = f32[] copy()", "none"),
        ],
        "/device:TPU:1": [(0 * MS, 10 * MS, "%fusion.3 = f32[] fusion()",
                           "local")],
    }
    spans = [
        (0 * MS, 7 * MS, "chipbench.sweep_run", {}),
        (0 * MS, 7 * MS, "repro.sweep.run", {"call": 4}),
        (0 * MS, 1 * MS, "repro.sweep.operands", {}),
        (1 * MS, 4 * MS, "repro.sweep.masks", {}),
        (4 * MS, 4 * MS + 500_000, "repro.sweep.executor", {}),
        (4 * MS + 500_000, 7 * MS, "repro.sweep.dispatch", {}),
        (7 * MS, 9 * MS, "chipbench.fetch", {}),
        (20 * MS, 21 * MS, "repro.sweep.run", {"call": 5}),  # outside
    ]
    r = pt.reduce(devices, spans, (0, 10 * MS))
    # chip 0: local 0-2, uplink 2-3 (its overlap credited once), eval 5-6,
    # none 8-10 (clipped); chip 1: local 0-10
    assert r["scope_s"] == {"local": pytest.approx(0.012),
                            "uplink": pytest.approx(0.001),
                            "eval": pytest.approx(0.001),
                            "none": pytest.approx(0.002)}
    old = trace.reduce({k: [op[:3] for op in v] for k, v in devices.items()},
                       [s[:3] for s in spans], (0, 10 * MS))
    assert sum(r["scope_s"].values()) == pytest.approx(2 * old["busy_s"])
    assert r["busy_s"] == pytest.approx(old["busy_s"])
    assert r["host_spans"]["repro.sweep.masks"] == [pytest.approx(0.003), 1]
    assert r["host_spans"]["repro.sweep.run"] == [pytest.approx(0.007), 1]
    assert r["host_spans"]["chipbench.fetch"] == [pytest.approx(0.002), 1]
    # chip 0 idles 3-5 ms (midpoint 4 ms: the executor span is open, and
    # innermost) and 6-8 ms (midpoint 7 ms: the benchmark's fetch)
    assert r["idle_gaps"] == [["sweep.executor", pytest.approx(0.002)],
                              ["fetch", pytest.approx(0.002)]]
    assert r["device_scopes"][0] == ["local", pytest.approx(0.012)]


def test_recorded_excerpt_reads_as_before():
    """The recorded v5e excerpt (no program spans, no scopes): busy time and
    idle gaps read what ``trace.reduce`` reads."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "trace_excerpt.json")
    with open(path) as f:
        rec = json.load(f)
    devices = {k: [tuple(e) + ("none",) for e in v]
               for k, v in rec["devices"].items()}
    spans = [tuple(s) + ({},) for s in rec["spans"]]
    window = tuple(rec["window"])
    r = pt.reduce(devices, spans, window)
    old = trace.reduce({k: [tuple(e) for e in v]
                        for k, v in rec["devices"].items()},
                       [tuple(s) for s in rec["spans"]], window,
                       rec["kernels"])
    assert r["busy_s"] == pytest.approx(rec["expect"]["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(rec["expect"]["window_s"],
                                          rel=1e-9)
    assert list(r["scope_s"]) == ["none"]
    assert r["idle_gaps"] == old["idle_gaps"]


def _scoped_trace(d):
    import jax
    import jax.numpy as jnp
    from repro.obs import annotate, scope

    def body(c, x):
        with scope("local"):
            c = jax.vmap(lambda r: jnp.sin(r) * x)(c)
        with scope("eval"):
            loss = jnp.sum(c * c)
        return c, loss

    f = jax.jit(lambda c, xs: jax.lax.scan(body, c, xs))
    c, xs = jnp.ones((2, 8)), jnp.ones((3, 8))
    f(c, xs)[0].block_until_ready()
    jax.profiler.start_trace(str(d))
    with annotate("repro.sweep.run", call=3):
        with annotate("repro.sweep.dispatch"):
            out = f(c, xs)
        out[0].block_until_ready()
    jax.profiler.stop_trace()
    return glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]


def test_hlo_protos_and_span_arguments_of_a_cpu_trace(tmp_path):
    path = _scoped_trace(tmp_path)
    with open(path, "rb") as f:
        names = pt.hlo_op_names(f.read())
    scopes = {pt.scope_of(op) for table in names.values()
              for op in table.values()}
    assert {"local", "eval"} <= scopes
    _, spans, _ = pt.extract(path)
    run = [s for s in spans if s[2] == "repro.sweep.run"]
    disp = [s for s in spans if s[2] == "repro.sweep.dispatch"]
    assert len(run) == 1 and len(disp) == 1
    assert run[0][3] == {"call": 3}
    assert run[0][0] <= disp[0][0] and disp[0][1] <= run[0][1]


def test_measure_on_a_small_cell_reads_the_host_spans():
    """The whole stretch on the CPU: a re-lowering call, profiled grid
    calls, the program's spans. (The CPU trace has no TPU op lines, so the
    device metrics find nothing to read here.)"""
    from conftest import small_cell

    cell = small_cell("logreg.c01-qsgd4ef", rounds=4)
    cell.setup(2**31 + 17)
    calls = [cell.grid_call(1)]
    log = io.StringIO()
    ctx = {"cell": cell, "calls": calls, "traced_calls": [], "log": log}
    out = pt.measure(ctx)
    assert "error" not in out, log.getvalue()
    assert out["trace_lower_s"] > 0
    n = out["calls"]
    assert n >= 2
    for child in ("operands", "masks", "executor", "dispatch"):
        assert out["host_spans"]["repro.sweep." + child][1] == n
    assert out["host_spans"]["repro.sweep.run"][1] == n
    for m in ("host_masks_ms_per_grid", "host_dispatch_ms_per_grid",
              "executor_trace_lower_s"):
        assert harness.load_module("metrics", m).read(ctx) > 0
    for m in ("round_local_us", "round_comm_us", "round_eval_us",
              "device_unscoped_share"):
        assert harness.load_module("metrics", m).read(ctx) is None
    assert pt.measure(ctx) is out  # made once per run


def test_readers_find_nothing_in_a_program_without_spans():
    """What the readers give on a program that opens no ``repro.`` span
    and scopes no op, or where the stretch failed."""
    ctx = {"program_trace": {"scope_s": {"none": 1.0},
                             "host_spans": {"chipbench.sweep_run": [1, 2]},
                             "calls": 2, "rounds": 50,
                             "trace_lower_s": 3.0}}
    for m in ("host_masks_ms_per_grid", "host_dispatch_ms_per_grid",
              "round_local_us", "round_comm_us", "round_eval_us",
              "device_unscoped_share"):
        assert harness.load_module("metrics", m).read(ctx) is None
    failed = {"program_trace": {"error": "RuntimeError()"}}
    for m in ("host_masks_ms_per_grid", "round_local_us",
              "device_unscoped_share", "executor_trace_lower_s"):
        assert harness.load_module("metrics", m).read(failed) is None


def test_readers_per_call_and_per_round():
    ctx = {"program_trace": {
        "scope_s": {"local": 0.4, "uplink": 0.1, "downlink": 0.05,
                    "eval": 0.02, "none": 0.03},
        "host_spans": {"repro.sweep.masks": [0.06, 4],
                       "repro.sweep.dispatch": [0.004, 4]},
        "calls": 4, "rounds": 50, "trace_lower_s": 11.0}}
    read = {m: harness.load_module("metrics", m).read(ctx) for m in (
        "host_masks_ms_per_grid", "host_dispatch_ms_per_grid",
        "round_local_us", "round_comm_us", "round_eval_us",
        "device_unscoped_share", "executor_trace_lower_s")}
    assert read["host_masks_ms_per_grid"] == pytest.approx(15.0)
    assert read["host_dispatch_ms_per_grid"] == pytest.approx(1.0)
    assert read["round_local_us"] == pytest.approx(2000.0)
    assert read["round_comm_us"] == pytest.approx(750.0)
    assert read["round_eval_us"] == pytest.approx(100.0)
    assert read["device_unscoped_share"] == pytest.approx(5.0)
    assert read["executor_trace_lower_s"] == 11.0


def test_stale_metadata_compiles_again_with_metadata_in_the_key(
        monkeypatch):
    """An executable read back with no scope on any op, from a program that
    defines scopes: compiled again with metadata in the cache key, and the
    JAX flag is restored after."""
    import jax

    flag = "jax_compilation_cache_include_metadata_in_key"
    seen = []
    spans = [(0, 10, "chipbench.sweep_run", {}), (10, 20, "chipbench.fetch",
                                                   {})]

    def profile(cell, first, min_calls, min_seconds):
        scope = "local" if getattr(jax.config, flag) else "none"
        return ({"/device:TPU:0": [(0, 5, "%fusion.1 = f32[] fusion()",
                                    scope)]}, spans, 1, 2)

    def relowered(cell, call):
        seen.append(getattr(jax.config, flag))
        return 7.0

    monkeypatch.setattr(pt, "_profile", profile)
    monkeypatch.setattr(pt, "_relowered_call", relowered)

    class Cell:
        rounds = 50

    ctx = {"cell": Cell(), "calls": [], "log": io.StringIO()}
    out = pt.measure(ctx)
    assert seen == [False, True] and not getattr(jax.config, flag)
    assert out["recompiled"] and out["scope_s"] == {"local": 5e-9}
    assert out["trace_lower_s"] == 7.0
