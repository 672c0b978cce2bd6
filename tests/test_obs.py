"""Run-telemetry subsystem (``repro.obs``) guarantees.

(a) ``telemetry=None`` is bitwise identical to a run without the obs layer
    on BOTH engines (vmapped and 1-device mesh), including the comm bits
    ledgers and the selection participation masks;
(b) enabling taps costs at most ONE extra compile per executor family and
    re-runs of either variant stay warm (``runner.TRACE_COUNTS``);
(c) the round taps satisfy their closed forms: ``update_norm`` is the norm
    of the server-iterate step, ``participation`` is the mask row-sum,
    ``policy_t`` counts rounds, EF-off residual norms are exactly 0.0;
(d) the event recorder writes the JSONL schema, closes under the context
    manager, turns executor traces into ``compile`` events, and the
    ``python -m repro.obs report`` CLI summarizes a log;
(e) the round-body scopes reach a compiled executor's op metadata, and a
    grid call opens its host spans, nested and in order, in a profiler
    trace.
"""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import CommConfig
from repro.core import algorithms as A, chain as chain_lib, runner, sweep
from repro.core import tree_math as tm
from repro.data import problems
from repro.obs import Telemetry, events as obs_events
from repro.selection import SelectionPolicy, run_selection_sweep

SEEDS = (0, 1)
ETAS = (0.3, 0.6)
R = 6


@pytest.fixture(scope="module")
def spec():
    return problems.quadratic_spec(
        jax.random.PRNGKey(3), num_clients=6, dim=10, mu=0.1, beta=1.0,
        zeta=1.0, sigma=0.2)


@pytest.fixture(scope="module")
def algo(spec):
    return A.SGD(eta=0.4, k=4, mu_avg=0.1)


@pytest.fixture(scope="module")
def comm_cfg():
    return CommConfig(compressor="qsgd", qsgd_bits=4, participation=0.5,
                      error_feedback=True)


def _assert_bitwise(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------- (a) telemetry=None bitwise parity --------------------------

def test_telemetry_off_bitwise_parity_vmapped(spec, algo, comm_cfg):
    off = sweep.run_sweep(algo, spec, spec.x0, R, seeds=SEEDS, etas=ETAS,
                          comm=comm_cfg)
    on = sweep.run_sweep(algo, spec, spec.x0, R, seeds=SEEDS, etas=ETAS,
                         comm=comm_cfg, telemetry=Telemetry())
    assert off.diagnostics is None
    _assert_bitwise(off.history, on.history)
    _assert_bitwise(off.bits_up, on.bits_up)
    _assert_bitwise(off.bits_down, on.bits_down)
    _assert_bitwise(off.final_sub, on.final_sub)
    taps = on.diagnostics
    assert {"update_norm", "participation", "bits_up", "bits_down",
            "residual_up_norm", "residual_mom_norm",
            "residual_down_norm"} <= set(taps)
    for leaf in taps.values():
        assert leaf.shape == (len(SEEDS), len(ETAS), R)
    # the bits taps are the ledgers themselves, re-emitted per round
    _assert_bitwise(taps["bits_up"], on.bits_up)
    _assert_bitwise(taps["bits_down"], on.bits_down)


def test_telemetry_one_device_mesh_bitwise(spec, algo, comm_cfg):
    from repro.dist import make_grid_mesh

    tel = Telemetry()
    vm = sweep.run_sweep(algo, spec, spec.x0, R, seeds=SEEDS, etas=ETAS,
                         comm=comm_cfg, telemetry=tel)
    mesh = sweep.run_sweep(algo, spec, spec.x0, R, seeds=SEEDS, etas=ETAS,
                           comm=comm_cfg, telemetry=tel,
                           mesh=make_grid_mesh(1))
    _assert_bitwise(vm.history, mesh.history)
    _assert_bitwise(vm.bits_up, mesh.bits_up)
    for k in vm.diagnostics:
        _assert_bitwise(vm.diagnostics[k], mesh.diagnostics[k])
    off = sweep.run_sweep(algo, spec, spec.x0, R, seeds=SEEDS, etas=ETAS,
                          comm=comm_cfg, mesh=make_grid_mesh(1))
    assert off.diagnostics is None
    _assert_bitwise(off.history, mesh.history)


def test_telemetry_selection_parity_and_policy_taps(spec, algo):
    pols = (SelectionPolicy("uniform", participation=0.5),
            SelectionPolicy("ucb", participation=0.5, ucb_c=0.5))
    off = run_selection_sweep(algo, None, None, R, policies=pols,
                              problems=[spec], seeds=SEEDS, etas=(1.0,))
    on = run_selection_sweep(algo, None, None, R, policies=pols,
                             problems=[spec], seeds=SEEDS, etas=(1.0,),
                             telemetry=Telemetry())
    assert off.diagnostics is None
    _assert_bitwise(off.history, on.history)
    _assert_bitwise(off.masks, on.masks)
    _assert_bitwise(off.bits_up, on.bits_up)
    taps = on.diagnostics
    # closed form: round_select advances t by 1.0 per round from 0
    _assert_bitwise(
        taps["policy_t"],
        jnp.broadcast_to(jnp.arange(1.0, R + 1.0), taps["policy_t"].shape))
    # participation tap is the mask row-sum the masks record also carries
    _assert_bitwise(taps["participation"],
                    np.asarray(on.masks).sum(axis=-1))


# --------------- (b) compile budget ------------------------------------------

def test_telemetry_adds_at_most_one_compile_per_family(spec, algo, comm_cfg):
    runner.clear_executor_cache()
    run_off = lambda: sweep.run_sweep(  # noqa: E731
        algo, spec, spec.x0, R, seeds=SEEDS, etas=ETAS, comm=comm_cfg)
    run_on = lambda: sweep.run_sweep(  # noqa: E731
        algo, spec, spec.x0, R, seeds=SEEDS, etas=ETAS, comm=comm_cfg,
        telemetry=Telemetry())
    run_off()  # cold compile of the taps-off executors
    before = runner.snapshot_traces()
    run_on()  # the taps-on variant may compile each family ONCE
    deltas = runner.trace_deltas(before)
    assert deltas, "enabling telemetry must compile a distinct executor"
    assert all(v == 1 for v in deltas.values()), deltas
    with runner.assert_no_retrace(what="warm taps-on/off sweep re-runs"):
        run_off()
        run_on()


# --------------- (c) closed forms --------------------------------------------

def test_update_norm_closed_form(spec, algo):
    tel = Telemetry()
    key = jax.random.PRNGKey(11)
    # one round: the tap IS ‖x_1 − x_0‖ of the final server iterate (the
    # key stream folds the round count, so prefixes of longer runs differ)
    r1 = runner.run(algo, spec, spec.x0, 1, key, telemetry=tel)
    _assert_bitwise(r1.diagnostics["update_norm"][0],
                    tm.tree_norm(tm.tree_sub(r1.state.x, spec.x0)))
    r2 = runner.run(algo, spec, spec.x0, 2, key, telemetry=tel)
    assert r2.diagnostics["update_norm"].shape == (2,)
    assert np.all(np.asarray(r2.diagnostics["update_norm"]) > 0.0)
    # taps are deterministic: an identical warm call reproduces them bitwise
    again = runner.run(algo, spec, spec.x0, 2, key, telemetry=tel)
    _assert_bitwise(r2.diagnostics["update_norm"],
                    again.diagnostics["update_norm"])


def test_participation_and_ef_off_residual_closed_forms(spec, algo):
    cfg = CommConfig(compressor="qsgd", qsgd_bits=4, participation=0.5)
    masks = cfg.plan().round_masks(R, spec.num_clients, fold=0)
    res = runner.run(algo, spec, spec.x0, R, jax.random.PRNGKey(0), comm=cfg,
                     comm_masks=masks, telemetry=Telemetry())
    taps = res.diagnostics
    _assert_bitwise(taps["participation"], np.asarray(masks).sum(axis=-1))
    # error feedback off → the residual tables are [N, 0] → norms exactly 0.0
    assert np.all(np.asarray(taps["residual_up_norm"]) == 0.0)
    assert np.all(np.asarray(taps["residual_mom_norm"]) == 0.0)
    assert np.all(np.asarray(taps["residual_down_norm"]) == 0.0)


def test_grad_norm_opt_in_and_stage_tap(spec, algo):
    res = sweep.run_sweep(algo, spec, spec.x0, R, seeds=SEEDS, etas=ETAS,
                          telemetry=Telemetry())
    assert "grad_norm" not in res.diagnostics  # costs a gradient: opt-in
    withg = sweep.run_sweep(algo, spec, spec.x0, R, seeds=SEEDS, etas=ETAS,
                            telemetry=Telemetry(grad_norm=True))
    assert np.all(np.asarray(withg.diagnostics["grad_norm"]) > 0.0)

    ch = chain_lib.fedchain(
        A.FedAvg(eta=0.3, local_steps=2, inner_batch=2),
        A.SGD(eta=0.4, k=4, mu_avg=0.1))
    cres = sweep.run_sweep(ch, spec, spec.x0, R, seeds=SEEDS, etas=ETAS,
                           eta_mode="scale", telemetry=Telemetry())
    stage = np.asarray(cres.diagnostics["stage"])
    assert stage.dtype == np.int32
    assert np.all(stage[..., 0] == 0) and np.all(stage[..., -1] == 1)
    assert np.all(np.diff(stage, axis=-1) >= 0)  # stages never rewind


def test_run_rejects_telemetry_for_decay_and_fraction_families(spec):
    ch = chain_lib.fedchain(
        A.FedAvg(eta=0.3, local_steps=2, inner_batch=2),
        A.SGD(eta=0.4, k=4, mu_avg=0.1))
    for axis in ({"fractions": (0.5,)}, {"decay_factors": (0.5,)}):
        with pytest.raises(ValueError, match="telemetry"):
            sweep.run(sweep.SweepRequest(
                algo_or_chain=ch, problem=spec, x0=spec.x0, rounds=4,
                seeds=(0,), telemetry=Telemetry(), **axis))


# --------------- (d) event recorder + report ---------------------------------

def test_event_recorder_jsonl_and_context_close(tmp_path):
    path = str(tmp_path / "events.jsonl")
    with obs_events.EventRecorder(path, window=2) as rec:
        rec.event("phase", name="x")
        rec.metric(0, loss=2.0)
        rec.metric(1, loss=4.0)
        rec.metric(2, loss=6.0)
        assert rec.mean("loss") == pytest.approx(5.0)  # window=2 keeps last 2
    assert rec._fh is None  # context manager closed the handle
    import json

    with open(path) as f:
        recs = [json.loads(line) for line in f]
    assert [r["kind"] for r in recs] == ["phase", "metric", "metric", "metric"]
    assert recs[1]["loss"] == 2.0 and recs[1]["step"] == 0


def test_recording_emits_compile_events_per_trace(spec, algo):
    runner.clear_executor_cache()
    with obs_events.recording() as rec:
        runner.run(algo, spec, spec.x0, 4, jax.random.PRNGKey(0))
        compiles = [r for r in rec.records if r["kind"] == "compile"]
        assert compiles, "a cold executor call must emit a compile event"
        assert all(r["compile_s"] > 0 and r["trace_tags"] for r in compiles)
        n = len(rec.records)
        runner.run(algo, spec, spec.x0, 4, jax.random.PRNGKey(0))
        warm = [r for r in rec.records[n:] if r["kind"] == "compile"]
        assert warm == [], "a warm cache hit must not emit compile events"
    assert obs_events.RECORDER is None  # recording() uninstalls on exit


def test_metrics_logger_is_obs_schema_and_report_cli(tmp_path, capsys):
    from repro.launch.metrics import MetricsLogger, read_jsonl
    from repro.obs.__main__ import main as obs_main

    path = str(tmp_path / "metrics.jsonl")
    with MetricsLogger(path) as m:
        for step in range(4):
            m.log(step, loss=float(step))
    recs = read_jsonl(path)
    assert all(r["kind"] == "metric" for r in recs)
    assert obs_main(["report", path]) == 0
    out = capsys.readouterr().out
    assert "metrics: 4 record(s)" in out and "loss" in out
    assert obs_main(["report", str(tmp_path / "missing.jsonl")]) == 2


# --------------- (e) program spans and round-body scopes --------------------

def _scopes_in(text):
    """Round-body scopes named in compiled HLO text's op_name metadata."""
    from repro.obs import SCOPES

    return {s for s in SCOPES
            if re.search(rf'op_name="[^"]*[/(]{s}[/)"]', text)}


def test_chain_comm_executor_carries_round_body_scopes(spec):
    from repro.comm import CommPlan, Leg

    chain = chain_lib.fedchain(
        A.FedAvg(eta=0.1, k=4, local_steps=2, inner_batch=2),
        A.SGD(eta=0.1, k=4, mu_avg=0.1), local_fraction=0.5)
    plan = CommPlan(uplink=Leg("qsgd", qsgd_bits=4, error_feedback=True),
                    downlink=Leg("qsgd", qsgd_bits=4), participation=0.5)
    states0 = chain.init_states(spec, spec.x0)
    masks = plan.round_masks(chain.schedule_len(R), spec.num_clients)
    fn = jax.jit(chain.executor_body(spec, R, comm=True))
    text = fn.lower(spec, spec.x0, states0, jax.random.PRNGKey(0),
                    chain.eta_schedule(R), masks,
                    plan.init_state(spec.num_clients, spec.x0)
                    ).compile().as_text()
    assert {"sample", "local", "downlink", "uplink", "select",
            "eval"} <= _scopes_in(text)


def test_sweep_run_opens_nested_host_spans_in_order(spec, algo, comm_cfg,
                                                    tmp_path):
    from jax.profiler import ProfileData

    sweep.run_sweep(algo, spec, spec.x0, R, seeds=SEEDS, etas=ETAS,
                    comm=comm_cfg)  # warm: the traced calls compile nothing
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            jax.block_until_ready(sweep.run_sweep(
                algo, spec, spec.x0, R, seeds=SEEDS, etas=ETAS,
                comm=comm_cfg).history)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = sorted(
        (e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines for e in line.events
        if e.name.startswith("repro.sweep."))
    runs = [s for s in spans if s[2] == "repro.sweep.run"]
    assert len(runs) == 2
    first, second = (r[3]["call"] for r in runs)
    assert first >= 1 and second == first + 1
    for lo, hi, _, _ in runs:
        inner = [s[2] for s in spans
                 if lo <= s[0] and s[1] <= hi and s[2] != "repro.sweep.run"]
        assert inner == ["repro.sweep." + c for c in
                         ("operands", "masks", "executor", "dispatch")]
