"""Distributed sweep scaling: the sharded grid engine over 1, 2, 4, … devices.

On an accelerator the real devices are measured in-process, over the first
1, 2 and 4 of ``jax.devices()`` (one process holds the chips; it starts no
children). On CPU the run is a rehearsal on fake host devices: XLA locks
the host device count at first JAX init, so each count runs in its OWN
subprocess (``--worker-devices``) with
``--xla_force_host_platform_device_count=N``. Either way one JSON record
per count lands in ``BENCH_dist.json``, with the platform and device kind
that produced it:

* ``warm_s`` / ``cell_rounds_per_s`` — warm-path time (min over reps) and
  throughput of ``run_sweep(..., mesh=...)`` on a ≥32-cell problems × seeds
  grid (cells × stepsizes × rounds per second);
* ``speedup_vs_1`` and ``efficiency`` — speedup over the 1-device sharded
  run, and that speedup normalized by min(devices, host cores): fake host
  devices beyond the physical core count cannot add compute, so efficiency
  is reported against what the HOST can deliver (``host_cores`` is in the
  record — judge 8-device numbers on ≥8-core machines);
* every measurement also asserts the dist invariants: equality with the
  vmapped single-device engine (bitwise on CPU; on an accelerator within
  1e-3 of each cell's first-round value, the ``dist.grid`` tolerance),
  exactly one trace per sharded executor,
  and zero warm re-traces — a scaling number from a silently re-tracing or
  numerically divergent run would be worthless.

  PYTHONPATH=src python -m benchmarks.dist_scaling            # parent
  PYTHONPATH=src python -m benchmarks.run --only dist_scaling
"""
from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time

ROOT = os.path.join(os.path.dirname(__file__), "..")
DEVICE_COUNTS = (1, 2, 4, 8)  # CPU rehearsal (fake host devices)
ACCEL_DEVICE_COUNTS = (1, 2, 4)  # real devices, in-process


def _grid_config(quick: bool):
    return {
        "n_problems": 8, "n_seeds": 4,  # 32 cells (acceptance floor)
        "etas": (0.3, 0.5),
        "rounds": 40 if quick else 160,
        "num_clients": 10, "dim": 64, "k": 8,
        "reps": 3 if quick else 5,
    }


def _measure(devices: int, quick: bool) -> dict:
    """Measure the sharded grid over the first ``devices`` devices."""
    import jax
    import numpy as np

    from repro.core import algorithms as A, runner, sweep
    from repro.data import spec as spec_lib
    from repro.dist import make_grid_mesh

    cfg = _grid_config(quick)
    mesh = make_grid_mesh(devices)
    specs = [
        spec_lib.quadratic_spec(
            jax.random.PRNGKey(7), num_clients=cfg["num_clients"],
            dim=cfg["dim"], mu=0.1, beta=1.0, zeta=0.25 * i, sigma=0.2,
            sigma_f=0.05)
        for i in range(cfg["n_problems"])
    ]
    seeds = tuple(range(cfg["n_seeds"]))
    algo = A.SGD(eta=0.4, k=cfg["k"], mu_avg=0.1)
    kw = dict(seeds=seeds, etas=cfg["etas"], problems=specs)
    rounds = cfg["rounds"]

    def block(res):
        jax.block_until_ready(res.history)
        return res

    # vmapped reference: cold + warm (and the bitwise parity target)
    t0 = time.perf_counter()
    ref = block(sweep.run_sweep(algo, None, None, rounds, **kw))
    vmapped_cold = time.perf_counter() - t0
    vmapped_warm = min(
        _timed(lambda: block(sweep.run_sweep(algo, None, None, rounds, **kw)))
        for _ in range(cfg["reps"]))

    before = runner.snapshot_traces()
    t0 = time.perf_counter()
    res = block(sweep.run_sweep(algo, None, None, rounds, mesh=mesh, **kw))
    cold_s = time.perf_counter() - t0
    deltas = runner.trace_deltas(before)
    if deltas.get("dist-probs/sgd") != 1:
        raise AssertionError(f"sharded executor traced != once: {deltas}")
    h_ref, h_res = np.asarray(ref.history), np.asarray(res.history)
    if jax.default_backend() == "cpu":
        agree = np.array_equal(h_ref, h_res)
    else:
        agree = np.all(np.abs(h_ref - h_res) <= 1e-3 * np.abs(h_ref[..., :1]))
    if not agree:
        raise AssertionError("sharded sweep diverged from vmapped engine")

    with runner.assert_no_retrace(what="warm sharded re-runs"):
        warm_s = min(
            _timed(lambda: block(
                sweep.run_sweep(algo, None, None, rounds, mesh=mesh, **kw)))
            for _ in range(cfg["reps"]))

    n_cells = cfg["n_problems"] * cfg["n_seeds"]
    lanes = n_cells * len(cfg["etas"])
    return {
        "devices": devices,
        "cold_s": cold_s, "warm_s": warm_s,
        "vmapped_cold_s": vmapped_cold, "vmapped_warm_s": vmapped_warm,
        "cell_rounds_per_s": lanes * rounds / warm_s,
    }


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _spawn(devices: int, quick: bool) -> dict:
    env = dict(os.environ)
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", "")).strip()
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={devices}".strip())
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT, env.get("PYTHONPATH", "")])
    env.pop("REPRO_DIST_DEVICES", None)  # the worker builds its own mesh
    cmd = [sys.executable, "-m", "benchmarks.dist_scaling",
           "--worker-devices", str(devices)]
    if not quick:
        cmd.append("--full")
    out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         timeout=1800, cwd=ROOT)
    if out.returncode != 0:
        raise RuntimeError(
            f"dist_scaling worker (devices={devices}) failed:\n"
            f"{out.stderr[-3000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def main(quick: bool = True):
    import jax

    from benchmarks.common import emit

    cfg = _grid_config(quick)
    cores = os.cpu_count() or 1
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        records = {d: _spawn(d, quick) for d in DEVICE_COUNTS}
    else:
        records = {d: _measure(d, quick) for d in ACCEL_DEVICE_COUNTS
                   if d <= len(jax.devices())}
    base = records[1]["warm_s"]
    report = {
        "grid": {k: v for k, v in cfg.items()},
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "host_cores": cores,
        "devices": {},
    }
    rows = []
    for d, rec in records.items():
        speedup = base / rec["warm_s"]
        # fake host devices beyond physical cores cannot add compute
        efficiency = speedup / (min(d, cores) if dev.platform == "cpu" else d)
        report["devices"][str(d)] = {
            **rec, "speedup_vs_1": speedup, "efficiency": efficiency}
        rows.append(emit(
            f"dist_scaling/devices={d}", rec["warm_s"] * 1e6,
            f"speedup={speedup:.2f}x;eff={efficiency:.2f};"
            f"cell_rounds_per_s={rec['cell_rounds_per_s']:.0f}"))
    report["speedup_at_max_devices"] = base / records[max(records)]["warm_s"]
    with open(os.path.join(ROOT, "BENCH_dist.json"), "w") as f:
        json.dump(report, f, indent=2)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker-devices", type=int, default=0)
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    if args.worker_devices:
        print(json.dumps(_measure(args.worker_devices, quick=not args.full)))
    else:
        main(quick=not args.full)
