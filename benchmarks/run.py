"""Benchmark orchestrator — one harness per paper table/figure plus the
roofline/kernel reports. Prints ``name,us_per_call,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only table1,...]
                                          [--devices N] [--profile]

``--devices N`` FORCES THE CPU BACKEND (``JAX_PLATFORMS=cpu``) with N fake
XLA host devices, set before the first JAX import: a CPU rehearsal of the
sharded sweep paths (``repro.dist``) for CPU-only machines and CI.
Harnesses pick the debug mesh up via ``repro.dist.auto_grid_mesh``. No chip
run uses it; on an accelerator host the harnesses see the real devices.

The persistent compilation cache goes where ``repro.compile_cache`` says.

``--profile`` runs the obs-instrumented variant (``repro.obs``): a run
manifest (backend, devices, XLA flags, config hash) is written to
``BENCH_manifest.json`` next to the ``BENCH_*.json`` numbers, an event
recorder captures every executor compile and cache op to
``obs_events.jsonl``, and each harness runs TWICE inside profiler-annotated
phases — cold (carries the compiles) then warm (only what survives the
executor cache; harnesses that clear it re-pay theirs) — the uniform
compile-vs-warm breakdown. ``--profile-dir DIR`` additionally
captures a ``jax.profiler`` trace. Summarize the event log afterwards with
``python -m repro.obs report``.
"""
from __future__ import annotations

import argparse
import contextlib
import os
import sys
import time


def _force_host_devices(n: int) -> None:
    """Set the XLA device-count flag — only valid before JAX initializes."""
    if "jax" in sys.modules:
        print("--devices must be handled before JAX is imported; run via "
              "`python -m benchmarks.run`, not from a live JAX process",
              file=sys.stderr)
        sys.exit(2)
    flags = os.environ.get("XLA_FLAGS", "")
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n}".strip())
    os.environ["JAX_PLATFORMS"] = "cpu"  # host devices are a CPU feature
    # literal name of repro.dist.mesh.DEVICES_ENV — importing it here would
    # initialize JAX before the flag lands
    os.environ["REPRO_DIST_DEVICES"] = str(n)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale rounds")
    ap.add_argument("--only", default="", help="comma-separated harness names")
    ap.add_argument("--devices", type=int, default=0,
                    help="force the CPU backend with N fake XLA host devices "
                    "(before JAX import): a CPU rehearsal of the sharded "
                    "benchmarks, never a chip run")
    ap.add_argument("--profile", action="store_true",
                    help="obs-instrumented run: BENCH_manifest.json, event "
                    "log, and a cold/warm phase per harness (each runs twice)")
    ap.add_argument("--profile-dir", default="",
                    help="with --profile: capture a jax.profiler trace here")
    args = ap.parse_args(argv)
    if args.devices:
        _force_host_devices(args.devices)
    from repro import compile_cache

    compile_cache.enable()

    from benchmarks import (
        ablation_selection, analysis_audit, appj1_large_k, comm_frontier,
        dist_scaling, fig2_convergence, kernels_bench, lower_bound_bench,
        memory_bench, obs_bench, problem_sweep, roofline, selection_sweep,
        sweep_bench, table1_strongly_convex, table2_general_convex,
        table3_nonconvex, table3_vision, table4_pl,
    )

    harnesses = {
        "table1": table1_strongly_convex.main,  # Table 1 (strongly convex)
        "table2": table2_general_convex.main,  # Table 2 (general convex)
        "table3": table3_nonconvex.main,  # Table 3 (per-call tuning loop)
        # repro: allow[R6] BENCH_vision has no stable warm-timing metric to gate
        "table3_vision": table3_vision.main,  # Table 3 on the sweep engine
        "table4": table4_pl.main,  # Table 4 (PL)
        "fig2": fig2_convergence.main,  # Figure 2 (heterogeneity sweep)
        "lower_bound": lower_bound_bench.main,  # Thm 5.4 / App G
        "appj1": appj1_large_k.main,  # App J.1 (large K)
        "ablation_selection": ablation_selection.main,  # Lemma H.2 on/off
        "selection": selection_sweep.main,  # policy bits-to-target frontiers
        "comm_frontier": comm_frontier.main,  # suboptimality-vs-bits frontier
        "dist_scaling": dist_scaling.main,  # sharded sweep, 1/2/4/8 devices
        "memory": memory_bench.main,  # indexed vs stacked operand layouts
        "sweep": sweep_bench.main,  # vmapped grid vs per-call loop
        "problem_sweep": problem_sweep.main,  # ζ×σ problem grid, one compile
        "kernels": kernels_bench.main,  # Pallas kernels
        "obs": obs_bench.main,  # telemetry round-tap overhead
        "analysis_audit": analysis_audit.main,  # lint + jaxpr const audit
        "roofline": roofline.main,  # deliverable (g) report
    }
    only = [s for s in args.only.split(",") if s]
    unknown = sorted(set(only) - set(harnesses))
    if unknown:
        # a typo'd --only used to match nothing and exit 0 — a CI leg would
        # then pass without running anything
        print(f"unknown benchmark name(s): {', '.join(unknown)}\n"
              f"valid names: {', '.join(sorted(harnesses))}", file=sys.stderr)
        sys.exit(2)
    profile_ctx = contextlib.nullcontext()
    if args.profile:
        from repro.obs import events as obs_events
        from repro.obs import profile as obs_profile

        manifest = obs_profile.write_manifest()
        print(f"# manifest {obs_profile.MANIFEST_PATH} "
              f"config_hash={manifest['config_hash']}", file=sys.stderr)
        obs_events.install(obs_events.EventRecorder(obs_events.DEFAULT_PATH))
        if args.profile_dir:
            import jax

            profile_ctx = jax.profiler.trace(args.profile_dir)

    print("name,us_per_call,derived")
    failures = 0
    with profile_ctx:
        for name, fn in harnesses.items():
            if only and name not in only:
                continue
            t0 = time.time()
            try:
                if args.profile:
                    from repro.obs import profile as obs_profile

                    # cold carries the harness's compiles; the warm repeat
                    # shows what survives the executor cache — the uniform
                    # compile-vs-warm split
                    with obs_profile.phase(f"{name}/cold") as cold:
                        fn(quick=not args.full)
                    with obs_profile.phase(f"{name}/warm") as warm:
                        fn(quick=not args.full)
                    print(f"# {name} cold {cold['seconds']:.1f}s "
                          f"({cold['traces']} traces), warm "
                          f"{warm['seconds']:.1f}s ({warm['traces']} traces)",
                          file=sys.stderr)
                else:
                    fn(quick=not args.full)
                    print(f"# {name} done in {time.time()-t0:.1f}s",
                          file=sys.stderr)
            except Exception as e:  # noqa: BLE001
                failures += 1
                print(f"{name}/ERROR,0,{type(e).__name__}:{e}")
    if args.profile:
        from repro.obs import events as obs_events

        rec = obs_events.RECORDER
        obs_events.uninstall()
        if rec is not None:
            rec.close()
            print(f"# event log: {rec.path} ({len(rec.records)} events); "
                  f"summarize with `python -m repro.obs report`",
                  file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
