"""The chip benchmark of the sweep engine: one run of one cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Builds the cell named in ``BENCHMARK.json`` from ``--seed`` (population,
weights, problem, method), makes one warm-up grid call of the cell's own
shape, then runs a closed loop of grid calls through
``repro.core.sweep.run`` for ``--seconds``: one caller that waits for each
grid's results before sending the next. Afterwards it replays a sample of
the window's cells, drawn from the seed, on the plain reference and
compares. The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (end-to-end with ``--trace 0``,
per-layer with ``--trace 1``), ``device``, in a traced run ``breakdown``,
and last ``checks``, each compared number beside its limit.

Exits non-zero, with no result, when JAX finds no TPU or fewer chips than
the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness

    bench = harness.benchmark()
    chips = int(harness.workload(args.workload, bench)["chips"])
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"chipbench: cell {args.workload} needs {chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    out = harness.run(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=T_START, bench=bench)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
