"""Readings the check's limits are set from, on the chip at a cell's size.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1 2 ... \
        [--control 3] [--matmul-precision default]

For every seed: build the cell from that seed, make one grid call through
the timed path, and replay the cells the check would sample on the plain
reference (float32, ``highest`` matrix products). That gives the lower
readings: how far sound runs of the program lie from the reference. With
``--matmul-precision default`` the program runs its own one-pass bf16
path instead of the configuration's precision: the precision control,
whose readings are upper ones. For the first ``--control`` seeds it also
replays the same cells

* with the reference's matrix products at ``high`` (three bf16 passes: the
  control, one step below the configuration's float32 at ``highest``), and
* with a planted fault in the reference: only half of each round's clients
  enter the server's mean ("half_clients"),

and compares each with the float32 reference by the same numbers: the
upper readings. One JSON line per seed on standard output.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def readings(cell, seed: int, *, control: bool, cache: dict) -> dict:
    """One seed's readings; ``cache`` keeps the references (and their
    compiled steps) from seed to seed."""
    t0 = time.perf_counter()
    cell.setup(seed)
    call = cell.grid_call(1)
    out = {"seed": seed, "grid_call_s": time.perf_counter() - t0}
    kinds = {"program": {}}
    if control:
        kinds["control"] = {"precision": "high"}
        kinds["half_clients"] = {"fault": "half_clients"}
    refs = {}
    for name, kw in kinds.items():
        if name not in cache:
            cache[name] = cell.reference(**kw)
        cache[name].set_population(cell.data, cell.frozen)
        refs[name] = cache[name]
    picks = cell.sample([call], seed)
    for _, c in picks:
        want = cell.replayed(cell.replay(refs["program"], call, c))
        rows = {"program": cell.outcome(call, c)}
        for name in ("control", "half_clients"):
            if name in refs:
                rows[name] = cell.replayed(cell.replay(refs[name], call, c))
        for name, got in rows.items():
            out.setdefault(name, []).append(cell.compare(got, want))
        print(f"seed {seed} cell {c}: replayed at {time.perf_counter() - t0:.1f}"
              f" s", file=sys.stderr, flush=True)
    for name in refs:
        out[name] = cell.worst(out[name])
    out["seconds"] = time.perf_counter() - t0
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--matmul-precision", default=None,
                    help="the program's matrix-product precision, in place "
                         "of the configuration's")
    args = ap.parse_args(argv)

    from chipbench import harness

    harness.enable_compile_cache()
    cell = harness.Cell(args.workload)
    if args.matmul_precision:
        cell.config = dict(cell.config,
                           matmul_precision=args.matmul_precision)
    cache = {}
    for k, seed in enumerate(args.seeds):
        print(json.dumps(readings(cell, seed, control=k < args.control,
                                  cache=cache)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
