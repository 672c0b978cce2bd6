"""Room for a frozen base on one chip: the toy adapter-on-a-frozen-base
configuration of ``test_extension.py``, its frozen embedding and head
scaled to 568,524,800 bfloat16 parameters (the size of the Moonlight-16B-A3B
cut: 1 dense + 4 MoE layers, 8 of 64 experts, a 20,480-row vocabulary),
through ``Cell.setup`` and a reference replay of two rounds (FedAvg, the
selection row, SGD) at 4 clients, every client in.

    python3 chipbench/tests/frozen_room.py

Prints one JSON line: the frozen bytes, the times, and the chip's
``peak_bytes_in_use``. Exits 2 without a TPU.
"""
from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, ROOT, os.path.join(ROOT, "src")]

VOCAB, HIDDEN = 138_800, 2048  # 2 x 138,800 x 2,048 = 568,524,800


def main() -> int:
    import jax

    from chipbench import harness
    from conftest import lookup_with_test_data
    from test_extension import TOY_LORA

    if jax.devices()[0].platform != "tpu":
        print("frozen_room: no TPU", file=sys.stderr)
        return 2
    harness._path = lookup_with_test_data(harness._path)
    config = dict(TOY_LORA, vocab=VOCAB, hidden=HIDDEN, rank=16,
                  per_client=8, eval_per_client=8)
    traffic = dict(harness.load_json("traffic", "c01-qsgd4ef"), rounds=2,
                   participation=1.0)
    bench = {"workloads": [{"name": "toy-lora.room", "config": "toy-lora",
                            "traffic": "c01-qsgd4ef", "chips": 1}]}
    cell = harness.Cell("toy-lora.room", bench, config=config,
                        traffic=traffic, limits={})
    t0 = time.perf_counter()
    cell.setup(2**31 + 29)
    jax.block_until_ready(cell.frozen)
    t1 = time.perf_counter()
    ref = cell.reference()
    out = ref.run_cell(cell.x0, seed=2**31 + 31, mult=1.0, mask_seed=3,
                       sel_seed=4, fold=0)
    t2 = time.perf_counter()
    stats = jax.devices()[0].memory_stats() or {}
    print(json.dumps({
        "frozen_params": sum(l.size for l in jax.tree.leaves(cell.frozen)),
        "frozen_bytes": sum(l.nbytes for l in jax.tree.leaves(cell.frozen)),
        "reference_frozen_bytes": sum(l.nbytes
                                      for l in jax.tree.leaves(ref.data[2])),
        "setup_s": t1 - t0, "replay_s": t2 - t1,
        "history": out.history.tolist(), "clients": ref.n_clients,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "device": jax.devices()[0].device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
