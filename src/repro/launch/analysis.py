"""Compiled-artifact analysis: collective-byte parsing from HLO text and
roofline term derivation (DESIGN.md §5, deliverable g).

``cost_analysis()`` gives per-device FLOPs/bytes of the SPMD-partitioned
module; collective bytes are NOT in cost_analysis, so we parse the HLO and
sum result-shape bytes of every collective op, bucketed by kind.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict

from repro.launch.mesh import hardware_constants

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

COLLECTIVE_OPS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def cost_dict(compiled) -> dict:
    """``compiled.cost_analysis()`` (the per-device cost of the SPMD
    program) as a plain dict."""
    return dict(compiled.cost_analysis())

# matches e.g.  bf16[128,7168]{1,0}  inside an HLO instruction line
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# one HLO instruction line: "%name = <shape(s)> opcode(" — opcode may have
# -start/-done suffixes for async collectives
_INSTR_RE = re.compile(
    r"=\s*(\(?[^=]*?\)?)\s+(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


_GROUPS_V2_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_GROUPS_V1_RE = re.compile(r"replica_groups=\{(\{[\d,{}\s]*\})\}")


def _groups_cross_pod(line: str, pod_size: int):
    """True if the instruction's replica groups span a pod boundary
    (device ids < pod_size vs ≥ pod_size; mesh order is pod-major).
    None when no groups are present (e.g. single full-module group)."""
    import numpy as np

    m = _GROUPS_V2_RE.search(line)
    if m:
        ng, gs = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            perm = [int(p) for p in m.group(4).split(",")]
            ids = ids.transpose(perm)
        groups = ids.reshape(ng, gs)
        pods = groups // pod_size
        return bool((pods.min(axis=1) != pods.max(axis=1)).any())
    m = _GROUPS_V1_RE.search(line)
    if m:
        for grp in re.findall(r"\{([\d,\s]+)\}", m.group(0)):
            ids = [int(x) for x in grp.replace(" ", "").split(",") if x]
            pods = {i // pod_size for i in ids}
            if len(pods) > 1:
                return True
        return False
    return None


def parse_collectives(hlo_text: str, *, pod_size: int = 0) -> Dict[str, dict]:
    """Per-collective-kind {count, bytes[, cross_pod_bytes]} from HLO.

    pod_size > 0 additionally buckets bytes whose replica groups span a pod
    boundary (exact iota/v1 replica_groups decoding)."""
    out = {k: {"count": 0, "bytes": 0} for k in COLLECTIVE_OPS}
    if pod_size:
        for k in out:
            out[k]["cross_pod_bytes"] = 0
    for line in hlo_text.splitlines():
        m = _INSTR_RE.search(line)
        if not m:
            continue
        shapes, op = m.group(1), m.group(2)
        if "-done(" in line:  # avoid double counting async pairs
            continue
        b = _shape_bytes(shapes)
        out[op]["count"] += 1
        out[op]["bytes"] += b
        if pod_size:
            crosses = _groups_cross_pod(line, pod_size)
            if crosses or crosses is None:  # no groups => global => crosses
                out[op]["cross_pod_bytes"] += b
    return out


def collective_wire_bytes(colls: Dict[str, dict]) -> float:
    """Approximate per-device wire traffic: ring all-reduce moves ~2× the
    buffer; gather/scatter/all-to-all move ~1× the result."""
    b = 0.0
    for kind, rec in colls.items():
        factor = 2.0 if kind == "all-reduce" else 1.0
        b += factor * rec["bytes"]
    return b


@dataclasses.dataclass
class Roofline:
    flops_per_device: float
    hbm_bytes_per_device: float
    collective_bytes_per_device: float
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float
    useful_ratio: float
    collectives: Dict[str, dict]

    def to_dict(self):
        return dataclasses.asdict(self)


def roofline(cost: dict, colls: Dict[str, dict], *, n_chips: int,
             model_flops: float, device_kind: str,
             links: int = 4) -> Roofline:
    """Derive the three roofline terms from the compiled per-device numbers.

    cost: compiled.cost_analysis() dict (per-device, post-partitioning).
    model_flops: 6·N·D (global); useful_ratio = model_flops / (flops·chips).
    device_kind: the chip the terms are modelled for (``mesh.PEAKS`` key).
    """
    hw = hardware_constants(device_kind)
    flops = float(cost.get("flops", 0.0))
    hbm_bytes = float(cost.get("bytes accessed", 0.0))
    wire = collective_wire_bytes(colls)
    compute_s = flops / hw["peak_flops_bf16"]
    memory_s = hbm_bytes / hw["hbm_bw"]
    collective_s = wire / (hw["ici_link_bw"] * links)
    dominant = max(
        (("compute", compute_s), ("memory", memory_s), ("collective", collective_s)),
        key=lambda kv: kv[1],
    )[0]
    total_hlo_flops = flops * n_chips
    useful = model_flops / total_hlo_flops if total_hlo_flops > 0 else 0.0
    return Roofline(
        flops_per_device=flops,
        hbm_bytes_per_device=hbm_bytes,
        collective_bytes_per_device=wire,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        dominant=dominant,
        model_flops=model_flops,
        useful_ratio=useful,
        collectives=colls,
    )


def memory_summary(mem) -> dict:
    return {
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "code_bytes": mem.generated_code_size_in_bytes,
    }
