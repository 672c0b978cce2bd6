"""From a profiler trace to device busy time, kernel time and idle gaps.

``extract`` reads an ``.xplane.pb`` into plain intervals: per device, the
operations on its "XLA Ops" line; on the host, the benchmark's own spans
(names starting ``chipbench.``). ``reduce`` turns those into numbers, so the
arithmetic can be checked on a small recorded trace without a chip.

* busy: the union of a device's operation intervals inside the window
  (leaf operations: a while, conditional or call op only holds others, and
  its event spans its body's, gaps included);
  ``busy_s`` is its mean over the devices, ``idle_share`` the largest
  1 - busy / window over them.
* kernel time: the summed durations of the events named after a kernel
  (the name a ``pallas_call`` is given, with any ``.N`` suffix XLA adds).
  Events are named by their HLO instruction.
* idle gaps: the stretches of the window in which the first device runs
  nothing, each named by the benchmark's host span open at its midpoint
  (``between_calls`` where none is).
"""
from __future__ import annotations

import glob
import os
import re

SPAN_PREFIX = "chipbench."
_SUFFIX = re.compile(r"\.\d+$")
# an "XLA Ops" event is named by its HLO instruction, "%name.N = shape op(...)"
_NAME = re.compile(r"^%?([^\s=]+)")
# ops that only hold other ops (their events span their children's)
_CONTAINER = re.compile(r"(?<![\w-])(while|conditional|call)\(")


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def extract(path: str, *, device_prefix: str = "/device:TPU:",
            op_line: str = "XLA Ops"):
    """(devices {plane: [(start_ns, end_ns, name)]}, spans [(start_ns,
    end_ns, name)]) from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith(device_prefix):
            ops = []
            for line in plane.lines:
                if line.name == op_line:
                    ops.extend((e.start_ns, e.start_ns + e.duration_ns,
                                e.name) for e in line.events)
            devices[plane.name] = ops
        else:
            for line in plane.lines:
                spans.extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    return devices, spans


def merge(intervals, lo, hi):
    """Sorted, disjoint union of ``intervals`` clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def base_name(name: str) -> str:
    """The op's name without XLA's ``.N`` suffix: ``qsgd_dequantize`` for
    ``%qsgd_dequantize.3 = f32[...] custom-call(...)``."""
    m = _NAME.match(name)
    return _SUFFIX.sub("", m.group(1) if m else name)


def is_container(name: str) -> bool:
    """A while, conditional or call op, whose event spans its body's."""
    return bool(_CONTAINER.search(name.split("=", 1)[-1]))


def reduce(devices: dict, spans: list, window, kernels=()):
    """Numbers of one traced window (times in seconds).

    ``window`` is (start_ns, end_ns) on the trace's clock. Returns a dict
    with busy_s, window_s, idle_share, kernel_s {name: s}, kernel_events
    {name: count}, device_ops [[name, s]] (top 10 by total time) and
    idle_gaps [[span, s]] (the 10 longest)."""
    lo, hi = window
    length = (hi - lo) * 1e-9
    if length <= 0:
        raise ValueError("empty trace window")
    devices = {k: [op for op in v if not is_container(op[2])]
               for k, v in devices.items()}
    busy, idle = [], []
    for ops in devices.values():
        b = sum(e - s for s, e in merge(ops, lo, hi)) * 1e-9
        busy.append(b)
        idle.append(1.0 - b / length)
    kernel_s = {k: 0.0 for k in kernels}
    kernel_events = {k: 0 for k in kernels}
    totals = {}
    for ops in devices.values():
        for s, e, name in ops:
            if e <= lo or s >= hi:
                continue
            base = base_name(name)
            totals[base] = totals.get(base, 0.0) + (e - s) * 1e-9
            if base in kernel_s:
                kernel_s[base] += (e - s) * 1e-9
                kernel_events[base] += 1
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    gaps = []
    first = devices[sorted(devices)[0]] if devices else []
    cursor = lo
    for s, e in merge(first, lo, hi) + [[hi, hi]]:
        if s > cursor:
            mid = 0.5 * (cursor + s)
            owner = [n for a, b, n in spans if a <= mid < b]
            label = (owner[-1][len(SPAN_PREFIX):] if owner
                     else "between_calls")
            gaps.append([label, (s - cursor) * 1e-9])
        cursor = max(cursor, e)
    gaps.sort(key=lambda g: -g[1])
    return {
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "window_s": length,
        "idle_share": max(idle) if idle else 1.0,
        "kernel_s": kernel_s,
        "kernel_events": kernel_events,
        "device_ops": [[n, t] for n, t in top],
        "idle_gaps": gaps[:10],
    }
