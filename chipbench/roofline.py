"""A kernel's share of its roofline in a traced window.

For every call of the kernel that the traced grid calls made, the least
time the chip could take is the larger of its operations over the FLOP
peak and its bytes over the HBM bandwidth (``kernels/<kernel>.py`` counts
both from the shapes the traffic implies, ``kernel_calls`` lists the
calls). The share is the sum of those least times over the kernel's summed
event time in the trace, in percent.
"""
from __future__ import annotations

from chipbench import kernel_calls, peaks
from chipbench.harness import load_module


def share(ctx: dict, kernel: str):
    tr = ctx.get("trace")
    if not tr or not tr["kernel_s"].get(kernel):
        return None
    cell = ctx["cell"]
    pk = peaks.peaks(ctx["device"]["kind"])
    cost = load_module("kernels", kernel).cost
    least, expected = 0.0, 0
    for name, cells, rows, width, n in kernel_calls.per_call(
            cell.config, cell.traffic, cell.cells // cell.chips):
        if name != kernel:
            continue
        flops, nbytes = cost(cells, rows, width)
        least += n * max(flops / pk["flops"], nbytes / pk["hbm_bytes_per_s"])
        expected += n
    traced = len(ctx["traced_calls"])
    events = tr["kernel_events"][kernel]
    if events != expected * traced * cell.chips:
        ctx["log"].write(f"{kernel}: {events} trace events, expected "
                         f"{expected * traced * cell.chips}\n")
    # kernel_s sums over the chips; the least time is per chip
    return 100.0 * least * traced * cell.chips / tr["kernel_s"][kernel]
