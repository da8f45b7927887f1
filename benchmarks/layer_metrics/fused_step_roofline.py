"""The fused step's share of the memory roofline: the least bytes it must
move at the B and S the core holds (benchmarks/opcount.py) over the HBM
peak of the device (benchmarks/peaks.json), over its measured device
time. Memory-bound."""

from benchmarks import opcount


def read(ctx):
    tr, fleet = ctx.get("trace"), ctx.get("fleet")
    if not tr or not tr["steps"] or not fleet:
        return None
    return opcount.step_roofline_pct(
        fleet["B"], fleet["S"], tr["step_seconds_total"] / tr["steps"],
        ctx["device_kind"])
