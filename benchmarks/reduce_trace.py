"""From a profiler trace (``*.xplane.pb``) to numbers (part of the
yardstick). Reads with ``jax.profiler.ProfileData`` and nothing else.

A TPU trace has one plane per chip, ``/device:TPU:<n>``, with the lines
``XLA Modules`` (one event per executed program, named
``jit_<function>(<fingerprint>)``), ``XLA Ops`` and ``Async XLA Ops``
(one event per operation). Busy time is the union of the operations'
intervals; the traced window is the span from the first to the last
device event. A trace with no device plane or no device event raises:
that is a failed measurement, not an idle device.
"""

from __future__ import annotations

import glob
import os

OP_LINES = ("XLA Ops", "Async XLA Ops")
MODULE_LINE = "XLA Modules"


class EmptyDeviceTrace(RuntimeError):
    pass


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise EmptyDeviceTrace(f"no *.xplane.pb under {trace_dir}")
    return found[-1]


def union_seconds(intervals: list[tuple[float, float]]) -> float:
    """Total length of the union of [start, end) intervals (ns in, s out)."""
    total = 0.0
    end = None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total / 1e9


def _gaps(intervals: list[tuple[float, float]], top: int) -> list[tuple[float, float]]:
    """The longest idle gaps as (start ns, length ns)."""
    gaps = []
    end = None
    for a, b in sorted(intervals):
        if end is not None and a > end:
            gaps.append((end, a - end))
        end = b if end is None else max(end, b)
    return sorted(gaps, key=lambda g: -g[1])[:top]


def reduce(path: str, device_prefix: str = "/device:TPU:",
           step_prefix: str = "jit_reconcile_step",
           any_line: bool = False) -> dict:
    """Reduce one xplane file. ``device_prefix`` selects the device
    planes; ``step_prefix`` selects the fused step's programs on the
    ``XLA Modules`` line. ``any_line`` (rehearsals on the CPU, whose
    trace has no device plane) counts every line of the chosen planes as
    operations, so that the path runs end to end; its numbers mean
    nothing and are printed under ``"platform": "cpu"``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = [p for p in data.planes if p.name.startswith(device_prefix)]
    if not planes:
        raise EmptyDeviceTrace(
            f"{path}: no plane named {device_prefix}*; planes are "
            f"{[p.name for p in data.planes]}")
    busy, windows, ops, gaps = [], [], {}, []
    step_ns, step_n, modules = 0.0, 0, {}
    for plane in planes:
        intervals: list[tuple[float, float]] = []
        lo = hi = None
        for line in plane.lines:
            for ev in line.events:
                a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                lo = a if lo is None else min(lo, a)
                hi = b if hi is None else max(hi, b)
                if any_line or line.name in OP_LINES:
                    intervals.append((a, b))
                    if any_line or line.name == OP_LINES[0]:
                        ops[ev.name] = ops.get(ev.name, 0.0) + ev.duration_ns
                if any_line or line.name == MODULE_LINE:
                    modules[ev.name] = modules.get(ev.name, 0.0) + ev.duration_ns
                    if ev.name.startswith(step_prefix):
                        step_ns += ev.duration_ns
                        step_n += 1
        if not intervals:
            raise EmptyDeviceTrace(
                f"{path}: plane {plane.name} holds no device operation")
        busy.append(union_seconds(intervals))
        windows.append((hi - lo) / 1e9)
        gaps += [(s - lo, d) for s, d in _gaps(intervals, 10)]
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps, key=lambda g: -g[1])[:10]
    return {
        "planes": [p.name for p in planes],
        "busy_s": sum(busy) / len(busy),
        "window_s": sum(windows) / len(windows),
        "step_seconds_total": step_ns / 1e9,
        "steps": step_n,
        "modules": {k: v / 1e9 for k, v in modules.items()},
        "device_ops": [[name.split(" = ")[0][:120], ns / 1e9]
                       for name, ns in top_ops],
        "idle_gaps": [[f"unattributed@{s / 1e9:.3f}s", d / 1e9]
                      for s, d in top_gaps],
    }
