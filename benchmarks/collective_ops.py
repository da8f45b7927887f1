"""Device time of the fused step's collective operations, from a
profiler trace (part of the yardstick; reads with
``jax.profiler.ProfileData`` and nothing else, like reduce_trace.py,
which keeps only a trace's ten largest operations and is left as it is).

A step that runs as one SPMD program over several chips holds
operations that move data between them: XLA names them ``all-reduce``,
``collective-permute``, ``all-gather``, ``reduce-scatter`` and
``all-to-all``, each possibly split into a ``-start`` and a ``-done``
half or fused (``all-reduce-scatter-fusion``), each numbered
(``%all-reduce.3 = ...``). On the ``XLA Ops`` line of a device plane an
event's duration is the time that operation held the core's operation
stream: for a collective, the latency the step could not hide. This
module sums those durations over the operations that START inside an
event of the fused step on the same plane's ``XLA Modules`` line, and
the step's own durations beside them.
"""

from __future__ import annotations

import bisect

from benchmarks.reduce_trace import MODULE_LINE, OP_LINES

COLLECTIVES = ("all-reduce", "collective-permute", "all-gather",
               "reduce-scatter", "all-to-all")


def kind_of(op_name: str) -> str | None:
    """The collective an ``XLA Ops`` event's name stands for, or None."""
    name = op_name.lstrip("%")
    for kind in COLLECTIVES:
        if name.startswith(kind):
            return kind
    return None


def reduce_planes(planes, device_prefix: str = "/device:TPU:",
                  step_prefix: str = "jit_reconcile_step") -> dict | None:
    """``planes``: objects with ``name`` and ``lines``; a line has
    ``name`` and ``events``; an event ``name``, ``start_ns`` and
    ``duration_ns`` (``ProfileData``'s own shape). Seconds of the step's
    programs and of the collectives inside them, summed over every
    device plane, the collectives also by kind; None where no device
    plane ran the step."""
    step_ns, steps, coll_ns, by_kind, n_planes = 0.0, 0, 0.0, {}, 0
    for plane in planes:
        if not plane.name.startswith(device_prefix):
            continue
        spans, ops = [], []
        for line in plane.lines:
            if line.name == MODULE_LINE:
                spans += [(ev.start_ns, ev.start_ns + ev.duration_ns)
                          for ev in line.events
                          if ev.name.startswith(step_prefix)]
            elif line.name == OP_LINES[0]:
                ops += [(ev.start_ns, ev.duration_ns, kind)
                        for ev in line.events
                        if (kind := kind_of(ev.name)) is not None]
        if not spans:
            continue
        n_planes += 1
        spans.sort()
        starts = [a for a, _b in spans]
        step_ns += sum(b - a for a, b in spans)
        steps += len(spans)
        for start, dur, kind in ops:
            i = bisect.bisect_right(starts, start) - 1
            if i >= 0 and start < spans[i][1]:
                coll_ns += dur
                by_kind[kind] = by_kind.get(kind, 0.0) + dur
    if not steps:
        return None
    return {"planes": n_planes, "steps": steps,
            "step_seconds": step_ns / 1e9,
            "collective_seconds": coll_ns / 1e9,
            "by_kind": {k: v / 1e9 for k, v in sorted(by_kind.items())}}


def reduce(path: str, **kw) -> dict | None:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, **kw)
