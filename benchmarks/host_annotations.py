"""The device's idle time by what the host was doing (part of the
yardstick; reads a profiler trace with ``jax.profiler.ProfileData`` and
nothing else, like reduce_trace.py, which it leaves as it is).

The program wraps its synchronous host sections in ``kcp.*`` annotations
(``kcp_tpu.obs.annotate``: ``kcp.tick`` and its phases, ``kcp.store.commit``,
``kcp.wal.sync``, ``kcp.store.fanout``, ``kcp.apply``, ``kcp.split``,
``kcp.aggregate``, ``kcp.watch.encode``, ``kcp.gc``). While the
profiler is open they are events on the ``/host:CPU`` plane, on the same
clock as the device planes. Idle time of a device is the complement of
the union of its operations' intervals between its first and last
event (reduce_trace's busy time, turned over); it is *attributed* where
at least one ``kcp.*`` annotation is open on any host thread.
"""

from __future__ import annotations

from benchmarks.reduce_trace import OP_LINES

HOST_PREFIX = "/host:CPU"
ANNOTATION_PREFIX = "kcp."


def merged(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, disjoint union of [start, end) intervals."""
    out: list[tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def overlap(a: list[tuple[float, float]], b: list[tuple[float, float]]) -> float:
    """Total length of the intersection of two sorted disjoint lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def gaps_of(busy: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """The idle intervals between the first and the last of ``busy``
    (already merged)."""
    return [(busy[k][1], busy[k + 1][0]) for k in range(len(busy) - 1)
            if busy[k + 1][0] > busy[k][1]]


def attribute(devices: list[list[tuple[float, float]]],
              by_name: dict[str, list[tuple[float, float]]],
              top: int = 10) -> dict:
    """``devices``: per device plane, its merged busy intervals (ns);
    ``by_name``: per annotation name, its merged intervals (ns). Idle and
    attributed time summed over the devices, and the ``top`` longest
    gaps, each with its start relative to its plane's first event and
    the seconds of it under each annotation."""
    under_any = merged([iv for ivs in by_name.values() for iv in ivs])
    idle = attributed = 0.0
    longest: list[tuple[float, float, float]] = []  # (length, start, origin)
    for busy in devices:
        gaps = gaps_of(busy)
        idle += sum(b - a for a, b in gaps)
        attributed += overlap(gaps, under_any)
        longest += [(b - a, a, busy[0][0]) for a, b in gaps]
    longest.sort(reverse=True)
    table = []
    for length, start, origin in longest[:top]:
        gap = [(start, start + length)]
        under = {name: overlap(gap, ivs) / 1e9 for name, ivs in by_name.items()}
        table.append([(start - origin) / 1e9, length / 1e9,
                      {n: s for n, s in sorted(under.items(),
                                               key=lambda kv: -kv[1]) if s > 0}])
    return {"idle_s": idle / 1e9, "attributed_s": attributed / 1e9,
            "annotations": {n: sum(b - a for a, b in ivs) / 1e9
                            for n, ivs in by_name.items()},
            "gaps": table}


def read(path: str, device_prefix: str = "/device:TPU:", top: int = 10):
    """Reduce one xplane file to the attribution of its idle time
    (:func:`attribute`), or None when it holds no device plane with
    operations (a rehearsal on the CPU) or no ``kcp.*`` annotation (a
    program without them, or a host tracer level that drops them)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    by_name: dict[str, list[tuple[float, float]]] = {}
    devices = []
    for plane in data.planes:
        if plane.name.startswith(HOST_PREFIX):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(ANNOTATION_PREFIX):
                        by_name.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
        elif plane.name.startswith(device_prefix):
            busy = merged([(ev.start_ns, ev.start_ns + ev.duration_ns)
                           for line in plane.lines if line.name in OP_LINES
                           for ev in line.events])
            if busy:
                devices.append(busy)
    if not devices or not by_name:
        return None
    return attribute(devices,
                     {name: merged(iv) for name, iv in by_name.items()}, top)
