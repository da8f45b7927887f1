"""Share of the device's idle time in the profiler slice during which
the host had NOTHING TO DO: under ``kcp.loop.select`` (the serving loop
inside ``select``, annotated while a session is open) and under no
other ``kcp.*`` annotation, in percent. Slack, not a bottleneck:
DESCRIPTIVE (``better: higher`` is the manifest's convention). What
``idle_attributed_pct`` (any ``kcp.*``, waiting included) leaves of a
hundred is idle time under unnamed host WORK; this, the named work and
that rest add up. Its own pass over the slice's xplane with
benchmarks/host_annotations.py's interval helpers."""

from benchmarks import host_annotations, reduce_trace
from benchmarks.layer_metrics.idle_attributed_pct import TRACE_DIR

SELECT = "kcp.loop.select"


def waiting(path: str, device_prefix: str = "/device:TPU:"):
    """(idle s, idle s under SELECT alone, idle s under other kcp.*) of
    one xplane file, or None when it holds no device plane with
    operations or no SELECT annotation (a program without the ledger)."""
    from jax.profiler import ProfileData

    select, other, devices = [], [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(host_annotations.HOST_PREFIX):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == SELECT:
                        select.append((ev.start_ns,
                                       ev.start_ns + ev.duration_ns))
                    elif ev.name.startswith(
                            host_annotations.ANNOTATION_PREFIX):
                        other.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
        elif plane.name.startswith(device_prefix):
            busy = host_annotations.merged(
                [(ev.start_ns, ev.start_ns + ev.duration_ns)
                 for line in plane.lines
                 if line.name in reduce_trace.OP_LINES
                 for ev in line.events])
            if busy:
                devices.append(busy)
    if not devices or not select:
        return None
    other = host_annotations.merged(other)
    # select with another annotation open (another thread's, or a
    # collection that began inside select) is not waiting
    alone = minus(host_annotations.merged(select), other)
    idle = in_alone = in_other = 0.0
    for busy in devices:
        gaps = host_annotations.gaps_of(busy)
        idle += sum(b - a for a, b in gaps)
        in_alone += host_annotations.overlap(gaps, alone)
        in_other += host_annotations.overlap(gaps, other)
    return idle / 1e9, in_alone / 1e9, in_other / 1e9


def minus(a, b):
    """``a`` less ``b`` (both sorted and disjoint), sorted and disjoint."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > lo:
                out.append((lo, b[k][0]))
            lo = max(lo, b[k][1])
            k += 1
        if lo < hi:
            out.append((lo, hi))
    return out


def read(ctx):
    if not ctx.get("trace"):
        return None
    try:
        path = reduce_trace.find_xplane(TRACE_DIR)
    except reduce_trace.EmptyDeviceTrace:
        return None
    got = waiting(path)
    if got is None or got[0] <= 0:
        return None
    idle, alone, other = got
    print(f"[layer] idle, host waiting: {alone:.4f} s of {idle:.4f} s idle "
          f"lie under {SELECT} and no other kcp.* annotation; {other:.4f} s "
          f"under another (named work); the rest, "
          f"{idle - alone - other:.4f} s, under unnamed host work", flush=True)
    return 100.0 * alone / idle
