"""Device time of the fused step's collective operations per hundred of
the step's device time, summed over every chip of the mesh, from the
run's own profiler slice (benchmarks/collective_ops.py; the xplane is
still under ``benchmarks/.out/trace`` when the readers run): the share
of a sharded step that is the chips waiting for each other — its stats
all-reduce, the patch compaction's exchange — and not the diff of its
own rows. A step on one chip holds no collective and reads 0."""

from benchmarks import collective_ops, reduce_trace
from benchmarks.layer_metrics.idle_attributed_pct import TRACE_DIR


def read(ctx):
    if not ctx.get("trace"):
        return None
    try:
        path = reduce_trace.find_xplane(TRACE_DIR)
    except reduce_trace.EmptyDeviceTrace:
        return None
    got = collective_ops.reduce(path)
    if got is None or got["step_seconds"] <= 0:
        return None
    print(f"[layer] collectives: {got['collective_seconds'] * 1e3:.3f} ms of "
          f"the fused step's {got['step_seconds'] * 1e3:.3f} ms over "
          f"{got['steps']} steps on {got['planes']} device plane(s); by "
          f"kind, ms: "
          f"{ {k: round(v * 1e3, 3) for k, v in got['by_kind'].items()} }",
          flush=True)
    return 100.0 * got["collective_seconds"] / got["step_seconds"]
