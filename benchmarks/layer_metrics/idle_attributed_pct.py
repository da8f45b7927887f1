"""Share of the device's idle time in the profiler slice that lies
under at least one ``kcp.*`` host annotation, in percent
(benchmarks/host_annotations.py). Re-opens the slice's xplane, which is
still under ``benchmarks/.out/trace`` when the readers run, and prints
the ten longest idle gaps with the annotations that cover each."""

import os

from benchmarks import host_annotations, reduce_trace

TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".out", "trace")


def read(ctx):
    if not ctx.get("trace"):
        return None
    try:
        path = reduce_trace.find_xplane(TRACE_DIR)
    except reduce_trace.EmptyDeviceTrace:
        return None
    got = host_annotations.read(path)
    if got is None or got["idle_s"] <= 0:
        return None
    print(f"[layer] idle: {got['attributed_s']:.4f} s of {got['idle_s']:.4f} s "
          f"idle lie under a kcp.* annotation; annotated seconds by name: "
          f"{ {n: round(s, 4) for n, s in sorted(got['annotations'].items(), key=lambda kv: -kv[1])} }",
          flush=True)
    for start, length, under in got["gaps"]:
        print(f"[layer] idle gap at +{start:.3f}s, {length * 1e3:.2f} ms: "
              + (", ".join(f"{n} {s * 1e3:.2f} ms" for n, s in under.items())
                 or "no annotation"), flush=True)
    return 100.0 * got["attributed_s"] / got["idle_s"]
