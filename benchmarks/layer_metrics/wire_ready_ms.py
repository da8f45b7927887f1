"""The device's answer as the HOST sees it: mean of
``fused_wire_ready_seconds`` (``syncer/core.py``: the end of a tick's
submit, stamped on the loop -> the waiter thread saw the wire on the
host, stamped on that thread; launch, device step and fetch) in the
window. Only where an asynchronous backend's completion wake runs; a
program without the histogram (the parent of the PR that added it)
reads nothing."""

from benchmarks import phase_means


def read(ctx):
    return phase_means.mean_ms(ctx, "fused_wire_ready_seconds")
