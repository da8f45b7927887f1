"""What is left of the wait for a wire once the device has answered:
mean of ``fused_collect_lag_seconds`` (``syncer/core.py``: the waiter
thread's stamp of a ready wire -> the collect of that wire begun on the
loop: the GIL's hand-over and the wake's turn in the loop's queue), over
the wakes of the window that collected their own wire. A program without
the histogram (the parent of the PR that added it) reads nothing."""

from benchmarks import phase_means


def read(ctx):
    return phase_means.mean_ms(ctx, "fused_collect_lag_seconds")
