"""Collects begun by the waiter thread's wake per hundred fleet ticks of
the window (``fused_collect_woken_total`` over
``fused_fleet_ticks_total``, both ``syncer/core.py``): the rest were
made by a tick's depth rule or the shutdown drain
(``fused_collect_depth_total``), whose fetch may block the loop. It
describes how often the wake came first; a program without the counter
(the parent of the PR that added it) reads nothing."""

from benchmarks import counter_ratio


def read(ctx):
    return counter_ratio.per(ctx, "fused_collect_woken_total",
                             "fused_fleet_ticks_total", 100.0)
