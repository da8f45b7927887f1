"""Rows the fused core encoded per fleet tick, in the window
(``fused_encoded_rows_total`` over ``fused_fleet_ticks_total``): how full
a tick is, so how many rows share a tick's fixed cost. It describes; it
is no goal of its own (``better`` is the manifest's convention): on the
chip it read 5.7 in ``syncer-1k.steady`` at a p50 of 30 ms, 38 in the
rollout cell at 330 ms, 8 in a burst at 364 ms (PERF.md, PR 35)."""

from benchmarks import counter_ratio


def read(ctx):
    return counter_ratio.per(ctx, "fused_encoded_rows_total",
                             "fused_fleet_ticks_total")
