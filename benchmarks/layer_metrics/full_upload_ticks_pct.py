"""Ticks of the window that rebuilt and re-uploaded the WHOLE resident
state, per hundred fleet ticks (the rise of ``fused_full_upload_seconds``'
count over ``fused_fleet_ticks_total``, both ``syncer/core.py``: a stale
tick's ``full_upload`` phase takes the place of its ``pack``, one
observation a tick). A full upload zeroes the placement lane's
``current``, so the device hands back every placement row; what makes a
tick stale is growth of rows, of the placement lane or of a status mask,
a quarantine, rejected placement counts (``invalidate_placement``) and,
until the PR that retires a placement row through the placement-leaves
swap, every retired root. It describes how often (``better`` is the
manifest's convention); the histogram is as old as the phases, so the
parent reads like the change, and a window with no full upload reads 0.
Beside it, where the program has them, the rises of two of those causes'
own counters: ``fused_placement_rows_retired_total`` (placement rows
their owner retired) and ``splitter_placement_invalidations_total``
(device counts the splitter's applier rejected)."""

from benchmarks import counter_ratio

CAUSES = ("fused_placement_rows_retired_total",
          "splitter_placement_invalidations_total")


def read(ctx):
    reg = ctx["registry"]
    for name in CAUSES:
        if name in reg:
            print(f"[layer] {name}: rose by {reg[name]:g} in the window",
                  flush=True)
    return counter_ratio.per(ctx, "fused_full_upload_seconds_count",
                             "fused_fleet_ticks_total", 100.0)
