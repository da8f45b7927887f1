"""Share of the placement rows the fused step handed to the deployment
splitter's applier in the window whose split was written
(``splitter_fused_placements_total`` per hundred
``splitter_placement_rows_total``, one add each a row in
``kcp_tpu/reconcilers/deployment/controller.py`` ``_apply_one_fused``).
A retired root frees its placement row, the resident state is rebuilt
with ``current`` zeroed and the device re-emits EVERY placement row,
resident roots and live ones alike: each costs the applier a pass and a
cluster lookup and all but the new ones are answered with nothing to
write. It describes the re-emission (``better`` is the manifest's
convention): a program that retires a row without re-emitting the
others reads near 100; a program without the counters (the parent of
the PR that added them) reads nothing."""

from benchmarks import counter_ratio


def read(ctx):
    return counter_ratio.per(ctx, "splitter_fused_placements_total",
                             "splitter_placement_rows_total", 100.0)
