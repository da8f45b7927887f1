"""Sections the ticks' encode phase gathered per bucket-wide staging, in
the window (``fused_encoded_sections_total`` over
``fused_stage_batches_total``): how many sections share the fixed cost of
one ``stage_many`` a side (PR 52). Near 1 where a tick touches one
section (or where the sections' widths differ and each is staged by
itself), 20-50 where a thousand engines bring a key or two each. It
describes; it is no goal of its own (``better`` is the manifest's
convention). None on a program without the counters."""

from benchmarks import counter_ratio


def read(ctx):
    return counter_ratio.per(ctx, "fused_encoded_sections_total",
                             "fused_stage_batches_total")
