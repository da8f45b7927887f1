"""Mean in the window of ``splitter_split_seconds``: a root's event ->
its leaves written (queue, placement lane, applier, the leaf writes)."""

from benchmarks import phase_means


def read(ctx):
    return phase_means.mean_ms(ctx, "splitter_split_seconds")
