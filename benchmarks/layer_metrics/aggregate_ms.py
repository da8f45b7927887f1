"""Mean in the window of ``splitter_aggregate_seconds``: a leaf's
status event -> the root's aggregated status written."""

from benchmarks import phase_means


def read(ctx):
    return phase_means.mean_ms(ctx, "splitter_aggregate_seconds")
