"""Share of a convergence that the program accounts for from inside:
the sum of the eight ``conv_*_ms`` means over the mean of the
generator's own due->seen of the timed operations, in percent."""

from benchmarks import phase_means


def read(ctx):
    return phase_means.accounted_pct(ctx)
