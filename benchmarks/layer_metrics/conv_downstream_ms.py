"""Mean, over every observation in the window, of the convergence phase
``downstream`` (``convergence_downstream_seconds``): downstream write applied -> the downstream status event re-staged the row (the physical cluster's controller)."""

from benchmarks import phase_means


def read(ctx):
    return phase_means.phase_ms(ctx, "downstream")
