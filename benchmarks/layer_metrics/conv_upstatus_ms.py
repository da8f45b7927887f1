"""Mean, over every observation in the window, of the convergence phase
``upstatus`` (``convergence_upstatus_seconds``): re-staged -> status committed upstream (second tick, applier, store commit)."""

from benchmarks import phase_means


def read(ctx):
    return phase_means.phase_ms(ctx, "upstatus")
