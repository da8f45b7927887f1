"""Mean, over every observation in the window, of the convergence phase
``tick`` (``convergence_tick_seconds``): tick start -> that tick's patches handed to the applier (the pipeline's wait for the wire included)."""

from benchmarks import phase_means


def read(ctx):
    return phase_means.phase_ms(ctx, "tick")
