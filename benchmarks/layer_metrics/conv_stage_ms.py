"""Mean, over every observation in the window, of the convergence phase
``stage`` (``convergence_stage_seconds``): staged -> start of the tick that carried the row."""

from benchmarks import phase_means


def read(ctx):
    return phase_means.phase_ms(ctx, "stage")
