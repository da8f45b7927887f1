"""Mean, over every observation in the window, of the convergence phase
``patch`` (``convergence_patch_seconds``): patches handed over -> downstream write applied (applier queue + the write)."""

from benchmarks import phase_means


def read(ctx):
    return phase_means.phase_ms(ctx, "patch")
