"""Mean, over every observation in the window, of the convergence phase
``propagate`` (``convergence_propagate_seconds``): commit -> the syncer engine staged the key (commit window, WAL sync, watch fan-out, informer)."""

from benchmarks import phase_means


def read(ctx):
    return phase_means.phase_ms(ctx, "propagate")
