"""Mean, over every observation in the window, of the convergence phase
``write`` (``convergence_write_seconds``): handler entry (store entry, for an in-process writer) -> the commit stamp on the write's event."""

from benchmarks import phase_means


def read(ctx):
    return phase_means.phase_ms(ctx, "write")
