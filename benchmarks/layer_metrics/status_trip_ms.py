"""Mean, over every observation in the window, of the convergence phase
``restatus`` (``convergence_restatus_seconds``): each LATER status trip of
a write whose first status is already up — the downstream status event
re-staged the row -> that status committed upstream (tick, applier, store
commit). ``conv_upstatus_ms`` is the same trip for a write's FIRST status."""

from benchmarks import phase_means


def read(ctx):
    return phase_means.phase_ms(ctx, "restatus")
