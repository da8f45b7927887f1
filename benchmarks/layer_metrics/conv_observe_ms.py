"""Mean, over every observation in the window, of the convergence phase
``observe`` (``convergence_observe_seconds``): commit of an event -> its frame handed to the transport of an HTTP watch stream; every delivered event, spec echo and status alike."""

from benchmarks import phase_means


def read(ctx):
    return phase_means.phase_ms(ctx, "observe")
