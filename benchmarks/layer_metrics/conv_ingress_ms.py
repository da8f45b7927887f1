"""Mean over the write requests in the window of the ``ingress`` phase
(``convergence_ingress_seconds``): the start of the loop pass that read
the request's first byte -> the serving handler's entry (the pass's
earlier callbacks, the ``recv``, the reader's wake-up, the parse). In
``frontend-1k.steady`` the BACKEND's requests: the frontend's store call
arriving at the backend."""

from benchmarks import phase_means


def read(ctx):
    return phase_means.phase_ms(ctx, "ingress")
