"""Median, over the timed operations of kept keys, of the server's
``rx`` (the loop pass that read the request's first byte) less the
generator's ``sent``, joined on the clock both processes share
(``benchmarks/edge_join.py``): the client's send, the kernel, and the
wait for the server's loop to reach ``select``. In ``frontend-1k.steady``
the records are the BACKEND's: the frontend tier's whole way in."""

from benchmarks import edge_join


def read(ctx):
    return edge_join.wire_in_p50_ms(ctx)
