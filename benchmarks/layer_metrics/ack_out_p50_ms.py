"""Median, over the timed operations of kept keys, of the generator's
``acked`` less the server's ``t_out`` (the response handed to the
transport), joined on the clock both processes share
(``benchmarks/edge_join.py``): the socket and the client's own read of
the ack. In ``frontend-1k.steady`` the records are the BACKEND's: the
frontend tier's way back."""

from benchmarks import edge_join


def read(ctx):
    return edge_join.ack_out_p50_ms(ctx)
