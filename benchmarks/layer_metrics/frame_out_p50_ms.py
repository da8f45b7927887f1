"""Median, over the timed operations of kept keys that converged, of the
generator's ``seen`` less the latest ``t_handed`` (a watch frame of the
key handed to the stream's transport) at or before it, joined on the
clock both processes share (``benchmarks/edge_join.py``): the socket, the
client's chunk reassembly, parse and its own stamp. In
``frontend-1k.steady`` the frames are the BACKEND's, handed to the
frontend's stream: the frontend's relay and its way out."""

from benchmarks import edge_join


def read(ctx):
    return edge_join.frame_out_p50_ms(ctx)
