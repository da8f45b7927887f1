"""Median sent-to-answered of the GETs of one object due in the window
(scope resource: ``kubectl get``, a CD job polling a rollout), from the
reader processes' own stamps (benchmarks/read_stamps.py)."""

from benchmarks import read_stamps


def read(ctx):
    return read_stamps.latency_percentile(ctx, ("get",), 50, "GET")
