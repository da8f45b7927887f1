"""99th percentile of sent-to-answered of the GETs of one object due in
the window: the API-call-latency SLI of scope resource (its SLO: 1 s),
from the reader processes' own stamps (benchmarks/read_stamps.py)."""

from benchmarks import read_stamps


def read(ctx):
    return read_stamps.latency_percentile(ctx, ("get",), 99, "GET")
