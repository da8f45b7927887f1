"""99th percentile of a WHOLE paged walk of cluster ``*`` due in the
window, first page sent to last page read (3,000 objects in pages of
``limit``): the API-call-latency SLI of scope cluster (its SLO: 30 s),
from the reader processes' own stamps (benchmarks/read_stamps.py)."""

from benchmarks import read_stamps


def read(ctx):
    return read_stamps.latency_percentile(ctx, ("list_all_paged",), 99,
                                          "paged walk of *")
