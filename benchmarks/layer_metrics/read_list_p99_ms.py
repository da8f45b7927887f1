"""99th percentile of sent-to-answered of the LISTs of one tenant's
namespace due in the window — under the selector and as a Table — the
API-call-latency SLI of scope namespace (its SLO: 5 s), from the reader
processes' own stamps (benchmarks/read_stamps.py). The tenant's
unselected cluster LIST that opens a ``relist_watch`` is of the same
size here (one namespace) and is counted with them."""

from benchmarks import read_stamps


def read(ctx):
    return read_stamps.latency_percentile(
        ctx, read_stamps.NAMESPACE_LISTS + ("relist_watch",), 99,
        "LIST of a tenant")
