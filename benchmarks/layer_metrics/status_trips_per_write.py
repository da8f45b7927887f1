"""Upstream status writes the syncer made per REST write request, in the
window (``kcp_sync_status_upsyncs_total`` over
``request_admission_seconds``' count): how many times a status crossed
the core for one tenant write. About 1 where the location's controller
answers once (deletes carry none, a repeat before the echo adds a few
per cent), 4-5 under a controller that rolls; under 3 there, the trips
coalesce and the cell's premise fails. It describes; it is no goal of its
own (``better`` is the manifest's convention)."""

from benchmarks import counter_ratio


def read(ctx):
    return counter_ratio.per(ctx, "kcp_sync_status_upsyncs_total",
                             "request_admission_seconds_count")
