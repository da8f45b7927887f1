"""Share of the upstream status writes of the window that were made by an
apply the downstream event itself queued
(``kcp_sync_status_upsyncs_direct_total`` per hundred
``kcp_sync_status_upsyncs_total``, both counted in
``kcp_tpu/syncer/engine.py``): such a write waits for one wake-up of the
applier, every other one for the tick that re-decided its row (a later
status of a write whose first is already up, a status that met a pending
apply, a failed apply's retry, the level-triggered backstop). It
describes how often the direct path engaged; a program without the
counter (the parent of the PR that added it) reads nothing."""

from benchmarks import counter_ratio


def read(ctx):
    return counter_ratio.per(ctx, "kcp_sync_status_upsyncs_direct_total",
                             "kcp_sync_status_upsyncs_total", 100.0)
