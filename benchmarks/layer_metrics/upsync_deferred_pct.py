"""Patches of a collected tick that were skipped because their key's
apply was still pending (``kcp_sync_patches_deferred_total``), per
hundred upstream status writes (``kcp_sync_status_upsyncs_total``), in
the window: how often one key's trips meet in flight."""

from benchmarks import counter_ratio


def read(ctx):
    return counter_ratio.per(ctx, "kcp_sync_patches_deferred_total",
                             "kcp_sync_status_upsyncs_total", 100.0)
