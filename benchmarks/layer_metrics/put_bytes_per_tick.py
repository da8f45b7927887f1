"""Bytes the tick's ``put`` phase handed to the devices, a tick: rise of
``fused_fleet_put_bytes_total`` (syncer/core.py ``_submit``, one add a
tick: the packed event wire's and the ack lane's bytes times the devices
each is written to — every device of a serving mesh, 1 with none) over
the rise of ``fused_fleet_ticks_total``: what replication costs the
link. A program without the counter (the parent of the PR that added
it) reads nothing."""

from benchmarks import counter_ratio


def read(ctx):
    return counter_ratio.per(ctx, "fused_fleet_put_bytes_total",
                             "fused_fleet_ticks_total")
