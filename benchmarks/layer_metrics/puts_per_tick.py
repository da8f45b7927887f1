"""Host->device transfers a tick makes: rise of ``fused_fleet_puts_total``
(syncer/core.py ``_submit``, one add a tick: every ``jax.device_put`` call
the submit made — the packed wire, which carries the ack lane in its tail
rows, and the placement-leaves swap's two on a tick that has one — times
the devices each is written to: every device of a serving mesh, 1 with
none) over the rise of ``fused_fleet_ticks_total``. The cost of the
tick's ``put`` phase is per CALL, not per byte (``tick_put_ms`` beside
``put_bytes_per_tick``): this is the count it scales with. A program
without the counter (the parent of the PR that added it) reads nothing."""

from benchmarks import counter_ratio


def read(ctx):
    return counter_ratio.per(ctx, "fused_fleet_puts_total",
                             "fused_fleet_ticks_total")
