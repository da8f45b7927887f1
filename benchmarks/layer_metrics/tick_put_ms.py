"""The tick's ``put`` phase, a tick: rise of the sum of
``fused_put_seconds`` (syncer/core.py: the packed event wire and the ack
lane handed to ``jax.device_put`` — to the one device, or replicated to
every device of a serving mesh) over the rise of
``fused_fleet_ticks_total``, in the window. Host time, a MEAN; one of
the two phases a mesh widens (the other: ``tick_step_dispatch_ms``)."""

from benchmarks import phase_means


def read(ctx):
    return phase_means.mean_ms(ctx, "fused_put_seconds",
                               over="fused_fleet_ticks_total")
