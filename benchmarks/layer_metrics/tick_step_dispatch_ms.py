"""The tick's ``step_dispatch`` phase, a tick: rise of the sum of
``fused_step_dispatch_seconds`` (syncer/core.py: the call of the jitted
fleet step with shapes it has dispatched before — the launch of one
executable on the one device, or on every device of a serving mesh —
and the wire's ``copy_to_host_async``) over the rise of
``fused_fleet_ticks_total``, in the window. Host time, a MEAN; a first
dispatch is the ``compile`` phase and is not in it."""

from benchmarks import phase_means


def read(ctx):
    return phase_means.mean_ms(ctx, "fused_step_dispatch_seconds",
                               over="fused_fleet_ticks_total")
