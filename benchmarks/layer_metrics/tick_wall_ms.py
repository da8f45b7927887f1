"""One fused tick, whole: rise of the sum of ``fused_tick_seconds``
(tick start -> its patches dispatched, every phase with ``pack`` and the
wait for the wire) over the rise of ``fused_fleet_ticks_total``. Wall
time on the host's clock, beside ``tick_host_ms``'s sum of host phases."""

from benchmarks import phase_means


def read(ctx):
    return phase_means.mean_ms(ctx, "fused_tick_seconds",
                               over="fused_fleet_ticks_total")
