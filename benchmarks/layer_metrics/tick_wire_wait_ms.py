"""What a tick's wall time holds beyond its own phases, a tick: (rise of
the sum of ``fused_tick_seconds`` - rise of the sums of all eight
``fused_<phase>_seconds`` histograms, ``syncer/core.py`` ``TICK_PHASES``)
over the rise of ``fused_fleet_ticks_total``, in the window. A tick's
wall runs from its start to its patches handed to the owners; its phases
cover the host's work on both sides of the device, so what is left is
the time between the end of its submit and the start of its collect —
the device's answer as the host sees it (``wire_ready_ms``) plus the
wait for somebody to collect it (``collect_lag_ms``) — and the few
microseconds of the tick between its phases. It reads any program that
has the histograms: the pair's own before and after. A MEAN."""

PHASES = ("encode", "pack", "full_upload", "put", "step_dispatch",
          "compile", "collect_wait", "dispatch")


def read(ctx):
    reg = ctx["registry"]
    ticks = reg.get("fused_fleet_ticks_total", 0.0)
    if "fused_tick_seconds" not in reg or ticks <= 0:
        return None
    wall = reg["fused_tick_seconds"]
    phases = sum(reg.get(f"fused_{p}_seconds", 0.0) for p in PHASES)
    value = 1e3 * (wall - phases) / ticks
    print(f"[layer] tick_wire_wait: {1e3 * wall / ticks:.4f} ms of wall a "
          f"tick less {1e3 * phases / ticks:.4f} ms of its eight phases "
          f"over {ticks:g} ticks: {value:.4f} ms", flush=True)
    return value
