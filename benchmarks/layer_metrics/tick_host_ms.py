"""Host time of one fused tick: rise of the sums of the
``fused_*_seconds`` histograms (syncer/core.py ``_phase``) over the rise
of ``fused_fleet_ticks_total``, in the window. Host time, not device."""

PHASES = ("put", "step_dispatch", "collect_wait", "dispatch", "encode",
          "full_upload")


def read(ctx):
    reg = ctx["registry"]
    ticks = reg.get("fused_fleet_ticks_total", 0.0)
    if ticks <= 0:
        return None
    total = sum(reg.get(f"fused_{p}_seconds", 0.0) for p in PHASES)
    return 1e3 * total / ticks
