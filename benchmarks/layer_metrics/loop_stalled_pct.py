"""Share of the window inside passes of the serving loop of 50 ms or
more: rise(``server_loop_long_pass_seconds_total``) per hundred of the
window. Prints their count and, from the ledger's ring of the last 64
(the run process holds the backend's server:
``kcp_tpu.obs.runtime.long_passes``; a program without it gives
nothing), each long pass that began in the window: seconds into the
window, wall, its three largest sections. Stamps are
CLOCK_MONOTONIC, the generator's clock, so a stall lies beside
run.py's "slowest seconds of the window"."""

from benchmarks.layer_metrics import loop_busy_pct


def ring(ctx):
    """The long passes that began in the window, or [] where the
    program keeps no ring."""
    try:
        from kcp_tpu.obs.runtime import long_passes
    except ImportError:
        return []
    w0, w1 = ctx["window"]
    return [p for p in long_passes() if w0 <= p["start"] < w1]


def read(ctx):
    got = loop_busy_pct.ledger(ctx)
    if got is None:
        return None
    kept = ring(ctx)
    print(f"[layer] loop stalls: {got['long_passes']:g} passes of 50 ms or "
          f"more, {got['long_pass_seconds']:.4f} s of the window; "
          f"{len(kept)} of them still in the ring (s into the window, wall "
          f"s, largest sections): "
          + str([(round(p["start"] - ctx["window"][0], 3),
                  round(p["wall_s"], 4),
                  [(n, round(s, 4)) for n, s in p["sections"]])
                 for p in kept]), flush=True)
    return 100.0 * got["long_pass_seconds"] / ctx["seconds"]
