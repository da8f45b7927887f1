"""95th percentile of due-to-status-seen over the timed operations due in
the window (a failed operation counts as beyond it). The tail
beyond the 90th percentile is made by the few full collections of the
server's heap that fall in a window, so it swings by 10-30 % from run to
run: reported for the record, with no bound (PERF.md, section 2)."""

from benchmarks import stats

MIN_SAMPLES = 1


def read(ctx):
    n = len(ctx["timed"]) + ctx["n_failed_timed"]
    if n < MIN_SAMPLES:
        return None
    return stats.percentile_with_failed(ctx["timed"], ctx["n_failed_timed"],
                                        95, ctx["beyond_ms"])
