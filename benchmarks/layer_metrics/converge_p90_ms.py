"""90th percentile of due-to-status-seen over the timed operations due in
the window (a failed operation counts as beyond it): the highest
percentile that the collector's pauses leave alone below the knee. No
bound: about one run in eight meets a stall of two to three seconds of
the machine itself, which moves it threefold (PERF.md, section 2)."""

from benchmarks import stats


def read(ctx):
    if not ctx["timed"] and not ctx["n_failed_timed"]:
        return None
    return stats.percentile_with_failed(ctx["timed"], ctx["n_failed_timed"],
                                        90, ctx["beyond_ms"])
