"""50th percentile of due-to-status-seen over the timed operations due in
the window; a failed operation counts as beyond it."""

from benchmarks import stats


def read(ctx):
    if not ctx["timed"] and not ctx["n_failed_timed"]:
        return None
    return stats.percentile_with_failed(ctx["timed"], ctx["n_failed_timed"],
                                        50, ctx["beyond_ms"])
