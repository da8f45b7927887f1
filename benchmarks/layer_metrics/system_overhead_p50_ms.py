"""Median, over the timed updates of the window that converged, of
(seen - due) less the rolling controller's span for that object and
generation (``controller_span_ms``): what ``converge_p50_ms`` would be on
a cluster whose pods are ready at once — the trip down, the first status
up, and what the later status trips add beyond the controller's own
pace."""

from benchmarks import controller_spans, stats


def read(ctx):
    got = controller_spans.pairs(ctx)
    if not got:
        return None
    return stats.percentile([lat - span for lat, span in got], 50)
