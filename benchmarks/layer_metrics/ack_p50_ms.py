"""Median send-to-acknowledgement of the writes due in the window: the
REST write path (httpd, handler, admission, store commit, WAL)."""

from benchmarks import stats


def read(ctx):
    acks = [(o["acked"] - o["sent"]) * 1e3 for o in ctx["ops"]
            if o["acked"] is not None and not o.get("aux")]
    return stats.percentile(acks, 50) if acks else None
