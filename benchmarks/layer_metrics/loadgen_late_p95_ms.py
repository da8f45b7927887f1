"""95th percentile of send time minus due time: how late the generator
ran. A starved generator must not be read as a fast server."""

from benchmarks import stats


def read(ctx):
    late = [(o["sent"] - o["due"]) * 1e3 for o in ctx["ops"]
            if o["sent"] is not None and not o.get("aux")]
    return stats.percentile(late, 95) if late else None
