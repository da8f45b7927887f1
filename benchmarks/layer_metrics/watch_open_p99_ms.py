"""99th percentile of WATCH sent (the connection asked for) to its
response head read, over the ``relist_watch`` reads due in the window
whose stream answered: what opening a watch costs a tenant's informer,
from the reader processes' own stamps (benchmarks/read_stamps.py)."""

from benchmarks import read_stamps, stats


def read(ctx):
    reads = read_stamps.in_window(ctx, ("relist_watch",))
    if reads is None:
        return None
    ms = [(w["head"] - w["sent"]) * 1e3 for w in
          (r.get("watch") for r in reads) if w and w.get("head") is not None]
    if len(ms) < read_stamps.MIN_SAMPLES:
        return None
    print(f"[layer] watches opened: {len(ms)} of {len(reads)} due in the "
          f"window, sent->head ms p50 {stats.percentile(ms, 50):.3f} p99 "
          f"{stats.percentile(ms, 99):.3f}; events delivered to them "
          f"{sum(r['watch']['events'] for r in reads if r.get('watch'))}",
          flush=True)
    return stats.percentile(ms, 99)
