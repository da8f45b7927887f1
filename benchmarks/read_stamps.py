"""The background reads' stamps, for the ``read_*`` readers: what the
``read_mostly`` generator's reader processes stamped on CLOCK_MONOTONIC
(``ctx["generator"]["reads"]``, put there by benchmarks/read_deploy.py;
each a dict of ``verb``, ``scope``, ``due``, ``sent``, ``done``, ...).
A run whose generator does not read (every other kind), or whose
topology does not hand the stamps on, gives every reader None.

A read is of the window when it fell DUE in it, like a write. A latency
is ``done - sent`` of the reads that were answered (no ``error``): the
whole answer read by the client, a walk's every page. A percentile is
nearest-rank (benchmarks/stats.py), and is given only over ten samples
or more.
"""

from __future__ import annotations

from benchmarks import stats

NAMESPACE_LISTS = ("list_selector", "list_table")
MIN_SAMPLES = 10


def in_window(ctx: dict, verbs: tuple[str, ...] | None = None):
    """The reads due in the window (of ``verbs``, or all), or None."""
    reads = (ctx.get("generator") or {}).get("reads")
    if reads is None:
        return None
    w0, w1 = ctx["window"]
    return [r for r in reads if w0 <= r["due"] < w1
            and (verbs is None or r["verb"] in verbs)]


def latency_percentile(ctx: dict, verbs: tuple[str, ...], q: float,
                       what: str):
    reads = in_window(ctx, verbs)
    if reads is None:
        return None
    ms = [(r["done"] - r["sent"]) * 1e3 for r in reads
          if not r.get("error") and r["done"] is not None]
    if len(ms) < MIN_SAMPLES:
        return None
    value = stats.percentile(ms, q)
    print(f"[layer] reads, {what}: {len(ms)} answered of {len(reads)} due "
          f"in the window, sent->done ms p50 {stats.percentile(ms, 50):.3f} "
          f"p90 {stats.percentile(ms, 90):.3f} p99 "
          f"{stats.percentile(ms, 99):.3f} max {max(ms):.3f}", flush=True)
    return value
