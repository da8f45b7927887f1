"""The residents of a cold sync as the generator stamped them (shared by
the ``full_sync_s``, ``sync_rate_per_s``, ``first_synced_ms`` and
``growth_stall_pct`` readers): the ``kind: "sync"`` records due in the
window (generators/cold_sync.py: one for every resident, all due at the
registration instant). A cell without such records gives None."""

from __future__ import annotations


def residents(ctx: dict):
    """(registration instant, seconds from it to each resident's status
    seen — ascending, one never seen at the deadline — and how many were
    never seen), or None."""
    syncs = [o for o in ctx["ops"] if o["kind"] == "sync"]
    if not syncs:
        return None
    due = min(o["due"] for o in syncs)
    beyond = ctx["beyond_ms"] / 1e3
    took = sorted(o["seen"] - due if o["seen"] is not None else beyond
                  for o in syncs)
    return due, took, sum(1 for o in syncs if o["seen"] is None)


def sync_seconds_in_window(ctx: dict):
    """Seconds of the window the sync took: registration due -> last
    resident seen, cut at the window's end."""
    got = residents(ctx)
    if got is None:
        return None
    due, took, _unseen = got
    return max(0.0, min(due + took[-1], ctx["window"][1]) - due)
