"""From both sides of a key seen gone to its row free for another key:
mean of ``fused_row_retire_seconds`` (``syncer/core.py``: stamped in
``Section.retire`` during a tick's encode, observed in
``FleetBatch.dispatch`` when the wire that carried the row's last events
has been dispatched — the rest of that tick, its wire's trip and the
collect). While it lasts the row is held back and a new key takes
another. A program without the histogram (the parent of the PR that
retires rows) reads nothing."""

from benchmarks import phase_means


def read(ctx):
    return phase_means.mean_ms(ctx, "fused_row_retire_seconds")
