"""A rise of one of the program's counters over the rise of another, in
the measured window (shared by the ``write_body_bytes``,
``wal_bytes_per_write``, ``watch_bytes_per_event`` and
``encode_us_per_row`` readers).

``ctx["registry"]`` holds the rise of every counter, of every
histogram's SUM (under the histogram's name) and of its COUNT (under
``<name>_count``). A program without the counter (the parent of the PR
that added it), or a window in which the divisor did not rise, gives
None: the harness leaves the metric out of the line.
"""

from __future__ import annotations


def per(ctx: dict, total: str, over: str, scale: float = 1.0):
    """``scale`` x rise(``total``) / rise(``over``), or None."""
    reg = ctx["registry"]
    n = reg.get(over, 0.0)
    if total not in reg or n <= 0:
        return None
    value = scale * reg[total] / n
    print(f"[layer] {total}: rose by {reg[total]:g} over {n:g} of {over} "
          f"in the window: {value:.4f}", flush=True)
    return value
