"""Mean lateness of a 50 ms timer on the storage FRONTEND's serving loop in the window (its ``server_loop_lag_seconds``, as ``loop_lag_ms`` reads the backend's).
Read from the frontend's own ``/metrics``, scraped at the window's
edges (``ctx["generator"]["frontend"]``, benchmarks/child_scrape.py);
None where the topology has no frontend or the frontend no such histogram."""

from benchmarks import child_scrape


def read(ctx):
    return child_scrape.mean_ms(ctx, "frontend", "server_loop_lag_seconds")
