"""Mean, over every store verb of the storage frontend in the window, of ``remote_store_queue_seconds``: the handler's submit to its store-I/O pool -> a pooled backend connection borrowed (the wait for a thread plus the wait for a connection).
Read from the frontend's own ``/metrics``, scraped at the window's
edges (``ctx["generator"]["frontend"]``, benchmarks/child_scrape.py);
None where the topology has no frontend or the frontend no such histogram."""

from benchmarks import child_scrape


def read(ctx):
    return child_scrape.mean_ms(ctx, "frontend", "remote_store_queue_seconds")
