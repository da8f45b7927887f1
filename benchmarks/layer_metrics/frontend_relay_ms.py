"""Mean, over every watch event the storage frontend relayed in the window, of ``watch_relay_seconds``: arrival off the backend's stream (``RestWatch._feed``) -> its frame handed to the tenant's stream.
Read from the frontend's own ``/metrics``, scraped at the window's
edges (``ctx["generator"]["frontend"]``, benchmarks/child_scrape.py);
None where the topology has no frontend or the frontend no such histogram."""

from benchmarks import child_scrape


def read(ctx):
    return child_scrape.mean_ms(ctx, "frontend", "watch_relay_seconds")
