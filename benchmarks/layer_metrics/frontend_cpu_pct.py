"""What the storage frontend costs in cores: its process's user + system
seconds between the window's two edges (``/proc/<pid>/stat``, all
threads) over the time between them, in percent of one core. Taken from
outside (``ctx["generator"]["frontend"]``, benchmarks/child_scrape.py);
None where the topology has no frontend."""

from benchmarks import child_scrape


def read(ctx):
    return child_scrape.cpu_pct(ctx, "frontend")
