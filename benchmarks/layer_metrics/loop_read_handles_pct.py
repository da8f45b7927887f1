"""Share of the serving loop's handle time inside the transports'
``_read_ready`` handles (the socket's ``recv``, ``data_received``, the
feed and the reader's wake-up), from the handle table of the profiler
slice (``benchmarks/handle_table.py``). DESCRIPTIVE."""

from benchmarks import handle_table


def read(ctx):
    return handle_table.share_pct(ctx, "socket reads", "_read_ready")
