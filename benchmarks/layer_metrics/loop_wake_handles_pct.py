"""Share of the serving loop's handle time inside ``_read_from_self``
(the self-pipe: wake-ups from the wire waiter's thread and the pools),
from the handle table of the profiler slice
(``benchmarks/handle_table.py``). DESCRIPTIVE."""

from benchmarks import handle_table


def read(ctx):
    return handle_table.share_pct(ctx, "self-pipe wake-ups", "_read_from_self")
