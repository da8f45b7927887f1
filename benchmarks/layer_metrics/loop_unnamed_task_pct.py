"""Share of the serving loop's UNNAMED handle time (under no ``kcp.*``
section) that lies inside steps of tasks — our own coroutines — and not
in transports, timers or plain callbacks, from the handle table of the
profiler slice (``benchmarks/handle_table.py``); prints the ten largest
kinds and the table's coverage of the busy seconds. DESCRIPTIVE."""

from benchmarks import handle_table


def read(ctx):
    return handle_table.unnamed_task_pct(ctx)
