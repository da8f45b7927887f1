"""Share of the events the program's informers dispatched in the window
that the store's fan-out pass handed over itself
(``informer_pushed_events_total`` per hundred ``informer_events_total``,
both counted once a batch in ``kcp_tpu/client/informer.py``): a pushed
event reaches its handlers in the loop pass that flushed it, a pulled
one wakes the informer's pump task first. Every informer of a
one-process deployment is local, so the cells read 100; it describes
whether the push half engaged, and a program without the counters (the
parent of the PR that added them) reads nothing."""

from benchmarks import counter_ratio


def read(ctx):
    return counter_ratio.per(ctx, "informer_pushed_events_total",
                             "informer_events_total", 100.0)
