"""Share of the events the store's fan-out handed to a resource's watches
in the window that met no residual watch
(``store_fanout_indexed_events_total`` per hundred
``store_fanout_events_total``, both counted once a flushed resource in
``kcp_tpu/store/store.py`` ``_fanout_resource``): such an event is
looked up in the plan's cluster and label-pair buckets and costs the
same whatever the number of watches; the others also cross an [events x
residual watches] matrix (wildcard-cluster watches with a compiled
selector). It describes whether the index engaged, and every cell's
watches are scoped, single-equality or empty, so the cells read 100; a
program without the counters (the parent of the PR that added them)
reads nothing."""

from benchmarks import counter_ratio


def read(ctx):
    return counter_ratio.per(ctx, "store_fanout_indexed_events_total",
                             "store_fanout_events_total", 100.0)
