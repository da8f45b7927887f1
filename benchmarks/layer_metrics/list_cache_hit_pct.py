"""Whole-list reads the handler's RV-keyed list-body cache answered, per
hundred that asked it (``list_cache_hits_total`` over
``list_cache_lookups_total``, kcp_tpu/server/handler.py
``_list_encoded``). The key holds the STORE's resourceVersion, so every
commit anywhere ages every entry. A program without the counters reads
nothing."""

from benchmarks import counter_ratio


def read(ctx):
    return counter_ratio.per(ctx, "list_cache_hits_total",
                             "list_cache_lookups_total", 100.0)
