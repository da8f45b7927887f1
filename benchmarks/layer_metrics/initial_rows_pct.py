"""How much of the ticks' rows the syncers' initial lists were: rise of
``kcp_sync_initial_rows_total`` (syncer/engine.py: rows staged by the
replay of an upstream informer's initial list) per hundred
``fused_encoded_rows_total``. DESCRIPTIVE (``better`` is the manifest's
convention): a resident is listed once and encoded about three times
(its own row, its copy's echo, its status)."""

from benchmarks import counter_ratio


def read(ctx):
    return counter_ratio.per(ctx, "kcp_sync_initial_rows_total",
                             "fused_encoded_rows_total", 100.0)
