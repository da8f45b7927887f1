"""Objects the store's lists examined per object they returned, in the
window (``store_list_scanned_total`` over ``store_list_returned_total``,
kcp_tpu/store/store.py ``_list_metrics``): 1 where the index does the
scoping, above it where a selector or a page boundary filters what was
walked. Every store of the process counts (the locations' too: their
controllers and syncers list at set-up, not in the window)."""

from benchmarks import counter_ratio


def read(ctx):
    return counter_ratio.per(ctx, "store_list_scanned_total",
                             "store_list_returned_total")
