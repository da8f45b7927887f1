"""Whole-object (or whole-subtree) copies the program made per REST
write request in the window (``object_tree_copies_total``, one add per
call of ``kcp_tpu.utils.treecopy.tree_copy``, over
``request_admission_seconds``' count). Every hand-over of an object
counts, the program's own included: the store's copy of a write's
argument and of its result, ``get``, the applier's
``transform_for_downstream``, the location controller's read and status
write, the status upsync, a splitter's leaves. It counts calls, not
nodes: what one copy costs follows ``write_body_bytes``."""

from benchmarks import counter_ratio


def read(ctx):
    return counter_ratio.per(ctx, "object_tree_copies_total",
                             "request_admission_seconds_count")
