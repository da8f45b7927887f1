"""Bytes of encoded watch frames handed to HTTP watch streams per event
delivered, in the window (``watch_stream_bytes_total`` over
``watch_stream_events_total``, both counted where a batch's lines are
encoded for a stream, push path and relay alike)."""

from benchmarks import counter_ratio


def read(ctx):
    return counter_ratio.per(ctx, "watch_stream_bytes_total",
                             "watch_stream_events_total")
