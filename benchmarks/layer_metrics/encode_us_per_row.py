"""Host microseconds of the tick's ``encode`` phase per row it encoded,
in the window (``fused_encode_seconds``' sum over
``fused_encoded_rows_total``): flatten, hash and stage of each touched
key, both sides of it."""

from benchmarks import counter_ratio


def read(ctx):
    return counter_ratio.per(ctx, "fused_encode_seconds",
                             "fused_encoded_rows_total", scale=1e6)
