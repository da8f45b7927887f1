"""Bytes appended to the write-ahead log per REST write request in the
window (``wal_appended_bytes_total``, counted where the store appends,
over ``request_admission_seconds``' count). The log takes every record
of the store, the writes of the program's own controllers included (a
status upsync, a splitter's leaves): this is what one tenant write costs
the log, not the size of one record."""

from benchmarks import counter_ratio


def read(ctx):
    return counter_ratio.per(ctx, "wal_appended_bytes_total",
                             "request_admission_seconds_count")
