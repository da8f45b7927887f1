"""Bytes of request body per REST write request in the window
(``write_request_body_bytes_total``, counted at ``handler._write``, over
``request_admission_seconds``' count: every create, update and delete; a
delete carries no body)."""

from benchmarks import counter_ratio


def read(ctx):
    return counter_ratio.per(ctx, "write_request_body_bytes_total",
                             "request_admission_seconds_count")
