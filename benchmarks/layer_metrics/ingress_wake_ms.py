"""Mean over EVERY request in the window (reads too) of
``http_ingress_wake_seconds``: the request's first bytes fed to the
connection's reader -> the serving handler's entry: the reader task's
wake-up, ``readuntil``, ``readexactly`` and the parse. ``conv_ingress_ms``
less this is what the bytes waited behind the pass's earlier callbacks
and the ``recv``."""

from benchmarks import phase_means


def read(ctx):
    return phase_means.mean_ms(ctx, "http_ingress_wake_seconds")
