"""Mean over the write requests in the window of one part of the
handler's write path (``request_finish_seconds``): store call's return -> response ready: the commit window's WAL append and sync, the standby wait, the encode."""

from benchmarks import phase_means


def read(ctx):
    return phase_means.mean_ms(ctx, "request_finish_seconds")
