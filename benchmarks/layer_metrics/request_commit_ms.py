"""Mean over the write requests in the window of one part of the
handler's write path (``request_commit_seconds``): the store call: mutation and joining the commit window."""

from benchmarks import phase_means


def read(ctx):
    return phase_means.mean_ms(ctx, "request_commit_seconds")
