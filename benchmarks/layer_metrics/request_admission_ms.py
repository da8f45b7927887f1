"""Mean over the write requests in the window of one part of the
handler's write path (``request_admission_seconds``): admission, flow-control parking included."""

from benchmarks import phase_means


def read(ctx):
    return phase_means.mean_ms(ctx, "request_admission_seconds")
