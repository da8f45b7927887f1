"""Mean lateness of a 50 ms timer on the server's loop in the window
(``server_loop_lag_seconds``): how long a ready callback waits behind
the one interpreter's current work."""

from benchmarks import phase_means


def read(ctx):
    return phase_means.mean_ms(ctx, "server_loop_lag_seconds")
