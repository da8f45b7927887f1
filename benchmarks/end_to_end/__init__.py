"""End-to-end metrics: one reader per metric, named as the metric is in
BENCHMARK.json, all from the generator's CLOCK_MONOTONIC stamps (source
``host_clock``). ``read(ctx)`` sees ``ops`` (the operations due in the
window), ``timed`` (latencies in ms of those that converged),
``n_failed_timed``, ``beyond_ms``, ``window`` (start, end), ``seconds``
and ``setup_s``; None leaves the metric out of the line."""
