"""Per-layer metrics: one small reader per metric, named as the metric
is in BENCHMARK.json. ``read(ctx)`` returns the value, or None when the
run holds nothing for it to read (the harness then leaves the metric out
of the line). ``ctx`` keys: ``ops`` (the generator's records due in the
window), ``registry`` (rise of the program's counters and histogram sums
over the window), ``trace`` (benchmarks.reduce_trace.reduce's result, or
None), ``fleet`` ({"B", "S"} of the serving core), ``device_kind``,
``compiles`` (backend compiles in the window), ``gc`` (collector pauses
of the server's process in the window, seconds), ``seconds``."""
