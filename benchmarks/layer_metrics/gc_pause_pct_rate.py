"""The same reading as ``gc_pause_pct_tail``, for cells at saturation:
there every second inside the collector is a second in which nothing
completes, so it moves the completed rate."""

from benchmarks.layer_metrics.gc_pause_pct_tail import read  # noqa: F401
