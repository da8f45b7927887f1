"""Reads due in the window whose answer the plain reference could not
pin to ONE body (a write of a returned key was in flight during the
request, so every body current between ``sent`` and ``done`` was
admitted), per hundred judged (benchmarks/k8s_load_read_reference.py).
How much of the judgement is exact: DESCRIPTIVE."""

from benchmarks import read_stamps


def read(ctx):
    reads = read_stamps.in_window(ctx)
    if not reads:
        return None
    n = sum(1 for r in reads if r.get("undetermined"))
    print(f"[layer] reads the reference could not pin to one body: {n} of "
          f"{len(reads)}", flush=True)
    return 100.0 * n / len(reads)
