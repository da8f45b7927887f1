"""Rows a new key took from a bucket's free list per hundred rows new
keys took in the window: the rise of ``fused_rows_reused_total`` over
the rises of it and of ``fused_rows_fresh_total`` (both
``syncer/core.py`` ``FusedBucket.alloc_row``; a fresh row is one past
the bucket's high-water mark, the only kind that can make ``B`` grow).
Where names churn at a constant live count it reads near 100 once the
free list covers the rows held back in flight; beside it the rise of
``fused_rows_retired_total`` (keys forgotten because both their sides
were gone). A program without the counters (the parent of the PR that
retires rows), or a window in which no key was new, reads nothing."""


def read(ctx):
    reg = ctx["registry"]
    if "fused_rows_reused_total" not in reg or "fused_rows_fresh_total" not in reg:
        return None
    reused, fresh = reg["fused_rows_reused_total"], reg["fused_rows_fresh_total"]
    if reused + fresh <= 0:
        return None
    print(f"[layer] rows: {reused:g} reused + {fresh:g} fresh for new keys, "
          f"{reg.get('fused_rows_retired_total', 0.0):g} retired in the "
          f"window", flush=True)
    return 100.0 * reused / (reused + fresh)
