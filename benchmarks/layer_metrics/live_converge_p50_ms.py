"""Median due-to-status-seen of the LIVE creates (``aux`` records of
kind ``create``) due in the window, while a cold sync runs beside them:
what a tenant's own write waits while the control plane catches up (its
logical cluster's syncer may not have started yet). A failed one counts
as beyond, at the deadline. None where the traffic has no such
records."""

from benchmarks import stats


def read(ctx):
    w0, w1 = ctx["window"]
    live = [o for o in ctx["all_ops"] if o.get("aux")
            and o["kind"] == "create" and w0 <= o["due"] < w1]
    if not live:
        return None
    lat = [(o["seen"] - o["due"]) * 1e3 for o in live
           if o["seen"] is not None]
    print(f"[layer] live creates: {len(live)} due in the window, "
          f"{len(lat)} converged", flush=True)
    return stats.percentile_with_failed(lat, len(live) - len(lat), 50,
                                        ctx["beyond_ms"])
