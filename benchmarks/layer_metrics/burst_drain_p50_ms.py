"""Median, over the bursts due in the window, of the time a burst took to
drain: the last status-seen of its timed operations minus the first due
instant of any of them (the generator stamps ``burst`` on every operation
of a burst; open_loop.py). A burst with an operation that never converged
did not drain and counts as beyond, like a failed operation in a
percentile. None where the traffic has no bursts."""

from benchmarks import stats


def read(ctx):
    bursts: dict[int, list[dict]] = {}
    for o in ctx["ops"]:
        if o.get("burst") is not None:
            bursts.setdefault(o["burst"], []).append(o)
    drains = []
    for ops in bursts.values():
        timed = [o for o in ops if o["kind"] != "delete"]
        if not timed:
            continue
        if any(o["seen"] is None for o in timed):
            drains.append(float(ctx["beyond_ms"]))
            continue
        first_due = min(o["due"] for o in ops)
        drains.append((max(o["seen"] for o in timed) - first_due) * 1e3)
    if not drains:
        return None
    print(f"[layer] burst_drain: {len(drains)} bursts due in the window, ms "
          f"min {min(drains):.1f} max {max(drains):.1f}", flush=True)
    return stats.percentile(drains, 50)
