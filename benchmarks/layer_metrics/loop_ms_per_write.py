"""What one tenant write costs the serving loop, with every trip it
causes: 1e3 x rise(``server_loop_busy_seconds_total``) /
rise(``request_admission_seconds_count``) (every write request). The
same quotient is printed per section (``server_loop_self_seconds_*``:
SELF seconds, a section's duration less the sections inside it),
largest first, and for the unnamed rest: the unit a change to the loop's
work is priced in."""

from benchmarks.layer_metrics import loop_busy_pct


def read(ctx):
    got = loop_busy_pct.ledger(ctx)
    writes = ctx["registry"].get("request_admission_seconds_count", 0.0)
    if got is None or writes <= 0:
        return None
    busy = got["busy_seconds"]
    table = sorted(got["self"].items(), key=lambda kv: -kv[1])
    table.append(("unnamed", busy - sum(got["self"].values())))
    print(f"[layer] loop ms per write: busy {busy:.4f} s over {writes:g} "
          f"writes; by section (self ms a write, share of busy): "
          + ", ".join(f"{name} {1e3 * s / writes:.4f} {100 * s / busy:.1f}%"
                      for name, s in table), flush=True)
    return 1e3 * busy / writes
