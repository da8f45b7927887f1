"""Share of the serving loop's busy time under no ``obs.annotate``
section: (rise(busy) - sum of the rises of
``server_loop_self_seconds_*``) per hundred of rise(busy). The
tracing's own coverage: what is left is host work nobody has named."""

from benchmarks.layer_metrics import loop_busy_pct


def read(ctx):
    got = loop_busy_pct.ledger(ctx)
    if got is None:
        return None
    busy, named = got["busy_seconds"], sum(got["self"].values())
    print(f"[layer] loop unnamed: {len(got['self'])} sections hold "
          f"{named:.4f} s of {busy:.4f} s busy", flush=True)
    return 100.0 * (busy - named) / busy
