"""Reads due in the window that were answered, per second of it, every
verb (a walk counts once). DESCRIPTIVE: an open loop completes what it
offers while the server keeps up; ``better`` is the manifest's
convention."""

from benchmarks import read_stamps


def read(ctx):
    reads = read_stamps.in_window(ctx)
    if not reads:
        return None
    done = [r for r in reads if not r.get("error")]
    by_verb: dict[str, int] = {}
    for r in done:
        by_verb[r["verb"]] = by_verb.get(r["verb"], 0) + 1
    print(f"[layer] reads: {len(done)} answered of {len(reads)} due in the "
          f"window of {ctx['seconds']:g} s; by verb {by_verb}; bytes read "
          f"{sum(r['bytes'] or 0 for r in done)}, pages "
          f"{sum(r['pages'] or 0 for r in done)}, walks restarted "
          f"{sum(r['restarts'] or 0 for r in done)}", flush=True)
    return len(done) / ctx["seconds"]
