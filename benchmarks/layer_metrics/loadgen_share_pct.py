"""Share of a convergence spent inside the load generator before the
request was sent: the mean of send - due over the mean of seen - due, over
the timed operations that converged, in percent (the population and the
means of ``converge_accounted_pct``, so that the two add: what neither
names is the sockets and the request's wait before the handler runs). An
open-loop generator is one client process; where a burst outruns it, this
is the part of ``converge_p50_ms`` that is the harness's own queue."""


def read(ctx):
    timed = [o for o in ctx["ops"]
             if o["kind"] != "delete" and not o.get("aux")
             and o["seen"] is not None and o["sent"] is not None]
    if not timed:
        return None
    late = sum(o["sent"] - o["due"] for o in timed) / len(timed)
    seen = sum(o["seen"] - o["due"] for o in timed) / len(timed)
    print(f"[layer] loadgen share: mean send - due {late * 1e3:.3f} ms of a "
          f"mean due->seen of {seen * 1e3:.3f} ms over {len(timed)} timed "
          f"operations", flush=True)
    return 100.0 * late / seen if seen > 0 else None
