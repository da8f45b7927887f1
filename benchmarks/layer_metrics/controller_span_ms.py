"""Mean, over the timed updates of the window that converged, of the
rolling controller's last minus first status write for that object and
generation (its own stamps, ``benchmarks/rolling_agent.py STAMPS``): the
part of due->seen that is the cluster's pods becoming ready
(``pod_ready_ms`` a step, stretched where the serving loop is late), not
this system."""

from benchmarks import controller_spans


def read(ctx):
    got = controller_spans.pairs(ctx)
    if not got:
        return None
    value = sum(span for _lat, span in got) / len(got)
    print(f"[layer] controller span: mean {value:.3f} ms over {len(got)} "
          f"timed updates that converged", flush=True)
    return value
