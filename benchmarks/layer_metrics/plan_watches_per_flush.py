"""Watches the store's fan-out walked to REBUILD its per-resource plans,
per fan-out flush of the window: the rise of
``store_fanout_plan_watches_total`` (kcp_tpu/store/store.py
``_fanout_plan``: one add a rebuild, the watches of the resource it
walked) over the rise of ``store_emit_seconds``' count (one observation
a flush, every store of the process: the locations' stores flush too
and never rebuild). A plan is rebuilt when a watch of its resource was
opened or closed since it was made: 0 where the watch set stands still,
as in every cell whose only watches are the syncers' informers and the
generator's. Beside it the rebuilds and the watches a rebuild walks. A
program without the counters reads nothing."""


def read(ctx):
    reg = ctx["registry"]
    flushes = reg.get("store_emit_seconds_count", 0.0)
    if "store_fanout_plan_watches_total" not in reg or flushes <= 0:
        return None
    walked = reg["store_fanout_plan_watches_total"]
    rebuilds = reg.get("store_fanout_plan_rebuilds_total", 0.0)
    print(f"[layer] fan-out plans: {rebuilds:g} rebuilds walked {walked:g} "
          f"watches ({walked / rebuilds if rebuilds else 0.0:.1f} a "
          f"rebuild) over {flushes:g} flushes in the window", flush=True)
    return walked / flushes
