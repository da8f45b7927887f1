"""Changes of the fleet batch's row count ``B`` inside the window: the
rise of ``fused_fleet_row_growths_total`` (``syncer/core.py``
``FleetBatch._refresh_layout``, one add where ``B`` changes after the
first layout). Each is a full upload of the resident state and a new
program for every delta shape, on the serving loop. The counter is
older than the cell that reads it, so the parent of the PR that retires
rows reads here too: at least 1 where every name ever seen keeps a row,
0 where the rows follow the live objects. A program without the counter
reads nothing."""


def read(ctx):
    reg = ctx["registry"]
    if "fused_fleet_row_growths_total" not in reg:
        return None
    fleet = ctx.get("fleet") or {}
    print(f"[layer] row growths in the window: "
          f"{reg['fused_fleet_row_growths_total']:g}; the fleet at the "
          f"run's end: B={fleet.get('B')} S={fleet.get('S')}", flush=True)
    return reg["fused_fleet_row_growths_total"]
