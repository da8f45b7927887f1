"""Share of the sync the serving loop stood in GROWTH: the rise of
``fused_full_upload_seconds``' sum (a stale tick rebuilds and re-uploads
the whole [B, S] state) plus that of ``fused_compile_seconds``' (the
first dispatch of a set of shapes: syncer/core.py's ``compile`` phase)
over the window, per hundred of the seconds of the window the sync took
(registration due -> last resident seen, cut at the window's end).
Prints the three growth counters and the bytes uploaded. A program
without the ``compile`` phase gives None."""

from benchmarks import sync_times


def read(ctx):
    reg = ctx["registry"]
    took = sync_times.sync_seconds_in_window(ctx)
    if "fused_compile_seconds" not in reg or not took:
        return None
    upload = reg.get("fused_full_upload_seconds", 0.0)
    compiled = reg["fused_compile_seconds"]
    print(f"[layer] growth: full uploads {upload:.3f} s "
          f"({reg.get('fused_full_upload_seconds_count', 0.0):g}), first "
          f"dispatches {compiled:.3f} s "
          f"({reg.get('fused_compile_seconds_count', 0.0):g}) of {took:.2f} s "
          f"of sync in the window; row growths "
          f"{reg.get('fused_fleet_row_growths_total', 0.0):g}, segment "
          f"growths {reg.get('fused_fleet_segment_growths_total', 0.0):g}, "
          f"patch growths {reg.get('fused_fleet_patch_growths_total', 0.0):g}, "
          f"uploaded {reg.get('fused_fleet_state_upload_bytes_total', 0.0) / 1e6:.1f} MB",
          flush=True)
    return 100.0 * (upload + compiled) / took
