"""The serving loop's busy time by asyncio HANDLE, as the program's
handle table counted it while the profiler slice was open (shared by
``loop_read_handles_pct``, ``loop_wake_handles_pct`` and
``loop_unnamed_task_pct``).

While a profiler slice is open the loop's ledger (``kcp_tpu/obs/
runtime.py`` ``HandleTable``) names every callback the loop runs by its
KIND — a transport's ``_read_ready``, the self-pipe's
``_read_from_self``, a step of a task by its coroutine (``task_…``), a
timer — and adds per kind the wall seconds of its runs
(``server_loop_handle_seconds_<kind>``) and the seconds of them under no
``kcp.*`` section (``server_loop_handle_unnamed_seconds_<kind>``);
``server_loop_handle_busy_seconds_total`` is the loop's busy seconds over
the same stretch. The counters rise only inside the slice, so their rise
over the window is the slice's. A program without the table (the parent
of the PR that added it), or an untraced window: None.

This is the instrument that tells the loop's unnamed time apart; the
sampling profiler (``/debug/profile``) is a THREAD, which sees the
loop's thread chiefly where it releases the GIL.
"""

from __future__ import annotations

WALL = "server_loop_handle_seconds_"
UNNAMED = "server_loop_handle_unnamed_seconds_"
BUSY = "server_loop_handle_busy_seconds_total"


def table(ctx: dict):
    """{"busy": s, "kinds": {kind: (wall s, unnamed s)}} or None."""
    reg = ctx["registry"]
    kinds = {name[len(WALL):]: (rise, reg.get(UNNAMED + name[len(WALL):], 0.0))
             for name, rise in reg.items() if name.startswith(WALL)}
    wall = sum(w for w, _u in kinds.values())
    if wall <= 0 or reg.get(BUSY, 0.0) <= 0:
        return None
    return {"busy": reg[BUSY], "kinds": kinds, "wall": wall}


def share_pct(ctx: dict, what: str, marker: str):
    """Wall seconds of the kinds whose name holds ``marker`` per hundred
    of the wall seconds of every kind."""
    got = table(ctx)
    if got is None:
        return None
    mine = {k: w for k, (w, _u) in got["kinds"].items() if marker in k}
    part = sum(mine.values())
    print(f"[layer] loop handles, {what}: {part:.4f} s of {got['wall']:.4f} "
          f"s over all kinds ({', '.join(sorted(mine)) or 'no such kind'})",
          flush=True)
    return 100.0 * part / got["wall"]


def unnamed_task_pct(ctx: dict):
    """Unnamed seconds inside task steps per hundred of the unnamed
    seconds of every kind; prints the ten largest kinds (wall and
    unnamed ms a write: each kind's share of the slice's busy seconds
    times the window's busy ms a write) and the table's coverage."""
    got = table(ctx)
    if got is None:
        return None
    kinds, busy = got["kinds"], got["busy"]
    unnamed = sum(u for _w, u in kinds.values())
    if unnamed <= 0:
        return None
    in_tasks = sum(u for k, (_w, u) in kinds.items() if k.startswith("task_"))
    reg = ctx["registry"]
    writes = reg.get("request_admission_seconds_count", 0.0)
    per_write = (1e3 * reg.get("server_loop_busy_seconds_total", 0.0) / writes
                 if writes > 0 else 0.0)
    top = sorted(kinds.items(), key=lambda kv: -kv[1][0])[:10]
    print(f"[layer] loop handles: {len(kinds)} kinds, wall {got['wall']:.4f} "
          f"s of {busy:.4f} s busy in the slice (coverage "
          f"{100 * got['wall'] / busy:.1f}%), unnamed {unnamed:.4f} s "
          f"({100 * unnamed / busy:.1f}% of busy), {in_tasks:.4f} s of it "
          f"in task steps; largest kinds (wall | unnamed, ms a write at "
          f"{per_write:.4f} ms of loop a write): "
          + ", ".join(f"{k} {per_write * w / busy:.4f} | "
                      f"{per_write * u / busy:.4f}" for k, (w, u) in top),
          flush=True)
    return 100.0 * in_tasks / unnamed
