"""Share of the window the serving loop's one thread was BUSY: rise of
``server_loop_busy_seconds_total`` (a return of ``select`` to the next
call: one pass's callbacks) per hundred of the rise of busy + idle
(inside ``select``), which is wall time by construction
(kcp_tpu/obs/runtime.py ``LoopLedger``). The utilisation of the one
processor every cell is bound by: the distance to the knee.
DESCRIPTIVE of the cell's rate; no gain may be claimed from it alone.

:func:`ledger` is shared by the other ``loop_*`` readers: the rises of
the ledger's counters in the window, or None on a program without them
(the parent of the PR that added them), or in a window in which the
loop made no pass."""

PREFIX = "server_loop_"
SELF = PREFIX + "self_seconds_"


def ledger(ctx):
    reg = ctx["registry"]
    if PREFIX + "busy_seconds_total" not in reg:
        return None
    got = {slot: reg.get(f"{PREFIX}{slot}_total", 0.0)
           for slot in ("busy_seconds", "idle_seconds", "cpu_seconds",
                        "passes", "long_passes", "long_pass_seconds",
                        "section_leaks")}
    if got["busy_seconds"] <= 0 or got["passes"] <= 0:
        return None
    got["self"] = {name[len(SELF):]: rise for name, rise in reg.items()
                   if name.startswith(SELF)}
    return got


def read(ctx):
    got = ledger(ctx)
    if got is None:
        return None
    busy, idle = got["busy_seconds"], got["idle_seconds"]
    print(f"[layer] loop: busy {busy:.4f} s + idle {idle:.4f} s = "
          f"{busy + idle:.4f} s of a window of {ctx['seconds']:g} s; cpu "
          f"{got['cpu_seconds']:.4f} s; {got['passes']:g} passes, mean pass "
          f"{1e6 * busy / got['passes']:.1f} us; section leaks "
          f"{got['section_leaks']:g}", flush=True)
    return 100.0 * busy / (busy + idle)
