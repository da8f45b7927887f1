"""Share of the serving loop's busy time in which its thread did not
run: (rise(busy) - rise(``server_loop_cpu_seconds_total``)) per hundred
of rise(busy). A pass that holds the loop off the CPU is a blocking call
(an ``fsync``, the wait for the device), the GIL in another thread's
hands, or the thread descheduled."""

from benchmarks.layer_metrics import loop_busy_pct


def read(ctx):
    got = loop_busy_pct.ledger(ctx)
    if got is None:
        return None
    busy, cpu = got["busy_seconds"], got["cpu_seconds"]
    print(f"[layer] loop off the CPU: busy {busy:.4f} s, of them on the CPU "
          f"{cpu:.4f} s", flush=True)
    return 100.0 * (busy - cpu) / busy
