"""The load generator's own CPU over the sync: its process's
``time.process_time()`` from the registration instant to the last
resident seen, per hundred of the wall seconds between them
(generators/cold_sync.py returns both). It is one interpreter: above 80
the generator's watch thread, not the server, set the sync's pace."""


def read(ctx):
    gen = ctx.get("generator") or {}
    cpu, wall = gen.get("sync_cpu_s"), gen.get("sync_wall_s")
    if cpu is None or not wall:
        return None
    print(f"[layer] generator: {cpu:.2f} s of CPU in {wall:.2f} s of sync",
          flush=True)
    return 100.0 * cpu / wall
