"""95th percentile of send time minus due time of the reads due in the
window: how late the reader processes ran. The harness's own queue: a
starved reader must not be read as a fast server. Beside it the reader
processes' own CPU over the traffic (the generator's ``read_cpu_s``):
they share the server's host."""

from benchmarks import read_stamps, stats


def read(ctx):
    reads = read_stamps.in_window(ctx)
    if not reads:
        return None
    gen = ctx["generator"]
    cpu, wall = gen.get("read_cpu_s"), gen.get("read_wall_s")
    if cpu and wall:
        print(f"[layer] readers: {len(cpu)} processes, {sum(cpu):.2f} s of "
              f"CPU in {wall:.2f} s of traffic, the busiest "
              f"{100 * max(cpu) / wall:.1f}% of one core", flush=True)
    late = [(r["sent"] - r["due"]) * 1e3 for r in reads
            if r["sent"] is not None and not r.get("restarts")]
    return stats.percentile(late, 95) if late else None
