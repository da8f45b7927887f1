"""The SHARDED fused step's share of one chip's memory roofline: the
least bytes a chip must move for the rows IT holds — ``B / shards`` rows
of ``S`` slots (benchmarks/opcount.py; ``shards`` and ``B`` from the
topology's ``fleet()``) — over that chip's HBM peak
(benchmarks/peaks.json), over the step's measured device time on one
chip: the reducer sums and counts the step's programs over every device
plane, so ``step_seconds_total / steps`` is the per-device mean. The
collectives' latency is inside the measured time and not in the least
bytes, so the share can only be understated. ``fused_step_roofline``
(one chip's formula: ALL of B over one chip's peak) would read
``shards`` times this for the same step and is not read in a mesh cell.
A fleet that does not say its shards (any other topology) reads
nothing."""

from benchmarks import opcount


def read(ctx):
    tr, fleet = ctx.get("trace"), ctx.get("fleet")
    if not tr or not tr["steps"] or not fleet or not fleet.get("shards"):
        return None
    per_device_s = tr["step_seconds_total"] / tr["steps"]
    print(f"[layer] mesh step: {tr['steps']} step programs on "
          f"{len(tr['planes'])} device plane(s), {per_device_s * 1e3:.4f} ms "
          f"each; {fleet['B'] // fleet['shards']} of B={fleet['B']} rows a "
          f"chip over {fleet['shards']} shards", flush=True)
    return opcount.step_roofline_pct(fleet["B"] // fleet["shards"],
                                     fleet["S"], per_device_s,
                                     ctx["device_kind"])
