"""The part of a convergence that is the physical cluster's pods, from the
rolling controller's own stamps (shared by the ``controller_span_ms`` and
``system_overhead_p50_ms`` readers).

``benchmarks/rolling_agent.py`` stamps, per (location, name, generation),
its first and last status write of a rollout (CLOCK_MONOTONIC, the
generator's clock too; it runs on the server's loop, in this process). A
timed update that converged names its walk itself: the location is its
body's ``kcp.dev/cluster`` label, the generation the
``status.observedGeneration`` of the object that ended its wait. A cell
whose controller answers once has no such stamps: nothing to read.
"""

from __future__ import annotations

from benchmarks import rolling_agent


def pairs(ctx: dict) -> list[tuple[float, float]]:
    """[(due -> seen, the controller's last - first status write)], ms,
    over the timed updates of the window that converged and whose walk
    the controller stamped."""
    stamps = rolling_agent.STAMPS
    out = []
    for r in ctx.get("ops") or []:
        if r["kind"] != "update" or r.get("seen") is None:
            continue
        status = (r.get("evidence") or {}).get("status") or {}
        labels = r["body"]["metadata"].get("labels") or {}
        walk = stamps.get((labels.get(rolling_agent.CLUSTER_LABEL, ""), r["key"][1],
                           status.get("observedGeneration")))
        if walk is not None:
            out.append(((r["seen"] - r["due"]) * 1e3,
                        (walk[1] - walk[0]) * 1e3))
    return out
