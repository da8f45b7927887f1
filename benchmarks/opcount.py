"""Operations and bytes a kernel must do, computed from its shapes, and
the table of peaks (part of the yardstick: the program reports neither).

The fused reconcile step compares the upstream and the downstream mirror
of every resident row: the least it can move is one read of ``up_vals``
and one of ``down_vals``, uint32 [B, S] each. Masks, existence bits, the
delta scatter and the compact patch wire are smaller by a factor of S or
more and are left out, so the share can only be understated. The step
does a handful of integer operations per word read: it is bound by
memory, not by arithmetic.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def step_min_bytes(b: int, s: int) -> int:
    """Least bytes one fused step moves at B rows x S slots."""
    if b <= 0 or s <= 0:
        raise ValueError(f"B={b} S={s}")
    return 2 * b * s * 4


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmarks/peaks.json (has {sorted(table)})")
    return table[device_kind]


def step_roofline_pct(b: int, s: int, step_seconds: float,
                      device_kind: str) -> float:
    """Share of the memory roofline: least time over measured time."""
    if step_seconds <= 0:
        raise ValueError("step time must be positive")
    least = step_min_bytes(b, s) / peaks(device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / step_seconds
