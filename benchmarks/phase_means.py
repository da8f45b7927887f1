"""Means of the program's always-on histograms over the measured window
(shared by the ``conv_*_ms``, ``request_*_ms``, ``loop_lag_ms``,
``tick_wall_ms``, ``split_ms`` and ``aggregate_ms`` readers).

``ctx["registry"]`` holds, for every histogram of the program, the rise
of its SUM over the window under the histogram's own name and, where the
program's ``Registry.snapshot()`` yields it, the rise of its COUNT under
``<name>_count``. A reader's value is rise(sum) / rise(count), in ms: a
MEAN over every observation in the window, not a median. A program that
has no such histogram, or no count beside it (the parent of the PR that
added them), gives None: the harness leaves the metric out of the line.
"""

from __future__ import annotations

PHASES = ("write", "propagate", "stage", "tick", "patch", "downstream",
          "upstatus", "observe")


def _mean(reg: dict, histogram: str, over: str | None = None):
    """(mean in ms, observations) of ``histogram`` in the window, or None."""
    count = reg.get(over or histogram + "_count", 0.0)
    if histogram not in reg or count <= 0:
        return None
    return 1e3 * reg[histogram] / count, count


def mean_ms(ctx: dict, histogram: str, over: str | None = None):
    """Mean of ``histogram`` in the window, ms; ``over`` names another
    counter to divide by (default: the histogram's own ``_count``)."""
    got = _mean(ctx["registry"], histogram, over)
    if got is None:
        return None
    value, count = got
    print(f"[layer] {histogram}: {count:g} observations in the window, "
          f"mean {value:.4f} ms", flush=True)
    return value


def phase_ms(ctx: dict, phase: str):
    """Mean of one phase of the convergence timeline
    (``convergence_<phase>_seconds``, kcp_tpu/obs/trace.py PHASES)."""
    return mean_ms(ctx, f"convergence_{phase}_seconds")


def accounted_pct(ctx: dict):
    """The sum of the eight phase means over the mean of the generator's
    own due->seen of the timed operations that converged, in percent:
    how much of what a client waits for the program can name from
    inside. What is left is outside the program's stamps: the client,
    the sockets, and the request's wait before the handler runs."""
    lat = ctx.get("timed") or []
    if not lat:
        return None
    total = 0.0
    for phase in PHASES:
        got = _mean(ctx["registry"], f"convergence_{phase}_seconds")
        if got is None:
            return None
        total += got[0]
    seen = sum(lat) / len(lat)
    print(f"[layer] accounted: the eight phase means sum to {total:.3f} ms "
          f"of a mean due->seen of {seen:.3f} ms over {len(lat)} timed "
          f"operations", flush=True)
    return 100.0 * total / seen if seen > 0 else None
