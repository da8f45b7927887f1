"""The generator's stamps joined to the program's edge log on the clock
both processes share (shared by ``wire_in_p50_ms``, ``ack_out_p50_ms``,
``frame_out_p50_ms`` and ``converge_edges_pct``).

The load generator stamps every operation ``due`` / ``sent`` / ``acked``
/ ``seen`` with ``time.monotonic()``; the program logs, for one object
in eight chosen by its NAME (``kcp_tpu.obs.edge_kept``), a ``("req",
cluster, name, rx, t0, t_out)`` record once a write request and a
``("frame", cluster, name, tm, t_handed)`` record once a delivered watch
event (``kcp_tpu/obs/trace.py``: ``rx`` the start of the loop pass that
read the request's first byte, ``t0`` the handler's entry, ``t_out`` the
response handed to the transport, ``tm`` the event's commit, ``t_handed``
its frame handed to the stream's transport), on the same clock: on Linux
``time.monotonic()`` is one CLOCK_MONOTONIC for every process of the
machine. The readers run in the server's process, so ``edge_kept`` here
keeps the keys the log kept.

The join, over the TIMED operations of the window (creates and updates,
not ``aux``) whose key the log keeps and that were acknowledged:

- way in and ack: the ``req`` records of the operation's key whose ``rx``
  lies in [``sent``, ``acked``] — the first gives ``rx - sent``, the
  last ``acked - t_out`` (a 409 is retried once: two records);
- way out: the latest ``frame`` record of its key with ``sent <=
  t_handed <= seen``, for an operation that converged: ``seen -
  t_handed``.

The log holds the last 32,768 records: an operation sent before the
oldest record's stamp is beyond its horizon and is left out of both
counts (said in the printed line). A join gives None, and says why, when
fewer than :data:`MIN_OPS` operations were joined or under
:data:`MIN_FOUND` of the kept keys' operations found their record; and on
a program without the log (the parent of the PR that added it).

``ctx["edges"]`` / ``ctx["edge_kept"]`` stand in for the program's log
and rule where a test hands them over.

**In ``frontend-1k.steady``** the ``req`` records are the BACKEND's (the
run's own process; the frontend is a child with a log of its own that
nobody reads): ``rx - sent`` there is the frontend tier's whole way in
(its socket, its handler, its store-I/O pool and the backend's socket),
``acked - t_out`` its way back, and ``seen - t_handed`` the frontend's
relay and its way out — the intervals that had no stamp.
"""

from __future__ import annotations

from benchmarks import stats

MIN_OPS = 200
MIN_FOUND = 0.90


def _program_log(ctx: dict):
    """(records, kept-rule) of the program, or None where it has none."""
    if ctx.get("edges") is not None:
        return ctx["edges"], ctx["edge_kept"]
    from kcp_tpu import obs

    edges = getattr(obs, "edges", None)
    if edges is None:
        return None
    return edges(), obs.edge_kept


def joined(ctx: dict):
    """The joined operations of the window, computed once a run: a dict
    with ``ops`` (one dict an operation: the generator's ``due`` /
    ``sent`` / ``acked`` / ``seen`` and, where found, ``rx`` / ``t0`` /
    ``t_out`` / ``t_handed``), ``kept`` (operations of kept keys inside
    the log's horizon), ``beyond`` (those before it) — or None on a
    program without the log."""
    if "_edge_join" in ctx:
        return ctx["_edge_join"]
    got = _program_log(ctx)
    if got is None:
        ctx["_edge_join"] = None
        return None
    log, kept = got
    reqs: dict[tuple, list] = {}
    frames: dict[tuple, list] = {}
    for rec in log:
        (reqs if rec[0] == "req" else frames).setdefault(
            (rec[1], rec[2]), []).append(rec)
    horizon = log[0][-1] if log else float("inf")
    ops, beyond = [], 0
    for o in ctx["ops"]:
        if (o["kind"] == "delete" or o.get("aux") or o["acked"] is None
                or o["sent"] is None or not kept(o["key"][1])):
            continue
        if o["sent"] <= horizon:
            beyond += 1
            continue
        key = (o["key"][0], o["key"][1])
        row = {k: o[k] for k in ("due", "sent", "acked", "seen")}
        mine = [r for r in reqs.get(key, ())
                if o["sent"] <= r[3] <= o["acked"]]
        if mine:
            row["rx"], row["t0"] = mine[0][3], mine[0][4]
            row["t_out"] = mine[-1][5]
        if o["seen"] is not None:
            handed = [f[4] for f in frames.get(key, ())
                      if o["sent"] <= f[4] <= o["seen"]]
            if handed:
                row["t_handed"] = max(handed)
        ops.append(row)
    ctx["_edge_join"] = out = {"ops": ops, "beyond": beyond,
                               "records": len(log)}
    return out


def _enough(what: str, found: list, of: int, join: dict) -> bool:
    ok = len(found) >= MIN_OPS and len(found) >= MIN_FOUND * of
    print(f"[layer] edge join, {what}: {len(found)} of {of} operations of "
          f"kept keys found their record ({join['beyond']} more sent "
          f"before the log's oldest of {join['records']} records)"
          + ("" if ok else f": under {MIN_OPS} operations or under "
             f"{MIN_FOUND:.0%} of them, nothing read"), flush=True)
    return ok


def _found(ctx: dict, what: str, stamp: str):
    """The joined operations that carry ``stamp``, out of those that could
    (for a frame: the converged ones) — judged, and said, once a run."""
    join = joined(ctx)
    if join is None:
        return None
    if what not in join:
        could = [o for o in join["ops"]
                 if stamp == "rx" or o["seen"] is not None]
        found = [o for o in could if stamp in o]
        join[what] = found if _enough(what, found, len(could), join) else None
    return join[what]


def _way_in(ctx: dict):
    return _found(ctx, "req", "rx")


def _way_out(ctx: dict):
    return _found(ctx, "frame", "t_handed")


def _p50_ms(what: str, values: list[float]) -> float:
    ms = [v * 1e3 for v in values]
    print(f"[layer] {what}: p50 {stats.percentile(ms, 50):.4f} ms, mean "
          f"{sum(ms) / len(ms):.4f} ms, p95 {stats.percentile(ms, 95):.4f} "
          f"ms over {len(ms)} operations", flush=True)
    return stats.percentile(ms, 50)


def wire_in_p50_ms(ctx: dict):
    """Median of ``rx - sent``: the client's send, the kernel, and what
    the bytes waited for the server's loop to come back to ``select``."""
    found = _way_in(ctx)
    if found is None:
        return None
    return _p50_ms("wire in (rx - sent)", [o["rx"] - o["sent"] for o in found])


def ack_out_p50_ms(ctx: dict):
    """Median of ``acked - t_out``: the response from the server's
    transport to the client's own stamp."""
    found = _way_in(ctx)
    if found is None:
        return None
    return _p50_ms("ack out (acked - t_out)",
                   [o["acked"] - o["t_out"] for o in found])


def frame_out_p50_ms(ctx: dict):
    """Median of ``seen - t_handed``: a watch frame from the server's
    transport to the client's sight of the event."""
    found = _way_out(ctx)
    if found is None:
        return None
    return _p50_ms("frame out (seen - t_handed)",
                   [o["seen"] - o["t_handed"] for o in found])


def converge_edges_pct(ctx: dict):
    """Over the operations joined at BOTH ends: the four intervals no
    phase holds — the generator's own queue (``sent - due``), the way in
    (``rx - sent``), the ``ingress`` of that request (``t0 - rx``) and
    the way out (``seen - t_handed``) — per hundred of their mean
    ``seen - due``. Prints the four means, the eight phase means of the
    window beside them (every write's, not only the joined ones') and
    what all twelve leave of a hundred."""
    from benchmarks import phase_means

    join = joined(ctx)
    if join is None:
        return None
    seen = [o for o in join["ops"] if o["seen"] is not None]
    both = [o for o in seen if "rx" in o and "t_handed" in o]
    if not _enough("both ends", both, len(seen), join):
        return None
    n = len(both)
    total = sum(o["seen"] - o["due"] for o in both) / n
    if total <= 0:
        return None
    parts = {
        "sent - due": sum(o["sent"] - o["due"] for o in both) / n,
        "wire in (rx - sent)": sum(o["rx"] - o["sent"] for o in both) / n,
        "ingress (t0 - rx)": sum(o["t0"] - o["rx"] for o in both) / n,
        "frame out (seen - t_handed)":
            sum(o["seen"] - o["t_handed"] for o in both) / n,
    }
    edges = sum(parts.values())
    line = (f"[layer] converge edges: mean due->seen {total * 1e3:.4f} ms "
            f"over {n} operations joined at both ends; "
            + ", ".join(f"{k} {v * 1e3:.4f} ms" for k, v in parts.items())
            + f": {100 * edges / total:.2f}%")
    phases = {}
    for phase in phase_means.PHASES:
        got = phase_means._mean(ctx["registry"],
                                f"convergence_{phase}_seconds")
        if got is not None:
            phases[phase] = got[0]
    if len(phases) == len(phase_means.PHASES):
        inside = sum(phases.values())
        line += ("; the eight phase means of the window "
                 + ", ".join(f"{k} {v:.4f}" for k, v in phases.items())
                 + f" = {inside:.4f} ms: {100 * inside / (total * 1e3):.2f}%"
                 f"; left of a hundred "
                 f"{100 - 100 * (edges * 1e3 + inside) / (total * 1e3):.2f}%")
    print(line, flush=True)
    return 100.0 * edges / total
