"""What one read request costs the serving loop: 1e3 x the rise of the
SELF seconds of the read path's sections (``kcp.read.get`` / ``list`` /
``page`` / ``table``, ``kcp.watch.open`` / ``close``:
``server_loop_self_seconds_*``, kcp_tpu/server/handler.py) over the rise
of the read requests (``read_requests_total_<verb>``). Each section is
printed over its own verb's requests beside it. What the sections do
not hold — the request's parse, the response's write
(``kcp.http.respond``, shared with the writes) — is not in the number.
A program without the sections (the parent of the PR that added them)
reads nothing."""

from benchmarks.layer_metrics import loop_busy_pct

VERBS = ("get", "list", "page", "table")
WATCH = ("kcp_watch_open", "kcp_watch_close")


def read(ctx):
    got = loop_busy_pct.ledger(ctx)
    reg = ctx["registry"]
    if got is None or not any(f"read_requests_total_{v}" in reg
                              for v in VERBS):
        return None
    requests = {v: reg.get(f"read_requests_total_{v}", 0.0) for v in VERBS}
    total = sum(requests.values())
    if total <= 0:
        return None
    self_s = {v: got["self"].get(f"kcp_read_{v}", 0.0) for v in VERBS}
    watch_s = {w: got["self"].get(w, 0.0) for w in WATCH}
    busy = got["busy_seconds"]
    spent = sum(self_s.values()) + sum(watch_s.values())
    print(f"[layer] loop ms per read: {spent:.4f} s of {busy:.4f} s busy "
          f"({100 * spent / busy:.1f}%) over {total:g} reads; by verb (self "
          f"ms a request, requests, share of busy): "
          + ", ".join(f"{v} {1e3 * self_s[v] / requests[v]:.4f} "
                      f"{requests[v]:g} {100 * self_s[v] / busy:.1f}%"
                      for v in VERBS if requests[v] > 0)
          + "; " + ", ".join(f"{w} {s:.4f} s {100 * s / busy:.1f}%"
                             for w, s in watch_s.items()), flush=True)
    return 1e3 * spent / total
