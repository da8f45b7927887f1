"""The comparison that decides ``correct``: answers only, after the
drain, identical in traced and untraced runs.

(a) every acknowledged create/update is read back over REST with the
    acknowledged values, every acknowledged delete is gone;
(b) every downstream store, read on the server's loop, holds exactly the
    objects the shape says, with equal values;
(c) every convergence the generator stamped was for the values it wrote
    (the watched object, and what a client read next, against the
    reference);
(d) no fused step failed, no row was quarantined, fused ticks ran in the
    window, and the fleet state lives on the platform of the run.

Lateness, a slow drain, compiles and anything else a profiler can slow
are not here: they are ``failed`` or metrics. All limits are exact
(0 mismatches); the values compared are the objects' own, never counts
alone.
"""

from __future__ import annotations

import time

from benchmarks import reference


class Check:
    def __init__(self, name: str, value, limit, ok: bool, examples=()):
        self.name, self.value, self.limit, self.ok = name, value, limit, ok
        self.examples = list(examples)[:3]

    def line(self) -> str:
        tail = "" if self.ok else f" FAILED e.g. {self.examples}"
        return (f"check {self.name}={self.value} limit {self.limit} "
                f"{'ok' if self.ok else ''}{tail}").rstrip()


def expected(dep, records: list[dict]):
    """(bodies by tenant, uncertain names by tenant) the stores must hold:
    the seeded population with every acknowledged operation applied."""
    state, uncertain = reference.final_state(dep.population, records)
    by_tenant: dict[str, dict[str, dict]] = {t: {} for t in dep.tenants}
    for (tenant, name), body in state.items():
        by_tenant[tenant][name] = body
    skip: dict[str, set[str]] = {t: set() for t in dep.tenants}
    for tenant, name in uncertain:
        skip[tenant].add(name)
    return by_tenant, skip, len(uncertain)


def read_upstream(dep) -> dict[str, list[dict]]:
    """Every object of the shape's resource, read back over REST."""
    from kcp_tpu.server.rest import RestClient

    wild = RestClient(dep.srv.address, cluster="*")
    try:
        items, _rv = wild.list(dep.shape.RESOURCE)
    finally:
        wild.close()
    out: dict[str, list[dict]] = {t: [] for t in dep.tenants}
    for o in items:
        out.setdefault(o["metadata"]["clusterName"], []).append(o)
    return out


def state_mismatches(dep, by_tenant, skip) -> tuple[list[str], list[str]]:
    shape = dep.shape
    up = read_upstream(dep)
    down = dep.downstream(dep.tenants)
    a, b = [], []
    for tenant in dep.tenants:
        a += shape.upstream_mismatches(tenant, by_tenant[tenant],
                                       up.get(tenant, []), dep.locations,
                                       skip[tenant])
        for loc in dep.locations:
            b += shape.downstream_mismatches(tenant, by_tenant[tenant], loc,
                                             down[tenant][loc], dep.locations,
                                             skip[tenant])
    for tenant in set(up) - set(dep.tenants):
        if up[tenant]:
            a.append(f"{tenant}: {len(up[tenant])} objects in an unknown "
                     f"logical cluster")
    return a, b


def drain(dep, by_tenant, skip, timeout: float = 60.0):
    """Wait for the state, not for a quiet interval: read everything until
    nothing differs, or ``timeout``; returns the last reading."""
    t0 = time.monotonic()
    while True:
        a, b = state_mismatches(dep, by_tenant, skip)
        waited = time.monotonic() - t0
        if not (a or b) or waited > timeout:
            return a, b, waited
        time.sleep(0.5)


def evidence_mismatches(dep, records: list[dict]) -> list[str]:
    out = []
    for r in records:
        if r.get("seen") is None or r["kind"] == "delete":
            continue
        for m in dep.shape.evidence_mismatches(
                r["body"], r.get("evidence") or {}, r.get("inspected"),
                dep.locations):
            out.append(f"{r['key'][0]}/{r['key'][1]}: {m}")
    return out


def judge(dep, records: list[dict], window_rise: dict, platform: str,
          counters_rise: dict, drain_s: float = 60.0):
    """(checks, fleet). ``drain_s`` is how long the state may take to
    settle (a control, which never settles, is given less)."""
    by_tenant, skip, n_uncertain = expected(dep, records)
    a, b, waited = drain(dep, by_tenant, skip, drain_s)
    print(f"drain: state read as expected after {waited:.1f}s"
          if not (a or b) else
          f"drain: still differing after {waited:.1f}s", flush=True)
    c = evidence_mismatches(dep, records)
    print(f"writes sent and never acknowledged (their objects are left out "
          f"of the comparison, and counted failed): {n_uncertain}", flush=True)
    fleet = dep.fleet()
    print(f"fleet state: {fleet['live']} live rows of B={fleet['B']} x "
          f"S={fleet['S']} on {fleet['on']}", flush=True)
    checks = [
        Check("rest_readback_mismatches", len(a), "<=0", not a, a),
        Check("downstream_mismatches", len(b), "<=0", not b, b),
        Check("converged_for_wrong_values", len(c), "<=0", not c, c),
        Check("agent_errors", dep.agent_errors(), "<=0",
              dep.agent_errors() == 0),
    ]
    for name in ("fused_step_failures_total", "quarantined_rows"):
        n = counters_rise.get(name, 0.0)
        checks.append(Check(f"{name}_rise", n, "<=0", n == 0))
    ticks = window_rise.get("fused_fleet_ticks_total", 0.0)
    checks.append(Check("fused_fleet_ticks_in_window", ticks, ">=1", ticks >= 1))
    off = [p for p in fleet["on"] if p != platform]
    checks.append(Check("fleet_state_off_platform", len(off), "<=0", not off,
                        fleet["on"]))
    return checks, fleet
