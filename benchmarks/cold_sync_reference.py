"""The plain reference of a cold full sync: what the deployment promises
when physical clusters are registered against logical clusters that
already hold objects (sttts/kcp docs/cluster-mapper.md:21-24, "a full
sync"), stated independently of the program (imports nothing of kcp_tpu,
nor of the benchmark's shapes).

- a location's store holds, after the sync, exactly the objects of ITS
  logical cluster whose placement label (``kcp.dev/cluster``) names it —
  those that existed before the location was registered and those
  created since — each once, with the data its tenant wrote, and nothing
  of another logical cluster or another location;
- a location that was never registered holds nothing;
- the status an upstream object shows is the one its location's
  controller writes for the object's OWN data: ``{"observedGen":
  data.gen}`` (benchmarks/agents.py ``StatusEcho``'s rule, which is the
  rule a resident's ``seen`` is stamped by).
"""

from __future__ import annotations

CLUSTER_LABEL = "kcp.dev/cluster"


def placed_at(body: dict) -> str | None:
    return ((body.get("metadata") or {}).get("labels") or {}).get(CLUSTER_LABEL)


def status_for(body: dict) -> dict:
    """The status an upstream object must show once it is synced."""
    return {"observedGen": body["data"]["gen"]}


def expected_downstream(population: dict, registered: list,
                        live_acked: dict | None = None) -> dict:
    """{(tenant, location): {name: data}} that every REGISTERED location's
    store must hold, from the seeded ``population`` ({(tenant, name):
    body}), the ``registered`` (tenant, location) pairs and the
    acknowledged live creates (same form as the population). A pair
    that is not registered is absent: its store must be empty."""
    out: dict = {tuple(pair): {} for pair in registered}
    for source in (population, live_acked or {}):
        for (tenant, name), body in source.items():
            held = out.get((tenant, placed_at(body)))
            if held is not None:
                held[name] = body["data"]
    return out


def downstream_mismatches(expected: dict, stores: dict) -> list[str]:
    """``stores`` is {(tenant, location): [objects]} as read from the
    locations; every difference from ``expected``, object by object."""
    out = []
    for pair in sorted(set(expected) | set(stores)):
        want = expected.get(pair, {})
        have: dict = {}
        for o in stores.get(pair, []):
            name = o["metadata"]["name"]
            if name in have:
                out.append(f"{pair}: {name} twice")
            have[name] = o.get("data")
        for name in sorted(set(have) - set(want)):
            out.append(f"{pair}: {name} downstream, not placed there")
        for name, data in want.items():
            if name not in have:
                out.append(f"{pair}: {name} not downstream")
            elif have[name] != data:
                out.append(f"{pair}: {name} downstream {have[name]}, "
                           f"written {data}")
    return out


def upstream_mismatches(population: dict, registered: list,
                        objects: dict) -> list[str]:
    """``objects`` is {(tenant, name): object} as read upstream: every
    placed object of a registered pair shows the status for ITS data."""
    pairs = {tuple(p) for p in registered}
    out = []
    for (tenant, name), body in population.items():
        if (tenant, placed_at(body)) not in pairs:
            continue
        o = objects.get((tenant, name))
        if o is None:
            out.append(f"{tenant}/{name}: not upstream")
        elif o.get("data") != body["data"]:
            out.append(f"{tenant}/{name}: upstream data {o.get('data')}")
        elif o.get("status") != status_for(body):
            out.append(f"{tenant}/{name}: status {o.get('status')} for gen "
                       f"{body['data']['gen']}")
    return out
