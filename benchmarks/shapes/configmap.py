"""Labelled ConfigMaps of chip_smoke.phase_served's shape: two
16-hex-digit data keys and ``gen``. A write has converged when the
upstream object's ``status.observedGen`` (written downstream by the
physical cluster's controller, carried up by the syncer) equals the
``gen`` written."""

from __future__ import annotations

RESOURCE = "configmaps"
PREFIX = "cm"
AGENT = "StatusEcho"
NAMESPACE = "default"
CLUSTER_LABEL = "kcp.dev/cluster"


def new(name: str, rng, locations: list[str]) -> dict:
    loc = locations[rng.randrange(len(locations))]
    return {"apiVersion": "v1", "kind": "ConfigMap",
            "metadata": {"name": name, "namespace": NAMESPACE,
                         "labels": {CLUSTER_LABEL: loc}},
            "data": {"k0": f"{rng.getrandbits(64):016x}",
                     "k1": f"{rng.getrandbits(64):016x}", "gen": "0"}}


def mutate(body: dict, rng) -> dict:
    data = dict(body["data"], k0=f"{rng.getrandbits(64):016x}",
                gen=str(int(body["data"]["gen"]) + 1))
    return dict(body, data=data)


def want(body: dict) -> str:
    return body["data"]["gen"]


def observe(obj: dict):
    return (obj.get("status") or {}).get("observedGen")


def evidence(obj: dict) -> dict:
    return {"data": obj.get("data"), "status": obj.get("status")}


def inspect(client, body: dict, locations: list[str]):
    return None


def evidence_mismatches(body: dict, seen: dict, inspected, locations) -> list[str]:
    out = []
    if seen.get("data") != body["data"]:
        out.append(f"watched data {seen.get('data')} != written {body['data']}")
    if seen.get("status") != {"observedGen": body["data"]["gen"]}:
        out.append(f"watched status {seen.get('status')} for gen "
                   f"{body['data']['gen']}")
    return out


def teardown(client, body: dict, locations: list[str]) -> list[str]:
    """Delete what a create made; returns the names deleted."""
    client.delete(RESOURCE, body["metadata"]["name"], NAMESPACE)
    return [body["metadata"]["name"]]


def corrupt(obj: dict) -> dict | None:
    """The control's fault: the copy written downstream differs from the
    object upstream in one data value, for one object in eight (by the
    CRC of its name)."""
    import zlib

    if zlib.crc32(obj["metadata"]["name"].encode()) % 8 or "data" not in obj:
        return None
    return dict(obj, data=dict(obj["data"], k1="corrupted-downstream"))


def _named(objs: list[dict]) -> dict[str, dict]:
    return {o["metadata"]["name"]: o for o in objs
            if o["metadata"].get("namespace", "") == NAMESPACE}


def upstream_mismatches(tenant: str, bodies: dict[str, dict],
                        objs: list[dict], locations: list[str],
                        skip: set[str]) -> list[str]:
    have = {n: o for n, o in _named(objs).items() if n not in skip
            and CLUSTER_LABEL in (o["metadata"].get("labels") or {})}
    out = [f"{tenant}/{n}: upstream but deleted or never written"
           for n in sorted(set(have) - set(bodies))]
    for name, body in bodies.items():
        o = have.get(name)
        if o is None:
            out.append(f"{tenant}/{name}: acknowledged, not read back")
        elif o.get("data") != body["data"]:
            out.append(f"{tenant}/{name}: read back {o.get('data')}, "
                       f"acknowledged {body['data']}")
    return out


def downstream_mismatches(tenant: str, bodies: dict[str, dict], location: str,
                          objs: list[dict], locations: list[str],
                          skip: set[str]) -> list[str]:
    want = {n: b for n, b in bodies.items()
            if b["metadata"]["labels"][CLUSTER_LABEL] == location}
    have = {n: o for n, o in _named(objs).items() if n not in skip}
    out = [f"{tenant}@{location}/{n}: downstream but not upstream"
           for n in sorted(set(have) - set(want))]
    for name, body in want.items():
        o = have.get(name)
        if o is None:
            out.append(f"{tenant}@{location}/{name}: not downstream")
        elif o.get("data") != body["data"]:
            out.append(f"{tenant}@{location}/{name}: downstream "
                       f"{o.get('data')}, acknowledged {body['data']}")
    return out
