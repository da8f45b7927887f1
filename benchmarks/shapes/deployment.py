"""Root Deployments that the server's DeploymentSplitter splits into one
leaf per location. A rollout has converged when the root upstream shows
``status.readyReplicas == spec.replicas``: split, downsync of every leaf,
the physical clusters' controllers, status upsync and aggregation, all
of it. The rule the leaves must obey is benchmarks/reference.py's."""

from __future__ import annotations

from benchmarks import reference

RESOURCE = "deployments.apps"
PREFIX = "app"
AGENT = "DeploymentReady"
NAMESPACE = "default"
CLUSTER_LABEL = "kcp.dev/cluster"
OWNED_BY_LABEL = "kcp.dev/owned-by"
REPLICAS = (8, 64)  # uniform, inclusive


def _body(name: str, replicas: int) -> dict:
    return {"apiVersion": "apps/v1", "kind": "Deployment",
            "metadata": {"name": name, "namespace": NAMESPACE},
            "spec": {"replicas": replicas,
                     "selector": {"matchLabels": {"app": name}},
                     "template": {"metadata": {"labels": {"app": name}},
                                  "spec": {"containers": [
                                      {"name": "main", "image": "registry.local/app:1"}]}}}}


def new(name: str, rng, locations: list[str]) -> dict:
    return _body(name, rng.randint(*REPLICAS))


def mutate(body: dict, rng) -> dict:
    """A re-scale. The splitter's default (``rebalance`` off) never
    re-splits, so an open loop over this shape would not converge: the
    mixes shipped for it only create and tear down."""
    return _body(body["metadata"]["name"], rng.randint(*REPLICAS))


def want(body: dict) -> int:
    return body["spec"]["replicas"]


def observe(obj: dict):
    if (obj["metadata"].get("labels") or {}).get(CLUSTER_LABEL):
        return None  # a leaf
    return (obj.get("status") or {}).get("readyReplicas")


def _counters(status: dict | None) -> dict:
    return {c: int((status or {}).get(c, 0) or 0) for c in reference.COUNTERS}


def evidence(obj: dict) -> dict:
    return {"replicas": obj["spec"]["replicas"],
            "status": _counters(obj.get("status"))}


def _leaves(client, root: str) -> list[dict]:
    from kcp_tpu.store.selectors import parse_selector

    items, _rv = client.list(RESOURCE, NAMESPACE,
                             selector=parse_selector(
                                 f"{OWNED_BY_LABEL}={root}"))
    return items


def inspect(client, body: dict, locations: list[str]):
    """What a deploy-and-wait client reads once its root is ready: the
    leaves (``kubectl get deploy -l kcp.dev/owned-by=<root>``)."""
    return [{"name": o["metadata"]["name"],
             "location": (o["metadata"].get("labels") or {}).get(CLUSTER_LABEL),
             "replicas": o["spec"]["replicas"],
             "status": _counters(o.get("status"))}
            for o in _leaves(client, body["metadata"]["name"])]


def evidence_mismatches(body: dict, seen: dict, inspected, locations) -> list[str]:
    root = body["metadata"]["name"]
    out = []
    rule = reference.split(body["spec"]["replicas"], locations)
    got = {l["location"]: l for l in inspected or []}
    if set(got) != set(rule):
        out.append(f"{root}: leaves on {sorted(got)}, rule says {sorted(rule)}")
    for loc, n in rule.items():
        leaf = got.get(loc)
        if leaf is None:
            continue
        if leaf["name"] != reference.leaf_name(root, loc):
            out.append(f"{root}: leaf {leaf['name']} misnamed")
        if leaf["replicas"] != n:
            out.append(f"{root}@{loc}: {leaf['replicas']} replicas, rule says {n}")
    if seen.get("replicas") != body["spec"]["replicas"]:
        out.append(f"{root}: watched spec.replicas {seen.get('replicas')}")
    # the status that ended the wait was read before the leaves; every
    # leaf is ready by then and nothing re-scales, so both must agree
    summed = reference.summed_status([l["status"] for l in got.values()])
    if seen.get("status") != summed:
        out.append(f"{root}: watched status {seen.get('status')} != sum of "
                   f"leaves {summed}")
    return out


def teardown(client, body: dict, locations: list[str]) -> list[str]:
    """Root first (the splitter re-creates the leaves of a root that has
    none), then its leaves by label; the program has no garbage
    collector, as the reference has none."""
    root = body["metadata"]["name"]
    client.delete(RESOURCE, root, NAMESPACE)
    names = [root]
    for leaf in _leaves(client, root):
        client.delete(RESOURCE, leaf["metadata"]["name"], NAMESPACE)
        names.append(leaf["metadata"]["name"])
    return names


def corrupt(obj: dict) -> dict | None:
    """The control's fault: the leaf of every root's first location (one
    in eight at full size) reaches its physical cluster with another
    image than the root names. Readiness does not depend on the image,
    so every rollout still converges."""
    if not obj["metadata"]["name"].endswith("--loc0") or "spec" not in obj:
        return None
    import copy

    bad = copy.deepcopy(obj)
    bad["spec"]["template"]["spec"]["containers"][0]["image"] = "corrupted"
    return bad


def _leaf_spec(body: dict, replicas: int) -> dict:
    """What a leaf's spec must be: its root's, with the leaf's replicas."""
    return dict(body["spec"], replicas=replicas)


def _named(objs: list[dict]) -> dict[str, dict]:
    return {o["metadata"]["name"]: o for o in objs
            if o["metadata"].get("namespace", "") == NAMESPACE}


def upstream_mismatches(tenant: str, bodies: dict[str, dict],
                        objs: list[dict], locations: list[str],
                        skip: set[str]) -> list[str]:
    have = {n: o for n, o in _named(objs).items() if n not in skip}
    want: dict[str, tuple[str, dict]] = {}
    for root, body in bodies.items():
        want[root] = ("root", body["spec"])
        for loc, n in reference.split(body["spec"]["replicas"],
                                      locations).items():
            want[reference.leaf_name(root, loc)] = (loc, _leaf_spec(body, n))
    out = [f"{tenant}/{n}: upstream but deleted or never written"
           for n in sorted(set(have) - set(want))]
    for name, (role, spec) in want.items():
        o = have.get(name)
        if o is None:
            out.append(f"{tenant}/{name}: expected upstream, not read back")
            continue
        if o["spec"] != spec:
            out.append(f"{tenant}/{name}: spec {o['spec']}, expected {spec}")
        labels = o["metadata"].get("labels") or {}
        if role != "root" and labels.get(CLUSTER_LABEL) != role:
            out.append(f"{tenant}/{name}: labelled for "
                       f"{labels.get(CLUSTER_LABEL)}, expected {role}")
    for root, body in bodies.items():
        o = have.get(root)
        leaves = [have.get(reference.leaf_name(root, loc)) for loc in locations]
        if o is None or any(l is None for l in leaves):
            continue
        summed = reference.summed_status([l.get("status") for l in leaves])
        if _counters(o.get("status")) != summed:
            out.append(f"{tenant}/{root}: status {_counters(o.get('status'))} "
                       f"!= sum of its leaves {summed}")
    return out


def downstream_mismatches(tenant: str, bodies: dict[str, dict], location: str,
                          objs: list[dict], locations: list[str],
                          skip: set[str]) -> list[str]:
    have = {n: o for n, o in _named(objs).items() if n not in skip}
    want = {reference.leaf_name(root, location): _leaf_spec(
                body, reference.split(body["spec"]["replicas"],
                                      locations)[location])
            for root, body in bodies.items()}
    out = [f"{tenant}@{location}/{n}: downstream but not expected"
           for n in sorted(set(have) - set(want))]
    for name, spec in want.items():
        o = have.get(name)
        if o is None:
            out.append(f"{tenant}@{location}/{name}: not downstream")
        elif o["spec"] != spec:
            out.append(f"{tenant}@{location}/{name}: downstream spec "
                       f"{o['spec']}, expected {spec}")
    return out
