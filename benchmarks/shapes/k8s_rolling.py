"""The Deployments of ``k8s_deployment`` (perf-tests' load test: same
object, same ``mutate``, same population) under a location that ROLLS
them: ``benchmarks/rolling_agent.py RollingDeployment`` answers a
scale-and-update with four to seven status writes, 20 ms apart.

A write has converged by ``kubectl rollout status``'s rule
(``benchmarks/k8s_rolling_reference.py complete``): the tenant's object
shows the revision written and ``updatedReplicas == replicas ==
availableReplicas`` of the replica count written; an intermediate status
can show ``readyReplicas == spec.replicas`` by chance.

``observe`` sees every event of a key the load generator waits for
(``loadgen.Session._on_event``), so it also keeps that key's TRAIL: every
status delivered since the revision waited for first showed (the first of
them is the spec write's own event, which still carries the status of
before). ``evidence`` hands the trail over with the object that ended
the wait, and ``evidence_mismatches`` holds it to the reference's
sequence: a stale, torn or backward status is a
``converged_for_wrong_values`` mismatch.
"""

from __future__ import annotations

from benchmarks import k8s_rolling_reference as ref
from benchmarks.shapes import k8s_deployment as base
from benchmarks.shapes.k8s_deployment import (  # noqa: F401 — the shape's own
    CLUSTER_LABEL,
    NAMESPACE,
    PREFIX,
    RESOURCE,
    REVISION,
    SIZES,
    corrupt,
    inspect,
    mutate,
    new,
    teardown,
)

AGENT = "RollingDeployment"
AGENT_MODULE = "benchmarks.rolling_agent"

# (logical cluster, name) -> [revision, [status or None, ...]]: the
# statuses shown under that revision, consecutive repeats left out
_trails: dict[tuple[str, str], list] = {}


def want(body: dict) -> list:
    n = body["spec"]["replicas"]
    return [body["metadata"]["annotations"][REVISION], n, n, n]


def observe(obj: dict) -> list:
    m = obj["metadata"]
    rev = (m.get("annotations") or {}).get(REVISION)
    st = obj.get("status")
    key = (m.get("clusterName", ""), m["name"])
    trail = _trails.get(key)
    if trail is None or trail[0] != rev:
        trail = _trails[key] = [rev, []]
    if not trail[1] or trail[1][-1] != st:
        trail[1].append(st)
    st = st or {}
    return [rev, st.get("updatedReplicas"), st.get("replicas"),
            st.get("availableReplicas")]


def evidence(obj: dict) -> dict:
    m = obj["metadata"]
    trail = _trails.pop((m.get("clusterName", ""), m["name"]), None)
    return dict(base.evidence(obj), trail=trail[1] if trail else [])


def evidence_mismatches(body: dict, seen: dict, inspected, locations) -> list[str]:
    """The watched object that ended the wait, whole, under the final
    status its spec calls for; and every status shown on the way there,
    against the sequence the location writes from the replica count of
    before (the first delivery's own status)."""
    trail = seen.get("trail") or [None]
    before = (trail[0] or {}).get("replicas", 0)
    return (ref.object_mismatches(body, seen)
            + ref.trail_mismatches(before, body, trail))


def upstream_mismatches(tenant: str, bodies: dict[str, dict],
                        objs: list[dict], locations: list[str],
                        skip: set[str]) -> list[str]:
    return ref.store_mismatches(tenant, bodies, base._named(objs, skip))


def downstream_mismatches(tenant: str, bodies: dict[str, dict], location: str,
                          objs: list[dict], locations: list[str],
                          skip: set[str]) -> list[str]:
    want_here = {n: b for n, b in bodies.items()
                 if ref.location_of(b) == location}
    return ref.store_mismatches(f"{tenant}@{location}", want_here,
                                base._named(objs, skip), copy=True)
