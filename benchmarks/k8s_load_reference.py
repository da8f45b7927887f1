"""The plain reference of the ``k8s-load`` deployment: what every store
must hold, and the status every object must show, stated independently
of the program (pure Python; imports nothing of kcp_tpu).

The deployment: tenants write Deployments labelled for one location; the
syncer carries each whole object down (spec, labels, annotations), the
location's controller answers with a status that follows
``spec.replicas``, and that status comes back up. So, from the seeded
population and the acknowledged operations alone:

- upstream holds exactly the objects of ``reference.final_state`` (the
  last acknowledged write of an object wins, an acknowledged delete
  removes it), each with the spec, labels and annotations written;
- a location's store holds exactly those of them labelled for it, with
  EQUAL spec, labels and annotations — the whole of each, not its
  replicas;
- both show the controller's status for the spec they hold:
  ``benchmarks/agents.py DeploymentReady``'s rule, restated here — the
  five replica counters follow ``spec.replicas`` (none unavailable), one
  ``Available`` condition, and ``observedGeneration`` is the generation
  of the copy the controller looked at (a whole number from 1; which
  one depends on how many downstream writes the syncer needed, so only
  the location's store can say: there it equals the copy's own
  ``metadata.generation``).
"""

from __future__ import annotations

from benchmarks.reference import final_state  # noqa: F401 — part of this reference

CLUSTER_LABEL = "kcp.dev/cluster"


def ready_status(replicas: int) -> dict:
    """Every field of the status that ``spec.replicas`` determines."""
    n = int(replicas or 0)
    return {"replicas": n, "updatedReplicas": n, "readyReplicas": n,
            "availableReplicas": n, "unavailableReplicas": 0,
            "conditions": [{"type": "Available", "status": "True",
                            "reason": "MinimumReplicasAvailable"}]}


def status_mismatches(replicas: int, status: dict | None) -> list[str]:
    want = ready_status(replicas)
    got = dict(status or {})
    gen = got.pop("observedGeneration", None)
    out = []
    if got != want:
        out.append(f"status {got} for {replicas} replicas, rule says {want}")
    if not isinstance(gen, int) or isinstance(gen, bool) or gen < 1:
        out.append(f"status.observedGeneration {gen!r} is no generation")
    return out


def location_of(body: dict) -> str:
    return body["metadata"]["labels"][CLUSTER_LABEL]


def object_mismatches(body: dict, obj: dict, copy: bool = False) -> list[str]:
    """How ``obj``, as a store holds it, differs from the acknowledged
    ``body`` and from the status its spec calls for. Metadata the stores
    own (uid, resourceVersion, generation, clusterName, timestamps) is
    not compared; everything a tenant wrote is, whole. ``copy``: the
    object is a location's copy, whose generation the controller saw."""
    out = []
    meta = obj.get("metadata") or {}
    seen_gen = (obj.get("status") or {}).get("observedGeneration")
    if copy and seen_gen != meta.get("generation"):
        out.append(f"status.observedGeneration {seen_gen} is not the "
                   f"copy's generation {meta.get('generation')}")
    if obj.get("spec") != body["spec"]:
        diff = sorted(k for k in set(obj.get("spec") or {}) | set(body["spec"])
                      if (obj.get("spec") or {}).get(k) != body["spec"].get(k))
        out.append(f"spec differs from the acknowledged one in {diff}")
    for part in ("labels", "annotations"):
        got = meta.get(part) or {}
        if got != (body["metadata"].get(part) or {}):
            out.append(f"{part} {got}, acknowledged "
                       f"{body['metadata'].get(part)}")
    return out + status_mismatches(body["spec"]["replicas"], obj.get("status"))


def store_mismatches(where: str, want: dict[str, dict],
                     have: dict[str, dict], copy: bool = False) -> list[str]:
    """A store's objects against the reference's, object for object:
    nothing missing, nothing more, each equal."""
    out = [f"{where}/{n}: held but deleted or never written"
           for n in sorted(set(have) - set(want))]
    for name, body in want.items():
        obj = have.get(name)
        if obj is None:
            out.append(f"{where}/{name}: acknowledged, not held")
            continue
        out += [f"{where}/{name}: {m}" for m in object_mismatches(body, obj, copy)]
    return out
