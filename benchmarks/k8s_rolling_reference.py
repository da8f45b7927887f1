"""The plain reference of the ``k8s-rolling`` deployment: the statuses a
location writes while it ROLLS one Deployment, which of them a tenant may
see and in what order, and what every store must hold at the end — stated
independently of the program and of the controller (pure Python; imports
nothing of kcp_tpu and nothing of ``benchmarks/rolling_agent.py``).

The rule is the upstream Deployment controller's, written from memory of
kubernetes/kubernetes ``pkg/controller/deployment``: ``rolling.go``
(``reconcileNewReplicaSet``, ``reconcileOldReplicaSets``),
``util/deployment_util.go`` (``NewRSNewReplicas``: the new ReplicaSet
grows to ``replicas + maxSurge`` less what exists; ``ResolveFenceposts``:
surge rounds up, unavailable rounds down, both 0 -> unavailable 1) and
``sync.go`` ``calculateStatus``. For one copy whose template and replica
count changed from ``R0`` to ``R`` under ``maxSurge`` = ``maxUnavailable``
= 25 %:

    s = ceil(0.25 R), u = floor(0.25 R), both 0 -> u = 1
    old = R0 (all ready), new = 0, ready = 0; repeat
    (a) new += max(0, min(R + s - (old + new), R - new))
    (b) old -= max(0, min(old, (old + new) - (R - u) - (new - ready)))
        (old pods go at once: terminationGracePeriodSeconds 1)
    (c) WRITE the status; stop when new == R == ready and old == 0
    (d) after ``pod_ready_ms`` the pods created so far are ready: ready = new

A create is ``R0 = 0``: two writes. Convergence is ``kubectl rollout
status``'s rule (``complete``), not ``readyReplicas == replicas``, which an
intermediate step can meet by chance (scaling 5 -> 3, the first status has
3 old pods ready and none updated).

Everything of an object but its status is ``k8s-load``'s to compare
(``benchmarks/k8s_load_reference.py``: whole spec, labels, annotations,
what a store must and must not hold): those comparisons run here with the
final status of this rule standing where ``DeploymentReady``'s stood.
"""

from __future__ import annotations

from benchmarks import k8s_load_reference as load
from benchmarks.k8s_load_reference import (  # noqa: F401 — part of this reference
    CLUSTER_LABEL,
    final_state,
    location_of,
)

def fenceposts(replicas: int) -> tuple[int, int]:
    """(maxSurge, maxUnavailable) in pods for 25 % / 25 %: surge rounds
    up, unavailable rounds down, and both 0 makes unavailable 1."""
    surge = -(-replicas // 4)
    unavailable = replicas // 4
    if surge == 0 and unavailable == 0:
        unavailable = 1
    return surge, unavailable


def status_of(replicas: int, old: int, new: int, ready: int, last: bool) -> dict:
    """``calculateStatus`` for ``old`` ready pods of the old template and
    ``new`` of the new one, ``ready`` of them ready, under a spec of
    ``replicas`` — every field but ``observedGeneration``."""
    _s, u = fenceposts(replicas)
    available = old + ready
    enough = available >= replicas - u
    return {
        "replicas": old + new, "updatedReplicas": new,
        "readyReplicas": available, "availableReplicas": available,
        "unavailableReplicas": max(0, replicas - available),
        "conditions": [
            {"type": "Available", "status": "True" if enough else "False",
             "reason": ("MinimumReplicasAvailable" if enough
                        else "MinimumReplicasUnavailable")},
            {"type": "Progressing", "status": "True",
             "reason": ("NewReplicaSetAvailable" if last
                        else "ReplicaSetUpdated")}]}


def rollout_statuses(old_replicas: int, replicas: int) -> list[dict]:
    """The statuses a location writes, in order, for one copy rolled from
    ``old_replicas`` pods (all ready) to ``replicas`` of a new template."""
    r = int(replicas)
    s, u = fenceposts(r)
    old, new, ready = int(old_replicas), 0, 0
    out: list[dict] = []
    while True:
        new += max(0, min(r + s - (old + new), r - new))
        old -= max(0, min(old, (old + new) - (r - u) - (new - ready)))
        last = new == r == ready and old == 0
        out.append(status_of(r, old, new, ready, last))
        if last:
            return out
        if len(out) > 4 * (r + old_replicas) + 8:
            raise AssertionError(f"no end to a rollout {old_replicas} -> {r}")
        ready = new


def final_status(replicas: int) -> dict:
    """The last member of every sequence to ``replicas``: what a store
    holds once the rollout is over."""
    r = int(replicas or 0)
    return status_of(r, 0, r, r, True)


def complete(spec: dict, status: dict | None) -> bool:
    """``kubectl rollout status``: the rollout of ``spec`` is over."""
    st, r = status or {}, (spec or {}).get("replicas")
    return (r is not None and st.get("updatedReplicas") == r
            and st.get("replicas") == r and st.get("availableReplicas") == r)


def _split(status: dict | None) -> tuple[dict, object]:
    """(the fields the rule determines, observedGeneration)."""
    got = dict(status or {})
    return got, got.pop("observedGeneration", None)


def _generation_mismatches(gen) -> list[str]:
    if not isinstance(gen, int) or isinstance(gen, bool) or gen < 1:
        return [f"status.observedGeneration {gen!r} is no generation"]
    return []


def _rule_mismatches(replicas: int, status: dict | None) -> list[str]:
    want = final_status(replicas)
    got, _gen = _split(status)
    if got == want:
        return []
    return [f"status {got} for {replicas} replicas, the rollout ends in {want}"]


def status_mismatches(replicas: int, status: dict | None) -> list[str]:
    """A store's status against the final one ``replicas`` calls for."""
    return (_rule_mismatches(replicas, status)
            + _generation_mismatches(_split(status)[1]))


def trail_mismatches(old_replicas: int, body: dict,
                     trail: list[dict | None]) -> list[str]:
    """Every status a watch delivered for the write of ``body``, in the
    order delivered: each is the status the object had before (none for a
    create, else the complete one of ``old_replicas``) or a member of the
    sequence the location writes; members never go backwards (skipping
    is allowed: the path is level-triggered), the generation the location
    saw never goes backwards, and the last is the final member."""
    r = body["spec"]["replicas"]
    seq = rollout_statuses(old_replicas, r)
    before = final_status(old_replicas) if old_replicas else None
    out: list[str] = []
    at, last_gen = -1, 0  # index of the newest member delivered so far
    for i, status in enumerate(trail):
        if status is None:
            if at >= 0 or before is not None:
                out.append(f"delivery {i}: no status after one was shown")
            continue
        got, gen = _split(status)
        if at < 0 and got == before:
            continue
        out += [f"delivery {i}: {m}" for m in _generation_mismatches(gen)]
        if isinstance(gen, int) and gen < last_gen:
            out.append(f"delivery {i}: observedGeneration {gen} after "
                       f"{last_gen}")
        last_gen = gen if isinstance(gen, int) else last_gen
        ahead = [j for j in range(max(at, 0), len(seq)) if seq[j] == got]
        if ahead:
            at = ahead[0]
        elif got in seq:
            out.append(f"delivery {i}: step {seq.index(got)} of the rollout "
                       f"{old_replicas} -> {r} shown after step {at}")
        else:
            out.append(f"delivery {i}: {got} is no status of the rollout "
                       f"{old_replicas} -> {r}")
    if at != len(seq) - 1:
        out.append(f"the last status delivered is step {at} of "
                   f"{len(seq) - 1}, not the final one")
    return out


def _with_ready_status(body: dict, obj: dict) -> dict:
    """``obj`` with the status ``k8s-load``'s comparison expects standing
    in for its own (which ``_rule_mismatches`` judges); the generation
    the location saw stays, for that comparison to judge."""
    gen = (obj.get("status") or {}).get("observedGeneration")
    return dict(obj, status=dict(load.ready_status(body["spec"]["replicas"]),
                                 observedGeneration=gen))


def object_mismatches(body: dict, obj: dict, copy: bool = False) -> list[str]:
    """``k8s_load_reference.object_mismatches`` (whole spec, labels,
    annotations, a copy's observed generation) under this rule's final
    status."""
    return (load.object_mismatches(body, _with_ready_status(body, obj), copy)
            + _rule_mismatches(body["spec"]["replicas"], obj.get("status")))


def store_mismatches(where: str, want: dict[str, dict],
                     have: dict[str, dict], copy: bool = False) -> list[str]:
    """``k8s_load_reference.store_mismatches`` (nothing missing, nothing
    more, each equal) under this rule's final status."""
    out, stood = [], {}
    for name, obj in have.items():
        body = want.get(name)
        stood[name] = obj if body is None else _with_ready_status(body, obj)
        if body is not None:
            out += [f"{where}/{name}: {m}" for m in _rule_mismatches(
                body["spec"]["replicas"], obj.get("status"))]
    return load.store_mismatches(where, want, stood, copy) + out
