"""The plain reference: the semantics the deployment promises, stated
independently of the program (imports nothing of kcp_tpu).

- the split rule of sttts/kcp pkg/reconciler/deployment/deployment.go:127-145:
  ``replicasEach := replicas / len(cls)``, ``rest := replicas % len(cls)``,
  the cluster at index 0 (clusters in name order) gets ``replicasEach +
  rest``; leaves are named ``<root>--<cluster>``;
- the root's status is the sum of its leaves' five replica counters
  (deployment.go:71-91);
- a store's final state is the initial population with every
  acknowledged operation applied in acknowledgement order: the last
  acknowledged write of an object wins, an acknowledged delete removes it.
"""

from __future__ import annotations

COUNTERS = ("replicas", "updatedReplicas", "readyReplicas",
            "availableReplicas", "unavailableReplicas")


def leaf_name(root: str, location: str) -> str:
    return f"{root}--{location}"


def split(replicas: int, locations: list[str]) -> dict[str, int]:
    """{location: leaf replicas} for a root of ``replicas``."""
    if not locations:
        return {}
    ordered = sorted(locations)
    each, rest = divmod(int(replicas), len(ordered))
    return {loc: each + (rest if i == 0 else 0)
            for i, loc in enumerate(ordered)}


def summed_status(leaf_statuses: list[dict]) -> dict[str, int]:
    """The five counters a root must show, from its leaves' statuses."""
    return {c: sum(int((s or {}).get(c, 0) or 0) for s in leaf_statuses)
            for c in COUNTERS}


def final_state(initial: dict, ops: list[dict]) -> tuple[dict, set]:
    """(expected objects by key, uncertain keys); a key that is in
    neither must be absent from every store.

    ``initial`` maps key -> body; each op is a record with ``key``,
    ``kind`` (create/update/delete), ``body`` (the body written) and
    ``acked`` (monotonic seconds, None when no acknowledgement came). An
    unacknowledged write leaves its object's state undetermined: such
    keys are excluded from the exact comparison, and counted.
    """
    state = dict(initial)
    uncertain: set = set()
    for op in sorted((o for o in ops if o.get("acked") is not None),
                     key=lambda o: o["acked"]):
        key = tuple(op["key"])
        if op["kind"] == "delete":
            state.pop(key, None)
        else:
            state[key] = op["body"]
    for op in ops:
        if op.get("acked") is None and op.get("sent") is not None:
            uncertain.add(tuple(op["key"]))
    for key in uncertain:
        state.pop(key, None)
    return state, uncertain
