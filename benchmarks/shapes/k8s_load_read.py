"""The Deployments of ``k8s_deployment`` (perf-tests' load test: same
object, same ``mutate``, same population, same controller) under tenants
that also READ them: this shape INSPECTS. A sampled write is probed by
the client that made it — a GET after the acknowledgement
(``inspect_acked``), a LIST of the tenant's namespace after the status it
waited for was seen (``inspect``) — and the answers ride on the write's
record as ``inspected``; ``evidence_mismatches`` holds them to
``benchmarks/k8s_load_read_reference.py probe_mismatches``
(``get_after_ack``, ``list_snapshot`` for the written key), so a stale or
torn read of one's own write is a ``converged_for_wrong_values``
mismatch. Everything else is ``k8s_deployment``'s.

An answer is kept as the reference's VIEWS ([cluster, namespace, name,
resourceVersion, digest of spec + labels + annotations]), never as
bodies: a record stays a few hundred bytes.
"""

from __future__ import annotations

import time

from benchmarks import k8s_load_read_reference as ref
from benchmarks.shapes import k8s_deployment as base
from benchmarks.shapes.k8s_deployment import (  # noqa: F401 — the shape's own
    AGENT,
    CLUSTER_LABEL,
    NAMESPACE,
    PREFIX,
    RESOURCE,
    REVISION,
    SIZES,
    corrupt,
    downstream_mismatches,
    evidence,
    mutate,
    new,
    observe,
    teardown,
    upstream_mismatches,
    want,
)


def _stamped(call) -> dict:
    """One probe: its instants, and the answer or the error."""
    out = {"sent": time.monotonic(), "status": 200, "error": None}
    try:
        out.update(call())
    except Exception as e:  # noqa: BLE001 — judged as a mismatch
        out["status"] = getattr(e, "code", 0)
        out["error"] = f"{type(e).__name__}: {e}"[:200]
    out["done"] = time.monotonic()
    return out


def inspect_acked(client, body: dict) -> dict:
    """What a tenant reads right after its write was acknowledged: the
    object, by name (``client`` is scoped to the tenant)."""
    name = body["metadata"]["name"]
    return _stamped(lambda: {"view": ref.view(
        client.get(RESOURCE, name, NAMESPACE))})


def inspect(client, body: dict, locations: list[str]) -> dict:
    """What a tenant reads once its write has converged: its namespace,
    listed in one response."""
    def call() -> dict:
        items, rv = client.list(RESOURCE, NAMESPACE, limit=0)
        return {"rv": rv, "items": [ref.view(o) for o in items]}
    return _stamped(call)


def evidence_mismatches(body: dict, seen: dict, inspected,
                        locations) -> list[str]:
    """``k8s_deployment``'s judgement of the watched object that ended
    the wait, and the reference's of the probes, where this write was
    probed."""
    out = base.evidence_mismatches(body, seen, inspected, locations)
    if inspected:
        out += ref.probe_mismatches(body, inspected)
    return out
