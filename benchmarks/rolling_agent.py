"""A physical cluster's Deployment controller that ROLLS: the benchmark's
own, beside ``agents.py`` (whose controllers answer once).

``RollingDeployment`` is one informer per location. On a copy whose
generation it has not finished it walks the rollout the upstream
Deployment controller would (kubernetes ``pkg/controller/deployment``
``rolling.go``, ``util/deployment_util.go``, ``sync.go``; the rule is
spelled out in ``benchmarks/k8s_rolling_reference.py``, which this module
does not import: the steps below are computed from the live copy by this
file's own code, and ``tests/test_k8s_rolling.py`` holds the two to each
other write for write). Each step scales the new ReplicaSet up, the old
one down, and writes the status (a ``get`` and an ``update_status``, as
every controller of ``agents.py``); then a timer of ``POD_READY_MS`` on
the serving loop makes the pods created so far ready and takes the next
step. No thread, no polling. A newer generation or a delete abandons the
walk.

``STAMPS`` holds, per (location, name, generation), the CLOCK_MONOTONIC
instants of the walk's first and last status write: the part of a
convergence that is the cluster's pods, not this system
(``layer_metrics/controller_span_ms.py`` reads it in the same process).
"""

from __future__ import annotations

import asyncio
import logging
import time

from kcp_tpu.client import Informer
from kcp_tpu.utils import errors

log = logging.getLogger(__name__)

POD_READY_MS = 20
CLUSTER_LABEL = "kcp.dev/cluster"

# (location, name, generation) -> [first status write, last status write]
STAMPS: dict[tuple[str, str, int], list[float]] = {}


class _Walk:
    """One rollout in progress: pods of the old and of the new template,
    how many of the new are ready, and the two fenceposts."""

    __slots__ = ("gen", "want", "surge", "slack", "old", "new", "ready",
                 "timer", "stamp")

    def __init__(self, gen: int, want: int, had: int, stamp: tuple):
        self.gen, self.want, self.stamp = gen, want, stamp
        self.surge = (want + 3) // 4     # maxSurge 25 %, rounded up
        self.slack = want // 4           # maxUnavailable 25 %, rounded down
        if not (self.surge or self.slack):
            self.slack = 1
        self.old, self.new, self.ready = had, 0, 0
        self.timer = None

    def scale(self) -> None:
        """reconcileNewReplicaSet, then reconcileOldReplicaSets."""
        room = self.want + self.surge - (self.old + self.new)
        self.new += max(0, min(room, self.want - self.new))
        floor = self.want - self.slack
        not_ready = self.new - self.ready
        spare = self.old + self.new - floor - not_ready
        self.old -= max(0, min(self.old, spare))

    def over(self) -> bool:
        return self.old == 0 and self.new == self.ready == self.want

    def status(self) -> dict:
        available = self.old + self.ready
        ok = available >= self.want - self.slack
        return {
            "replicas": self.old + self.new,
            "updatedReplicas": self.new,
            "readyReplicas": available,
            "availableReplicas": available,
            "unavailableReplicas": max(0, self.want - available),
            "observedGeneration": self.gen,
            "conditions": [
                {"type": "Available", "status": str(ok),
                 "reason": "MinimumReplicasAvailable" if ok
                 else "MinimumReplicasUnavailable"},
                {"type": "Progressing", "status": "True",
                 "reason": "NewReplicaSetAvailable" if self.over()
                 else "ReplicaSetUpdated"}]}


class RollingDeployment:
    RESOURCE = "deployments.apps"

    def __init__(self, client):
        self.client = client
        self.errors = 0
        self.writes = 0
        self.informer = Informer(client, self.RESOURCE)
        self.informer.add_handler(self._on_event)
        self._walks: dict[tuple[str, str], _Walk] = {}
        self._done: dict[tuple[str, str], int] = {}
        self._loop: asyncio.AbstractEventLoop | None = None

    def _on_event(self, etype: str, old: dict | None, new: dict | None) -> None:
        m = (new or old)["metadata"]
        key = (m.get("namespace", ""), m["name"])
        if etype == "DELETED" or new is None:
            self._abandon(key)
            self._done.pop(key, None)
            return
        gen = m.get("generation", 1)
        walk = self._walks.get(key)
        if self._done.get(key) == gen or (walk is not None and walk.gen == gen):
            return  # our own status writes come back as events too
        self._abandon(key)
        want = (new.get("spec") or {}).get("replicas", 0) or 0
        st = new.get("status") or {}
        if (st.get("observedGeneration") == gen and st.get("replicas") == want
                == st.get("updatedReplicas") == st.get("availableReplicas")):
            self._done[key] = gen  # rolled before this controller started
            return
        location = (m.get("labels") or {}).get(CLUSTER_LABEL, "")
        walk = self._walks[key] = _Walk(gen, want, st.get("replicas", 0) or 0,
                                        (location, m["name"], gen))
        self._step(key, walk)

    def _abandon(self, key) -> None:
        walk = self._walks.pop(key, None)
        if walk is not None and walk.timer is not None:
            walk.timer.cancel()

    def _step(self, key, walk: _Walk) -> None:
        """Scale, write the status; unless the rollout is over, the pods
        just created are ready ``POD_READY_MS`` from now."""
        if self._walks.get(key) is not walk:
            return
        walk.timer = None
        walk.scale()
        ns, name = key
        try:
            fresh = self.client.get(self.RESOURCE, name, ns)
            if fresh["metadata"].get("generation", 1) != walk.gen:
                self._abandon(key)  # the newer copy's event starts its own
                return
            fresh["status"] = walk.status()
            self.client.update_status(self.RESOURCE, fresh, namespace=ns)
        except errors.NotFoundError:
            self._abandon(key)  # deleted since the event
            return
        except Exception:  # noqa: BLE001 — counted, reported by the run
            self.errors += 1
            self._abandon(key)
            log.exception("rolling agent: status write of %s failed", name)
            return
        now = time.monotonic()
        self.writes += 1
        STAMPS.setdefault(walk.stamp, [now, now])[1] = now
        if walk.over():
            del self._walks[key]
            self._done[key] = walk.gen
        else:
            walk.timer = self._loop.call_later(POD_READY_MS / 1e3,
                                               self._pods_ready, key, walk)

    def _pods_ready(self, key, walk: _Walk) -> None:
        walk.ready = walk.new
        self._step(key, walk)

    async def start(self) -> None:
        self._loop = asyncio.get_running_loop()
        await self.informer.start()

    async def stop(self) -> None:
        for key in list(self._walks):
            self._abandon(key)
        await self.informer.stop()
