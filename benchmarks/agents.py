"""The physical clusters' controllers, the benchmark's own.

A physical cluster is a fake:// location in the server's process; what
makes it behave like a cluster is a controller that reacts to the copies
the syncer writes. These are the benchmark's copies, so that the program
(``kcp_tpu/physical/fake.py`` included) may change without moving the
yardstick. Each agent is one informer per location with one callback; it
answers in the callback, with no delay and no polling.

- ``StatusEcho``: on every add or change of a labelled ConfigMap copy,
  writes ``status = {"observedGen": <data.gen>}`` downstream — the write
  chip_smoke.phase_served made by hand.
- ``DeploymentReady``: a copy of ``FakeClusterAgent`` with ``delay=0``:
  every Deployment is at once fully ready (the five counters follow
  ``spec.replicas``).
"""

from __future__ import annotations

import logging

from kcp_tpu.client import Informer
from kcp_tpu.utils import errors

log = logging.getLogger(__name__)


class _Agent:
    RESOURCE = ""

    def __init__(self, client):
        self.client = client
        self.errors = 0
        self.writes = 0
        self.informer = Informer(client, self.RESOURCE)
        self.informer.add_handler(self._on_event)

    def _on_event(self, etype: str, old: dict | None, new: dict | None) -> None:
        if etype == "DELETED" or new is None:
            return
        status = self.status_for(new)
        if status is None or self.up_to_date(new.get("status"), status):
            return
        m = new["metadata"]
        try:
            fresh = self.client.get(self.RESOURCE, m["name"],
                                    m.get("namespace", ""))
            want = self.status_for(fresh)
            if want is None or self.up_to_date(fresh.get("status"), want):
                return
            fresh["status"] = want
            self.client.update_status(self.RESOURCE, fresh,
                                      namespace=m.get("namespace", ""))
            self.writes += 1
        except errors.NotFoundError:
            pass  # deleted since the event
        except Exception:  # noqa: BLE001 — counted, reported by the run
            self.errors += 1
            log.exception("agent: status write of %s failed", m.get("name"))

    def status_for(self, obj: dict) -> dict | None:
        raise NotImplementedError

    @staticmethod
    def up_to_date(have: dict | None, want: dict) -> bool:
        return have == want

    async def start(self) -> None:
        await self.informer.start()

    async def stop(self) -> None:
        await self.informer.stop()


class StatusEcho(_Agent):
    RESOURCE = "configmaps"

    def status_for(self, obj: dict) -> dict | None:
        gen = (obj.get("data") or {}).get("gen")
        return None if gen is None else {"observedGen": gen}


class DeploymentReady(_Agent):
    RESOURCE = "deployments.apps"

    def status_for(self, obj: dict) -> dict | None:
        n = (obj.get("spec") or {}).get("replicas", 0) or 0
        return {"replicas": n, "updatedReplicas": n, "readyReplicas": n,
                "availableReplicas": n, "unavailableReplicas": 0,
                "observedGeneration": obj["metadata"].get("generation", 1),
                "conditions": [{"type": "Available", "status": "True",
                                "reason": "MinimumReplicasAvailable"}]}

    @staticmethod
    def up_to_date(have: dict | None, want: dict) -> bool:
        have = have or {}
        return (have.get("readyReplicas") == want["readyReplicas"]
                and have.get("replicas") == want["replicas"])
