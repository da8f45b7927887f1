"""The ``mapper`` topology: the default deployment (benchmarks/deploy.py)
brought up COLD. The server is up, every resident is in the store and
every location's controller runs against an empty ``fake://`` store —
and no ``Cluster`` object exists: no syncer, no row in the fused core, no
watch but the controllers' own. Nothing is registered, settled or warmed
in set-up; the traffic (generators/cold_sync.py) registers the locations
over REST inside the window, and the syncers' starts, their initial
lists and the fleet's growth are the timed path.

What differs from ``deploy.Deployment`` is ``bring_up`` and one key of
the generator's spec: ``clusters``, the ``Cluster`` body of every
(logical cluster, location) pair and the resource it is created under.
"""

from __future__ import annotations

import time

from benchmarks import deploy


class Deployment(deploy.Deployment):
    def bring_up(self, say=print) -> None:
        agent = self.agent_class()
        steps = (("server up", self.start),
                 (f"{len(self.population)} residents populated (no Cluster "
                  f"exists)", self.populate),
                 (f"agents started on empty locations ({agent.__module__}."
                  f"{agent.__qualname__})", self.start_agents))
        for what, step in steps:
            t = time.monotonic()
            step()
            say(f"set-up: {what} {time.monotonic() - t:.1f}s")

    def loadgen_spec(self, traffic: dict, seed: int, seconds: float) -> dict:
        from kcp_tpu.apis import cluster as capi

        return dict(
            super().loadgen_spec(traffic, seed, seconds),
            clusters={"resource": capi.CLUSTERS.storage_name,
                      "bodies": [[t, l, capi.new_cluster(l, self.fake(t, l))]
                                 for t in self.tenants
                                 for l in self.locations]})
