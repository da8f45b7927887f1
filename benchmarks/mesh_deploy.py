"""The ``mesh`` topology: the default deployment (benchmarks/deploy.py)
served by ONE ``kcp start --mesh <spec>`` server. The configuration's
``mesh`` key (``"4x1"``: tenants 4, slots 1) goes into the server's
``Config``; the server installs the process's serving mesh itself
(kcp_tpu/server/server.py ``_install_controllers``), and the fused core
its engines share then shards the ``[B, S]`` fleet state by rows over
the chips, puts the event wire and the ack lane replicated on every one
of them each tick, and runs one SPMD step.

What differs from ``deploy.Deployment`` is that one key of the
``Config``, and ``fleet()``: it also says over how many devices the
resident state lies (``shards``) and how many rows each holds
(``shard_rows``), and it REFUSES — the run ends with no result — a state
that does not lie in equal row ranges on exactly as many devices as the
mesh has: a cell across chips that ran on one would be the one-chip
cell under another name.
"""

from __future__ import annotations

import os
import shutil

from benchmarks import deploy


def mesh_devices(spec: str) -> int:
    """Devices a mesh spec asks for: the product of its factors."""
    n = 1
    for part in spec.lower().replace("*", "x").split("x"):
        n *= int(part)
    return n


class Deployment(deploy.Deployment):
    def start(self) -> None:
        """``deploy.Deployment.start`` value for value, with the
        configuration's ``mesh`` in the server's ``Config``."""
        from kcp_tpu.physical import PhysicalRegistry
        from kcp_tpu.server.server import Config
        from kcp_tpu.server.threaded import ServerThread

        shutil.rmtree(self.root, ignore_errors=True)
        os.makedirs(self.root)
        self.registry = PhysicalRegistry()
        self.counters0 = deploy.registry_snapshot()
        cfg = Config(durable=True, root_dir=self.root, tls=False,
                     install_controllers=True, auto_publish_apis=True,
                     resources_to_sync=list(self.cfg["resources_to_sync"]),
                     syncer_mode="push", mesh=self.cfg["mesh"])
        self.srv = ServerThread(cfg, registry=self.registry).start(timeout=120)

    def fleet(self) -> dict:
        from kcp_tpu.syncer.core import FusedCore

        out = super().fleet()  # raises unless ONE core serves the loop
        (core,) = [c for c in FusedCore._instances.values()
                   if c._loop is self.srv._loop]
        want = mesh_devices(self.cfg["mesh"])
        shards = core._fleet._state.up_vals.addressable_shards
        devices = {sh.device.id for sh in shards}
        rows = sorted({int(sh.data.shape[0]) for sh in shards})
        if len(devices) != want or rows != [out["B"] // want]:
            raise RuntimeError(
                f"mesh {self.cfg['mesh']!r}: the fleet state of B={out['B']} "
                f"rows lies on {len(devices)} device(s) in shards of {rows} "
                f"rows, not on {want} in shards of {out['B'] // want}: no "
                f"result")
        return dict(out, shards=len(devices), shard_rows=rows[0])
