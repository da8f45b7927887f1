"""Host profiling endpoint: the /debug/pprof analog (SURVEY §5).

The reference inherits /debug/pprof from its generic apiserver chain
(pkg/server/server.go:145); kcp-tpu serves /debug/profile — a sampling
wall profiler over all threads + asyncio task dump + span histograms —
next to /metrics.
"""

from __future__ import annotations

import asyncio
import json
import threading
import time

from kcp_tpu.apis.scheme import default_scheme
from kcp_tpu.server.authz import Authenticator, Authorizer
from kcp_tpu.server.handler import RestHandler
from kcp_tpu.server.httpd import Request
from kcp_tpu.store import LogicalStore
from kcp_tpu.utils.trace import REGISTRY, dump_tasks, sample_profile


def _req(method, path, headers=None, query=None):
    return Request(method=method, path=path, query=query or {},
                   headers=headers or {}, body=b"")


def _busy(stop: threading.Event) -> None:
    x = 0
    while not stop.is_set():
        for _ in range(1000):
            x = (x * 31 + 7) % 1000003
    return x


def test_sample_profile_catches_a_hot_thread():
    stop = threading.Event()
    t = threading.Thread(target=_busy, args=(stop,), name="hotspot",
                         daemon=True)
    t.start()
    try:
        async def main():
            REGISTRY.histogram("kcp_profile_test_seconds").observe(0.001)
            return await sample_profile(seconds=0.4)

        prof = asyncio.run(main())
    finally:
        stop.set()
        t.join()

    assert prof["samples"] > 5
    flat = json.dumps(prof["stacks"])
    assert "_busy" in flat, f"hot thread not sampled: {flat[:500]}"
    hot = [s for s in prof["stacks"] if s["thread"] == "hotspot"]
    assert hot and hot[0]["pct"] > 10
    assert "kcp_profile_test_seconds" in prof["spans"]


def test_dump_tasks_sees_waiting_coroutines():
    async def main():
        async def parked():
            await asyncio.sleep(30)

        t = asyncio.create_task(parked(), name="parked-task")
        await asyncio.sleep(0.01)
        tasks = dump_tasks()
        t.cancel()
        return tasks

    tasks = asyncio.run(main())
    names = [t["name"] for t in tasks]
    assert "parked-task" in names
    parked = next(t for t in tasks if t["name"] == "parked-task")
    assert any("parked" in f for f in parked["stack"])


def test_debug_profile_endpoint_and_gating():
    async def main():
        store = LogicalStore()
        # open mode: served to anyone
        handler = RestHandler(store, default_scheme())
        resp = await handler(_req("GET", "/debug/profile",
                                  query={"seconds": ["0.2"]}))
        assert resp.status == 200
        prof = json.loads(resp.body)
        assert prof["samples"] >= 1
        assert "stacks" in prof and "tasks" in prof and "spans" in prof

        # authz on: anonymous forbidden, admin allowed
        authn = Authenticator(tokens={"admin-tok": "admin"})
        handler = RestHandler(store, default_scheme(),
                              authenticator=authn, authorizer=Authorizer(store))
        resp = await handler(_req("GET", "/debug/profile"))
        assert resp.status == 403
        resp = await handler(_req("GET", "/debug/profile",
                                  headers={"authorization": "Bearer admin-tok"},
                                  query={"seconds": ["0.2"]}))
        assert resp.status == 200

    asyncio.run(main())


def test_debug_trace_endpoint():
    """/debug/trace captures an on-demand XLA device trace (the xprof
    half of the profiling surface); gated like /debug/profile."""
    import os
    import tempfile

    async def main():
        handler = RestHandler(LogicalStore(), default_scheme())
        with tempfile.TemporaryDirectory() as d:
            resp = await handler(_req("GET", "/debug/trace",
                                      query={"seconds": ["0.2"],
                                             "dir": [d]}))
            assert resp.status == 200
            out = json.loads(resp.body)
            assert out["dir"] == d
            if out["started"]:
                # the jax profiler wrote a trace dir
                assert os.listdir(d)

        # gated when authz is on
        authn = Authenticator(tokens={"admin-tok": "admin"})
        store = LogicalStore()
        handler = RestHandler(store, default_scheme(),
                              authenticator=authn, authorizer=Authorizer(store))
        resp = await handler(_req("GET", "/debug/trace"))
        assert resp.status == 403

    asyncio.run(main())
