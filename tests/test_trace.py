"""Observability tests: metrics registry, spans, /metrics endpoint."""

from __future__ import annotations

import asyncio

from kcp_tpu.utils.trace import REGISTRY, Registry


class TestRegistry:
    def test_counter_gauge_histogram(self):
        r = Registry()
        r.counter("c", "help").inc()
        r.counter("c").inc(2)
        r.gauge("g").set(7.5)
        h = r.histogram("h")
        for v in (0.001, 0.002, 0.2):
            h.observe(v)
        snap = r.snapshot()
        assert snap["c"] == 3
        assert snap["g"] == 7.5
        assert snap["h"]["count"] == 3
        assert 0 < snap["h"]["p50"] <= 0.01
        assert snap["h"]["p99"] >= 0.2

    def test_exposition_format(self):
        r = Registry()
        r.counter("kcp_things_total", "things counted").inc(5)
        r.histogram("kcp_lat").observe(0.003)
        text = r.expose()
        assert "# TYPE kcp_things_total counter" in text
        assert "kcp_things_total 5.0" in text
        assert 'kcp_lat_bucket{le="+Inf"} 1' in text
        assert "kcp_lat_count 1" in text

    def test_hoisted_histogram_times_a_section(self):
        # the surviving idiom: fetch the histogram once, observe on it
        r = Registry()
        h = r.histogram("work_seconds")
        assert r.histogram("work_seconds") is h
        h.observe(0.002)
        snap = r.snapshot()
        assert snap["work_seconds"]["count"] == 1
        # the windowed count rides beside it as a plain number
        assert snap["work_seconds_count"] == 1


def test_metrics_endpoint_served():
    async def main():
        from kcp_tpu.server.handler import RestHandler
        from kcp_tpu.server.httpd import Request
        from kcp_tpu.apis.scheme import default_scheme
        from kcp_tpu.store import LogicalStore

        REGISTRY.counter("kcp_test_metric_total").inc()
        handler = RestHandler(LogicalStore(), default_scheme())
        resp = await handler(Request(method="GET", path="/metrics", query={},
                                     headers={}, body=b""))
        assert resp.status == 200
        assert b"kcp_test_metric_total" in resp.body

    asyncio.run(main())


def test_sync_engine_records_metrics():
    async def main():
        from kcp_tpu.client import Client
        from kcp_tpu.store import LogicalStore
        from kcp_tpu.syncer import start_syncer

        before = REGISTRY.counter("kcp_sync_ticks_total").value
        kcp, phys = LogicalStore(), LogicalStore()
        up, down = Client(kcp, "tenant"), Client(phys, "pcluster")
        syncer = await start_syncer(up, down, ["configmaps"], "east", backend="host")
        up.create("configmaps", {
            "apiVersion": "v1", "kind": "ConfigMap",
            "metadata": {"name": "m", "namespace": "default",
                         "labels": {"kcp.dev/cluster": "east"}},
            "data": {"k": "v"}})
        await asyncio.sleep(0.3)
        await syncer.stop()
        assert REGISTRY.counter("kcp_sync_ticks_total").value > before

    asyncio.run(main())
