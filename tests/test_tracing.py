"""Distributed tracing (kcp_tpu/obs/): propagation, assembly, phases,
wire neutrality — plus first-ever coverage for the ``/metrics`` and
``/debug/profile`` endpoints.

The two contracts under test:

- **wire neutrality** — KCP_TRACE on/off changes no response byte, no
  watch-stream byte, no stored object (the differential fuzz);
- **honest assembly** — a sampled write's spans connect client → router
  → shard → store commit across real process boundaries, and the
  convergence phase decomposition sum-reconciles with the end-to-end
  wall time by construction.
"""

import asyncio
import http.client
import json
import os
import re
import time
from urllib.parse import urlsplit

import pytest

from kcp_tpu import obs
from kcp_tpu.apis.scheme import default_scheme
from kcp_tpu.obs import assemble
from kcp_tpu.server.handler import RestHandler
from kcp_tpu.server.httpd import Request
from kcp_tpu.server.rest import RestClient
from kcp_tpu.server.server import Config
from kcp_tpu.server.threaded import ServerThread
from kcp_tpu.store.store import LogicalStore
from kcp_tpu.utils.trace import REGISTRY, Registry

@pytest.fixture
def trace_env(monkeypatch):
    """Reconfigure the process-global tracer from explicit env; the
    autouse fixture below restores the default configuration after."""

    def configure(**env):
        for k in ("KCP_TRACE", "KCP_TRACE_SAMPLE", "KCP_TRACE_SEED",
                  "KCP_TRACE_SLO_MS", "KCP_TRACE_BUFFER"):
            monkeypatch.delenv(k, raising=False)
        for k, v in env.items():
            monkeypatch.setenv(k, str(v))
        obs.TRACER.reconfigure()
        return obs.TRACER

    return configure


@pytest.fixture(autouse=True)
def _restore_tracer():
    yield
    # monkeypatch already popped the env; re-read the defaults (this
    # also empties the span buffer, isolating tests from each other)
    obs.TRACER.reconfigure()


def _cm(name: str, data: str = "x", ns: str = "default") -> dict:
    return {"apiVersion": "v1", "kind": "ConfigMap",
            "metadata": {"name": name, "namespace": ns, "uid": f"u-{name}"},
            "data": {"v": data}}


def _http_get(address: str, path: str) -> tuple[int, bytes]:
    parts = urlsplit(address)
    conn = http.client.HTTPConnection(parts.hostname, parts.port, timeout=30)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


# ---------------------------------------------------------------------------
# /metrics + /debug/profile endpoint coverage (previously untested)
# ---------------------------------------------------------------------------


_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+[-+0-9.einfa]+$")


def _parse_exposition(text: str) -> dict[str, dict]:
    """Strict-enough Prometheus text parse: every non-comment line must
    be a sample; HELP/TYPE comments must be well-formed."""
    metrics: dict[str, dict] = {}
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            parts = line.split(None, 3)
            assert len(parts) >= 3, line
            metrics.setdefault(parts[2], {"samples": []})
            continue
        assert not line.startswith("#"), f"unexpected comment: {line!r}"
        m = _SAMPLE_RE.match(line)
        assert m is not None, f"malformed sample line: {line!r}"
        name = m.group(1)
        family = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix):
                family = name[: -len(suffix)]
        metrics.setdefault(family, {"samples": []})["samples"].append(line)
    return metrics


def test_metrics_endpoint_serves_parseable_exposition():
    srv = ServerThread(Config(durable=False, tls=False,
                              install_controllers=False)).start()
    try:
        c = RestClient(srv.address)
        c.create("configmaps", dict(_cm("m0"),
                                    metadata={"name": "m0",
                                              "namespace": "default",
                                              "clusterName": "admin"}))
        c.close()
        status, body = _http_get(srv.address, "/metrics")
        assert status == 200
        metrics = _parse_exposition(body.decode())
        # the watch/store counters this fleet always registers
        assert "encode_cache_misses_total" in metrics
        # histogram families expose bucket+sum+count coherently
        hist = [name for name, m in metrics.items()
                if any("_bucket{" in s for s in m["samples"])]
        assert hist, "no histogram families exposed"
    finally:
        srv.stop()


def test_metrics_help_text_is_escaped():
    reg = Registry()
    reg.counter("weird_total", "line one\nline two \\ backslash")
    text = reg.expose()
    assert "# HELP weird_total line one\\nline two \\\\ backslash" in text
    # the exposition still parses line-by-line (no raw newline leaked)
    _parse_exposition(text)


def test_debug_profile_returns_stacks_and_tasks_while_serving():
    srv = ServerThread(Config(durable=False, tls=False,
                              install_controllers=False)).start()
    try:
        status, body = _http_get(srv.address, "/debug/profile?seconds=0.3")
        assert status == 200
        prof = json.loads(body)
        assert prof["samples"] > 0
        assert prof["stacks"], "profiler returned no stacks"
        assert any(frame for s in prof["stacks"] for frame in s["stack"])
        # the serving loop's own tasks are visible
        assert isinstance(prof["tasks"], list) and prof["tasks"]
        assert "spans" in prof
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# sampling + buffer mechanics
# ---------------------------------------------------------------------------


def test_sampling_deterministic_under_fixed_seed(trace_env):
    tracer = trace_env(KCP_TRACE="1", KCP_TRACE_SAMPLE="8",
                       KCP_TRACE_SEED="1234")
    first = [tracer.head_sampled() for _ in range(512)]
    ids_a = [tracer.mint(sampled=True).trace_id for _ in range(16)]
    tracer = trace_env(KCP_TRACE="1", KCP_TRACE_SAMPLE="8",
                       KCP_TRACE_SEED="1234")
    second = [tracer.head_sampled() for _ in range(512)]
    ids_b = [tracer.mint(sampled=True).trace_id for _ in range(16)]
    assert first == second
    assert ids_a == ids_b
    # ~1/8 of decisions sample (binomial slack)
    rate = sum(first) / len(first)
    assert 0.04 < rate < 0.30, rate


def test_debug_trace_queries_and_slo_force_record(trace_env):
    tracer = trace_env(KCP_TRACE="1", KCP_TRACE_SAMPLE="1000000000",
                       KCP_TRACE_SLO_MS="1")

    async def main():
        store = LogicalStore()
        handler = RestHandler(store, default_scheme(), admission=None)
        # an unsampled request that breaches the 1ms SLO force-records
        resp = await handler(Request(
            "GET", "/debug/profile", {"seconds": ["0.15"]}, {}, b""))
        assert resp.status == 200
        spans = [s for s in tracer.spans() if s["name"] == "server.request"]
        assert spans and spans[-1]["attrs"]["slo_breach"] is True
        # ?slowest= serves it back, ranked
        q = await handler(Request("GET", "/debug/trace",
                                  {"slowest": ["2"]}, {}, b""))
        doc = json.loads(q.body)
        assert doc["traces"] and doc["traces"][0]["spans"]
        durs = [t["dur"] for t in doc["traces"]]
        assert durs == sorted(durs, reverse=True)
        # ?id= returns exactly one trace's spans
        tid = doc["traces"][0]["id"]
        q = await handler(Request("GET", "/debug/trace",
                                  {"id": [tid]}, {}, b""))
        one = json.loads(q.body)
        assert one["spans"] and all(s["trace"] == tid
                                    for s in one["spans"])
        handler.close()
        store.close()

    asyncio.run(main())


def test_commit_stamp_rides_wal_event_and_link(trace_env):
    trace_env(KCP_TRACE="1", KCP_TRACE_SAMPLE="1")
    store = LogicalStore()
    shipped = []
    store.set_repl_hook(shipped.append)
    w = store.watch("configmaps")
    ctx = obs.TRACER.mint(sampled=True)
    with obs.use(ctx):
        store.create("configmaps", "t0", _cm("stamped"))
    store._flush_events()
    # WAL record carries tc under the same trace
    assert shipped and shipped[-1].get("tc")
    assert shipped[-1]["tc"][0] == ctx.trace_id
    # the shared Event carries the context out-of-band
    evs = w.drain()
    assert evs and evs[0].__dict__["_tc"].trace_id == ctx.trace_id
    # and the stored snapshot identity-links back to the commit
    snap = store.get_snapshot("configmaps", "t0", "stamped", "default")
    link = obs.obj_link(snap)
    assert link is not None and link.trace_id == ctx.trace_id
    # an UNSAMPLED write stamps nothing
    store.create("configmaps", "t0", _cm("plain"))
    assert "tc" not in shipped[-1]
    w.close()
    store.close()


# ---------------------------------------------------------------------------
# wire neutrality: the differential fuzz
# ---------------------------------------------------------------------------


def test_wire_bytes_identical_with_tracing_on(trace_env):
    """The same seeded CRUD+watch workload against two deterministic
    stores — tracing off vs always-on — must produce byte-identical
    responses and byte-identical watch event lines."""
    import random

    def run(env: dict) -> list[bytes]:
        trace_env(**env)

        async def main() -> list[bytes]:
            store = LogicalStore(indexed=True, clock=lambda: 1.7e9)
            handler = RestHandler(store, default_scheme(), admission=None)
            watch = store.watch("configmaps")
            rng = random.Random(99)
            out: list[bytes] = []
            live: list[str] = []
            for step in range(120):
                roll = rng.random()
                if live and roll < 0.15:
                    name = live.pop(rng.randrange(len(live)))
                    req = Request(
                        "DELETE",
                        f"/clusters/t0/api/v1/namespaces/default"
                        f"/configmaps/{name}", {}, {}, b"")
                elif live and roll < 0.4:
                    name = live[rng.randrange(len(live))]
                    req = Request(
                        "PUT",
                        f"/clusters/t0/api/v1/namespaces/default"
                        f"/configmaps/{name}",
                        {}, {"content-type": "application/json"},
                        json.dumps(_cm(name, f"s{step}")).encode())
                elif roll < 0.85:
                    name = f"cm-{len(live)}-{step}"
                    live.append(name)
                    req = Request(
                        "POST", "/clusters/t0/api/v1/namespaces/default"
                                "/configmaps",
                        {}, {"content-type": "application/json"},
                        json.dumps(_cm(name, str(step))).encode())
                else:
                    req = Request(
                        "GET", "/clusters/t0/api/v1/configmaps",
                        {}, {}, b"")
                resp = await handler(req)
                out.append(resp.body)
                store._flush_events()
                out.extend(store.encode_events(watch.drain()))
            watch.close()
            handler.close()
            store.close()
            return out

        return asyncio.run(main())

    plain = run({"KCP_TRACE": "0"})
    traced = run({"KCP_TRACE": "1", "KCP_TRACE_SAMPLE": "1",
                  "KCP_TRACE_SEED": "5"})
    assert plain == traced


# ---------------------------------------------------------------------------
# propagation + assembly
# ---------------------------------------------------------------------------


def test_traceparent_propagates_client_to_server(trace_env):
    trace_env(KCP_TRACE="1", KCP_TRACE_SAMPLE="1")
    srv = ServerThread(Config(durable=False, tls=False,
                              install_controllers=False)).start()
    try:
        ctx = obs.TRACER.mint(sampled=True)
        c = RestClient(srv.address, cluster="t0")
        with obs.use(ctx):
            c.create("configmaps", dict(
                _cm("prop"), metadata={"name": "prop",
                                       "namespace": "default",
                                       "clusterName": "t0"}))
        # the ServerThread shares this process's buffer: query over HTTP
        # anyway (the real endpoint surface)
        doc = c._request("GET", f"/debug/trace?id={ctx.trace_id}")
        c.close()
        names = {s["name"] for s in doc["spans"]}
        assert {"client.request", "server.request",
                "store.commit"} <= names, names
        by_id = {s["span"]: s for s in doc["spans"]}
        server = next(s for s in doc["spans"]
                      if s["name"] == "server.request")
        parent = by_id.get(server["parent"])
        assert parent is not None and parent["name"] == "client.request"
        commit = next(s for s in doc["spans"]
                      if s["name"] == "store.commit")
        assert by_id.get(commit["parent"])["name"] == "server.request"
    finally:
        srv.stop()


def test_cross_process_assembly_over_2_shard_router():
    """Two REAL shard subprocesses behind an in-process router: a traced
    write's spans live in different processes and only the router's
    /debug/trace scatter can assemble the tree."""
    from kcp_tpu.scenarios.topology import spawn_server

    os.environ["KCP_TRACE"] = "1"
    os.environ["KCP_TRACE_SAMPLE"] = "1"
    obs.TRACER.reconfigure()
    procs, addrs = [], []
    router = None
    try:
        for i in range(2):
            # ephemeral port + in-memory store: two shards must coexist
            # and leave no WAL behind for a later run to trip over
            p, addr = spawn_server(
                extra_args=["--listen-port", "0", "--in-memory"],
                env_overrides={
                    "KCP_TRACE": "1", "KCP_TRACE_SAMPLE": "1",
                    "KCP_TRACE_PROC": f"shard{i}"})
            procs.append(p)
            addrs.append(addr)
        spec = ",".join(f"s{i}={a}" for i, a in enumerate(addrs))
        router = ServerThread(Config(role="router", shards=spec,
                                     durable=False, tls=False)).start()
        ctx = obs.TRACER.mint(sampled=True)
        c = RestClient(router.address, cluster="t7")
        with obs.use(ctx):
            c.create("configmaps", dict(
                _cm("xp"), metadata={"name": "xp", "namespace": "default",
                                     "clusterName": "t7"}))
        doc = c._request("GET", f"/debug/trace?id={ctx.trace_id}")
        c.close()
        assert doc["partial"] == [], doc["partial"]
        spans = doc["spans"]
        procs_seen = {s["proc"] for s in spans}
        names = {s["name"] for s in spans}
        # spans from at least two processes assembled into one trace
        assert any(p.startswith("shard") for p in procs_seen), procs_seen
        assert any(not p.startswith("shard") for p in procs_seen)
        assert {"router.relay", "server.request",
                "store.commit"} <= names, names
        # the shard's server span parents onto the router's relay span
        by_id = {s["span"]: s for s in spans}
        server = next(s for s in spans if s["name"] == "server.request")
        assert by_id.get(server["parent"])["name"] == "router.relay"
    finally:
        for p in procs:
            p.kill()
        if router is not None:
            router.stop()
        for k in ("KCP_TRACE", "KCP_TRACE_SAMPLE"):
            os.environ.pop(k, None)
        obs.TRACER.reconfigure()


# ---------------------------------------------------------------------------
# convergence phase decomposition
# ---------------------------------------------------------------------------


def test_convergence_phases_sum_reconcile_in_process(trace_env):
    """Monolith spec→status round trip through a host-backend sync
    engine: all phases land under ONE trace id (the object-identity
    link), and the phase sum telescopes to the end-to-end wall time."""
    trace_env(KCP_TRACE="1", KCP_TRACE_SAMPLE="1")
    from kcp_tpu.client import Client
    from kcp_tpu.syncer.engine import CLUSTER_LABEL, BatchSyncEngine

    async def main():
        kcp = LogicalStore()
        phys = LogicalStore()
        up = Client(kcp, "tenant-1")
        down = Client(phys, "phys")
        engine = BatchSyncEngine(up, down, "configmaps", "loc-1",
                                 backend="host", batch_window=0.002,
                                 resync_period=None)
        await engine.start()
        try:
            ctx = obs.TRACER.mint(sampled=True)
            t0 = time.monotonic()  # phase stamps are monotonic
            obj = {"apiVersion": "v1", "kind": "ConfigMap",
                   "metadata": {"name": "phased", "namespace": "default",
                                "labels": {CLUSTER_LABEL: "loc-1"}},
                   "data": {"v": "0"}}
            with obs.use(ctx):
                created = up.create("configmaps", obj)
            rv = created["metadata"]["resourceVersion"]
            obs.phase("write", ctx, t0, time.monotonic(), rv=str(rv))
            deadline = time.time() + 10
            while time.time() < deadline:
                try:
                    dobj = down.get("configmaps", "phased", "default")
                    break
                except Exception:
                    await asyncio.sleep(0.01)
            else:
                raise AssertionError("never synced downstream")
            dobj["status"] = {"ok": True}
            down.update_status("configmaps", dobj)
            while time.time() < deadline:
                if (up.get("configmaps", "phased", "default")
                        .get("status") or {}).get("ok"):
                    break
                await asyncio.sleep(0.01)
            else:
                raise AssertionError("status never upsynced")
            obs.phase("e2e", ctx, t0, time.monotonic(), rv=str(rv))
            spans = obs.TRACER.get(ctx.trace_id)
            names = {s["name"] for s in spans}
            # the identity link keeps the engine's phases on THIS trace
            assert {"conv.write", "conv.stage", "conv.tick", "conv.patch",
                    "conv.downstream", "conv.upstatus",
                    "store.commit"} <= names, names
            prof = assemble.phase_profile(spans)
            assert prof["sum_ok"], prof
            for phase in ("write", "propagate", "stage", "tick", "patch",
                          "downstream", "upstatus", "observe"):
                assert phase in prof["phases"], (phase, prof)
            # the histogram family observed alongside the spans
            assert REGISTRY.histogram(
                "convergence_upstatus_seconds").n >= 1
        finally:
            await engine.stop()
        kcp.close()
        phys.close()

    asyncio.run(main())


# ---------------------------------------------------------------------------
# fleet metrics federation (router /metrics?fleet=1)
# ---------------------------------------------------------------------------


def test_fleet_metrics_federation_labels_and_partial():
    from kcp_tpu.scenarios.topology import shard_fleet

    with shard_fleet(2) as (router, shards, _ring):
        c = RestClient(shards[0].address, cluster="t1")
        c.create("configmaps", dict(
            _cm("fed"), metadata={"name": "fed", "namespace": "default",
                                  "clusterName": "t1"}))
        c.close()
        status, body = _http_get(router.address, "/metrics?fleet=1")
        assert status == 200
        text = body.decode()
        assert 'shard="s0"' in text and 'shard="s1"' in text
        assert 'shard="router"' in text
        # valid exposition: one TYPE per family, samples parse
        lines = [ln for ln in text.splitlines() if ln.strip()]
        type_lines = [ln for ln in lines if ln.startswith("# TYPE ")]
        assert len(type_lines) == len({ln.split()[2]
                                       for ln in type_lines})
        for ln in lines:
            if not ln.startswith("#"):
                assert _SAMPLE_RE.match(ln), ln
        # histogram label merge keeps existing labels
        assert re.search(r'_bucket\{le="[^"]+",shard="s0"\}', text)
        # partial scatter: stop one shard → annotated, never silent
        shards[1].stop()
        before = REGISTRY.counter("router_fleet_scrape_failed_total").value
        status, body = _http_get(router.address, "/metrics?fleet=1")
        assert status == 200
        text = body.decode()
        assert "# fleet: source s1 unreachable" in text
        assert 'shard="s0"' in text  # the live half still federates
        after = REGISTRY.counter("router_fleet_scrape_failed_total").value
        assert after > before
