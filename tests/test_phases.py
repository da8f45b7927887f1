"""The always-on measurement of a convergence (ISSUE 29): one timeline per
write in counters, whatever the sampling coin says; the same boundaries
as annotations on the profiler's clock; the runtime's own probes."""

from __future__ import annotations

import asyncio
import gc
import glob
import json
import os
import subprocess
import sys
import tempfile
import threading
import time

import pytest

from kcp_tpu import obs
from kcp_tpu.apis.scheme import default_scheme
from kcp_tpu.client import Client, Informer
from kcp_tpu.store.store import LogicalStore
from kcp_tpu.utils.trace import REGISTRY, Registry, device_trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENGINE_PHASES = ("write", "propagate", "stage", "tick", "patch",
                 "downstream", "upstatus")
CLUSTER_LABEL = "kcp.dev/cluster"


def snap() -> dict:
    """Every histogram's (count, sum) and every counter's value."""
    out = {}
    for name, v in REGISTRY.snapshot().items():
        out[name] = (v["count"], v["count"] * v["mean"]) \
            if isinstance(v, dict) else v
    return out


def rise(a: dict, b: dict, name: str) -> tuple[float, float]:
    n0, s0 = a.get(name, (0, 0.0))
    n1, s1 = b.get(name, (0, 0.0))
    return n1 - n0, s1 - s0


def cm(name: str, gen: int, loc: str = "east") -> dict:
    return {"apiVersion": "v1", "kind": "ConfigMap",
            "metadata": {"name": name, "namespace": "default",
                         "labels": {CLUSTER_LABEL: loc}},
            "data": {"gen": str(gen)}}


def wait_for(pred, timeout: float = 20.0, what: str = "condition"):
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        got = pred()
        if got:
            return got
        time.sleep(0.01)
    raise AssertionError(f"timed out: {what}")


class StatusEcho:
    """The physical cluster's controller: answers a copy at once with
    ``status.observedGen`` (benchmarks/agents.py's, for this test)."""

    def __init__(self, client):
        self.client = client
        self.informer = Informer(client, "configmaps")
        self.informer.add_handler(self._on)

    def _on(self, etype, old, new):
        if etype == "DELETED" or new is None:
            return
        want = {"observedGen": (new.get("data") or {}).get("gen")}
        if new.get("status") == want:
            return
        fresh = self.client.get("configmaps", new["metadata"]["name"],
                                "default")
        fresh["status"] = want
        self.client.update_status("configmaps", fresh, namespace="default")


# ---------------------------------------------------------------------------
# the served path: ServerThread + a fake:// location + REST client + watch
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    from kcp_tpu.apis import cluster as capi
    from kcp_tpu.physical import PhysicalRegistry
    from kcp_tpu.server.rest import RestClient
    from kcp_tpu.server.server import Config
    from kcp_tpu.server.threaded import ServerThread

    root = tempfile.mkdtemp(prefix="kcp-phases-")
    registry = PhysicalRegistry()
    srv = ServerThread(Config(
        durable=True, root_dir=root, tls=False, install_controllers=True,
        auto_publish_apis=True, resources_to_sync=["configmaps"],
        syncer_mode="push"), registry=registry).start(timeout=120)
    mc = srv.server.client
    srv.call(lambda: mc.cluster_client("t1").create(
        capi.CLUSTERS, capi.new_cluster("east", "fake://t1-east")))

    def ready():
        items, _rv = mc.list(capi.CLUSTERS)
        return any(capi.is_ready(o) and "configmaps"
                   in capi.synced_resources(o) for o in items)

    wait_for(lambda: srv.call(ready), 60, "the location Ready")
    agent = StatusEcho(registry.resolve("fake://t1-east"))
    srv.submit(agent.informer.start())

    seen: dict[str, dict] = {}
    stop = threading.Event()
    up = threading.Event()

    def watch_main():
        async def run():
            wild = RestClient(srv.address, cluster="*")
            w = wild.watch("configmaps")
            w._ensure_started()
            while not w.responded and not w.closed:
                await asyncio.sleep(0.005)
            up.set()
            while not stop.is_set():
                for ev in await w.next_batch(max_wait=0.1):
                    if ev.type != "DELETED":
                        seen[ev.name] = ev.object
            w.close()
            wild.close()
        asyncio.run(run())

    t = threading.Thread(target=watch_main, daemon=True)
    t.start()
    assert up.wait(30)
    client = RestClient(srv.address, cluster="t1")
    try:
        yield {"srv": srv, "client": client, "seen": seen,
               "down": registry.resolve("fake://t1-east")}
    finally:
        stop.set()
        t.join(timeout=5)
        client.close()
        srv.submit(agent.informer.stop())
        srv.stop()


def _converged(served, name: str, gen: int) -> bool:
    obj = served["seen"].get(name)
    return bool(obj) and (obj.get("status") or {}).get("observedGen") == str(gen)


@pytest.mark.parametrize("verb", ["create", "update", "delete"])
def test_phase_sums_telescope_to_the_engines_own_total(served, verb):
    """A scripted write over REST: every phase the write passes is
    observed once, and the phase sums add up to the engine's own
    start->end (kcp_sync_convergence_seconds) within 1 ms — adjacent
    phases share their boundary stamp."""
    client, name = served["client"], f"tele-{verb}"
    if verb != "create":
        client.create("configmaps", cm(name, 0))
        wait_for(lambda: _converged(served, name, 0), what="warm create")
        time.sleep(0.1)  # its status echo retires the finished entry
    before = snap()
    t_send = time.monotonic()
    if verb == "create":
        client.create("configmaps", cm(name, 1))
    elif verb == "update":
        body = client.get("configmaps", name, "default")
        body["data"] = {"gen": "1"}
        t_send = time.monotonic()
        client.update("configmaps", body)
    else:
        client.delete("configmaps", name, "default")
    if verb == "delete":
        def gone():
            items, _rv = served["srv"].call(
                lambda: served["down"].list("configmaps"))
            return not any(o["metadata"]["name"] == name for o in items)
        wait_for(gone, what="downstream delete")
        passed = ENGINE_PHASES[:-1]  # a delete has no status to upsync
    else:
        wait_for(lambda: _converged(served, name, 1), what="status seen")
        passed = ENGINE_PHASES
    t_seen = time.monotonic()
    wait_for(lambda: rise(before, snap(),
                          "kcp_sync_convergence_seconds")[0] >= 1,
             what="the entry closed")
    after = snap()
    total_n, total_s = rise(before, after, "kcp_sync_convergence_seconds")
    assert total_n == 1
    phase_sum = 0.0
    for p in passed:
        n, s = rise(before, after, f"convergence_{p}_seconds")
        assert n == 1, (p, n)
        assert s >= 0.0
        phase_sum += s
    assert abs(phase_sum - total_s) < 1e-3, (phase_sum, total_s)
    # observe: at least the write's own event reached the HTTP watch
    n_obs, s_obs = rise(before, after, "convergence_observe_seconds")
    assert n_obs >= 1
    # and what the program accounts for fits inside what the client saw
    assert total_s <= (t_seen - t_send) + 1e-3
    # the handler timed the request itself, every request
    for part in ("admission", "commit", "finish"):
        assert rise(before, after, f"request_{part}_seconds")[0] >= 1


def test_served_path_leaves_no_entry_behind(served):
    """After a full round trip the engine's own status echo retires the
    finished entry: the per-key table does not grow with traffic."""
    from kcp_tpu.syncer.engine import BatchSyncEngine

    client = served["client"]
    for i in range(5):
        client.create("configmaps", cm(f"clean-{i}", 7))
    for i in range(5):
        wait_for(lambda: _converged(served, f"clean-{i}", 7))

    def entries():
        import gc as _gc
        return sum(len(e._dirty) for e in _gc.get_objects()
                   if isinstance(e, BatchSyncEngine))

    wait_for(lambda: served["srv"].call(entries) == 0, 5, "entries retired")


def test_loop_lag_and_gc_probes_run_with_the_server(served):
    before = snap()
    time.sleep(0.3)
    served["srv"].call(gc.collect)
    after = snap()
    assert rise(before, after, "server_loop_lag_seconds")[0] >= 3
    assert rise(before, after, "py_gc_pause_seconds")[0] >= 1


def test_debug_trace_reports_a_profiler_that_cannot_start(served, tmp_path):
    from kcp_tpu.server.rest import RestClient

    rc = RestClient(served["srv"].address)
    try:
        with device_trace(str(tmp_path / "outer")):
            with pytest.raises(Exception) as ei:
                rc._request("GET", f"/debug/trace?seconds=0.05&dir="
                            f"{tmp_path / 'inner'}")
            assert "already" in str(ei.value).lower() or "409" in str(ei.value)
    finally:
        rc.close()


# ---------------------------------------------------------------------------
# phases do not depend on the sampling coin; conv.* spans do
# ---------------------------------------------------------------------------


@pytest.fixture
def trace_env():
    saved = {k: os.environ.get(k) for k in
             ("KCP_TRACE", "KCP_TRACE_SAMPLE", "KCP_TRACE_SEED")}

    def set_mode(**env):
        for k in saved:
            os.environ.pop(k, None)
        os.environ.update(env)
        obs.TRACER.reconfigure()

    yield set_mode
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    obs.TRACER.reconfigure()


@pytest.mark.parametrize("mode,env,spans", [
    ("off", {"KCP_TRACE": "0"}, "none"),
    ("sampled-1-in-64", {"KCP_TRACE": "1", "KCP_TRACE_SAMPLE": "64",
                         "KCP_TRACE_SEED": "7"}, "few"),
    ("always", {"KCP_TRACE": "1", "KCP_TRACE_SAMPLE": "1"}, "all"),
])
def test_every_write_is_observed_whatever_the_sampling(trace_env, mode, env,
                                                       spans):
    from kcp_tpu.server.handler import RestHandler
    from kcp_tpu.server.httpd import Request
    from kcp_tpu.syncer.engine import BatchSyncEngine

    trace_env(**env)
    n = 40

    async def main():
        kcp, phys = LogicalStore(), LogicalStore()
        handler = RestHandler(kcp, default_scheme(), admission=None)
        engine = BatchSyncEngine(Client(kcp, "t1"), Client(phys, "p"),
                                 "configmaps", "east", backend="host",
                                 batch_window=0.001, resync_period=None)
        agent = StatusEcho(Client(phys, "p"))
        await engine.start()
        await agent.informer.start()
        before = snap()
        spans0 = len([s for s in obs.TRACER.spans()
                      if s["name"] == "conv.stage"])
        try:
            for i in range(n):
                resp = await handler(Request(
                    "POST", "/clusters/t1/api/v1/namespaces/default/configmaps",
                    {}, {"content-type": "application/json"},
                    json.dumps(cm(f"s-{i}", 1)).encode()))
                assert resp.status == 201, resp.body
            for _ in range(500):
                if rise(before, snap(),
                        "convergence_upstatus_seconds")[0] >= n:
                    break
                await asyncio.sleep(0.01)
        finally:
            await agent.informer.stop()
            await engine.stop()
            handler.close()
            kcp.close()
            phys.close()
        after = snap()
        for p in ENGINE_PHASES:
            assert rise(before, after, f"convergence_{p}_seconds")[0] == n, p
        assert rise(before, after, "kcp_sync_convergence_seconds")[0] == n
        assert rise(before, after, "request_commit_seconds")[0] == n
        got = len([s for s in obs.TRACER.spans()
                   if s["name"] == "conv.stage"]) - spans0
        if spans == "none":
            assert got == 0
        elif spans == "all":
            assert got == n
        else:
            assert got < n // 2, got

    asyncio.run(main())


def test_a_resync_replay_opens_no_entry():
    from kcp_tpu.syncer.engine import BatchSyncEngine

    async def main():
        kcp, phys = LogicalStore(), LogicalStore()
        up = Client(kcp, "t1")
        up.create("configmaps", cm("r", 1))
        engine = BatchSyncEngine(up, Client(phys, "p"), "configmaps", "east",
                                 backend="host", batch_window=0.001,
                                 resync_period=None)
        await engine.start()
        await asyncio.sleep(0.1)
        before = snap()
        engine.up_informer.resync()
        await asyncio.sleep(0.05)
        assert rise(before, snap(), "convergence_stage_seconds")[0] == 0
        await engine.stop()
        kcp.close()
        phys.close()

    asyncio.run(main())


# ---------------------------------------------------------------------------
# stamps on the shared Event, the informer's view of them
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("indexed", [True, False])
def test_event_carries_write_and_commit_stamps(indexed):
    async def main():
        store = LogicalStore(indexed=indexed)
        inf = Informer(Client(store, "t1"), "configmaps")
        got = []
        inf.add_handler(lambda e, o, n: got.append((e, inf.event_stamps)))
        await inf.start()
        t0 = time.monotonic()
        store.write_t0 = t0 - 1.0  # what the serving handler hands over
        store.create("configmaps", "t1", cm("a", 1))
        assert store.write_t0 is None  # consumed by that one write
        store.update("configmaps", "t1", cm("a", 2))
        for _ in range(100):
            if len(got) >= 2:
                break
            await asyncio.sleep(0.005)
        (e1, (tw1, tm1)), (e2, (tw2, tm2)) = got[:2]
        assert (e1, e2) == ("ADDED", "MODIFIED")
        assert tw1 == t0 - 1.0 and tm1 >= t0
        assert t0 <= tw2 <= tm2 == store.last_commit  # store entry, then commit
        assert inf.event_stamps is None  # only while a dispatch runs
        await inf.stop()
        store.close()

    asyncio.run(main())


def test_label_transition_keeps_the_commits_stamps():
    from kcp_tpu.store.selectors import parse_selector

    store = LogicalStore()
    w = store.watch("configmaps", "t1", None, parse_selector("tier=gold"))
    obj = cm("a", 1)
    store.create("configmaps", "t1", obj)
    obj["metadata"]["labels"]["tier"] = "gold"
    store.update("configmaps", "t1", obj)
    (ev,) = w.drain()
    assert ev.type == "ADDED"  # rewritten for the selector ...
    assert ev.__dict__["_tm"] == store.last_commit  # ... the same commit
    assert "_tw" in ev.__dict__
    store.close()


# ---------------------------------------------------------------------------
# registry, annotations, device_trace
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [0, 3])
def test_snapshot_carries_the_windowed_count(n):
    r = Registry()
    h = r.histogram("phase_seconds")
    for _ in range(n):
        h.observe(0.01)
    s = r.snapshot()
    assert s["phase_seconds_count"] == n
    assert s["phase_seconds"]["count"] == n
    assert abs(s["phase_seconds"]["mean"] * n - 0.01 * n) < 1e-9


def test_a_served_write_names_its_sections_and_leaks_none(served):
    """The serving loop's ledger under the served path (PR 42): a write
    over REST and its convergence pass every layer's section, the
    ledger's beat publishes them, and no section is found open across
    an ``await`` (``server_loop_section_leaks_total`` does not rise)."""
    client, name = served["client"], "ledger-write"
    before = snap()
    for gen in (0, 1):
        if gen == 0:
            client.create("configmaps", cm(name, gen))
        else:
            body = client.get("configmaps", name, "default")
            body["data"] = {"gen": str(gen)}
            client.update("configmaps", body)
        wait_for(lambda: _converged(served, name, gen), what="status seen")
    named = ("kcp_http_respond", "kcp_store_commit", "kcp_wal_sync",
             "kcp_store_fanout", "kcp_store_sinks", "kcp_queue_drain",
             "kcp_tick_encode", "kcp_apply", "kcp_watch_push",
             "kcp_watch_encode")

    def published():
        after = snap()
        return all(after.get(f"server_loop_self_seconds_{n}", 0.0)
                   > before.get(f"server_loop_self_seconds_{n}", 0.0)
                   for n in named)

    wait_for(published, 5, "a beat published every section's rise")
    after = snap()
    busy = after["server_loop_busy_seconds_total"] - before.get(
        "server_loop_busy_seconds_total", 0.0)
    selfs = sum(v - before.get(k, 0.0) for k, v in after.items()
                if k.startswith("server_loop_self_seconds_"))
    assert 0 < selfs <= busy
    assert after["server_loop_passes_total"] > before.get(
        "server_loop_passes_total", 0.0)
    assert after["server_loop_section_leaks_total"] == before.get(
        "server_loop_section_leaks_total", 0.0)


def test_phase_uses_hoisted_histograms_and_monotonic_stamps(trace_env):
    trace_env(KCP_TRACE="1", KCP_TRACE_SAMPLE="1")
    h = REGISTRY.histogram("convergence_stage_seconds")
    n0, ctx = h.n, obs.TRACER.mint(sampled=True)
    t1 = time.monotonic()
    obs.phase("stage", ctx, t1 - 0.25, t1, rv="1")
    assert h.n == n0 + 1
    (span,) = [s for s in obs.TRACER.get(ctx.trace_id)]
    assert span["name"] == "conv.stage" and abs(span["dur"] - 0.25) < 1e-6
    # the span's start is wall-clock, though the stamps were monotonic
    assert abs(span["t0"] - (time.time() - 0.25)) < 0.5


def test_annotate_is_the_noop_singleton_without_jax():
    code = ("import sys\n"
            "from kcp_tpu import obs\n"
            "a = obs.annotate('kcp.tick', tick=1, mono=0.5)\n"
            "assert 'jax' not in sys.modules, 'obs imported jax'\n"
            "assert a is obs.annotate('kcp.gc')\n"
            "with a:\n    pass\n"
            "print('noop')\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "noop"


def _host_events(trace_dir: str) -> dict[str, list]:
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    found: dict[str, list] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("kcp."):
                        found.setdefault(ev.name, []).append(ev)
    return found


def test_a_profiler_session_holds_the_ticks_annotations(tmp_path):
    """A fused tick under an open session: kcp.tick, its phases inside
    it, and the applier's kcp.apply — on the host plane, where the
    benchmark's reader looks."""
    from kcp_tpu.syncer import start_syncer

    async def main():
        kcp, phys = LogicalStore(), LogicalStore()
        up, down = Client(kcp, "t1"), Client(phys, "p")
        syncer = await start_syncer(up, down, ["configmaps"], "east",
                                    backend="tpu")
        up.create("configmaps", cm("warm", 1))  # compile outside the session
        for _ in range(400):
            if phys.resource_version:
                break
            await asyncio.sleep(0.01)
        t_open = time.monotonic()
        with device_trace(str(tmp_path)):
            up.create("configmaps", cm("traced", 1))
            rv0 = phys.resource_version
            for _ in range(400):
                if phys.resource_version > rv0:
                    break
                await asyncio.sleep(0.01)
            await asyncio.sleep(0.05)
        await syncer.stop()
        kcp.close()
        phys.close()
        return t_open

    t_open = asyncio.run(main())
    found = _host_events(str(tmp_path))
    assert "kcp.tick" in found, sorted(found)
    for phase in ("encode", "pack", "put", "step_dispatch", "collect_wait",
                  "dispatch"):
        assert f"kcp.tick.{phase}" in found, (phase, sorted(found))
    assert "kcp.apply" in found and "kcp.store.fanout" in found
    # no stat rides a tick: nothing is formatted per section (PR 42)
    assert not any(dict(ev.stats) for ev in found["kcp.tick"])
    # a phase lies inside a tick, on one clock
    tick_iv = [(e.start_ns, e.start_ns + e.duration_ns)
               for e in found["kcp.tick"]]
    pack = found["kcp.tick.pack"][0]
    assert any(a <= pack.start_ns and pack.start_ns + pack.duration_ns <= b
               for a, b in tick_iv)


def test_device_trace_raises_when_a_session_is_open(tmp_path):
    with device_trace(str(tmp_path / "a")):
        with pytest.raises(Exception, match="(?i)already|one profile"):
            with device_trace(str(tmp_path / "b")):
                pass
    # and the failed start left the outer session to close normally
    assert glob.glob(str(tmp_path / "a" / "**" / "*.xplane.pb"),
                     recursive=True)


def test_gc_hook_is_installed_once_and_removed():
    from kcp_tpu.obs.runtime import RuntimeProbes, _on_gc

    async def main():
        loop = asyncio.get_running_loop()
        base = gc.callbacks.count(_on_gc)
        a, b = RuntimeProbes(loop).start(), RuntimeProbes(loop).start()
        assert gc.callbacks.count(_on_gc) == base + (0 if base else 1)
        h = REGISTRY.histogram("py_gc_pause_seconds")
        n0 = h.n
        gc.collect()
        assert h.n == n0 + 1  # one observation per collection, not per server
        await asyncio.sleep(0.12)
        a.stop()
        b.stop()
        b.stop()  # idempotent
        assert gc.callbacks.count(_on_gc) == base

    asyncio.run(main())


@pytest.mark.parametrize("generation", [0, 1, 2])
def test_gc_hook_counts_collections_by_generation(generation):
    from kcp_tpu.obs.runtime import _on_gc

    names = [f"py_gc_collections_total_gen{g}" for g in range(3)] + [
        "py_gc_collected_objects_total", "py_gc_uncollectable_total"]
    pauses = REGISTRY.histogram("py_gc_pause_seconds")
    before, n0 = snap(), pauses.n
    # a stop with no start behind it (the hook came in mid-collection)
    _on_gc("stop", {"generation": generation, "collected": 5})
    assert [snap()[n] for n in names] == [before[n] for n in names]
    _on_gc("start", {"generation": generation})
    _on_gc("stop", {"generation": generation, "collected": 17,
                    "uncollectable": 2})
    after = snap()
    rose = [after[n] - before[n] for n in names]
    assert rose == [float(g == generation) for g in range(3)] + [17.0, 2.0]
    assert pauses.n == n0 + 1


def test_full_collections_reader_reads_the_rise_or_nothing(capsys):
    import importlib

    mod = importlib.import_module(
        "benchmarks.layer_metrics.gc_full_collections_in_window")
    rise = {"py_gc_collections_total_gen0": 41.0,
            "py_gc_collections_total_gen1": 4.0,
            "py_gc_collections_total_gen2": 1.0,
            "py_gc_collected_objects_total": 1234.0,
            "py_gc_uncollectable_total": 0.0}
    assert mod.read({"registry": rise}) == 1.0
    out = capsys.readouterr().out
    assert f"thresholds {gc.get_threshold()}" in out and "peak RSS" in out
    assert "41 young, 4 of generation 1, 1 full; 1234 objects" in out
    # a window without a full collection reads 0, not nothing
    assert mod.read({"registry": dict(
        rise, py_gc_collections_total_gen2=0.0)}) == 0.0
    # the parent: no counter, nothing to read, and the process's own
    # line all the same (its thresholds and peak RSS are the comparison)
    capsys.readouterr()
    assert mod.read({"registry": {"py_gc_pause_seconds": 3.0}}) is None
    assert "peak RSS" in capsys.readouterr().out
    kb, source = mod.peak_rss_kb()
    assert kb > 0 and source in ("VmHWM", "ru_maxrss")
    assert 0 < mod.resident_now_kb() <= kb
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry, = (m for m in manifest["per_layer"]
              if m["name"] == "gc_full_collections_in_window")
    assert entry == {
        "name": "gc_full_collections_in_window", "unit": "count",
        "better": "lower", "source": "program_counter",
        "layer": "Python runtime of the server process",
        "moves": "converge_p50_ms",
        "workloads": [w["name"] for w in manifest["workloads"]]}


# ---------------------------------------------------------------------------
# splitter
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["tpu", "host"])
def test_splitter_times_split_and_aggregate(backend):
    from kcp_tpu.apis.cluster import new_cluster
    from kcp_tpu.client import MultiClusterClient
    from kcp_tpu.reconcilers.deployment import DeploymentSplitter
    from kcp_tpu.reconcilers.deployment.controller import DEPLOYMENTS

    async def main():
        store = LogicalStore()
        mc = MultiClusterClient(store)
        tenant = mc.cluster_client("tenant-1")
        for loc in ("us-east1", "us-west1"):
            tenant.create("clusters.cluster.example.dev", new_cluster(loc))
        splitter = DeploymentSplitter(mc, backend=backend)
        await splitter.start()
        before = snap()
        tenant.create(DEPLOYMENTS, {
            "apiVersion": "apps/v1", "kind": "Deployment",
            "metadata": {"name": "web", "namespace": "default"},
            "spec": {"replicas": 10,
                     "template": {"spec": {"containers": []}}}})

        async def until(pred):
            for _ in range(500):
                if pred():
                    return
                await asyncio.sleep(0.01)
            raise AssertionError("not reached")

        await until(lambda: rise(before, snap(),
                                 "splitter_split_seconds")[0] == 1)
        leaf = tenant.get(DEPLOYMENTS, "web--us-east1", "default")
        leaf["status"] = {"replicas": 5, "readyReplicas": 5}
        tenant.update_status(DEPLOYMENTS, leaf)
        await until(lambda: (tenant.get(DEPLOYMENTS, "web", "default")
                             .get("status") or {}).get("readyReplicas") == 5)
        after = snap()
        assert rise(before, after, "splitter_split_seconds")[0] == 1
        assert rise(before, after, "splitter_aggregate_seconds")[0] >= 1
        # answered keys leave nothing behind (the root's own status
        # write is one more root event: popped by the pass that sees it)
        await until(lambda: not splitter._split_t0 and not splitter._agg_t0)
        await splitter.stop()
        store.close()

    asyncio.run(main())


# ---------------------------------------------------------------------------
# native build: a build in progress is waited for, not raced
# ---------------------------------------------------------------------------


def test_concurrent_builders_wait_for_one_build(tmp_path):
    """Four processes find no library at once: one runs make, the rest
    wait on native/.build.lock and find it built — none reads the
    half-written file. (make is a stub that writes its target slowly.)"""
    native = tmp_path / "native"
    native.mkdir()
    (native / "x.cc").write_text("// source\n")
    bindir = tmp_path / "bin"
    bindir.mkdir()
    (bindir / "make").write_text(
        "#!/bin/sh\n"
        f"printf half > {native}/lib.so\nsleep 0.4\n"
        f"printf whole >> {native}/lib.so\necho ran >> {tmp_path}/runs\n")
    (bindir / "make").chmod(0o755)
    code = ("import sys, kcp_tpu.native as n\n"
            f"n._NATIVE_DIR = {str(native)!r}\n"
            "built = n._ensure_built('lib.so')\n"
            f"print(built, open({str(native / 'lib.so')!r}).read())\n")
    env = dict(os.environ, PATH=f"{bindir}{os.pathsep}{os.environ['PATH']}")
    procs = [subprocess.Popen([sys.executable, "-c", code], cwd=REPO, env=env,
                              stdout=subprocess.PIPE, text=True)
             for _ in range(4)]
    outs = [p.communicate(timeout=60)[0].split() for p in procs]
    assert all(p.returncode == 0 for p in procs)
    assert sorted(o[0] for o in outs) == ["False"] * 3 + ["True"]
    assert all(o[1] == "halfwhole" for o in outs)
    assert (tmp_path / "runs").read_text().count("ran") == 1
