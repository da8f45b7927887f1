"""The ``frontend`` deployment at a small size on the CPU: tenants served
through a stateless ``kcp start --store-server`` frontend (a child
process) over a backend with controllers and ``fake://`` locations
(``benchmarks/frontend_deploy.Deployment``, the topology of the cell
``frontend-1k.steady``).

(a) a seeded schedule of creates, updates and deletes written THROUGH the
    frontend: the list read through the frontend, the list read from the
    backend and every downstream store equal the plain reference
    (``benchmarks/reference.final_state``) object for object;
(b) the event trail of a wildcard watch through the frontend equals the
    trail of the same watch on the backend — types, names,
    resourceVersions, order — also when the frontend's stream is cut and
    resumed from ``last_rv`` again and again: none lost, none doubled
    but a delete the backend's own resume rule replays;
(c) the frontend's new histograms and the dict path's byte and event
    counters rise by what was sent; a verb that finds every connection
    out, or every thread busy, observes its wait;
(d) the topology's own parts on hand-made input: a ``/metrics`` page to
    rises, a handle that samples at the window's edges, the five readers
    (a missing name gives None from each), the configuration and the
    traffic file against ``syncer-1k``'s.
"""

import asyncio
import contextlib
import importlib
import json
import os
import random
import signal
import sys
import threading
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import child_scrape, compare, frontend_deploy  # noqa: E402
from benchmarks import reference  # noqa: E402
from benchmarks.shapes import configmap as shape  # noqa: E402

from kcp_tpu.server.rest import RestClient  # noqa: E402

READERS = {"frontend_store_queue_ms": "remote_store_queue_seconds",
           "frontend_store_call_ms": "remote_store_call_seconds",
           "frontend_relay_ms": "watch_relay_seconds",
           "frontend_loop_lag_ms": "server_loop_lag_seconds"}


def _json(*parts: str) -> dict:
    with open(os.path.join(ROOT, "benchmarks", *parts)) as f:
        return json.load(f)


def _config() -> dict:
    cfg = _json("configs", "frontend-1k.json")
    return {**cfg, **cfg["rehearsal"], "logical_clusters": 4,
            "resident_per_cluster": 3}


@pytest.fixture(scope="module")
def dep(tmp_path_factory):
    d = frontend_deploy.Deployment(_config(), 2**31 + 38,
                                   str(tmp_path_factory.mktemp("frontend")))
    try:
        d.bring_up(say=lambda _m: None)
        yield d
    finally:
        d.stop()
    assert d.proc.poll() is not None  # no child outlives its deployment
    assert signal.getsignal(signal.SIGTERM) is not frontend_deploy._exit_on_sigterm


def _converged(dep, bodies: dict, timeout: float = 30.0) -> None:
    deadline = time.monotonic() + timeout
    while not dep.backend.converged(bodies):
        assert time.monotonic() < deadline, "writes did not converge"
        time.sleep(0.01)


class _Writer:
    """Operations through the frontend, recorded as the load generator
    records them; a key is never written before its last write
    converged (as the benchmark's generators hold)."""

    def __init__(self, dep):
        self.dep = dep
        self.client = RestClient(dep.srv.address)
        self.bodies = dict(dep.population)
        self.ops: list[dict] = []

    def write(self, kind: str, tenant: str, name: str, body=None) -> None:
        self.client.cluster = tenant
        rec = {"kind": kind, "key": [tenant, name], "body": body,
               "sent": time.monotonic(), "acked": None}
        self.ops.append(rec)
        if kind == "create":
            self.client.create(shape.RESOURCE, body)
        elif kind == "update":
            self.client.update(shape.RESOURCE, body)
        else:
            self.client.delete(shape.RESOURCE, name, shape.NAMESPACE)
        rec["acked"] = time.monotonic()
        if body is None:
            del self.bodies[(tenant, name)]
        else:
            self.bodies[(tenant, name)] = body
            _converged(self.dep, {(tenant, name): body})

    def schedule(self, rng, n: int) -> None:
        for i in range(n):
            keys = sorted(self.bodies)
            tenant, name = keys[rng.randrange(len(keys))]
            u = rng.random()
            if u < 0.2:
                tenant = self.dep.tenants[rng.randrange(len(self.dep.tenants))]
                name = f"{shape.PREFIX}-n{i:03d}-{rng.getrandbits(32):08x}"
                self.write("create", tenant, name,
                           shape.new(name, rng, self.dep.locations))
            elif u < 0.35:
                self.write("delete", tenant, name)
            else:
                self.write("update", tenant, name,
                           shape.mutate(self.bodies[(tenant, name)], rng))


def _marker(dep, name: str) -> None:
    """An unlabelled object, written on the backend once everything
    before it has converged: the last event of every watch."""
    client = RestClient(dep.backend.srv.address, cluster=dep.tenants[0])
    try:
        client.create(shape.RESOURCE, {
            "apiVersion": "v1", "kind": "ConfigMap",
            "metadata": {"name": name, "namespace": shape.NAMESPACE},
            "data": {}})
    finally:
        client.close()


async def _trail(address: str, since_rv: int, until: str,
                 cut_every: int | None = None) -> tuple[list, int]:
    """Every event of a wildcard watch from ``since_rv`` up to the
    object named ``until``; with ``cut_every`` the stream is closed
    after that many events and resumed from ``last_rv``. Returns the
    events and the number of streams it took."""
    client = RestClient(address, cluster="*")
    out, streams = [], 0
    try:
        while True:
            w = client.watch(shape.RESOURCE, since_rv=since_rv)
            streams += 1
            got = 0
            try:
                async for ev in w:
                    out.append(ev)
                    got += 1
                    if ev.name == until:
                        return out, streams
                    if cut_every and got >= cut_every:
                        break
            finally:
                w.close()
            # what the stream had handed over before the cut counts as
            # received: last_rv covers it
            rest = w.drain()
            out.extend(rest)
            if any(ev.name == until for ev in rest):
                return out, streams
            since_rv = w.last_rv
    finally:
        client.close()


def _rv_now(dep) -> int:
    client = RestClient(dep.backend.srv.address, cluster="*")
    try:
        return client.list(shape.RESOURCE)[1]
    finally:
        client.close()


def _watched(dep, work, until: str, cut_every=None):
    """Run ``work`` (blocking writes) while two wildcard watches collect:
    one through the frontend (cut and resumed where asked), one on the
    backend. Returns (frontend's events, its streams, backend's events)."""
    since = _rv_now(dep)

    async def main():
        front = asyncio.ensure_future(
            _trail(dep.srv.address, since, until, cut_every))
        back = asyncio.ensure_future(
            _trail(dep.backend.srv.address, since, until))
        await asyncio.get_running_loop().run_in_executor(None, work)
        (f, streams), (b, _one) = await asyncio.wait_for(
            asyncio.gather(front, back), 60)
        return f, streams, b

    return asyncio.run(main())


def _row(ev) -> tuple:
    return (ev.type, ev.cluster, ev.name, ev.rv,
            json.dumps(ev.object, sort_keys=True))


# ------------------------------------------------ (a) the stores, (b) trails


@pytest.mark.parametrize("cut_every", [None, 5])
def test_writes_through_the_frontend_equal_the_reference(dep, cut_every):
    """(a) and (b) on one seeded schedule: the uncut watch, and one cut
    every five events."""
    writer = _Writer(dep)
    rng = random.Random(38 + (cut_every or 0))
    until = f"zz-end-{cut_every}"

    def work():
        writer.schedule(rng, 40)
        _converged(dep, writer.bodies)
        _marker(dep, until)

    front, streams, back = _watched(dep, work, until, cut_every)
    try:
        kinds = {op["kind"] for op in writer.ops}
        assert kinds == {"create", "update", "delete"}
        state, uncertain = reference.final_state(dep.population, writer.ops)
        assert not uncertain and state == writer.bodies

        # (a) through the frontend (compare.read_upstream reads
        # dep.srv.address), from the backend directly, and downstream
        by_tenant, skip, n_uncertain = compare.expected(dep, writer.ops)
        assert n_uncertain == 0
        up, down, _waited = compare.drain(dep, by_tenant, skip, 30.0)
        assert not up + down, "\n".join(up + down)
        direct = compare.read_upstream(types.SimpleNamespace(
            srv=dep.backend.srv, shape=shape, tenants=dep.tenants))
        through = compare.read_upstream(dep)
        for tenant in dep.tenants:
            assert not shape.upstream_mismatches(
                tenant, by_tenant[tenant], direct[tenant], dep.locations,
                skip[tenant])
            strip = lambda objs: sorted(  # noqa: E731
                json.dumps(o, sort_keys=True) for o in objs)
            assert strip(direct[tenant]) == strip(through[tenant])
        assert dep.agent_errors() == 0

        # (b) event for event: every spec write and every status upsync.
        # A DELETED carries its object's LAST resourceVersion, below the
        # store's own: a stream cut right behind one resumes from a
        # last_rv that does not cover it, and the backend replays it —
        # its own resume rule, on a direct watch too. Only such a
        # replay may double, and the frontend adds none of its own.
        seen, replayed, once = set(), [], []
        for e in front:
            if _row(e)[:4] in seen:
                replayed.append(e)
            else:
                seen.add(_row(e)[:4])
                once.append(e)
        assert [_row(e) for e in once] == [_row(e) for e in back]
        assert all(e.type == "DELETED" for e in replayed)
        assert len(replayed) <= (streams - 1 if cut_every else 0) * 2
        rows = [_row(e)[:4] for e in back]
        assert len(set(rows)) == len(rows)
        front = once
        # the backend's order (a DELETED carries its object's last RV)
        rvs = [e.rv for e in front if e.type != "DELETED"]
        assert rvs == sorted(set(rvs))
        per_key: dict[tuple, list] = {}
        for e in front:
            per_key.setdefault((e.cluster, e.name), []).append(e.type)
        for op in writer.ops:
            assert tuple(op["key"]) in per_key
        assert len(front) >= 2 * sum(op["kind"] != "delete"
                                     for op in writer.ops)
        if cut_every:
            assert streams >= len(front) // (2 * cut_every) > 2
        else:
            assert streams == 1
    finally:
        dep.population = writer.bodies  # the next case starts from here
        writer.client.close()


def test_a_run_that_is_told_to_end_unwinds_to_stop(dep):
    """While the deployment is up a SIGTERM (a time limit's) raises in
    the main thread, so the caller's ``finally`` reaches ``stop()`` and
    the child ends with the run; ``stop()`` puts the old handler back
    (the fixture looks)."""
    if threading.current_thread() is threading.main_thread():
        assert (signal.getsignal(signal.SIGTERM)
                is frontend_deploy._exit_on_sigterm)
    with pytest.raises(SystemExit) as e:
        frontend_deploy._exit_on_sigterm(signal.SIGTERM, None)
    assert e.value.code == 128 + signal.SIGTERM


# ------------------------------------------------------------ (c) counters


def test_the_frontends_counters_rise_by_what_was_sent(dep):
    writer = _Writer(dep)
    rng = random.Random(3)
    address, pid = dep.srv.address, dep.proc.pid
    before = child_scrape.sample(address, pid)
    n = 12

    def work():
        for _ in range(n):
            keys = sorted(writer.bodies)
            tenant, name = keys[rng.randrange(len(keys))]
            writer.write("update", tenant, name,
                         shape.mutate(writer.bodies[(tenant, name)], rng))
        _marker(dep, "zz-end-counters")

    try:
        front, streams, back = _watched(dep, work, "zz-end-counters")
        time.sleep(0.1)
        got = child_scrape.rises(before, child_scrape.sample(address, pid))
    finally:
        dep.population = writer.bodies
        writer.client.close()
    m = got["metrics"]
    assert streams == 1 and len(front) == len(back) == 2 * n + 1
    # every event the frontend relayed: counted once, timed once, and
    # its bytes are the lines the tenant was sent
    assert m["watch_stream_events_total"] == len(front)
    assert m["watch_relay_seconds_count"] == len(front)
    assert m["watch_stream_bytes_total"] == sum(
        len(json.dumps({"type": e.type, "object": e.object}).encode()) + 1
        for e in front)
    assert 0 < m["watch_relay_seconds"] < 5.0
    assert 1 <= m["watch_relay_batches_total"] <= len(front)
    assert "watch_push_batches_total" not in m  # a frontend pushes nothing
    # every store verb: the n updates and the watch's opening at least
    assert m["remote_store_call_seconds_count"] >= n + 1
    assert (m["remote_store_queue_seconds_count"]
            == m["remote_store_call_seconds_count"])
    assert m["request_commit_seconds_count"] == n
    # the thread hop splits into its two histograms: what is left is the
    # executor's hand-over of the result to the loop
    inside = m["remote_store_queue_seconds"] + m["remote_store_call_seconds"]
    assert 0 < m["remote_store_call_seconds"] < inside
    assert got["cpu_s"] is not None and got["cpu_s"] >= 0
    assert got["window_s"] > 0
    # the five readers on this run's rises
    ctx = {"generator": {"frontend": got}}
    for name in sorted(READERS) + ["frontend_cpu_pct"]:
        value = importlib.import_module(
            f"benchmarks.layer_metrics.{name}").read(ctx)
        assert value is not None and value >= 0, name


@contextlib.contextmanager
def _remote_store():
    from kcp_tpu.server.server import Config
    from kcp_tpu.server.threaded import ServerThread
    from kcp_tpu.store.remote import RemoteStore

    with ServerThread(Config(durable=False, install_controllers=False,
                             tls=False)) as backend:
        store = RemoteStore(backend.address)
        try:
            yield store
        finally:
            store.close()


def _hist(name: str) -> tuple[int, float]:
    from kcp_tpu.utils.trace import REGISTRY

    h = REGISTRY.histogram(name)
    return h.n, h.total


def test_a_ninth_concurrent_verb_observes_its_wait_for_a_connection():
    with _remote_store() as store, contextlib.ExitStack() as held:
        assert store.io_concurrency == 8
        for _ in range(8):
            held.enter_context(store._pool.client("t"))
        n0, total0 = _hist("remote_store_queue_seconds")
        c0, _ = _hist("remote_store_call_seconds")
        ninth = threading.Thread(target=store.list, args=("configmaps", "t"))
        ninth.start()
        time.sleep(0.1)
        assert _hist("remote_store_queue_seconds")[0] == n0  # still waiting
        held.close()
        ninth.join(10)
        assert not ninth.is_alive()
        n1, total1 = _hist("remote_store_queue_seconds")
        assert n1 == n0 + 1 and total1 - total0 >= 0.09
        assert _hist("remote_store_call_seconds")[0] == c0 + 1


def test_a_verb_that_waited_for_a_thread_observes_that_wait_once():
    with _remote_store() as store:
        def two_verbs():
            store.list("configmaps", "t")
            return store.list("configmaps", "t")

        job = store.offloaded(two_verbs)
        n0, total0 = _hist("remote_store_queue_seconds")
        time.sleep(0.05)  # the submit waits for a thread
        assert job()[0] == []
        n1, total1 = _hist("remote_store_queue_seconds")
        # the first verb holds the wait since the submit; the second
        # waits anew, from its own entry
        assert n1 == n0 + 2 and 0.05 <= total1 - total0 < 0.09
        # not offloaded: from the verb's own entry
        store.list("configmaps", "t")
        n2, total2 = _hist("remote_store_queue_seconds")
        assert n2 == n1 + 1 and total2 - total1 < 0.04


@pytest.mark.parametrize("cap,depth", [("", ""), ("3", ""), ("2", "3")])
def test_the_store_io_pool_is_sized_from_the_stores_connections(
        cap, depth, monkeypatch):
    from kcp_tpu.apis.scheme import default_scheme
    from kcp_tpu.server.handler import RestHandler
    from kcp_tpu.store.remote import RemoteStore

    for key, value in (("KCP_ROUTER_POOL", cap),
                       ("KCP_ROUTER_POOL_DEPTH", depth)):
        if value:
            monkeypatch.setenv(key, value)
        else:
            monkeypatch.delenv(key, raising=False)
    store = RemoteStore("http://127.0.0.1:9")
    handler = RestHandler(store, default_scheme(), admission=None)
    try:
        want = int(cap or 8) * int(depth or 1)
        assert store.io_concurrency == want
        assert handler._store_pool._max_workers == want
    finally:
        handler.close()
        store.close()


# ------------------------------------------------- (d) the topology's parts

PAGE = """\
# HELP watch_relay_seconds one watch event through a storage frontend
# TYPE watch_relay_seconds histogram
watch_relay_seconds_bucket{le="0.001"} 3
watch_relay_seconds_bucket{le="+Inf"} 4
watch_relay_seconds_sum 0.25
watch_relay_seconds_count 4
# TYPE watch_stream_events_total counter
watch_stream_events_total 4.0
# TYPE checksum counter
checksum 7
# TYPE fleet_rows gauge
fleet_rows 16384.0
not a metric line
"""


def test_a_metrics_page_by_name():
    got = child_scrape.parse_metrics(PAGE)
    # a histogram's sum under its name, its count beside it: the form
    # of deploy.registry_snapshot; no bucket, nothing torn
    assert got == {"watch_relay_seconds": 0.25,
                   "watch_relay_seconds_count": 4.0,
                   "watch_stream_events_total": 4.0,
                   "checksum": 7.0, "fleet_rows": 16384.0}
    from kcp_tpu.utils.trace import Registry

    reg = Registry()
    reg.counter("c_total", "a counter").inc(3)
    h = reg.histogram("h_seconds", "a histogram\nof two lines")
    h.observe(0.5)
    h.observe(1.5)
    assert child_scrape.parse_metrics(reg.expose()) == {
        "c_total": 3.0, "h_seconds": 2.0, "h_seconds_count": 2.0}


def test_rises_between_two_samples():
    a = {"t": 10.0, "cpu_s": 1.5, "metrics": {"x": 1.0, "h": 0.5, "same": 2}}
    b = {"t": 61.0, "cpu_s": 4.0,
         "metrics": {"x": 4.0, "h": 0.75, "same": 2, "new_total": 9.0}}
    assert child_scrape.rises(a, b) == {
        "window_s": 51.0, "cpu_s": 2.5,
        "metrics": {"x": 3.0, "h": 0.25, "new_total": 9.0}}
    # a scrape or a /proc read that failed: None, not an exception
    assert child_scrape.rises(dict(a, metrics=None), b)["metrics"] is None
    assert child_scrape.rises(a, dict(b, cpu_s=None))["cpu_s"] is None
    assert child_scrape.scrape("http://127.0.0.1:9", timeout=0.5) is None
    assert child_scrape.cpu_seconds(2**22 + 7) is None
    assert child_scrape.cpu_seconds(os.getpid()) >= 0


class _Inner:
    """deploy.LoadGen's surface, with a window of a few milliseconds."""

    def __init__(self, out: dict):
        self.out, self.killed, self.t_start = out, False, None

    def go(self, lead_s: float = 0.3) -> float:
        self.t_start = time.monotonic() + lead_s
        return self.t_start

    @property
    def window(self):
        return self.t_start + 0.02, self.t_start + 0.07

    def result(self, timeout: float) -> dict:
        time.sleep(max(0.0, self.window[1] + 0.02 - time.monotonic()))
        return dict(self.out)

    def kill(self) -> None:
        self.killed = True


def test_the_handle_samples_at_the_windows_edges():
    taken = []

    def take():
        taken.append(time.monotonic())
        return {"t": taken[-1], "cpu_s": 0.5 * len(taken),
                "metrics": {"n_total": 10.0 * len(taken)}}

    lg = child_scrape.ScrapedLoadGen(_Inner({"records": [1], "skipped": 0}),
                                     "frontend", take)
    assert lg.go(0.01) == lg.inner.t_start
    w0, w1 = lg.window
    out = lg.result(timeout=5)
    assert len(taken) == 2
    assert 0 <= taken[0] - w0 < 0.02 and 0 <= taken[1] - w1 < 0.02
    assert out["records"] == [1] and out["skipped"] == 0
    assert out["frontend"]["metrics"] == {"n_total": 10.0}
    assert out["frontend"]["cpu_s"] == 0.5
    assert out["frontend"]["window_s"] == pytest.approx(0.05, abs=0.02)
    # killed before the window: no sample is waited for, none is given
    late = child_scrape.ScrapedLoadGen(_Inner({"records": []}), "frontend",
                                       take)
    late.go(30.0)
    late.kill()
    late._thread.join(2)
    assert late.inner.killed and not late._thread.is_alive()
    assert len(taken) == 2


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_of_the_frontends_histograms(name):
    read = importlib.import_module(f"benchmarks.layer_metrics.{name}").read
    hist = READERS[name]
    fe = {"window_s": 51.0, "cpu_s": 1.0,
          "metrics": {hist: 0.6, hist + "_count": 200.0}}
    assert read({"generator": {"frontend": fe}}) == pytest.approx(3.0)
    # a frontend without the histogram (the parent of the PR that added
    # it), a window in which it did not move, a scrape that failed, a
    # topology without a frontend: None, never an exception
    for metrics in ({}, {hist: 0.6}, None):
        assert read({"generator": {"frontend": dict(fe, metrics=metrics)}}) is None
    for generator in ({}, {"frontend": None}, None):
        assert read({"generator": generator}) is None
    assert read({}) is None


def test_the_reader_of_the_frontends_cores():
    read = importlib.import_module(
        "benchmarks.layer_metrics.frontend_cpu_pct").read
    fe = {"window_s": 50.0, "cpu_s": 20.0, "metrics": None}
    assert read({"generator": {"frontend": fe}}) == pytest.approx(40.0)
    assert read({"generator": {"frontend": dict(fe, cpu_s=None)}}) is None
    assert read({"generator": {"frontend": dict(fe, window_s=0.0)}}) is None
    assert read({"generator": {}}) is None and read({}) is None


def test_the_configuration_is_syncer_1k_behind_a_frontend():
    cfg, base = _json("configs", "frontend-1k.json"), _json(
        "configs", "syncer-1k.json")
    for key in ("shape", "logical_clusters", "locations_per_cluster",
                "resources_to_sync", "resident_per_cluster", "warm_bursts",
                "server", "reduced", "reduced_why", "rehearsal"):
        assert cfg[key] == base[key], key
    assert cfg["reduced"] == ["resident_per_cluster"]
    for key, text in base["guarantees"].items():
        assert cfg["guarantees"][key] == text
    assert set(cfg["guarantees"]) - set(base["guarantees"]) == {
        "stateless_frontend", "read_through_frontend", "watch_relay"}
    assert set(base["assumed"]) < set(cfg["assumed"])
    assert cfg["deployment"] == "benchmarks.frontend_deploy"
    assert cfg["frontends"] == 1 and len(cfg["source"]) <= 200
    # the command line the configuration states is the one the topology
    # starts: spawn_server's own flags, --store-server, frontend_args
    said = cfg["frontend_command"].split()
    assert said[:5] == ["python", "-m", "kcp_tpu.cli.kcp", "start",
                        "--store-server"]
    assert sorted(said[6:]) == sorted(
        ["--no-install-controllers", "--no-tls", "--syncer-mode", "none",
         *cfg["frontend_args"]])
    with pytest.raises(SystemExit):
        frontend_deploy.Deployment(dict(_config(), frontends=2), 1, "/tmp")

    traffic, steady = _json("traffic", "frontend-steady.json"), _json(
        "traffic", "steady.json")
    assert set(traffic) == set(steady)
    for key in set(steady) - {"rate_per_s", "rate_source"}:
        assert traffic[key] == steady[key], key
    assert traffic["rate_per_s"] % 10 == 0 and "burst" not in traffic

    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = [w for w in manifest["workloads"]
            if w["name"] == "frontend-1k.steady"]
    assert cell == [dict(cell[0], config="frontend-1k",
                         traffic="frontend-steady", chips=1)]
    ours = {m["name"] for m in manifest["per_layer"]
            if "frontend-1k.steady" in m.get("workloads", [])}
    steadys = {m["name"] for m in manifest["per_layer"]
               if "syncer-1k.steady" in m.get("workloads", [])}
    # PR 46's three readers of the tick's put and dispatch list
    # syncer-1k.steady as the CONTROL of the mesh cell, and no other cell;
    # PR 53's count of the put's transfers does too, and the rollout
    # beside them (the cell whose ticks also swap the placement leaves)
    control_only = {m["name"] for m in manifest["per_layer"]
                    if m.get("workloads", [])[:2] == ["syncer-1k.steady",
                                                      "mesh4-1k.steady"]
                    and len(m["workloads"]) <= 3}
    assert control_only == {"tick_put_ms", "tick_step_dispatch_ms",
                            "put_bytes_per_tick", "puts_per_tick"}
    assert ours == (steadys - control_only) | set(READERS) | {
        "frontend_cpu_pct"}
    assert {m["layer"] for m in manifest["per_layer"]
            if m["name"].startswith("frontend_")} == {"storage frontend"}
