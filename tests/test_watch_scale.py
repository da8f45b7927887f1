"""Watcher-scale serving: shared resume window, bounded queues +
eviction, flush coalescing, bookmark-advanced fast resume, and the
router's watch spread across replicas.

The differential contract under test: a watcher that drops and resumes
through the shared window (``watch(since_rv=...)`` answered by one
bisect over the window index) must observe a byte-identical event
stream to one that never dropped — including through an eviction → 410
→ relist recovery, which may *re-deliver* but must never *lose*.
"""

from __future__ import annotations

import asyncio
import json
import random
import time
from collections import deque

import pytest

from kcp_tpu import faults
from kcp_tpu.apis.scheme import default_scheme
from kcp_tpu.client.informer import Informer
from kcp_tpu.server.handler import RestHandler
from kcp_tpu.server.httpd import HttpServer
from kcp_tpu.server.rest import RestClient
from kcp_tpu.store.selectors import parse_selector
from kcp_tpu.store.store import LogicalStore
from kcp_tpu.utils import errors
from kcp_tpu.utils.trace import REGISTRY


@pytest.fixture(autouse=True)
def _clear_faults():
    yield
    faults.clear()


def _cm(name: str, cluster: str, data: str = "", labels: dict | None = None):
    meta = {"name": name, "namespace": "default", "clusterName": cluster}
    if labels:
        meta["labels"] = labels
    return {"apiVersion": "v1", "kind": "ConfigMap", "metadata": meta,
            "data": {"v": data}}


# ---------------------------------------------------------------------------
# shared resume window: differential fuzz vs a never-dropped watcher
# ---------------------------------------------------------------------------


def _drive(store: LogicalStore, rng: random.Random, n: int) -> None:
    clusters = ["t0", "t1", "t2"]
    for i in range(n):
        cl = clusters[rng.randrange(3)]
        name = f"cm-{rng.randrange(24)}"
        labels = {"team": f"g{rng.randrange(3)}"}
        try:
            if rng.random() < 0.2:
                store.delete("configmaps", cl, name)
            elif rng.random() < 0.5:
                store.update("configmaps", cl,
                             _cm(name, cl, str(i), labels))
            else:
                store.create("configmaps", cl, _cm(name, cl, str(i), labels))
        except (errors.NotFoundError, errors.AlreadyExistsError):
            pass


@pytest.mark.parametrize("seed", [3, 17, 92])
def test_window_resume_byte_identical_to_continuous(seed):
    """Drop/resume through the shared window at random points; the
    resumed stream's encoded wire lines must be byte-identical to what
    a continuous watcher saw over the same rv span — for unselected AND
    selector-bound watches (whose replay runs the label-transition
    rewrite)."""
    rng = random.Random(seed)
    store = LogicalStore()
    _drive(store, rng, 60)

    for selector in (None, parse_selector("team=g1")):
        continuous = store.watch("configmaps", selector=selector)
        seen: list = []
        resumer = store.watch("configmaps", selector=selector,
                              since_rv=store.resource_version)
        for _round in range(6):
            _drive(store, rng, rng.randrange(5, 40))
            seen.extend(continuous.drain())
            # sever + resume from the last rv this watcher observed
            last = seen[-1].rv if seen else store.resource_version
            resumer.close()
            resumer = store.watch("configmaps", selector=selector,
                                  since_rv=last)
        seen.extend(continuous.drain())
        resumed_tail = resumer.drain()
        # the final resume's replay must equal the continuous stream's
        # suffix over the same span, byte for byte on the wire
        span = [ev for ev in seen if ev.rv > (seen[-len(resumed_tail) - 1].rv
                                              if len(resumed_tail) < len(seen)
                                              else 0)]
        assert [store.encode_event(e) for e in resumed_tail] == \
            [store.encode_event(e) for e in span[-len(resumed_tail):]]
        continuous.close()
        resumer.close()
    store.close()


def test_resume_served_from_shared_index_and_survives_history_surgery():
    store = LogicalStore()
    for i in range(30):
        store.create("configmaps", "t0", _cm(f"a{i}", "t0"))
    before = REGISTRY.counter("watch_resume_shared_total").value
    w = store.watch("configmaps", since_rv=store.resource_version - 10)
    assert len(w.drain()) == 10
    assert REGISTRY.counter("watch_resume_shared_total").value == before + 1
    w.close()

    # direct history surgery (what tests do to shrink the window): the
    # mirror must self-heal, honoring the NEW window
    store._history = deque(store._history, maxlen=8)
    with pytest.raises(errors.GoneError):
        store.watch("configmaps", since_rv=store.resource_version - 20)
    w2 = store.watch("configmaps", since_rv=store.resource_version - 4)
    assert len(w2.drain()) == 4
    w2.close()
    store.close()


# ---------------------------------------------------------------------------
# bounded queues + eviction
# ---------------------------------------------------------------------------


def test_queue_overflow_evicts_slow_watcher_only():
    store = LogicalStore()
    store._watch_queue = 8
    slow = store.watch("configmaps")
    store._watch_queue = 0
    healthy = store.watch("configmaps")
    before = REGISTRY.counter("watch_evicted_total").value
    for i in range(20):
        store.create("configmaps", "t0", _cm(f"x{i}", "t0"))
    store._flush_events()
    assert slow.closed and slow.evicted
    assert REGISTRY.counter("watch_evicted_total").value == before + 1
    # the healthy watcher is untouched: every committed event delivered
    assert len(healthy.drain()) == 20
    assert not healthy.evicted
    healthy.close()
    store.close()


def test_watch_evict_fault_drill():
    """The ``watch.evict`` KCP_FAULTS point force-evicts as if the
    bounded queue overflowed — the backpressure path has a drill."""
    faults.install(faults.FaultInjector("watch.evict:drop@tick=3"))
    store = LogicalStore()
    w = store.watch("configmaps")
    for i in range(5):
        store.create("configmaps", "t0", _cm(f"d{i}", "t0"))
    store._flush_events()
    assert w.closed and w.evicted
    assert len(w.drain()) == 2  # pushes 1..2 landed; tick 3 evicted
    store.close()


def test_eviction_recovery_zero_lost_updates():
    """Eviction → typed 410 → informer relist: the consumer converges
    on the store's final state with zero lost updates (the PR 6
    relist-NOW path closing the loop on backpressure)."""
    from kcp_tpu.server.server import Config
    from kcp_tpu.server.threaded import ServerThread

    srv = ServerThread(Config(durable=False, install_controllers=False,
                              tls=False)).start()
    client = RestClient(srv.address, cluster="t0")

    async def run() -> None:
        loop = asyncio.get_running_loop()
        client.create("configmaps", _cm("seed", "t0"))
        inf = Informer(client, "configmaps")
        await inf.start()
        try:
            # force-evict the server-side watch: stream must end in a
            # terminal typed 410 and the informer must recover by relist
            faults.install(faults.FaultInjector("watch.evict:drop@tick=1"))
            await loop.run_in_executor(
                None, client.create, "configmaps", _cm("during", "t0"))
            await asyncio.sleep(0.3)
            faults.clear()
            await loop.run_in_executor(
                None, client.create, "configmaps", _cm("after", "t0"))
            deadline = loop.time() + 15
            while loop.time() < deadline:
                if {"seed", "during", "after"} <= \
                        {k[2] for k in inf.cache}:
                    break
                await asyncio.sleep(0.05)
            assert {"seed", "during", "after"} <= \
                {k[2] for k in inf.cache}
        finally:
            await inf.stop()

    try:
        asyncio.run(run())
    finally:
        faults.clear()
        client.close()
        srv.stop()


def test_slow_socket_evicted_with_terminal_410(monkeypatch):
    """Handler-level eviction: a client that stops reading while the
    fan-out keeps writing crosses KCP_WATCH_BUFFER_MAX and gets a
    terminal typed 410 buffered on its way out."""
    import socket as _socket
    from urllib.parse import urlsplit

    from kcp_tpu.server.server import Config
    from kcp_tpu.server.threaded import ServerThread

    monkeypatch.setenv("KCP_WATCH_BUFFER_MAX", "2048")
    srv = ServerThread(Config(durable=False, install_controllers=False,
                              tls=False)).start()
    client = RestClient(srv.address, cluster="t0")
    sk = _socket.socket()
    try:
        client.create("configmaps", _cm("seed", "t0"))
        before = REGISTRY.counter("watch_evicted_total").value
        parts = urlsplit(srv.address)
        # a tiny receive window: backpressure must reach the server's
        # transport buffer instead of vanishing into kernel buffers
        sk.setsockopt(_socket.SOL_SOCKET, _socket.SO_RCVBUF, 2048)
        sk.settimeout(5)
        sk.connect((parts.hostname, parts.port))
        sk.sendall(b"GET /clusters/t0/api/v1/configmaps?watch=true "
                   b"HTTP/1.1\r\nHost: t\r\n\r\n")
        pad = "x" * 8192
        deadline = time.time() + 20
        i = 0
        while (REGISTRY.counter("watch_evicted_total").value == before
               and time.time() < deadline):
            client.update("configmaps", {
                "apiVersion": "v1", "kind": "ConfigMap",
                "metadata": {"name": "seed", "namespace": "default",
                             "clusterName": "t0"},
                "data": {"v": str(i), "pad": pad}})
            i += 1
        assert REGISTRY.counter("watch_evicted_total").value == before + 1
        data = b""
        try:
            while True:
                chunk = sk.recv(65536)
                if not chunk:
                    break
                data += chunk
        except (TimeoutError, OSError):
            pass
        assert b'"code": 410' in data and b'"reason": "Expired"' in data
    finally:
        sk.close()
        client.close()
        srv.stop()


# ---------------------------------------------------------------------------
# push (one write per fan-out pass): byte-identical to the per-batch relay
# ---------------------------------------------------------------------------


def test_coalesced_stream_byte_identical_to_per_batch(monkeypatch):
    """The same seeded mutation run served both ways — the pull relay
    (a write and a drain per event batch) and the push path a local
    store's watch takes (the fan-out pass writes each socket once per
    commit window) — yields the exact same reassembled line stream
    (chunk framing may differ; the payload and its order may not)."""
    from kcp_tpu.store import store as store_mod

    async def one_mode(mode: str) -> tuple[list[bytes], float]:
        with monkeypatch.context() as mp:
            if mode != "push":
                # no knob selects the relay: the choice is made on the
                # watch object, so a watch without the push half (the
                # REST client's, of a storage frontend) is relayed
                mp.delattr(store_mod.Watch, "set_sink")
            push0 = REGISTRY.counter("watch_push_batches_total").value
            lines = await serve()
            return (lines,
                    REGISTRY.counter("watch_push_batches_total").value
                    - push0)

    async def serve() -> list[bytes]:
        store = LogicalStore(clock=lambda: 0.0)
        for i in range(8):
            store.create("configmaps", "t0", {
                "apiVersion": "v1", "kind": "ConfigMap",
                "metadata": {"name": f"c{i}", "namespace": "default",
                             "uid": f"u{i}"},
                "data": {"v": "0"}})
        handler = RestHandler(store, default_scheme(), admission=None)
        handler.ready = True
        srv = HttpServer(handler)
        await srv.start()
        reader, writer = await asyncio.open_connection(srv.host, srv.port)
        lines: list[bytes] = []
        try:
            writer.write(b"GET /clusters/t0/api/v1/configmaps?watch=true "
                         b"HTTP/1.1\r\nHost: t\r\n\r\n")
            await writer.drain()
            await reader.readuntil(b"\r\n\r\n")

            async def pump() -> None:
                buf = b""
                while True:
                    size_line = await reader.readline()
                    size = int(size_line.strip() or b"0", 16)
                    if size == 0:
                        return
                    buf += await reader.readexactly(size)
                    await reader.readexactly(2)
                    *done, buf = buf.split(b"\n")
                    lines.extend(d for d in done if d)

            task = asyncio.ensure_future(pump())
            for i in range(40):
                store.update("configmaps", "t0", {
                    "apiVersion": "v1", "kind": "ConfigMap",
                    "metadata": {"name": f"c{i % 8}",
                                 "namespace": "default"},
                    "data": {"v": f"m{i}"}})
                await asyncio.sleep(0.001)
            deadline = asyncio.get_running_loop().time() + 5
            while (len(lines) < 40
                   and asyncio.get_running_loop().time() < deadline):
                await asyncio.sleep(0.02)
            task.cancel()
        finally:
            writer.close()
            await srv.stop()
            handler.close()
            store.close()
        return lines

    async def run() -> None:
        per_batch, p_pb = await one_mode("per-batch")
        pushed, p_pu = await one_mode("push")
        assert per_batch == pushed
        assert len(per_batch) == 40
        assert p_pb == 0 and p_pu > 0

    asyncio.run(run())


# ---------------------------------------------------------------------------
# bookmarks: quiet-period resume without a relist (satellite regression)
# ---------------------------------------------------------------------------


def test_bookmark_quiet_period_resumes_without_410(monkeypatch):
    """A stream that sat quiet while OTHER tenants churned past its
    original rv must still resume without a 410: periodic server
    BOOKMARKs advance the informer's resume point (without waking any
    handler), so the drop lands inside the window and fast resume skips
    the relist entirely."""
    from kcp_tpu.server.server import Config
    from kcp_tpu.server.threaded import ServerThread

    monkeypatch.setenv("KCP_WATCH_BOOKMARK_S", "0.15")
    srv = ServerThread(Config(durable=False, install_controllers=False,
                              tls=False)).start()
    client = RestClient(srv.address, cluster="t0")
    other = RestClient(srv.address, cluster="t9")
    lists = 0
    orig_list = client.list

    def counting_list(*a, **kw):
        nonlocal lists
        lists += 1
        return orig_list(*a, **kw)

    client.list = counting_list

    async def run() -> None:
        loop = asyncio.get_running_loop()
        store = srv.server.store
        client.create("configmaps", _cm("seed", "t0"))
        inf = Informer(client, "configmaps")
        await inf.start()
        try:
            assert lists == 1
            # the RestWatch connects lazily from the pump task — it must
            # be ESTABLISHED (at its in-window resume point) before the
            # window shrinks, or the shrink races the initial connect
            deadline = loop.time() + 10
            while (not getattr(inf._watch, "responded", False)
                   and loop.time() < deadline):
                await asyncio.sleep(0.01)
            assert getattr(inf._watch, "responded", False)
            # shrink the window, then churn a DIFFERENT tenant far past
            # it — without bookmarks the informer's resume point (the
            # initial list rv) would now be outside the window
            srv.call(lambda: setattr(
                store, "_history", deque(store._history, maxlen=8)))
            for i in range(40):
                other.create("configmaps", _cm(f"noise{i}", "t9"))
            # quiet period long enough for >=1 bookmark at 0.15s cadence
            deadline = loop.time() + 8
            while loop.time() < deadline:
                if (inf._watch is not None
                        and getattr(inf._watch, "last_rv", 0)
                        >= store.resource_version):
                    break
                await asyncio.sleep(0.05)
            assert getattr(inf._watch, "last_rv", 0) >= \
                store.resource_version, "bookmark never advanced last_rv"
            before = REGISTRY.counter("informer_fast_resumes_total").value
            # sever the stream; the informer must fast-resume (no 410,
            # no relist) because the bookmark kept it inside the window
            inf._watch.close()
            deadline = loop.time() + 10
            while loop.time() < deadline:
                if REGISTRY.counter(
                        "informer_fast_resumes_total").value > before:
                    break
                await asyncio.sleep(0.05)
            assert REGISTRY.counter(
                "informer_fast_resumes_total").value == before + 1
            # the resumed stream is live: a new event reaches the cache
            await loop.run_in_executor(
                None, client.create, "configmaps", _cm("fresh", "t0"))
            deadline = loop.time() + 10
            while loop.time() < deadline:
                if any(k[2] == "fresh" for k in inf.cache):
                    break
                await asyncio.sleep(0.05)
            assert any(k[2] == "fresh" for k in inf.cache)
            assert lists == 1, "fast resume must not relist"
        finally:
            await inf.stop()

    try:
        asyncio.run(run())
    finally:
        client.close()
        other.close()
        srv.stop()


# ---------------------------------------------------------------------------
# router: fresh watch streams spread across a shard's replicas
# ---------------------------------------------------------------------------


def test_router_spreads_fresh_watches_across_replicas(tmp_path):
    from kcp_tpu.server.server import Config
    from kcp_tpu.server.threaded import ServerThread

    primary = ServerThread(Config(
        durable=True, install_controllers=False, tls=False,
        root_dir=str(tmp_path / "p"))).start()
    replica = ServerThread(Config(
        durable=False, install_controllers=False, tls=False,
        role="replica", primary=primary.address)).start()
    router = ServerThread(Config(
        role="router", durable=False, tls=False,
        shards=f"s0={primary.address}|{replica.address}")).start()
    try:
        pc = RestClient(primary.address, cluster="t0")
        pc.create("configmaps", _cm("pre", "t0"))
        pc.close()
        # wait for the replica to apply the seed write
        rc = RestClient(replica.address, cluster="t0")
        deadline = time.time() + 10
        while time.time() < deadline:
            st = rc._request("GET", "/replication/status")
            if st["applied_rv"] >= 1 and st["connected"]:
                break
            time.sleep(0.05)
        rc.close()

        before = REGISTRY.counter("router_watch_spread_total").value
        c = RestClient(router.address, cluster="t0")
        wc = RestClient(router.address, cluster="t0")

        async def scenario() -> None:
            loop = asyncio.get_running_loop()
            watches = [c.watch("configmaps", "default") for _ in range(4)]
            try:
                for w in watches:
                    w._ensure_started()
                deadline = loop.time() + 10
                while (not all(w.responded for w in watches)
                       and loop.time() < deadline):
                    await asyncio.sleep(0.05)
                assert all(w.responded for w in watches)
                await loop.run_in_executor(
                    None, wc.create, "configmaps", _cm("during", "t0"))
                for w in watches:
                    ev = await asyncio.wait_for(w.__anext__(), timeout=15)
                    assert ev.name == "during"
            finally:
                for w in watches:
                    w.close()

        asyncio.run(scenario())
        wc.close()
        c.close()
        # round-robin over [replica, primary]: 4 fresh streams = 2 spread
        assert REGISTRY.counter(
            "router_watch_spread_total").value == before + 2
    finally:
        router.stop()
        replica.stop()
        primary.stop()


def test_resume_through_router_spreads_to_replica(tmp_path):
    """A watch resume (?resourceVersion=) is no longer pinned to the
    primary: the replica's RV barrier parks the resume until its applied
    RV covers the pin, so resumes round-robin across primary+replicas
    like fresh watches. Two consecutive resumes land one on each, and
    both replay the identical window."""
    from kcp_tpu.server.server import Config
    from kcp_tpu.server.threaded import ServerThread

    primary = ServerThread(Config(
        durable=True, install_controllers=False, tls=False,
        root_dir=str(tmp_path / "p"))).start()
    replica = ServerThread(Config(
        durable=False, install_controllers=False, tls=False,
        role="replica", primary=primary.address)).start()
    router = ServerThread(Config(
        role="router", durable=False, tls=False,
        shards=f"s0={primary.address}|{replica.address}")).start()
    try:
        pc = RestClient(router.address, cluster="t0")
        for i in range(5):
            pc.create("configmaps", _cm(f"r{i}", "t0"))
        before = REGISTRY.counter("router_watch_spread_total").value

        async def collect(w) -> list:
            out = []
            async for ev in w:
                out.append(ev.name)
                if len(out) == 3:
                    break
            return out

        # round-robin over [replica, primary]: exactly one of the two
        # resumes is spread, and both must replay the same window
        for _ in range(2):
            w = pc.watch("configmaps", "default", since_rv=2)
            assert asyncio.run(collect(w)) == ["r2", "r3", "r4"]
            w.close()
        assert REGISTRY.counter(
            "router_watch_spread_total").value == before + 1
        pc.close()
    finally:
        router.stop()
        replica.stop()
        primary.stop()


# ---------------------------------------------------------------------------
# push delivery: the store's fan-out pass writes a local watch's stream
# ---------------------------------------------------------------------------


class _PushStream:
    """A duck-typed stream WITH the buffered write half (what
    httpd.StreamResponse offers): every frame is recorded in the order
    of the calls, which on one transport is the order on the wire."""

    def __init__(self):
        self.frames: list[bytes] = []
        self.backlog = 0
        self.fail_writes = False

    async def send_json(self, obj):
        self.frames.append(json.dumps(obj).encode() + b"\n")

    async def send_raw_many(self, lines):
        await asyncio.sleep(0)  # a drain that yields: writes may race it
        self.frames.extend(lines)

    def write_raw_many(self, lines):
        if self.fail_writes:
            raise RuntimeError("transport exploded")
        self.frames.extend(lines)

    def write_buffer_size(self):
        return self.backlog

    def decoded(self) -> list[dict]:
        return [json.loads(f) for f in self.frames]


def _watch_req(cluster: str = "t0", **params: str):
    from kcp_tpu.server.httpd import Request

    query = {"watch": ["true"]}
    query.update({k: [v] for k, v in params.items()})
    return Request(method="GET",
                   path=f"/clusters/{cluster}/api/v1/configmaps",
                   query=query, headers={}, body=b"")


def _push_counts() -> tuple[float, float]:
    return (REGISTRY.counter("watch_push_batches_total").value,
            REGISTRY.counter("watch_relay_batches_total").value)


async def _serve_watch(handler, stream, **params):
    """Start a watch producer on ``stream``; returns its task once the
    watch is subscribed (a stream with no initial part attaches its sink
    in that same step)."""
    store = handler.store
    n0 = len(store._watches)
    resp = await handler(_watch_req(**params))
    task = asyncio.ensure_future(resp.producer(stream))
    for _ in range(400):
        if len(store._watches) > n0:
            break
        await asyncio.sleep(0.005)
    return task


def _rv(frame: dict) -> int:
    return int(frame["object"]["metadata"]["resourceVersion"])


def test_push_hands_frame_to_transport_inside_the_fanout_pass(monkeypatch):
    """Delivery takes ONE loop pass: with a local store and a real
    HttpServer stream, the frame has been handed to the stream's
    transport by the time ``_flush_events`` returns — no ``await``
    between the fan-out and the write — and the ``observe`` phase is
    stamped right there."""
    from kcp_tpu.server.httpd import StreamResponse

    written: list[list[bytes]] = []
    orig = StreamResponse.write_raw_many

    def recording(self, lines):
        written.append(list(lines))
        return orig(self, lines)

    monkeypatch.setattr(StreamResponse, "write_raw_many", recording)

    async def run() -> None:
        store = LogicalStore(clock=lambda: 0.0)
        store.create("configmaps", "t0", _cm("seed", "t0"))
        handler = RestHandler(store, default_scheme(), admission=None)
        handler.ready = True
        srv = HttpServer(handler)
        await srv.start()
        reader, writer = await asyncio.open_connection(srv.host, srv.port)
        try:
            writer.write(b"GET /clusters/t0/api/v1/configmaps?watch=true "
                         b"HTTP/1.1\r\nHost: t\r\n\r\n")
            await writer.drain()
            await reader.readuntil(b"\r\n\r\n")
            for _ in range(400):
                if store._watches and store._watches[0]._sink is not None:
                    break
                await asyncio.sleep(0.005)
            assert store._watches[0]._sink is not None
            observed = REGISTRY.histogram("convergence_observe_seconds")
            n0, push0 = observed.n, _push_counts()[0]
            # ---- one synchronous stretch: commit, fan-out, written
            for i in range(3):
                store.update("configmaps", "t0", _cm("seed", "t0", f"v{i}"))
            assert written == []  # nothing before the fan-out pass
            store._flush_events()
            assert len(written) == 1 and len(written[0]) == 3
            assert observed.n == n0 + 3
            assert _push_counts()[0] == push0 + 1
            # ---- and the client's socket shows exactly those bytes
            size = int((await reader.readline()).strip(), 16)
            payload = await reader.readexactly(size)
            assert payload == b"".join(written[0])
            assert [json.loads(ln)["object"]["data"]["v"]
                    for ln in payload.splitlines()] == ["v0", "v1", "v2"]
        finally:
            writer.close()
            await srv.stop()
            handler.close()
            store.close()

    asyncio.run(run())


def test_push_waits_for_the_commit_windows_wal_sync(tmp_path):
    """No event is handed to a socket before its commit window's WAL
    append + sync: a lazy flush (another consumer's pending()) fans the
    event out early, but the sink only runs from the window's flush."""

    async def run() -> None:
        store = LogicalStore(wal_path=str(tmp_path / "w.wal"))
        store._gc_linger_s = 30.0  # the window stays open until flushed
        handler = RestHandler(store, default_scheme(), admission=None)
        stream = _PushStream()
        task = await _serve_watch(handler, stream)
        other = store.watch("configmaps")
        try:
            store.create("configmaps", "t0", _cm("a", "t0"))
            window = store._gc_window
            assert window is not None and window.recs
            assert other.pending() == 1  # lazily fanned out already
            assert stream.frames == []  # ...but not to the socket
            synced = store._wal_sync_total.value
            store._gc_flush(window)
            assert store._wal_sync_total.value == synced + 1
            assert [f["type"] for f in stream.decoded()] == ["ADDED"]
            assert window.fut.done() and not window.fut.exception()
        finally:
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)
            store.close()

    asyncio.run(run())


def test_watch_list_snapshot_then_bookmark_then_live_in_rv_order():
    """A watch-list stream with writes racing the snapshot delivers
    snapshot → sync BOOKMARK → live events in RV order, none lost, none
    doubled: what commits while the snapshot streams stays buffered in
    the watch and is flushed, in order, when the sink attaches."""

    async def run() -> None:
        store = LogicalStore()
        for i in range(1100):  # three snapshot batches of <= 512
            store.create("configmaps", "t0", _cm(f"s{i:04d}", "t0"))
        handler = RestHandler(store, default_scheme(), admission=None)
        stream = _PushStream()
        written: list[int] = []
        stop = False

        async def writer() -> None:
            i = 0
            while not stop:
                obj = store.update("configmaps", "t0",
                                   _cm(f"s{i % 1100:04d}", "t0", f"w{i}"))
                written.append(int(obj["metadata"]["resourceVersion"]))
                i += 1
                await asyncio.sleep(0)

        wtask = asyncio.ensure_future(writer())
        await asyncio.sleep(0)
        task = await _serve_watch(handler, stream, sendInitialEvents="true",
                                  allowWatchBookmarks="true")
        await asyncio.sleep(0.05)
        stop = True
        await wtask
        store._flush_events()
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)
        frames = stream.decoded()
        marks = [i for i, f in enumerate(frames) if f["type"] == "BOOKMARK"]
        assert marks, "no sync bookmark"
        sync = marks[0]
        init_rv = _rv(frames[sync])
        assert frames[sync]["object"]["metadata"]["annotations"]
        snapshot, live = frames[:sync], frames[sync + 1:]
        assert len(snapshot) == 1100
        assert all(f["type"] == "ADDED" and _rv(f) <= init_rv
                   for f in snapshot)
        live_rvs = [_rv(f) for f in live if f["type"] != "BOOKMARK"]
        racing = [rv for rv in written if rv > init_rv]
        assert any(rv <= init_rv for rv in written), "no write raced"
        assert racing and live_rvs == racing  # in order, each exactly once
        store.close()

    asyncio.run(run())


def test_push_slow_socket_evicted_through_the_sink():
    """A socket past KCP_WATCH_BUFFER_MAX is evicted by the sink itself
    — never awaited: the batch that crossed the bound is followed by the
    terminal typed 410, watch_evicted_total rises by one, and the
    stream's watch is closed."""

    async def run() -> None:
        store = LogicalStore()
        handler = RestHandler(store, default_scheme(), admission=None)
        stream = _PushStream()
        task = await _serve_watch(handler, stream)
        watch = store._watches[-1]
        before = REGISTRY.counter("watch_evicted_total").value
        store.create("configmaps", "t0", _cm("ok", "t0"))
        store._flush_events()
        assert len(stream.frames) == 1 and not watch.closed
        stream.backlog = handler._buffer_max + 1
        store.create("configmaps", "t0", _cm("over", "t0"))
        store._flush_events()  # the eviction happens inside this pass
        assert watch.closed
        assert REGISTRY.counter("watch_evicted_total").value == before + 1
        await asyncio.wait_for(task, 5)
        frames = stream.decoded()
        assert [f["type"] for f in frames] == ["ADDED", "ADDED", "ERROR"]
        assert frames[-1]["object"]["code"] == 410
        assert frames[-1]["object"]["reason"] == "Expired"
        store.create("configmaps", "t0", _cm("later", "t0"))
        store._flush_events()
        assert len(stream.frames) == 3  # nothing after the terminal 410
        store.close()

    asyncio.run(run())


def test_push_bookmark_never_ahead_of_an_unwritten_event(tmp_path,
                                                         monkeypatch):
    """A BOOKMARK never carries an RV ahead of an event not yet written
    to that stream — also while commit windows hold events back from the
    sink: on the recorded wire every event at or below a bookmark's RV
    precedes it."""
    monkeypatch.setenv("KCP_WATCH_BOOKMARK_S", "0.001")

    async def run() -> None:
        store = LogicalStore(wal_path=str(tmp_path / "w.wal"))
        store._gc_linger_s = 0.004  # windows stay open across loop passes
        handler = RestHandler(store, default_scheme(), admission=None)
        stream = _PushStream()
        task = await _serve_watch(handler, stream, allowWatchBookmarks="true")
        rng = random.Random(7)
        store.create("configmaps", "t0", _cm("c", "t0"))
        for i in range(150):
            store.update("configmaps", "t0", _cm("c", "t0", str(i)))
            if rng.random() < 0.5:
                await asyncio.sleep(rng.choice((0, 0.001, 0.003, 0.006)))
        await asyncio.sleep(0.05)
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)
        frames = stream.decoded()
        events = [_rv(f) for f in frames if f["type"] != "BOOKMARK"]
        assert events == sorted(set(events)) and len(events) == 151
        marks = 0
        high = 0  # highest event RV on the wire so far
        for f in frames:
            if f["type"] == "BOOKMARK":
                marks += 1
                assert _rv(f) <= high, (
                    f"bookmark {_rv(f)} ahead of the stream ({high})")
            else:
                high = _rv(f)
        assert marks > 0
        store.close()

    asyncio.run(run())


@pytest.mark.parametrize("signal", ["draining", "watch_fence"])
def test_push_drain_and_fence_end_with_bookmark_and_status(signal):
    """Drain and fence after pushed events end the stream with every
    buffered event, the anchoring BOOKMARK at the store's RV, and the
    terminal Status — on ONE wake-up of the stream's coroutine."""

    async def run() -> None:
        store = LogicalStore()
        handler = RestHandler(store, default_scheme(), admission=None)
        stream = _PushStream()
        task = await _serve_watch(handler, stream)
        for i in range(3):
            store.create("configmaps", "t0", _cm(f"a{i}", "t0"))
        store._flush_events()
        assert len(stream.frames) == 3
        # two more commit and are still pending in the store when the
        # signal fires: they must precede the bookmark
        for i in range(2):
            store.create("configmaps", "t0", _cm(f"b{i}", "t0"))
        getattr(handler, signal).set()
        await asyncio.wait_for(task, 5)
        frames = stream.decoded()
        assert [f["type"] for f in frames] == \
            ["ADDED"] * 5 + ["BOOKMARK", "ERROR"]
        assert [_rv(f) for f in frames[:5]] == sorted(
            _rv(f) for f in frames[:5])
        assert _rv(frames[5]) == store.resource_version
        assert frames[6]["object"]["code"] == 503
        assert not handler._stream_wakes  # the wake-up is unregistered
        assert store._watches == []
        store.close()

    asyncio.run(run())


def test_push_sink_failure_closes_only_its_own_watch(tmp_path):
    """A sink that raises closes its own watch like a dropped stream;
    the other watches of the same fan-out pass still get the batch and
    the commit window still resolves for its writers."""

    async def run() -> None:
        store = LogicalStore(wal_path=str(tmp_path / "w.wal"))
        handler = RestHandler(store, default_scheme(), admission=None)
        bad, good = _PushStream(), _PushStream()
        bad_task = await _serve_watch(handler, bad)
        bad_watch = store._watches[-1]
        good_task = await _serve_watch(handler, good)
        good_watch = store._watches[-1]
        puller = store.watch("configmaps")
        bad.fail_writes = True
        store.create("configmaps", "t0", _cm("x", "t0"))
        durable = store.commit_durable()
        if durable is not None:
            assert await asyncio.wait_for(durable, 5) == \
                store.resource_version
        await asyncio.wait_for(bad_task, 5)
        assert bad_watch.closed and not bad_watch.evicted
        assert bad.frames == []
        assert not good_watch.closed and not good_task.done()
        assert [f["type"] for f in good.decoded()] == ["ADDED"]
        assert len(puller.drain()) == 1
        store.create("configmaps", "t0", _cm("y", "t0"))
        store._gc_barrier()
        assert len(good.frames) == 2 and bad.frames == []
        good_task.cancel()
        await asyncio.gather(good_task, return_exceptions=True)
        store.close()

    asyncio.run(run())


@pytest.mark.parametrize("durable", [False, True])
def test_push_stream_behind_a_writing_informer_waits_for_the_sync(
        durable, tmp_path):
    """An informer is a sink whose handlers are program code: one that
    answers inside the fan-out pass (``get`` + ``update_status`` into the
    store it is fed from) and then flushes lazily puts an UNSYNCED event
    into the watches the pass has not reached yet. The HTTP stream behind
    it is handed nothing while that window is open, and the answer is
    never delivered recursively: it arrives with the next flush."""
    from kcp_tpu.client import Client

    async def run() -> None:
        store = LogicalStore(
            wal_path=str(tmp_path / "w.wal") if durable else None)
        store._gc_linger_s = 30.0  # windows stay open until flushed
        client = Client(store, "t0")
        puller = store.watch("configmaps")
        in_handler = answers = 0

        def answer(etype, old, new) -> None:
            nonlocal in_handler, answers
            assert in_handler == 0, "delivered recursively"
            in_handler += 1
            try:
                if "status" not in new:
                    obj = client.get("configmaps", new["metadata"]["name"],
                                     "default")
                    obj["status"] = {"seen": True}
                    client.update_status("configmaps", obj, "default")
                    answers += 1
                    assert puller.pending() >= 1  # lazy flush, in the pass
            finally:
                in_handler -= 1

        agent = Informer(client, "configmaps")
        agent.add_handler(answer)
        await agent.start()
        handler = RestHandler(store, default_scheme(), admission=None)
        stream = _PushStream()  # subscribed behind the informer
        task = await _serve_watch(handler, stream)
        try:
            store.create("configmaps", "t0", _cm("a", "t0"))
            if durable:
                store._gc_flush(store._gc_window)  # sync, then the pass
            else:
                store._flush_events()
            assert answers == 1
            if durable:
                window = store._gc_window
                assert window is not None and len(window.recs) == 1
                assert stream.frames == []  # held whole: a window is open
                synced = store._wal_sync_total.value
                store._gc_flush(window)
                assert store._wal_sync_total.value == synced + 1
            frames = stream.decoded()
            assert [f["type"] for f in frames] == ["ADDED", "MODIFIED"]
            assert "status" not in frames[0]["object"]
            assert frames[1]["object"]["status"] == {"seen": True}
            assert _rv(frames[0]) < _rv(frames[1])
        finally:
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)
            await agent.stop()
            store.close()

    asyncio.run(run())


def test_a_flush_from_inside_a_sink_leaves_the_sinks_to_the_next_pass():
    """What a sink writes is fanned out by a lazy flush at once, but no
    sink — its own or another's — runs inside a sink: the touched
    watches are queued, and the pass the store schedules delivers."""

    async def run() -> None:
        store = LogicalStore()
        first, second = store.watch("configmaps"), store.watch("configmaps")
        got: dict[str, list[list[str]]] = {"first": [], "second": []}
        depth = 0

        def sink(name):
            def take(batch) -> None:
                nonlocal depth
                assert depth == 0, "a sink ran inside a sink"
                depth += 1
                got[name].append([e.name for e in batch])
                if name == "first" and len(got["first"]) == 1:
                    store.create("configmaps", "t0", _cm("b", "t0"))
                    store._flush_events()
                depth -= 1
            return take

        first.set_sink(sink("first"))
        second.set_sink(sink("second"))
        store.create("configmaps", "t0", _cm("a", "t0"))
        store._flush_events()
        # the pass is over: `second`, not reached when `b` was fanned
        # out, took both; `first` waits for the pass that is due
        assert got == {"first": [["a"]], "second": [["a", "b"]]}
        assert store._sink_dirty == [first] and store._flush_scheduled
        await asyncio.sleep(0)
        assert got == {"first": [["a"], ["b"]], "second": [["a", "b"]]}
        assert store._sink_dirty == []
        store.close()

    asyncio.run(run())


class _PullOnlyWatch:
    """The REST client's watch surface (server/rest.py RestWatch): it
    is fed by a network reader of its own, so it offers iteration,
    drain/pending/close — and no push half."""

    def __init__(self, inner):
        self._inner = inner
        self.last_rv = 0

    def __aiter__(self):
        return self

    async def __anext__(self):
        return await self._inner.__anext__()

    def drain(self):
        return self._inner.drain()

    def pending(self):
        return self._inner.pending()

    def close(self):
        self._inner.close()

    @property
    def closed(self):
        return self._inner.closed

    @property
    def evicted(self):
        return self._inner.evicted


@pytest.mark.parametrize("case", ["duck-typed-stream", "pull-only-watch",
                                  "encode-cache-off"])
def test_streams_without_the_push_half_keep_the_pull_relay(case, monkeypatch):
    """The choice is made on what the code can observe, not on a knob:
    a duck-typed stream without ``write_raw_many``, a watch without
    ``set_sink`` (the REST client's, on a storage frontend or a router
    relay) and a store without the encode cache are all served by the
    pull relay — watch_relay_batches_total rises, push does not."""
    from kcp_tpu.server.rest import RestWatch

    assert not hasattr(RestWatch, "set_sink")

    async def run() -> None:
        store = LogicalStore(encode_cache=case != "encode-cache-off")
        if case == "pull-only-watch":
            local_watch = store.watch
            monkeypatch.setattr(
                store, "watch",
                lambda *a, **kw: _PullOnlyWatch(local_watch(*a, **kw)))
        handler = RestHandler(store, default_scheme(), admission=None)
        stream = _PushStream()
        if case == "duck-typed-stream":
            stream = type("_NoWriteHalf", (), {
                "frames": [],
                "send_json": _PushStream.send_json,
                "send_raw_many": _PushStream.send_raw_many,
                "decoded": _PushStream.decoded})()
        push0, relay0 = _push_counts()
        task = await _serve_watch(handler, stream)
        for i in range(3):
            store.create("configmaps", "t0", _cm(f"r{i}", "t0"))
            await asyncio.sleep(0.02)
        for _ in range(200):
            if len(stream.frames) == 3:
                break
            await asyncio.sleep(0.005)
        assert [f["type"] for f in stream.decoded()] == ["ADDED"] * 3
        assert all(w._sink is None for w in store._watches)
        push1, relay1 = _push_counts()
        assert push1 == push0 and relay1 >= relay0 + 1
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)
        store.close()

    asyncio.run(run())
