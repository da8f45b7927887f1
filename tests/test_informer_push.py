"""Push delivery to in-process informers: where the watch an Informer
holds offers ``set_sink`` (a local ``store.Watch``), the store's fan-out
pass dispatches the events itself — cache, indices, handlers and
``event_stamps`` in the loop pass that flushed them, never before their
commit window is synced, never recursively — and the pump task only
sleeps for what ends a watch. A watch fed over the wire keeps the pull
loop.
"""

from __future__ import annotations

import asyncio
from collections import deque

import pytest

from helpers import wait_until
from kcp_tpu import faults
from kcp_tpu.client import Client, Informer
from kcp_tpu.client.informer import by_namespace
from kcp_tpu.server import Config, RestClient
from kcp_tpu.server.rest import RestWatch
from kcp_tpu.server.threaded import ServerThread
from kcp_tpu.store.store import ADDED, Event, LogicalStore
from kcp_tpu.utils.trace import REGISTRY

NS = "default"


@pytest.fixture(autouse=True)
def _clear_faults():
    yield
    faults.clear()


def _cm(name: str, v: str = "") -> dict:
    return {"apiVersion": "v1", "kind": "ConfigMap",
            "metadata": {"name": name, "namespace": NS}, "data": {"v": v}}


def _counts() -> tuple[float, float]:
    return (REGISTRY.counter("informer_events_total").value,
            REGISTRY.counter("informer_pushed_events_total").value)


def _rv(obj: dict) -> int:
    return int(obj["metadata"]["resourceVersion"])


def _store(tmp_path, durable: bool, held: bool = False) -> LogicalStore:
    store = LogicalStore(
        wal_path=str(tmp_path / "w.wal") if durable else None)
    if durable and held:
        store._gc_linger_s = 30.0  # a window stays open until flushed
    return store


def _unsynced(store: LogicalStore) -> bool:
    w = store._gc_window
    return w is not None and bool(w.recs)


def test_handler_runs_inside_the_fanout_pass():
    """One synchronous stretch — commit, ``_flush_events``, handler run:
    no task is woken between the fan-out and the handlers, the cache and
    the indices are already updated when a handler runs, and the event's
    stamps are readable inside it (and only there)."""

    async def run() -> None:
        store = LogicalStore()
        client = Client(store, "t")
        inf = Informer(client, "configmaps")
        inf.add_indexer("ns", by_namespace)
        seen: list[tuple] = []

        def handler(etype, old, new) -> None:
            name = (new or old)["metadata"]["name"]
            seen.append((etype, inf.get("t", name, NS) is new,
                         sorted(o["metadata"]["name"]
                                for o in inf.index("ns", NS)),
                         inf.event_stamps))

        inf.add_handler(handler)
        await inf.start()
        ev0, push0 = _counts()
        client.create("configmaps", _cm("a"), NS)
        client.create("configmaps", _cm("b"), NS)
        assert seen == []  # nothing before the fan-out pass
        store._flush_events()
        assert [(s[0], s[1], s[2]) for s in seen] == [
            (ADDED, True, ["a"]), (ADDED, True, ["a", "b"])]
        for *_, stamps in seen:
            tw, tm = stamps
            assert isinstance(tm, float) and (tw is None or tw <= tm)
        assert inf.event_stamps is None
        assert _counts() == (ev0 + 2, push0 + 2)
        client.delete("configmaps", "a", NS)
        store._flush_events()
        # new is None on a delete: the cache has let go of it already
        assert seen[-1][:3] == ("DELETED", True, ["b"])
        assert _counts() == (ev0 + 3, push0 + 3)
        await inf.stop()
        store.close()

    asyncio.run(run())


class _RacingClient(Client):
    """Writes where an informer's start cannot look: between its list
    and its watch, and between the watch and the attach."""

    def list(self, *a, **kw):
        out = super().list(*a, **kw)
        self.create("configmaps", _cm("after-list"), NS)
        return out

    def watch(self, *a, **kw):
        # committed before the watch opens: replayed from since_rv
        self.update("configmaps", _cm("seed", "v1"), NS)
        w = super().watch(*a, **kw)
        # still pending in the store when the sink attaches
        self.create("configmaps", _cm("after-watch"), NS)
        return w


@pytest.mark.parametrize("durable", [False, True])
def test_start_hands_over_what_buffered_between_list_and_attach(
        durable, tmp_path):
    """list -> watch(since_rv) -> attach: what committed in between is
    delivered exactly once, in RV order, by the attach — for a durable
    store once the racing writes' commit window is synced."""

    async def run() -> None:
        store = _store(tmp_path, durable)
        Client(store, "t").create("configmaps", _cm("seed", "v0"), NS)
        store._gc_barrier()
        inf = Informer(_RacingClient(store, "t"), "configmaps")
        log: list[tuple[str, str, int]] = []
        inf.add_handler(lambda t, old, new: log.append(
            (t, (new or old)["metadata"]["name"], _rv(new or old))))
        _, push0 = _counts()
        await inf.start()
        if durable:
            assert await wait_until(lambda: len(log) == 4, 5)
        assert [(t, n) for t, n, _ in log] == [
            (ADDED, "seed"), (ADDED, "after-list"), ("MODIFIED", "seed"),
            (ADDED, "after-watch")]
        rvs = [rv for *_, rv in log]
        assert rvs == sorted(set(rvs))
        assert _counts()[1] == push0 + 3  # the list's item was not pushed
        await inf.stop()
        store.close()

    asyncio.run(run())


class _SnapshotThenLive:
    """A watch-list stream over a local store: the snapshot as ADDED
    events and the sync BOOKMARK by iteration (each step yields to the
    loop, so writes race the snapshot), then the live watch itself —
    iteration AND its push half."""

    def __init__(self, items, rv, inner):
        self._head = deque(
            [Event(ADDED, "configmaps", "t", NS, o["metadata"]["name"], o,
                   _rv(o)) for o in items]
            + [Event("BOOKMARK", "configmaps", "t", "", "", {}, rv)])
        self._inner = inner

    def __aiter__(self):
        return self

    async def __anext__(self):
        if self._head:
            await asyncio.sleep(0)
            return self._head.popleft()
        return await self._inner.__anext__()

    def __getattr__(self, name):  # set_sink, detach, clear_sink, close
        return getattr(self._inner, name)


class _WatchListClient(Client):
    supports_watch_list = True

    def watch(self, gvr, namespace=None, selector=None, since_rv=None,
              initial_events=False):
        if not initial_events:
            return super().watch(gvr, namespace, selector, since_rv)
        items, rv = self.list(gvr, namespace, selector)
        return _SnapshotThenLive(
            items, rv, super().watch(gvr, namespace, selector, since_rv=rv))


def test_watch_list_start_consumes_by_pull_then_attaches():
    """The watch-list start pulls up to the sync BOOKMARK and attaches
    after: with writes racing the snapshot, every live event arrives
    exactly once, in RV order, behind the snapshot."""

    async def run() -> None:
        store = LogicalStore()
        client = _WatchListClient(store, "t")
        for i in range(40):
            client.create("configmaps", _cm(f"s{i:02d}"), NS)
        written: list[int] = []
        stop = False

        async def writer() -> None:
            i = 0
            while not stop:
                written.append(_rv(client.update(
                    "configmaps", _cm(f"s{i % 40:02d}", f"w{i}"), NS)))
                i += 1
                await asyncio.sleep(0)

        inf = Informer(client, "configmaps", watch_list=True)
        log: list[tuple[str, int]] = []
        inf.add_handler(lambda t, old, new: log.append((t, _rv(new))))
        wtask = asyncio.ensure_future(writer())
        starts0 = REGISTRY.counter("informer_watch_list_starts_total").value
        _, push0 = _counts()
        await inf.start()
        assert REGISTRY.counter(
            "informer_watch_list_starts_total").value == starts0 + 1
        raced = len(written)
        assert raced > 0, "no write raced the snapshot"
        # what raced was buffered in the watch and handed over, whole, by
        # the attach: dispatched by the time start() returns
        assert [rv for _, rv in log[40:]] == written[:raced]
        await asyncio.sleep(0.02)
        stop = True
        await wtask
        store._flush_events()
        snapshot, live = log[:40], log[40:]
        assert all(t == ADDED for t, _ in snapshot)
        assert max(rv for _, rv in snapshot) < written[0]
        assert len(written) > raced and [rv for _, rv in live] == written
        assert _counts()[1] == push0 + len(written)  # the live part: pushed
        await inf.stop()
        store.close()

    asyncio.run(run())


def test_handler_sees_nothing_before_the_wal_sync(tmp_path):
    """A lazy flush by another consumer fans an event out while its
    commit window is still open: the pull consumer sees it, the informer
    is handed nothing until the window's WAL append + sync."""

    async def run() -> None:
        store = _store(tmp_path, durable=True, held=True)
        client = Client(store, "t")
        inf = Informer(client, "configmaps")
        seen: list[tuple[str, bool]] = []
        inf.add_handler(lambda t, old, new: seen.append(
            (new["metadata"]["name"], _unsynced(store))))
        await inf.start()
        other = store.watch("configmaps")
        client.create("configmaps", _cm("a"), NS)
        window = store._gc_window
        assert window is not None and window.recs
        assert other.pending() == 1  # lazily fanned out already
        assert seen == []  # ...but not to the informer
        synced = store._wal_sync_total.value
        store._gc_flush(window)
        assert store._wal_sync_total.value == synced + 1
        assert seen == [("a", False)]
        await inf.stop()
        store.close()

    asyncio.run(run())


@pytest.mark.parametrize("durable", [False, True])
def test_a_writing_handler_is_never_served_recursively(durable, tmp_path):
    """A location's controller answers inside the pass: ``get`` +
    ``update_status`` into the store it is fed from, then a lazy flush.
    Its write is not delivered recursively — not to it, not to anyone —
    but by the next flush; on a durable store no informer behind it in
    the same pass is handed the unsynced status (nor anything else while
    a window is open)."""

    async def run() -> None:
        store = _store(tmp_path, durable, held=True)
        client = Client(store, "t")
        other = store.watch("configmaps")
        depth = max_depth = 0
        agent_log: list[tuple[str, bool]] = []
        observer_log: list[tuple[str, bool, bool]] = []

        def answer(etype, old, new) -> None:
            nonlocal depth, max_depth
            depth += 1
            max_depth = max(max_depth, depth)
            try:
                agent_log.append((etype, "status" in new))
                if "status" not in new:
                    obj = client.get("configmaps", new["metadata"]["name"],
                                     NS)
                    obj["status"] = {"seen": True}
                    client.update_status("configmaps", obj, NS)
                    assert other.pending() >= 1  # a lazy flush, in the pass
            finally:
                depth -= 1

        def observe(etype, old, new) -> None:
            nonlocal depth, max_depth
            max_depth = max(max_depth, depth + 1)
            observer_log.append((etype, "status" in new, _unsynced(store)))

        agent = Informer(client, "configmaps")
        agent.add_handler(answer)
        await agent.start()
        observer = Informer(client, "configmaps")  # behind the agent
        observer.add_handler(observe)
        await observer.start()

        client.create("configmaps", _cm("a"), NS)
        if durable:
            store._gc_flush(store._gc_window)  # sync, then the pass
        else:
            store._flush_events()
        # the pass is over: the agent answered once, saw no echo yet
        assert agent_log == [(ADDED, False)] and max_depth == 1
        if durable:
            # the status sits in an open window, and a lazy flush put it
            # into the observer's watch: the observer was held back whole
            assert _unsynced(store) and observer_log == []
            store._gc_flush(store._gc_window)
        else:
            # nothing to sync: the observer took both in RV order, the
            # agent's own echo waits for the next pass, which is due
            assert observer_log == [(ADDED, False, False),
                                    ("MODIFIED", True, False)]
            await asyncio.sleep(0)
            await asyncio.sleep(0)
        assert agent_log == [(ADDED, False), ("MODIFIED", True)]
        assert observer_log == [(ADDED, False, False),
                                ("MODIFIED", True, False)]
        assert max_depth == 1
        await agent.stop()
        await observer.stop()
        store.close()

    asyncio.run(run())


def test_a_raising_handler_closes_nothing():
    async def run() -> None:
        store = LogicalStore()
        client = Client(store, "t")
        inf = Informer(client, "configmaps")
        good: list[str] = []

        def bad(etype, old, new) -> None:
            raise RuntimeError("handler exploded")

        inf.add_handler(bad)
        inf.add_handler(lambda t, old, new: good.append(
            new["metadata"]["name"]))
        await inf.start()
        watch = inf._watch
        _, push0 = _counts()
        client.create("configmaps", _cm("a"), NS)
        store._flush_events()
        client.create("configmaps", _cm("b"), NS)
        store._flush_events()
        assert good == ["a", "b"]
        assert inf._watch is watch and not watch.closed
        assert inf.get("t", "b", NS) is not None
        assert _counts()[1] == push0 + 2
        await inf.stop()
        store.close()

    asyncio.run(run())


@pytest.mark.parametrize("how", ["drop", "evict", "close"])
def test_a_lost_watch_wakes_the_pump_which_resumes_and_attaches_again(how):
    """A ``watch:drop`` drill (the event is LOST with the watch), an
    eviction or a plain close ends the watch: ``on_close`` wakes the
    pump, which fast-resumes from the last dispatched RV and attaches
    again — every event exactly once, in RV order, and the new watch is
    pushed too."""

    async def run() -> None:
        store = LogicalStore()
        client = Client(store, "t")
        inf = Informer(client, "configmaps")
        inf.rewatch_backoff = 0.02
        rvs: list[int] = []
        inf.add_handler(lambda t, old, new: rvs.append(_rv(new)))
        await inf.start()
        written = [_rv(client.create("configmaps", _cm(f"a{i}"), NS))
                   for i in range(3)]
        store._flush_events()
        assert rvs == written
        first = inf._watch
        resumes0 = REGISTRY.counter("informer_fast_resumes_total").value
        if how == "close":
            first.close()
        else:
            point = "watch" if how == "drop" else "watch.evict"
            faults.install(faults.FaultInjector(f"{point}:drop@tick=1"))
        written.append(_rv(client.create("configmaps", _cm("lost"), NS)))
        store._flush_events()
        faults.clear()
        assert first.closed and first.evicted == (how == "evict")
        if how != "close":
            assert rvs == written[:-1]  # the event went with the watch
        assert await wait_until(lambda: rvs == written, 5)
        assert inf._watch is not first and not inf._watch.closed
        assert REGISTRY.counter(
            "informer_fast_resumes_total").value == resumes0 + 1
        # the new watch rides the fan-out pass again
        _, push0 = _counts()
        written.append(_rv(client.create("configmaps", _cm("again"), NS)))
        store._flush_events()
        assert rvs == written and _counts()[1] == push0 + 1
        assert store._watches == [inf._watch]
        await inf.stop()
        store.close()

    asyncio.run(run())


def test_a_failed_resume_does_not_count_old_deliveries_twice():
    """The pump's resume decision counts what was dispatched since the
    attach; a resume that fails leaves the closed watch in place, and
    the next lap must read 0, not the old count again (it would reset
    the fast-resume budget on a stream that delivers nothing)."""

    async def run() -> None:
        store = LogicalStore()
        client = Client(store, "t")
        inf = Informer(client, "configmaps")
        inf.rewatch_backoff = 0.01
        await inf.start()
        client.create("configmaps", _cm("a"), NS)
        store._flush_events()
        assert inf._delivered == 1
        real_watch, fails = client.watch, [2]

        def flaky(*a, **kw):
            if fails[0]:
                fails[0] -= 1
                raise ConnectionError("server down")
            return real_watch(*a, **kw)

        client.watch = flaky
        inf._watch.close()
        assert await wait_until(
            lambda: inf._watch is not None and not inf._watch.closed, 5)
        assert fails == [0] and inf._delivered == 0
        client.create("configmaps", _cm("b"), NS)
        store._flush_events()
        assert inf.get("t", "b", NS) is not None
        await inf.stop()
        store.close()

    asyncio.run(run())


def test_stop_detaches():
    async def run() -> None:
        store = LogicalStore()
        client = Client(store, "t")
        inf = Informer(client, "configmaps")
        seen: list[str] = []
        inf.add_handler(lambda t, old, new: seen.append(
            new["metadata"]["name"]))
        await inf.start()
        client.create("configmaps", _cm("a"), NS)
        store._flush_events()
        watch = inf._watch
        await inf.stop()
        assert watch.closed and watch._sink is None
        assert store._watches == [] and store._sink_dirty == []
        client.create("configmaps", _cm("b"), NS)
        store._flush_events()
        assert seen == ["a"]
        store.close()

    asyncio.run(run())


def test_an_informer_fed_over_the_wire_keeps_the_pull_loop():
    """The choice is made on the watch object: a ``RestWatch`` has no
    fan-out pass to ride, its informer is pulled by the pump task —
    ``informer_events_total`` rises, ``informer_pushed_events_total``
    does not."""
    assert not hasattr(RestWatch, "set_sink")

    async def run() -> None:
        with ServerThread(Config(durable=False, tls=False,
                                 install_controllers=False)) as srv:
            c = RestClient(srv.address, cluster="t")
            inf = Informer(c, "configmaps")
            await inf.start()
            try:
                assert not inf._pushing
                ev0, push0 = _counts()
                loop = asyncio.get_running_loop()
                for i in range(3):
                    await loop.run_in_executor(
                        None, c.create, "configmaps", _cm(f"r{i}"), NS)
                assert await wait_until(lambda: len(inf.list()) == 3, 10)
                ev1, push1 = _counts()
                assert ev1 >= ev0 + 3 and push1 == push0
            finally:
                await inf.stop()
                c.close()

    asyncio.run(run())
