"""A downstream status goes up from the event that brought it (ISSUE 39).

On the fused backend ``BatchSyncEngine._on_down_event`` hands the status
that ANSWERS a write (the key's convergence entry stands between its
downstream write and its first status) to the applier pool itself; the
tick that carries the row is the backstop, and a LATER status of the same
write rides that tick as before. What must hold, each a case here:

(a) the status that answers a write goes upstream with NO tick between
    the event and the write, ``kcp_sync_status_upsyncs_direct_total``
    rises by one, and the device mirror ends equal; a later status
    arrives by the tick, and the direct counter does not rise for it;
(b) a failed direct apply (``KCP_FAULTS`` ``syncer.apply`` error) is
    recovered by the tick's re-emitted patch: the status arrives, the
    direct counter does not rise for it;
(c) a status event that meets a pending spec apply of the same key marks
    ``_REARM``, and both the spec and the status land (the quiet-tenant
    shape of ROADMAP S9: nothing else ever makes a tick);
(d) a resync replay (``old is new``), an absent upstream object, equal
    statuses and a status no write is waiting for queue nothing;
(e) host and fused backends end in the same upstream and downstream
    stores over a seeded churn;
(f) a rolling walk of 7 statuses delivers a trail
    ``k8s_rolling_reference`` accepts;
(g) a key whose location reports progress (a status came after the one
    that answered a write) keeps the tick for the answer to its next
    write too, and is handed over again once a write was answered once.
"""

import asyncio
import os
import random
import sys
import time
from types import SimpleNamespace

import pytest
from helpers import device_mirror_equal

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import agents  # noqa: E402
from benchmarks import k8s_rolling_reference as ref  # noqa: E402
from benchmarks import rolling_agent  # noqa: E402
from benchmarks.shapes import k8s_rolling as shape  # noqa: E402
from kcp_tpu import faults  # noqa: E402
from kcp_tpu.client import Client, Informer  # noqa: E402
from kcp_tpu.ops.diff import DECISION_NOOP, DECISION_UPDATE  # noqa: E402
from kcp_tpu.store import LogicalStore  # noqa: E402
from kcp_tpu.syncer import start_syncer  # noqa: E402
from kcp_tpu.syncer.engine import (  # noqa: E402
    _DONE,
    _PATCHED,
    _REARM,
    CLUSTER_LABEL,
    BatchSyncEngine,
    _Convergence,
)
from kcp_tpu.utils.trace import REGISTRY  # noqa: E402

TICKS = "fused_fleet_ticks_total"
DIRECT = "kcp_sync_status_upsyncs_direct_total"
UPSYNCS = "kcp_sync_status_upsyncs_total"
DEFERRED = "kcp_sync_patches_deferred_total"
COUNTERS = (TICKS, DIRECT, UPSYNCS, DEFERRED)


def _counter(name: str) -> float:
    return REGISTRY.snapshot().get(name, 0.0)


def _rise(before: dict) -> dict:
    return {n: _counter(n) - v for n, v in before.items()}


def _now() -> dict:
    return {n: _counter(n) for n in COUNTERS}


def cm(name: str, data: dict) -> dict:
    return {"apiVersion": "v1", "kind": "ConfigMap",
            "metadata": {"name": name, "namespace": "default",
                         "labels": {CLUSTER_LABEL: "c1"}},
            "data": data}


async def _until(pred, timeout: float, what: str, interval: float = 0.002):
    deadline = time.monotonic() + timeout
    while True:
        try:
            if pred():
                return
        except Exception:  # noqa: BLE001 — not there yet
            pass
        assert time.monotonic() < deadline, what
        await asyncio.sleep(interval)


async def _pair(backend: str = "tpu"):
    up = Client(LogicalStore(), "tenant")
    down = Client(LogicalStore(), "pcluster")
    syncer = await start_syncer(up, down, ["configmaps"], "c1",
                                backend=backend)
    return up, down, syncer, syncer.engines[0]


def _write_status(down, name: str, status: dict) -> None:
    obj = down.get("configmaps", name, "default")
    obj["status"] = status
    down.update_status("configmaps", obj)


def _record_writes(eng) -> list:
    """The value of the tick counter at every upstream status write."""
    at_write: list[float] = []
    sound = eng._up_update_status

    def recording(*args, **kwargs):
        at_write.append(_counter(TICKS))
        return sound(*args, **kwargs)

    eng._up_update_status = recording
    return at_write


# ------------------------------------------------ (a) no tick on the path


@pytest.mark.parametrize("trips", [1, 3], ids=["first", "later"])
def test_a_status_event_writes_upstream_with_no_tick_between(trips):
    async def main():
        up, down, syncer, eng = await _pair()
        try:
            up.create("configmaps", cm("a", {"k": "v"}))
            await _until(lambda: down.get("configmaps", "a", "default"),
                         90, "the copy reached the location")
            await asyncio.sleep(0.1)  # the create's own ticks have run
            at_write = _record_writes(eng)
            for trip in range(trips):
                status = {"ready": True, "trip": trip}
                before = _now()
                _write_status(down, "a", status)
                await _until(
                    lambda: up.get("configmaps", "a", "default").get(
                        "status") == status, 5, "the status is upstream")
                # the backstop: the row's own tick runs (for the answer,
                # after the write: it finds both sides equal and asks for
                # nothing more)
                await _until(lambda: _counter(TICKS) > before[TICKS], 5,
                             "the tick that carries the row ran")
                await asyncio.sleep(0.05)
                rise = _rise(before)
                assert rise[UPSYNCS] == 1 and rise[DEFERRED] == 0, rise
                if trip == 0:
                    # the answer to the create was written under the tick
                    # count its event found
                    assert at_write == [before[TICKS]], (at_write, before)
                    assert rise[DIRECT] == 1, rise
                else:
                    # a later status of the same write rode its tick
                    assert len(at_write) == 1, at_write
                    assert at_write[0] > before[TICKS], (at_write, before)
                    assert rise[DIRECT] == 0, rise
                assert device_mirror_equal(eng, ("default", "a"))
                assert not eng._apply_pending
                at_write.clear()
            # the echo of the last status write retires the timeline
            await _until(lambda: not eng._dirty, 5, "the entry retired")
        finally:
            await syncer.stop()

    asyncio.run(main())


# ------------------------------------------- (b) the tick is the backstop


def test_a_failed_direct_apply_is_recovered_by_the_tick():
    async def main():
        up, down, syncer, eng = await _pair()
        try:
            up.create("configmaps", cm("a", {"k": "v"}))
            await _until(lambda: down.get("configmaps", "a", "default"),
                         90, "the copy reached the location")
            await asyncio.sleep(0.1)
            at_write = _record_writes(eng)
            before = _now()
            # the first apply from here on (the event's own) answers 503
            faults.install(faults.FaultInjector(
                "syncer.apply:error@tick=1", seed=39))
            _write_status(down, "a", {"ready": True})
            await _until(
                lambda: up.get("configmaps", "a", "default").get("status")
                == {"ready": True}, 5, "the status is upstream")
            # written by a patch a later tick re-emitted, not by the event
            assert len(at_write) == 1 and at_write[0] > before[TICKS]
            await asyncio.sleep(0.1)  # the failed apply's own retry too
            rise = _rise(before)
            assert rise[UPSYNCS] == 1 and rise[DIRECT] == 0, rise
            assert device_mirror_equal(eng, ("default", "a"))
            assert not eng._apply_pending and not eng._apply_failures
        finally:
            faults.clear()
            await syncer.stop()

    asyncio.run(main())


# --------------------------------- (c) a status meets a pending spec apply


class _Core:
    def __init__(self):
        self.enqueued = []

    def enqueue(self, section, side, key):
        self.enqueued.append((side, key))


def _bare_engine() -> BatchSyncEngine:
    """A fused engine that was never started: caches filled by hand, a
    queue nobody reads, a core that only records."""
    eng = BatchSyncEngine(Client(LogicalStore(), "tenant"),
                          Client(LogicalStore(), "pcluster"),
                          "configmaps", "c1", backend="tpu")
    eng.core = _Core()
    eng._section = SimpleNamespace(bucket=SimpleNamespace(stats={}))
    eng._apply_q = asyncio.Queue()
    return eng


def _carried_down(eng, key) -> None:
    """The key's convergence entry as a write's applied patch leaves it:
    the downstream write is made, its status is awaited."""
    ent = _Convergence(0.0, 0.0, "1", key[1], None)
    ent.state = _PATCHED
    eng._dirty[key] = ent


def _stored(name: str, status, rv: str = "1") -> dict:
    obj = cm(name, {"k": "v"})
    obj["metadata"]["resourceVersion"] = rv
    if status is not None:
        obj["status"] = status
    return obj


def test_a_status_event_behind_a_pending_spec_apply_is_rearmed():
    eng = _bare_engine()
    key = ("default", "a")
    eng.up_informer.cache[("tenant", "default", "a")] = _stored("a", None)
    before = _now()
    _carried_down(eng, key)  # an earlier patch's copy awaits its status
    eng.fused_apply([(key, DECISION_UPDATE, False)])  # the spec patch
    assert eng._apply_pending == {key: (DECISION_UPDATE, False)}
    old, new = _stored("a", None, "2"), _stored("a", {"ready": True}, "3")
    eng._on_down_event("MODIFIED", old, new)
    assert eng._apply_pending == {key: _REARM}
    assert eng._apply_q.qsize() == 1  # the status was not queued twice
    assert _rise(before)[DEFERRED] == 1
    assert eng.core.enqueued == [(True, key)]  # the row still goes down
    # a pending apply of the SAME decision covers the event
    other = ("default", "b")
    eng.up_informer.cache[("tenant", "default", "b")] = _stored("b", None)
    _carried_down(eng, other)
    new_b = _stored("b", {"ready": True}, "3")
    eng._on_down_event("MODIFIED", _stored("b", None, "2"), new_b)
    assert eng._apply_pending[other] == (DECISION_NOOP, True)
    eng._on_down_event("MODIFIED", new_b, _stored("b", {"ready": 2}, "4"))
    assert eng._apply_pending[other] == (DECISION_NOOP, True)
    assert eng._apply_q.qsize() == 2
    # a key over its failure budget is left alone by a collected tick
    # (an event resets the budget first: new data)
    spent = ("default", "c")
    eng._apply_failures[spent] = eng.max_apply_retries + 1
    eng.fused_apply([(spent, DECISION_NOOP, True)])
    assert spent not in eng._apply_pending and eng._apply_q.qsize() == 2


@pytest.mark.parametrize("slow_apply_ms,rounds", [(0, 100), (4, 100)])
def test_spec_and_status_that_meet_in_flight_both_land(slow_apply_ms, rounds):
    """One tenant, one object, nothing else: a spec write, and a status
    the location writes while that spec's apply is pending (slow applies)
    or as soon as its copy shows it (fast ones). No other key's event
    ever makes a tick, so whatever is skipped behind a pending apply and
    not re-armed is lost for good."""

    async def main():
        up, down, syncer, eng = await _pair()
        key = ("default", "a")
        idle = (DECISION_NOOP, True)  # no apply, or a status upsync's own
        met = 0
        try:
            body = cm("a", {"gen": "0"})
            up.create("configmaps", body)
            await _until(lambda: down.get("configmaps", "a", "default"),
                         90, "the copy reached the location")
            before = _now()
            for rnd in range(1, rounds + 1):
                body = cm("a", {"gen": str(rnd)})
                up.update("configmaps", body)
                if slow_apply_ms:
                    # a spec apply is pending (or already re-armed by a
                    # second patch of the same collect)
                    await _until(
                        lambda: eng._apply_pending.get(key, idle) != idle,
                        5, f"round {rnd}: the spec patch was handed over",
                        interval=0)
                    met += 1
                else:
                    await _until(
                        lambda: down.get("configmaps", "a", "default")[
                            "data"] == body["data"], 5,
                        f"round {rnd}: the spec never reached the location")
                status = {"observedGen": str(rnd)}
                _write_status(down, "a", status)
                t0 = time.monotonic()
                while True:
                    d = down.get("configmaps", "a", "default")
                    u = up.get("configmaps", "a", "default")
                    if d["data"] == body["data"] and u.get("status") == status:
                        break
                    assert time.monotonic() - t0 < 5, (
                        f"round {rnd}: lost: downstream {d['data']}, "
                        f"upstream status {u.get('status')}")
                    await asyncio.sleep(0.001)
            await _until(lambda: not eng._apply_pending and not eng._dirty,
                         5, "quiet")
            # the last event's tick may still be inside its window
            await _until(lambda: device_mirror_equal(eng, key), 5,
                         "the device mirror ends equal")
            return _rise(before), met
        finally:
            await syncer.stop()

    if slow_apply_ms:
        faults.install(faults.FaultInjector(
            f"syncer.apply:latency={slow_apply_ms}ms", seed=39))
    try:
        rise, met = asyncio.run(main())
    finally:
        faults.clear()
    assert rise[UPSYNCS] >= rounds, rise
    if slow_apply_ms:
        # every round's status met the pending spec apply and fell to
        # the tick: the test is known to reach the branch
        assert met == rounds and rise[DEFERRED] >= rounds, (rise, met)
    else:
        assert rise[DIRECT] >= 0.9 * rounds, rise


# ------------------------------------------------- (d) nothing is queued


@pytest.mark.parametrize("case", [
    "resync-replay", "no-upstream-object", "equal-statuses", "deleted",
    "no-write-awaits-it", "after-the-answer", "differs"])
def test_what_an_event_must_carry_to_be_handed_over(case):
    eng = _bare_engine()
    key = ("default", "a")
    if case != "no-write-awaits-it":
        _carried_down(eng, key)
    if case == "after-the-answer":
        eng._dirty[key].state = _DONE  # the first status went up already
    up_status = {"ready": True} if case == "equal-statuses" else None
    if case != "no-upstream-object":
        eng.up_informer.cache[("tenant", "default", "a")] = _stored(
            "a", up_status)
    old, new = _stored("a", None, "2"), _stored("a", {"ready": True}, "3")
    if case == "resync-replay":
        old = new
    if case == "deleted":
        old, new = new, None
    eng._on_down_event("DELETED" if new is None else "MODIFIED", old, new)
    # the row goes to the core whatever the event carries
    assert eng.core.enqueued == [(True, key)]
    if case == "differs":  # the control: this one IS handed over
        assert eng._apply_pending == {key: (DECISION_NOOP, True)}
        assert eng._apply_q.get_nowait() == (key, DECISION_NOOP, True, True)
    else:
        assert not eng._apply_pending and eng._apply_q.empty()


def test_a_key_whose_location_reports_progress_keeps_its_tick():
    eng = _bare_engine()
    key = ("default", "a")
    eng.up_informer.cache[("tenant", "default", "a")] = _stored("a", None)
    rv = iter(range(2, 99))

    def write() -> None:
        """A tenant's write, carried down."""
        eng._stage_up(key, _stored("a", None, str(next(rv))), True)
        eng._dirty[key].state = _PATCHED

    def status(n: int) -> bool:
        """A status event of the location's; was it handed over?"""
        eng._on_down_event("MODIFIED", _stored("a", {"n": n - 1}, "1"),
                           _stored("a", {"n": n}, str(next(rv))))
        handed = eng._apply_pending.pop(key, None) is not None
        if handed:  # the apply wrote: the entry awaits the write's echo
            eng._dirty[key].state = _DONE
        return handed

    write()
    assert status(1)        # nothing is known of the key: handed over
    assert not status(2)    # after the answer: the tick's
    assert eng._reports == {key: True}
    write()
    assert not status(3)    # the location reported progress last time
    assert eng._reports == {key: False}
    eng._dirty[key].state = _DONE  # (the tick carried it up)
    write()                 # that write was answered once: the mark goes
    assert key not in eng._reports
    assert status(4)
    assert not status(5) and key in eng._reports
    eng._on_down_event("DELETED", _stored("a", {"n": 5}, "9"), None)
    assert key not in eng._reports  # the copy is gone, and what was known


def test_the_host_backend_keeps_its_tick():
    eng = BatchSyncEngine(Client(LogicalStore(), "tenant"),
                          Client(LogicalStore(), "pcluster"),
                          "configmaps", "c1", backend="host")
    eng._apply_q = asyncio.Queue()
    eng.up_informer.cache[("tenant", "default", "a")] = _stored("a", None)
    _carried_down(eng, ("default", "a"))
    eng._on_down_event("MODIFIED", _stored("a", None, "2"),
                       _stored("a", {"ready": True}, "3"))
    assert not eng._apply_pending and eng._apply_q.empty()
    assert len(eng.controller.queue) == 1


# ------------------------------------------ (e) host and fused end equal


_VOLATILE = ("uid", "resourceVersion", "creationTimestamp", "generation",
             "managedFields")


def _dump(client) -> dict:
    out = {}
    for obj in client.list("configmaps", namespace="default")[0]:
        meta = {k: v for k, v in obj["metadata"].items()
                if k not in _VOLATILE}
        out[obj["metadata"]["name"]] = {**obj, "metadata": meta}
    return out


async def _churn(backend: str, seed: int) -> tuple[dict, dict, dict]:
    up, down, syncer, eng = await _pair(backend)
    agent = agents.StatusEcho(down)
    await agent.start()
    rng = random.Random(seed)
    want: dict[str, str] = {}  # name -> gen
    try:
        for step in range(120):
            name = f"cm-{rng.randrange(12)}"
            roll = rng.random()
            gen = str(step)
            if name not in want:
                up.create("configmaps", cm(name, {"gen": gen,
                                                  "pad": "x" * rng.randrange(9)}))
                want[name] = gen
            elif roll < 0.15:
                up.delete("configmaps", name, "default")
                del want[name]
            else:
                up.update("configmaps", cm(name, {"gen": gen}))
                want[name] = gen
            pause = rng.choice([None, None, 0, 0.001, 0.004])
            if pause is not None:
                await asyncio.sleep(pause)

        def settled() -> bool:
            u, d = _dump(up), _dump(down)
            return (set(u) == set(d) == set(want) and all(
                u[n].get("status") == d[n].get("status")
                == {"observedGen": g} and d[n]["data"] == u[n]["data"]
                for n, g in want.items()))

        await _until(settled, 90, f"{backend}: the churn settled", 0.01)
        await asyncio.sleep(0.1)
        assert settled() and agent.errors == 0
        return _dump(up), _dump(down), want
    finally:
        await agent.stop()
        await syncer.stop()


@pytest.mark.parametrize("seed", [39, 2**31 + 39])
def test_host_and_fused_backends_end_in_the_same_stores(seed):
    before = _now()
    f_up, f_down, f_want = asyncio.run(_churn("tpu", seed))
    assert _rise(before)[DIRECT] > 0  # the fused run took the new path
    mid = _now()
    h_up, h_down, h_want = asyncio.run(_churn("host", seed))
    assert _rise(mid)[DIRECT] == 0  # the plain reference keeps its tick
    assert f_want == h_want and f_want
    assert f_up == h_up
    assert f_down == h_down


# ----------------------------------------------- (f) a rolling walk's trail


@pytest.mark.parametrize("old,new", [(5, 3), (250, 200)])
def test_a_rolling_walk_delivers_a_trail_the_reference_accepts(
        old, new, monkeypatch):
    monkeypatch.setattr(rolling_agent, "POD_READY_MS", 5)
    steps = len(ref.rollout_statuses(old, new))
    assert steps == (7 if (old, new) == (5, 3) else 5)

    async def main():
        up = Client(LogicalStore(), "tenant")
        down = Client(LogicalStore(), "pcluster")
        syncer = await start_syncer(up, down, [shape.RESOURCE], "loc0",
                                    backend="tpu")
        agent = rolling_agent.RollingDeployment(down)
        await agent.start()
        trail: list = []
        watcher = Informer(up, shape.RESOURCE)
        watcher.add_handler(lambda _t, _o, obj: trail.append(
            (obj or {}).get("status")))
        try:
            rng = random.Random(39)
            body = shape.new("deployment-000-00000039", rng, ["loc0"])
            body["spec"]["replicas"] = old
            name = body["metadata"]["name"]
            up.create(shape.RESOURCE, body)
            await _until(lambda: ref.complete(body["spec"], up.get(
                shape.RESOURCE, name, shape.NAMESPACE).get("status")),
                90, "the first rollout is upstream")
            await watcher.start()
            await asyncio.sleep(0.05)
            trail.clear()
            before = _now()
            body = shape.mutate(body, rng)
            body["spec"]["replicas"] = new
            up.update(shape.RESOURCE, body)
            await _until(lambda: ref.complete(body["spec"], up.get(
                shape.RESOURCE, name, shape.NAMESPACE).get("status")),
                10, "the rollout is upstream")
            await asyncio.sleep(0.05)
            assert agent.errors == 0
            return body, list(trail), _rise(before)
        finally:
            await watcher.stop()
            await agent.stop()
            await syncer.stop()

    body, trail, rise = asyncio.run(main())
    assert ref.trail_mismatches(old, body, trail) == [], trail
    # the create's walk showed that this location reports progress, so
    # every status of the update's walk rode its tick, where two that
    # meet go up as one (never more trips than steps)
    assert rise[DIRECT] == 0 and 1 <= rise[UPSYNCS] <= steps, rise
