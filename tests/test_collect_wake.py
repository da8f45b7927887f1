"""The completion wake: on an asynchronous backend a waiter thread blocks
on each in-flight wire and wakes the serving loop when it is on the host
(``FusedCore._watch`` / ``_wait_for_wires`` / ``_on_wire_ready``); nothing
polls. The CPU backend keeps the quiet-loop ``IDLE_FLUSH_S`` collect.

The wake path is forced on the CPU as ``tests/test_fused_core.py`` does
(``core._eager_collect = True``); a fake wire turns ready when its
``threading.Event`` is set, and its ``__array__`` blocks until then, as a
device array's host copy does.
"""

from __future__ import annotations

import asyncio
import random
import sys
import threading

import numpy as np
import pytest

from kcp_tpu.client import Client
from kcp_tpu.store import LogicalStore
from kcp_tpu.syncer import start_syncer
from kcp_tpu.syncer.core import IDLE_FLUSH_S, FusedCore
from kcp_tpu.utils.trace import REGISTRY

WAITER = "fused-wire-waiter"


class FakeWire:
    def __init__(self, tag: int):
        self.tag = tag
        self.done = threading.Event()

    def is_ready(self) -> bool:
        return self.done.is_set()

    def __array__(self, dtype=None, copy=None):
        assert self.done.wait(10.0), f"wire {self.tag} never turned ready"
        return np.array([self.tag])


class FakeFleet:
    """Stands where ``FleetBatch`` does at collect time: records the
    order of the collects and what the loop had armed at each."""

    def __init__(self, loop, fail_on=()):
        self.loop, self.fail_on = loop, set(fail_on)
        self.collected: list[int] = []
        self.timers_at_collect: list[int] = []
        self.changed = asyncio.Event()

    def submit(self):
        return None  # a tick with nothing dirty

    def dispatch(self, wire, meta) -> bool:
        tag = int(np.asarray(wire)[0])
        self.collected.append(tag)
        self.timers_at_collect.append(len(self.loop._scheduled))
        self.changed.set()
        if tag in self.fail_on:
            raise RuntimeError(f"collect of wire {tag} failed")
        return False


def woken_core(fail_on=()) -> tuple[FusedCore, FakeFleet]:
    core = FusedCore(batch_window=0.0005)
    core._eager_collect = True  # the asynchronous backend's path, on the CPU
    core._started = True        # so that stop() runs its shutdown
    fleet = FakeFleet(asyncio.get_running_loop(), fail_on)
    core._fleet = fleet
    return core, fleet


def fly(core: FusedCore, *wires: FakeWire) -> None:
    """What ``_tick`` does with a submitted wire."""
    for wire in wires:
        core._inflight.append((wire, (0, 8)))
        core._watch(wire)


async def until(fleet: FakeFleet, n: int) -> None:
    """Wait for ``n`` collects on a bare event: the loop arms no timer,
    and a watchdog THREAD (not a loop timer) bounds the wait."""
    loop = asyncio.get_running_loop()
    dog = threading.Timer(10.0, loop.call_soon_threadsafe, (fleet.changed.set,))
    dog.start()
    try:
        while len(fleet.collected) < n and dog.is_alive():
            fleet.changed.clear()
            await fleet.changed.wait()
    finally:
        dog.cancel()
    assert len(fleet.collected) >= n, fleet.collected


def waiters() -> list[threading.Thread]:
    return [t for t in threading.enumerate() if t.name == WAITER]


def counter(name: str) -> float:
    return REGISTRY.snapshot().get(name, 0.0)


def test_a_ready_wire_is_collected_by_a_wake_with_no_timer_armed():
    async def main():
        loop = asyncio.get_running_loop()
        core, fleet = woken_core()
        wire = FakeWire(1)
        woken0 = counter("fused_collect_woken_total")
        ready0 = counter("fused_wire_ready_seconds_count")
        lag0 = counter("fused_collect_lag_seconds_count")
        fly(core, wire)
        await asyncio.sleep(0)
        await asyncio.sleep(0)
        # the waiter is parked on the wire; the loop holds no timer and
        # no flush task for it
        assert core._inflight and not fleet.collected
        assert loop._scheduled == [] and core._flush_task is None
        assert len(waiters()) == 1 and waiters()[0].daemon
        wire.done.set()
        await until(fleet, 1)
        assert fleet.collected == [1] and not core._inflight
        assert fleet.timers_at_collect == [0]
        assert counter("fused_collect_woken_total") == woken0 + 1
        assert counter("fused_wire_ready_seconds_count") == ready0 + 1
        assert counter("fused_collect_lag_seconds_count") == lag0 + 1
        await core.stop()

    asyncio.run(main())


def test_wires_are_collected_in_submit_order_when_the_second_is_ready_first():
    async def main():
        core, fleet = woken_core()
        first, second = FakeWire(1), FakeWire(2)
        fly(core, first, second)
        second.done.set()
        for _ in range(5):
            await asyncio.sleep(0.002)
        assert fleet.collected == []  # the head is not ready: nothing moves
        first.done.set()
        await until(fleet, 2)
        assert fleet.collected == [1, 2] and not core._inflight
        await core.stop()

    asyncio.run(main())


def test_a_head_the_depth_rule_took_is_not_collected_twice():
    async def main():
        core, fleet = woken_core()
        depth0 = counter("fused_collect_depth_total")
        woken0 = counter("fused_collect_woken_total")
        wires = [FakeWire(i) for i in (1, 2, 3)]
        fly(core, *wires)
        # a tick's depth rule: three in flight, the window is two — its
        # blocking fetch takes the head while the waiter is parked on it
        core.fetch_depth = 2
        threading.Timer(0.02, wires[0].done.set).start()
        core._tick([], 0.0)
        assert fleet.collected == [1] and len(core._inflight) == 2
        assert counter("fused_collect_depth_total") == depth0 + 1
        # the head's wake arrives and finds the next head not ready
        for _ in range(5):
            await asyncio.sleep(0.002)
        assert fleet.collected == [1]
        wires[1].done.set()
        wires[2].done.set()
        await until(fleet, 3)
        assert fleet.collected == [1, 2, 3]
        assert counter("fused_collect_woken_total") == woken0 + 2
        await core.stop()

    asyncio.run(main())


def test_a_collect_that_raises_does_not_stop_the_next_wake():
    async def main():
        loop = asyncio.get_running_loop()
        reported = []
        loop.set_exception_handler(lambda _l, ctx: reported.append(ctx))
        core, fleet = woken_core(fail_on={1})
        first, second = FakeWire(1), FakeWire(2)
        fly(core, first, second)
        first.done.set()
        await until(fleet, 1)
        assert not waiters() or waiters()[0].is_alive()
        second.done.set()
        await until(fleet, 2)
        assert fleet.collected == [1, 2] and not core._inflight
        assert len(reported) == 1
        assert "wire 1 failed" in str(reported[0]["exception"])
        assert core.collecting_tick_start is None
        await core.stop()

    asyncio.run(main())


@pytest.mark.parametrize("when", ["while-blocked", "after-wake", "twice"])
def test_stop_joins_the_waiter_drains_and_leaves_no_thread(when):
    async def main():
        core, fleet = woken_core()
        depth0 = counter("fused_collect_depth_total")
        first, second = FakeWire(1), FakeWire(2)
        fly(core, first, second)
        if when == "after-wake":
            first.done.set()
            second.done.set()
            await until(fleet, 2)
        else:
            # the device finishes while stop() is joining the waiter
            threading.Timer(0.02, first.done.set).start()
            threading.Timer(0.03, second.done.set).start()
        await core.stop()
        if when == "twice":
            await core.stop()
        assert fleet.collected == [1, 2] and not core._inflight
        assert core._waiter is None and not waiters()
        drained = counter("fused_collect_depth_total") - depth0
        assert drained == (0 if when == "after-wake" else 2)
        # the wakes the waiter sent before it ended find nothing in flight
        await asyncio.sleep(0.005)
        assert fleet.collected == [1, 2]

    asyncio.run(main())


def test_a_wake_after_the_loop_closed_is_swallowed():
    wire = FakeWire(1)
    escaped = []

    async def main():
        core, _fleet = woken_core()
        fly(core, wire)
        await asyncio.sleep(0)
        return waiters()

    hook = threading.excepthook
    threading.excepthook = lambda args: escaped.append(args)
    try:
        (thread,) = asyncio.run(main())  # the loop closes, the core unstopped
        wire.done.set()
        thread.join(5.0)
    finally:
        threading.excepthook = hook
    assert not thread.is_alive()
    assert escaped == []


def test_many_wires_readied_from_another_thread_each_collected_once_in_order():
    """Stress: a short switch interval, wires readied in bursts and out
    of order by a third thread while the loop keeps taking its depth
    rule's share."""
    n = 200

    async def main():
        core, fleet = woken_core()
        core.fetch_depth = 2
        wires = [FakeWire(i) for i in range(n)]
        rng = random.Random(7)

        def ready_them():
            pending = list(wires)
            while pending:
                k = min(len(pending), rng.randint(1, 4))
                burst = pending[:k]
                del pending[:k]
                rng.shuffle(burst)
                for w in burst:
                    w.done.set()
                threading.Event().wait(rng.random() * 0.0005)

        feeder = threading.Thread(target=ready_them)
        feeder.start()
        for w in wires:
            fly(core, w)
            core._tick([], 0.0)  # the depth rule, as a tick applies it
            if rng.random() < 0.5:
                await asyncio.sleep(0)
        await core.stop()
        feeder.join(10.0)
        assert not feeder.is_alive()
        assert fleet.collected == list(range(n))
        assert not waiters()

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        asyncio.run(main())
    finally:
        sys.setswitchinterval(old)


def cm(name: str, data: dict) -> dict:
    return {"apiVersion": "v1", "kind": "ConfigMap",
            "metadata": {"name": name, "namespace": "default",
                         "labels": {"kcp.dev/cluster": "c1"}},
            "data": data}


async def eventually(cond, timeout: float = 10.0) -> None:
    loop = asyncio.get_running_loop()
    end = loop.time() + timeout
    while not cond():
        assert loop.time() < end, "condition not met in time"
        await asyncio.sleep(0.005)


@pytest.mark.parametrize("woken", [False, True], ids=["cpu-flush", "wake"])
def test_a_served_sync_collects_by_the_path_its_backend_gives(woken):
    """Real wires through a real syncer. The CPU backend (every test's)
    keeps the IDLE_FLUSH_S flush task and starts no thread; with the
    asynchronous backend's path forced, the same sync converges through
    the waiter thread and arms no flush task."""

    async def main():
        core = FusedCore.for_current_loop()
        if woken:
            core._eager_collect = True
        woken0 = counter("fused_collect_woken_total")
        kcp, phys = LogicalStore(), LogicalStore()
        up, down = Client(kcp, "t"), Client(phys, "p")
        syncer = await start_syncer(up, down, ["configmaps"], "c1",
                                    backend="tpu")
        assert syncer.engines[0]._section.core is core
        for i in range(12):
            up.create("configmaps", cm(f"cm-{i}", {"v": str(i)}))
        await eventually(lambda: len(down.list("configmaps")[0]) == 12)
        obj = up.get("configmaps", "cm-3", "default")
        obj["data"] = {"v": "again"}
        up.update("configmaps", obj)
        await eventually(lambda: down.get(
            "configmaps", "cm-3", "default")["data"]["v"] == "again")
        await eventually(lambda: not core._inflight)
        rose = counter("fused_collect_woken_total") - woken0
        if woken:
            assert core._flush_task is None and len(waiters()) == 1
            assert rose >= 1
        else:
            assert core._eager_collect is False
            assert core._flush_task is not None and not waiters()
            assert rose == 0
        await syncer.stop()
        assert not waiters() and not core._inflight

    asyncio.run(main())


def test_the_cpu_flush_waits_for_a_quiet_loop():
    """IDLE_FLUSH_S as it was: armed by a tick that leaves a wire in
    flight, re-armed by the next, and collecting only after the quiet."""

    async def main():
        loop = asyncio.get_running_loop()
        core, fleet = woken_core()
        core._eager_collect = False
        wire = FakeWire(1)
        wire.done.set()
        core._inflight.append((wire, (0, 8)))
        core._tick([], 0.0)
        first = core._flush_task
        assert first is not None and not waiters()
        await asyncio.sleep(0)  # the flush task starts its quiet wait
        assert [0 < h.when() - loop.time() <= IDLE_FLUSH_S
                for h in loop._scheduled if not h.cancelled()] == [True]
        core._tick([], 0.0)  # a tick inside the quiet re-arms the flush
        assert core._flush_task is not first
        await asyncio.sleep(0)
        assert first.done() and fleet.collected == []
        await until(fleet, 1)
        assert fleet.collected == [1] and not core._inflight
        assert IDLE_FLUSH_S == 0.003
        await core.stop()

    asyncio.run(main())


@pytest.mark.parametrize("meshed", [False, True], ids=["one-device", "mesh-8"])
def test_a_woken_fleet_emits_the_oracles_streams(monkeypatch, meshed):
    """The fleet's differential fuzz (tests/test_fleet.py: a multi-bucket
    churn schedule against a numpy oracle) with every between-tick
    collect made by a wake — on one device and with the wire replicated
    over an 8-device virtual mesh, where the waiter thread's fetch reads a
    sharded array: which collects happen and their order is invisible in
    the patch streams."""
    from test_fleet import _run_schedule, _stream_bytes

    monkeypatch.setattr(FusedCore, "_collects_by_wake", lambda self: True)

    async def main():
        mesh = None
        if meshed:
            from kcp_tpu.parallel.mesh import make_mesh

            mesh = make_mesh(n_devices=8, tenants=8, slots=1)
        woken0 = counter("fused_collect_woken_total")
        ready0 = counter("fused_wire_ready_seconds_count")
        owners, oracles, core = await _run_schedule(5, mesh=mesh)
        # the wakes the waiter sent before stop() joined it run (and
        # observe their wire) in the loop's next pass
        await asyncio.sleep(0)
        await asyncio.sleep(0)
        assert ([_stream_bytes(o.stream) for o in owners]
                == [_stream_bytes(o.stream) for o in oracles])
        ticks = core._fleet.stats["ticks"]
        assert counter("fused_wire_ready_seconds_count") - ready0 == ticks
        assert 1 <= counter("fused_collect_woken_total") - woken0 <= ticks
        assert core._waiter is None and not waiters()

    asyncio.run(main())
