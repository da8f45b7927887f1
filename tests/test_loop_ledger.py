"""The serving loop's ledger of its own time (kcp_tpu/obs/runtime.py
``LoopLedger``, kcp_tpu/obs/trace.py ``annotate``): busy, idle and CPU
seconds of a driven loop, self seconds by section, leaks, long passes
and their ring, ``/debug/loop``, what a closed profiler costs, and what
the beat publishes. Every test drives a loop of its own under a time
limit of its own; none sleeps more than a few hundred ms."""

import asyncio
import json
import sys
import time
import types

import pytest

from kcp_tpu import obs
from kcp_tpu.obs import runtime, trace
from kcp_tpu.obs.runtime import LONG_PASS_S, RING, LoopLedger, RuntimeProbes
from kcp_tpu.utils.trace import REGISTRY

LIMIT_S = 20.0


def drive(main, limit: float = LIMIT_S):
    """Run ``main(loop)`` on a fresh loop under ``limit`` seconds."""
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(main(loop), limit))
    finally:
        loop.close()


def counters() -> dict[str, float]:
    return {k: v for k, v in REGISTRY.snapshot().items()
            if k.startswith("server_loop_") and not isinstance(v, dict)}


def rise(before: dict, after: dict) -> dict[str, float]:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def spin(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


async def passes(n: int = 1) -> None:
    for _ in range(n):
        await asyncio.sleep(0)


def test_busy_plus_idle_is_the_wall_time_of_a_driven_loop():
    async def main(loop):
        probes = RuntimeProbes(loop).start()
        await passes()  # the ledger learns the loop's thread
        led = probes.ledger
        b0, i0, t0 = led.busy_seconds, led.idle_seconds, time.monotonic()
        for _ in range(20):
            await asyncio.sleep(0.01)  # idle
            spin(0.003)  # busy
        await passes()
        wall = time.monotonic() - t0
        busy, idle = led.busy_seconds - b0, led.idle_seconds - i0
        probes.stop()
        return wall, busy, idle

    wall, busy, idle = drive(main)
    assert busy + idle == pytest.approx(wall, rel=0.01)
    assert 0.06 <= busy < wall and idle >= 0.15


def test_a_pass_that_blocks_is_busy_and_not_on_the_cpu():
    async def main(loop):
        probes = RuntimeProbes(loop).start()
        await passes()
        led = probes.ledger
        await asyncio.sleep(0.002)  # CPU time is taken between waits
        b0, c0 = led.busy_seconds, led.cpu_seconds
        time.sleep(0.12)  # holds the loop, off the CPU
        await asyncio.sleep(0.002)
        blocked = (led.busy_seconds - b0, led.cpu_seconds - c0)
        b0, c0 = led.busy_seconds, led.cpu_seconds
        spin(0.12)  # holds the loop, on the CPU
        await asyncio.sleep(0.002)
        spun = (led.busy_seconds - b0, led.cpu_seconds - c0)
        probes.stop()
        return blocked, spun

    (busy, cpu), (busy2, cpu2) = drive(main)
    assert busy >= 0.12 and cpu < 0.03
    assert busy2 >= 0.12 and cpu2 >= 0.05  # a loaded machine preempts


def test_a_loop_that_never_waits_still_accounts_its_cpu_time(monkeypatch):
    """CPU time is taken between waits and at every beat: a saturated
    loop (every ``select`` has ready work behind it) publishes it too."""
    monkeypatch.setattr(runtime, "LAG_INTERVAL_S", 0.01)

    async def main(loop):
        probes = RuntimeProbes(loop).start()
        await asyncio.sleep(0.002)
        led = probes.ledger
        c0, i0, end = led.cpu_seconds, led.idle_seconds, time.monotonic() + 0.15
        while time.monotonic() < end:
            spin(0.002)
            await passes()  # never waits: ready work is always behind it
        got = led.cpu_seconds - c0, led.idle_seconds - i0
        probes.stop()
        return got

    cpu, idle = drive(main)
    assert cpu >= 0.08 and idle < 0.02


def test_a_parents_self_time_is_its_duration_less_its_children():
    async def main(loop):
        probes = RuntimeProbes(loop).start()
        await passes()
        led = probes.ledger
        b0 = led.busy_seconds
        t0 = time.perf_counter()
        with obs.annotate("kcp.test.parent"):
            spin(0.02)
            with obs.annotate("kcp.test.child"):
                spin(0.03)
                with obs.annotate("kcp.test.grandchild"):
                    spin(0.01)
            with obs.annotate("kcp.test.child"):
                spin(0.01)
        outer = time.perf_counter() - t0
        await passes()
        got = {n: led.sections[n].seconds for n in
               ("kcp.test.parent", "kcp.test.child", "kcp.test.grandchild")}
        busy = led.busy_seconds - b0
        probes.stop()
        return got, outer, busy

    got, outer, busy = drive(main)
    # at least what each spun itself (a preempted spin reads longer) ...
    assert 0.02 <= got["kcp.test.parent"] < 0.02 + (outer - 0.07) + 1e-4
    assert 0.04 <= got["kcp.test.child"] < 0.04 + (outer - 0.07) + 1e-4
    assert 0.01 <= got["kcp.test.grandchild"] < 0.01 + (outer - 0.07) + 1e-4
    # ... self times partition the outermost section, and lie inside a pass
    assert sum(got.values()) == pytest.approx(outer, abs=0.001)
    assert sum(got.values()) <= busy


def test_stamps_handed_over_count_like_the_with_form():
    """``begin(now)`` / ``end(now)``: the tick's phases and the store's
    fan-out read the clock once and hand the stamp over."""
    async def main(loop):
        probes = RuntimeProbes(loop).start()
        await passes()
        outer = obs.annotate("kcp.test.handed")
        outer.begin(100.0)
        inner = obs.annotate("kcp.test.handed.inner")
        inner.begin(100.25)
        inner.end(100.75)
        outer.end(101.0)
        led = probes.ledger
        got = (led.sections["kcp.test.handed"].seconds,
               led.sections["kcp.test.handed.inner"].seconds, list(led.stack))
        probes.stop()
        return got

    assert drive(main) == (0.5, 0.5, [])


def test_a_section_left_open_across_an_await_is_counted_and_swept():
    async def main(loop):
        probes = RuntimeProbes(loop).start()
        await passes()
        led = probes.ledger
        leaks0 = led.section_leaks
        stale = obs.annotate("kcp.test.leaky")
        stale.__enter__()
        await passes()  # the loop goes back to select with it open
        assert led.section_leaks == leaks0 + 1 and led.cur is None
        # a later pass is accounted as if nothing had happened ...
        with obs.annotate("kcp.test.after"):
            spin(0.01)
            # ... and the stale section's late exit closes nobody else's
            stale.__exit__(None, None, None)
        assert led.cur is None and not led.stack
        await passes()
        got = (led.section_leaks - leaks0,
               led.sections["kcp.test.after"].seconds,
               led.sections["kcp.test.leaky"].seconds)
        probes.stop()
        return got

    before = counters()
    leaks, after, leaky = drive(main)
    assert leaks == 1 and leaky == 0.0
    assert after >= 0.01
    published = rise(before, counters())
    assert published["server_loop_section_leaks_total"] == 1.0


def test_an_exception_inside_a_section_closes_it():
    async def main(loop):
        probes = RuntimeProbes(loop).start()
        await passes()
        led = probes.ledger
        with pytest.raises(ValueError):
            with obs.annotate("kcp.test.outer"):
                with obs.annotate("kcp.test.raises"):
                    spin(0.005)
                    raise ValueError("boom")
        got = (list(led.stack), led.sections["kcp.test.raises"].seconds)
        leaks0 = led.section_leaks
        await passes()
        probes.stop()
        return got, led.section_leaks - leaks0

    (stack, seconds), leaks = drive(main)
    assert stack == [] and seconds >= 0.005 and leaks == 0


def test_two_probes_on_one_loop_wrap_the_selector_once():
    async def main(loop):
        plain = loop._selector.select
        a = RuntimeProbes(loop).start()
        wrapped = loop._selector.select
        b = RuntimeProbes(loop).start()
        assert a.ledger is b.ledger and a.ledger.users == 2
        assert loop._selector.select == wrapped != plain
        await passes(3)
        tid = a.ledger._tid
        assert trace._LEDGERS[tid] is a.ledger
        a.stop()
        assert loop._selector.select == wrapped  # b still counts
        await passes(3)
        assert obs.annotate("kcp.test.still") is not trace._NOOP
        b.stop()
        b.stop()  # idempotent, like the gc hook's
        assert loop._selector.select == plain
        assert tid not in trace._LEDGERS
        assert obs.annotate("kcp.test.still") is trace._NOOP
        await passes(3)  # and the loop still turns

    drive(main)


def test_a_loop_without_a_selector_serves_with_the_ledger_absent():
    class Bare:
        """A loop that is not asyncio's selector loop (no ``_selector``)."""

        def __init__(self):
            self.armed = []

        def call_later(self, delay, cb):
            self.armed.append(cb)
            return types.SimpleNamespace(cancel=lambda: None)

    loop = Bare()
    before = counters()
    probes = RuntimeProbes(loop).start()
    assert probes.ledger is None and LoopLedger.attach(loop) is None
    loop.armed.pop()()  # a beat: the lag is observed, nothing published
    probes.stop()
    got = rise(before, counters())
    assert got["server_loop_lag_seconds_count"] == 1
    assert got["server_loop_busy_seconds_total"] == 0.0


def _req(method, path, query=None, headers=None, body=b""):
    from kcp_tpu.server.httpd import Request

    return Request(method=method, path=path, query=query or {},
                   headers=headers or {}, body=body)


def test_the_ring_keeps_the_last_long_passes_and_debug_loop_serves_it(
        monkeypatch):
    from kcp_tpu.apis.scheme import default_scheme
    from kcp_tpu.server.authz import Authenticator, Authorizer
    from kcp_tpu.server.handler import RestHandler
    from kcp_tpu.store import LogicalStore

    # 2 ms stands for 50: the ring's rule is the same, the test is short
    monkeypatch.setattr(runtime, "LONG_PASS_S", 0.002)

    async def main(loop):
        store = LogicalStore()
        handler = RestHandler(store, default_scheme())
        # no ledger on this loop yet: an empty answer, not an error
        resp = await handler(_req("GET", "/debug/loop"))
        assert resp.status == 200 and json.loads(resp.body) == {}

        probes = RuntimeProbes(loop).start()
        await passes()
        led = probes.ledger
        t_first = time.monotonic()
        for i in range(RING + 6):
            with obs.annotate("kcp.test.big"):
                spin(0.0025)
            with obs.annotate("kcp.test.small"):
                pass
            await passes()
        assert led.long_passes >= RING + 6 and len(led.ring) == RING
        assert runtime.long_passes() == list(led.ring)
        resp = await handler(_req("GET", "/debug/loop"))
        body = json.loads(resp.body)
        probes.stop()

        # gated like /debug/profile: anonymous forbidden, admin served
        authn = Authenticator(tokens={"admin-tok": "admin"})
        gated = RestHandler(store, default_scheme(), authenticator=authn,
                            authorizer=Authorizer(store))
        denied = await gated(_req("GET", "/debug/loop"))
        allowed = await gated(_req(
            "GET", "/debug/loop", headers={"authorization": "Bearer admin-tok"}))
        store.close()
        return body, t_first, denied.status, allowed.status

    body, t_first, denied, allowed = drive(main)
    assert (denied, allowed) == (403, 200)
    ring = body["long_passes_recent"]
    assert len(ring) == RING and body["long_passes"] >= RING + 6
    # the LAST ones, oldest first, on time.monotonic()'s clock
    starts = [p["start"] for p in ring]
    assert starts == sorted(starts) and t_first < starts[0] <= body["now"]
    for p in ring:
        assert p["wall_s"] >= 0.002 and set(p) == {"start", "wall_s",
                                                   "sections"}
        assert p["sections"][0][0] == "kcp.test.big"
        assert 0.0025 <= p["sections"][0][1] <= p["wall_s"]
        assert len(p["sections"]) <= 3
    assert body["self_seconds"]["kcp.test.big"] >= 0.0025 * (RING + 6)
    assert body["busy_seconds"] > 0 and body["long_pass_threshold_s"] == 0.002


def test_the_ring_threshold_is_the_collectors_report_threshold():
    assert LONG_PASS_S == 0.05 and RING == 64


class CountingAnnotation:
    """Stands for ``jax.profiler.TraceAnnotation``: counts what is
    built, and is "enabled" as the test says."""

    built: list = []
    open_now = False

    def __init__(self, name, **stats):
        CountingAnnotation.built.append((name, stats))

    @classmethod
    def is_enabled(cls):
        return cls.open_now

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def profiler(monkeypatch):
    monkeypatch.setattr(trace, "_trace_annotation", CountingAnnotation)
    monkeypatch.setattr(CountingAnnotation, "built", [])
    monkeypatch.setattr(CountingAnnotation, "open_now", False)
    return CountingAnnotation


def test_a_closed_profiler_builds_no_annotation(profiler):
    """PR 29's regression: a ``TraceAnnotation`` built per section with
    the profiler closed cost 7 % of the median."""
    async def main(loop):
        assert obs.annotate("kcp.test.off", generation=2) is trace._NOOP
        probes = RuntimeProbes(loop).start()
        await passes()
        for _ in range(50):
            with obs.annotate("kcp.test.closed", generation=2):
                pass
            await passes()
        # one object per name, whatever the stats: nothing is formatted
        assert (obs.annotate("kcp.test.closed", generation=3)
                is probes.ledger.sections["kcp.test.closed"])
        probes.stop()

    drive(main)
    assert profiler.built == []


def test_an_open_profiler_names_sections_and_select(profiler, monkeypatch):
    monkeypatch.setattr(runtime, "LAG_INTERVAL_S", 0.005)

    async def main(loop):
        probes = RuntimeProbes(loop).start()
        await passes()
        profiler.open_now = True
        await asyncio.sleep(0.02)  # the ledger asks once a beat
        with obs.annotate("kcp.test.traced", generation=1):
            spin(0.005)
        await asyncio.sleep(0.01)
        profiler.open_now = False
        led = probes.ledger
        got = led.sections["kcp.test.traced"].seconds, dict(led.sections)
        probes.stop()
        return got

    seconds, sections = drive(main)
    names = [n for n, _ in profiler.built]
    assert ("kcp.test.traced", {"generation": 1}) in profiler.built
    assert "kcp.loop.select" in names
    # the ledger counted the section all the same; waiting has no slot
    assert seconds >= 0.005
    assert sections["kcp.loop.select"] is trace._NOOP


def test_an_open_profiler_annotates_a_thread_without_a_ledger(profiler):
    profiler.open_now = True
    with obs.annotate("kcp.remote.call"):
        pass
    ann = obs.annotate("kcp.test.handed")
    ann.begin(1.0)
    ann.end(2.0)
    assert [n for n, _ in profiler.built] == ["kcp.remote.call",
                                              "kcp.test.handed"]


def test_the_beat_publishes_and_a_stopped_probe_publishes_its_last_rise(
        monkeypatch):
    monkeypatch.setattr(runtime, "LAG_INTERVAL_S", 0.01)

    async def main(loop):
        before = counters()
        probes = RuntimeProbes(loop).start()
        await passes()
        with obs.annotate("kcp.test.published"):
            spin(0.004)
        await asyncio.sleep(0.05)  # several beats
        beat = rise(before, counters())
        led = probes.ledger
        # nothing is published twice: the registry holds what the ledger
        # held at the last beat
        assert beat["server_loop_busy_seconds_total"] <= led.busy_seconds
        with obs.annotate("kcp.test.published"):
            spin(0.004)
        probes._handle.cancel()  # no beat between here and stop
        probes._handle = loop.call_later(60, lambda: None)
        await passes()
        mid = rise(before, counters())
        probes.stop()
        return beat, mid, rise(before, counters()), led

    beat, mid, end, led = drive(main)
    name = "server_loop_self_seconds_kcp_test_published"
    assert beat[name] >= 0.004
    assert beat["server_loop_passes_total"] >= 3
    assert beat["server_loop_idle_seconds_total"] > 0.03
    assert mid[name] == beat[name]  # the second section waits for a beat
    assert end[name] >= 0.008 > 0 and end[name] - mid[name] >= 0.004
    assert end["server_loop_busy_seconds_total"] == pytest.approx(
        led.busy_seconds)
    assert end["server_loop_passes_total"] == led.passes
    assert end["server_loop_cpu_seconds_total"] == pytest.approx(
        led.cpu_seconds)


def test_collector_policy_lives_with_the_first_start_and_the_last_stop(
        monkeypatch):
    import gc

    # as in a process no server lives in, whatever ran before this file
    monkeypatch.setattr(RuntimeProbes, "_gc_users", 0)
    monkeypatch.setattr(RuntimeProbes, "_gc_found", None)
    policy, found = runtime.GC_THRESHOLDS, gc.get_threshold()
    odd = (found[0] + 1, found[1] + 1, found[2] + 1)
    gc.set_threshold(*odd)  # what the process had, not CPython's own

    async def main(loop):
        a, b = RuntimeProbes(loop).start(), RuntimeProbes(loop).start()
        assert gc.get_threshold() == policy
        a.stop()
        assert gc.get_threshold() == policy  # a server still lives
        a.stop()  # idempotent: no second count down
        assert gc.get_threshold() == policy
        b.stop()
        assert gc.get_threshold() == odd
        b.stop()
        # a later server restores what it finds THEN
        gc.set_threshold(*found)
        RuntimeProbes(loop).start().stop()
        return gc.get_threshold()

    try:
        assert drive(main) == found
    finally:
        gc.set_threshold(*found)
    assert policy[0] > 3000 and policy[1:] == (10, 10)


def test_a_burst_is_freed_before_the_collector_looks_and_cycles_still_go():
    import gc

    class Node:
        pass

    async def main(loop):
        probes = RuntimeProbes(loop).start()
        try:
            gc.collect()  # the young count starts from nothing
            runs = [g["collections"] for g in gc.get_stats()]
            # one page of a paged walk: 3,000 fresh tuples, then dropped
            page = [(i, str(i)) for i in range(3000)]
            assert len(page) == 3000
            del page
            assert [g["collections"] for g in gc.get_stats()] == runs
            # nothing was disabled: a cycle made under the policy goes
            a, b = Node(), Node()
            a.other, b.other = b, a
            del a, b
            assert gc.isenabled() and gc.collect() >= 2
        finally:
            probes.stop()

    drive(main)


def test_a_gc_callback_under_a_half_imported_jax_does_not_raise(monkeypatch):
    """``sys.modules`` has ``jax`` before ``jax.profiler`` exists; a
    collection can fire there (PERF.md §7's hazard)."""
    monkeypatch.setattr(trace, "_trace_annotation", None)
    monkeypatch.setitem(sys.modules, "jax", types.ModuleType("jax"))
    h = REGISTRY.histogram("py_gc_pause_seconds")

    async def main(loop):
        probes = RuntimeProbes(loop).start()
        await passes()
        n0 = h.n
        runtime._on_gc("start", {"generation": 2})
        spin(0.003)
        runtime._on_gc("stop", {"generation": 2})
        # no annotation, and the ledger still counts the collection
        got = h.n - n0, probes.ledger.sections["kcp.gc"].seconds
        probes.stop()
        return got

    observed, seconds = drive(main)
    assert observed >= 1 and seconds >= 0.003
    assert trace._trace_annotation is None  # nothing half-bound
    # off the loop's thread too: the no-op, not an AttributeError
    assert obs.annotate("kcp.gc", generation=0) is trace._NOOP


def test_the_fanouts_histogram_is_fed_from_one_clock_pair():
    """``store_emit_seconds`` comes from the stamps the fan-out hands
    its section, with a ledger on the thread or without one."""
    from kcp_tpu.store import LogicalStore

    h = REGISTRY.histogram("store_emit_seconds")

    def write(store, name):
        store.create("configmaps", "t", {
            "apiVersion": "v1", "kind": "ConfigMap",
            "metadata": {"name": name, "namespace": "default"}})
        store._flush_events()

    async def main(loop):
        store = LogicalStore()
        w = store.watch("configmaps", "t")
        n0 = h.n
        write(store, "a")  # no ledger on this thread
        probes = RuntimeProbes(loop).start()
        await passes()
        write(store, "b")
        got = h.n - n0, probes.ledger.sections["kcp.store.fanout"].seconds
        probes.stop()
        w.close()
        store.close()
        return got

    observed, seconds = drive(main)
    assert observed == 2 and seconds > 0
