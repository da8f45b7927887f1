"""The way in and the way out (kcp_tpu/server/httpd.py ``_StampedProtocol``,
kcp_tpu/obs/trace.py's ``ingress`` phase and edge log, kcp_tpu/obs/
runtime.py ``HandleTable``): a request is stamped from the loop pass that
read its first byte, a kept key's request and frames are logged on the
clock a client on the same machine reads, and the loop's passes are told
apart by handle while a profiler slice is open. Every test serves a
server (or drives a loop) of its own under a time limit of its own."""

import asyncio
import json
import socket
import sys
import time

import pytest

from kcp_tpu import obs
from kcp_tpu.obs import runtime, trace
from kcp_tpu.obs.runtime import LoopLedger, RuntimeProbes
from kcp_tpu.server import Config, RestClient
from kcp_tpu.server.threaded import ServerThread
from kcp_tpu.utils.trace import REGISTRY

LIMIT_S = 20.0
RUN_CODE = asyncio.events.Handle._run.__code__


def cm(name, data=None, **labels):
    meta = {"name": name, "namespace": "default"}
    if labels:
        meta["labels"] = labels
    return {"apiVersion": "v1", "kind": "ConfigMap", "metadata": meta,
            "data": data or {"k": "v"}}


def served(tls: bool = False) -> ServerThread:
    return ServerThread(Config(durable=False, install_controllers=False,
                               tls=tls))


def spy(st: ServerThread) -> list:
    """Every request the server's handler is handed, in order."""
    seen: list = []
    inner = st.server.http.handler

    async def handler(req):
        seen.append(req)
        return await inner(req)

    st.server.http.handler = handler
    return seen


def hist_n(name: str) -> int:
    return REGISTRY.histogram(name).n


def rose_to(name: str, n0: int, by: int) -> bool:
    """Whether histogram ``name`` has risen by exactly ``by`` since
    ``n0``, given a moment: the way out is stamped after the response
    is on the wire, so a client may have read it first."""
    deadline = time.monotonic() + 2.0
    while hist_n(name) - n0 < by and time.monotonic() < deadline:
        time.sleep(0.002)
    return hist_n(name) - n0 == by


def names(kept: bool, n: int, prefix: str = "edge") -> list[str]:
    out = [f"{prefix}-{i}" for i in range(400)
           if obs.edge_kept(f"{prefix}-{i}") is kept]
    return out[:n]


def our_tools() -> list:
    mon = sys.monitoring
    return [t for t in range(6) if mon.get_tool(t) == runtime._TOOL_NAME]


@pytest.fixture(autouse=True)
def _fresh_edge_log():
    trace._EDGES.clear()
    yield
    trace._EDGES.clear()


# ---------------------------------------------------------------- way in


@pytest.mark.parametrize("tls", [False, True], ids=["plain", "tls"])
def test_a_request_is_stamped_from_the_pass_that_read_its_first_byte(tls):
    with served(tls) as st:
        reqs = spy(st)
        n0 = {h: hist_n(h) for h in (
            "http_ingress_wake_seconds", "request_served_seconds",
            "convergence_ingress_seconds")}
        c = RestClient(st.address, cluster="t", ca_data=st.ca_pem)
        t_a = time.monotonic()
        c.create("configmaps", cm("a"), namespace="default")
        assert c.get("configmaps", "a", "default")["data"] == {"k": "v"}
        t_b = time.monotonic()
        assert len(reqs) == 2
        for req in reqs:
            assert t_a <= req.rx <= req.fed <= req.t0 <= t_b, vars(req)
        # keep-alive: the hand-over cleared the slot, so the second
        # request was stamped anew, after the first was served
        assert reqs[1].rx > reqs[0].t0
        # every request feeds the wake-up and the served histogram, a
        # write the `ingress` phase as well
        assert rose_to("request_served_seconds",
                       n0["request_served_seconds"], 2)
        assert rose_to("http_ingress_wake_seconds",
                       n0["http_ingress_wake_seconds"], 2)
        assert rose_to("convergence_ingress_seconds",
                       n0["convergence_ingress_seconds"], 1)


def test_a_pipelined_second_request_carries_no_stamp_and_observes_nothing():
    with served() as st:
        reqs = spy(st)
        host, port = st.address.removeprefix("http://").split(":")
        wire = b""
        for name in ("p1", "p2"):
            body = json.dumps(cm(name)).encode()
            wire += (b"POST /clusters/t/api/v1/namespaces/default/configmaps"
                     b" HTTP/1.1\r\nHost: x\r\nContent-Type: application/"
                     b"json\r\nContent-Length: %d\r\n\r\n" % len(body)) + body
        n_in = hist_n("convergence_ingress_seconds")
        n_wake = hist_n("http_ingress_wake_seconds")
        n_served = hist_n("request_served_seconds")
        with socket.create_connection((host, int(port)), timeout=10) as s:
            s.sendall(wire)  # one segment: both requests in one read
            got = b""
            while got.count(b"HTTP/1.1 201") < 2:
                chunk = s.recv(65536)
                assert chunk, got
                got += chunk
        assert [r.path.rsplit("/", 1)[-1] for r in reqs] == [
            "configmaps", "configmaps"]
        first, second = reqs
        assert 0.0 < first.rx <= first.fed <= first.t0
        assert second.rx == 0.0 and second.fed == 0.0 and second.t0 > 0.0
        assert rose_to("request_served_seconds", n_served, 1)
        assert rose_to("convergence_ingress_seconds", n_in, 1)
        assert rose_to("http_ingress_wake_seconds", n_wake, 1)


def test_ingress_and_the_phases_telescope_from_rx_to_the_status_commit(
        monkeypatch):
    """Beside ``test_convergence_phases_sum_reconcile_in_process``: the
    same monolith round trip with the write coming over HTTP, so the
    timeline starts at the socket — ``ingress`` ends on the stamp
    ``write`` starts from, and so on to the status commit."""
    from kcp_tpu.client import Client
    from kcp_tpu.store import LogicalStore
    from kcp_tpu.syncer.engine import CLUSTER_LABEL, BatchSyncEngine

    monkeypatch.setenv("KCP_TRACE", "1")
    monkeypatch.setenv("KCP_TRACE_SAMPLE", "1")
    obs.TRACER.reconfigure()
    try:
        with served() as st:
            reqs = spy(st)

            async def start():
                phys = LogicalStore()
                engine = BatchSyncEngine(
                    Client(st.server.store, "tenant-1"), Client(phys, "phys"),
                    "configmaps", "loc-1", backend="host",
                    batch_window=0.002, resync_period=None)
                await engine.start()
                return engine, Client(phys, "phys"), phys

            engine, down, phys = st.submit(start())
            try:
                ctx = obs.TRACER.mint(sampled=True)
                c = RestClient(st.address, cluster="tenant-1")
                with obs.use(ctx):
                    c.create("configmaps",
                             cm("phased", **{CLUSTER_LABEL: "loc-1"}),
                             namespace="default")

                def answer():
                    try:
                        dobj = down.get("configmaps", "phased", "default")
                    except Exception:  # noqa: BLE001 — not synced yet
                        return False
                    dobj["status"] = {"ok": True}
                    down.update_status("configmaps", dobj)
                    return True

                deadline = time.monotonic() + LIMIT_S
                while not st.call(answer):
                    assert time.monotonic() < deadline, "never synced down"
                    time.sleep(0.01)
                while not (c.get("configmaps", "phased", "default")
                           .get("status") or {}).get("ok"):
                    assert time.monotonic() < deadline, "status never up"
                    time.sleep(0.01)
                chain = ("ingress", "write", "propagate", "stage", "tick",
                         "patch", "downstream", "upstatus")
                by: dict = {}
                while time.monotonic() < deadline and len(by) < len(chain):
                    for s in obs.TRACER.get(ctx.trace_id):
                        if s["name"].startswith("conv."):
                            by.setdefault(s["name"][5:], s)
                    time.sleep(0.01)
                assert set(chain) <= set(by), sorted(by)
            finally:
                st.submit(engine.stop())
                st.call(phys.close)
        # adjacent phases share their stamp (a span rounds to the us)
        for a, b in zip(chain, chain[1:]):
            assert by[a]["t0"] + by[a]["dur"] == pytest.approx(
                by[b]["t0"], abs=5e-6), (a, b)
        extent = (by["upstatus"]["t0"] + by["upstatus"]["dur"]
                  - by["ingress"]["t0"])
        assert sum(by[p]["dur"] for p in chain) == pytest.approx(
            extent, abs=2e-5)
        # and the timeline starts at the request's own `rx`
        write = next(r for r in reqs if r.method == "POST")
        assert by["ingress"]["t0"] == pytest.approx(
            write.rx + trace._MONO_TO_WALL, abs=5e-6)
        assert by["ingress"]["dur"] == pytest.approx(
            write.t0 - write.rx, abs=5e-6)
    finally:
        monkeypatch.undo()
        obs.TRACER.reconfigure()


def test_an_slo_breach_before_the_handler_is_force_recorded(monkeypatch):
    """A request that waited past the SLO BEFORE the handler's entry is
    recorded though the handler itself was fast, with the wait as
    ``ingress_s``."""
    monkeypatch.setenv("KCP_TRACE", "1")
    monkeypatch.setenv("KCP_TRACE_SAMPLE", "1000000")
    monkeypatch.setenv("KCP_TRACE_SLO_MS", "50")
    obs.TRACER.reconfigure()
    try:
        with served() as st:
            inner = st.server.http.handler

            async def late(req):
                # as if the request's bytes had waited 80 ms for the loop
                if req.method == "POST":
                    req.rx -= 0.08
                return await inner(req)

            st.server.http.handler = late
            c = RestClient(st.address, cluster="t")
            c.create("configmaps", cm("slow"), namespace="default")
            c.get("configmaps", "slow", "default")
        spans = [s for s in obs.TRACER.spans()
                 if s["name"] == "server.request"]
        assert len(spans) == 1, spans  # the fast GET is not recorded
        attrs = spans[0]["attrs"]
        assert attrs["slo_breach"] and attrs["method"] == "POST"
        assert 0.08 <= attrs["ingress_s"] < 0.2
        assert spans[0]["dur"] < 0.05  # the handler's own time was short
    finally:
        monkeypatch.undo()
        obs.TRACER.reconfigure()


# -------------------------------------------------------------- edge log


def test_the_edge_log_is_bounded():
    assert trace._EDGES.maxlen == 32768
    for i in range(trace._EDGES.maxlen + 100):
        obs.edge_append(("frame", "c", "n", 0.0, float(i)))
    log = obs.edges()
    assert len(log) == trace._EDGES.maxlen
    assert log[0][4] == 100.0 and log is not trace._EDGES


def test_a_kept_keys_records_bracket_a_clients_own_stamps():
    """The same keys at both sockets, and on the client's clock: a kept
    key's ``req`` record lies between the client's send and ack, its
    ``frame`` records between the commit and the client's sight of the
    event; a key the log does not keep leaves no record at all."""
    kept, dropped = names(True, 3), names(False, 3)
    assert len(kept) == 3 and len(dropped) == 3
    with served() as st:
        async def main():
            c = RestClient(st.address, cluster="t")
            watch = c.watch("configmaps")
            watch._ensure_started()
            while not watch.responded:
                await asyncio.sleep(0.005)
            stamps: dict = {}
            for name in kept + dropped:
                sent = time.monotonic()
                await asyncio.to_thread(
                    c.create, "configmaps", cm(name), "default")
                stamps[name] = {"sent": sent, "acked": time.monotonic()}
            left = set(stamps)
            while left:
                for ev in await watch.next_batch(max_wait=0.2):
                    stamps[ev.name]["seen"] = time.monotonic()
                    left.discard(ev.name)
            watch.close()
            return stamps

        stamps = asyncio.run(asyncio.wait_for(main(), LIMIT_S))
    log = obs.edges()
    assert {r[2] for r in log} == set(kept)
    for name in kept:
        reqs = [r for r in log if r[0] == "req" and r[2] == name]
        frames = [r for r in log if r[0] == "frame" and r[2] == name]
        assert len(reqs) == 1 and len(frames) == 1, (reqs, frames)
        _k, cluster, _n, rx, t0, t_out = reqs[0]
        mine = stamps[name]
        assert cluster == "t"
        assert mine["sent"] <= rx <= t0 <= t_out and t0 <= mine["acked"]
        # the way out is stamped once the bytes are on the wire: a client
        # on another thread may have read them first, by a thread switch
        assert t_out <= mine["acked"] + 0.05
        _k, cluster, _n, tm, t_handed = frames[0]
        assert cluster == "t"
        assert t0 <= tm <= t_handed <= mine["seen"]


# ---------------------------------------------------------- handle table


def drive(main, limit: float = LIMIT_S):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(main(loop), limit))
    finally:
        loop.close()


def spin(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def test_the_handle_table_covers_the_busy_seconds_of_its_passes():
    async def step_a():
        for _ in range(100):
            spin(0.0005)
            await asyncio.sleep(0)

    async def main(loop):
        probes = RuntimeProbes(loop).start()
        await asyncio.sleep(0)  # the ledger learns the loop's thread
        led = probes.ledger
        led._open_handles()
        table = led.handles
        assert table is not None and our_tools()
        for _ in range(100):
            loop.call_soon(spin, 0.0005)
        await asyncio.gather(step_a(), asyncio.sleep(0.05))
        report = table.report()
        led._close_handles()
        probes.stop()
        return report

    report = drive(main)
    kinds = report["kinds"]
    wall = sum(k["wall_s"] for k in kinds.values())
    assert wall == pytest.approx(report["busy_s"], rel=0.10), report
    # a task step by its coroutine, a plain callback by its name
    step = next(v for k, v in kinds.items()
                if k.startswith("task:") and k.endswith("step_a"))
    assert step["runs"] >= 100 and step["wall_s"] >= 0.05
    assert kinds["spin"]["runs"] == 100 and kinds["spin"]["wall_s"] >= 0.05
    # no section ran: every second of it is unnamed
    assert step["unnamed_s"] == pytest.approx(step["wall_s"])


def test_a_section_inside_a_task_step_is_not_the_handles_unnamed_time():
    async def worker():
        for _ in range(20):
            with obs.annotate("kcp.test.named"):
                spin(0.002)
            spin(0.001)
            await asyncio.sleep(0)

    async def main(loop):
        probes = RuntimeProbes(loop).start()
        await asyncio.sleep(0)
        led = probes.ledger
        led._open_handles()
        await worker()
        report = led.handles.report()
        led._close_handles()
        probes.stop()
        return report

    kinds = drive(main)["kinds"]
    # main's own steps run `worker` inline: the kind is main's coroutine
    step = next(v for k, v in kinds.items() if k.startswith("task:"))
    # (the step that opened the table is not in it: 19 of the 20 turns,
    # 2 ms named and 1 ms not in each)
    assert step["wall_s"] >= 0.05
    assert 0.25 * step["wall_s"] <= step["unnamed_s"] <= 0.45 * step["wall_s"]


class FakeAnnotation:
    """Stands for ``jax.profiler.TraceAnnotation``, "enabled" as the
    test says."""

    open_now = False

    def __init__(self, name, **stats):
        pass

    @classmethod
    def is_enabled(cls):
        return cls.open_now

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_nothing_is_registered_without_a_slice_and_nothing_is_left_after(
        monkeypatch):
    """The beat opens the table with the slice and closes it with it;
    a ledger that detaches with its table open frees the tool too."""
    monkeypatch.setattr(trace, "_trace_annotation", FakeAnnotation)
    monkeypatch.setattr(FakeAnnotation, "open_now", False)
    mon = sys.monitoring

    def registered() -> bool:
        tools = our_tools()
        return bool(tools) and bool(mon.get_local_events(tools[0], RUN_CODE))

    def counters() -> dict:
        return {k: v for k, v in REGISTRY.snapshot().items()
                if k.startswith("server_loop_handle_")}

    async def main(loop):
        c0 = counters()
        probes = RuntimeProbes(loop).start()
        await asyncio.sleep(0)
        led = probes.ledger
        led.publish()
        assert led.handles is None and not registered()
        FakeAnnotation.open_now = True
        led.publish()  # the beat sees the slice: the table opens
        assert led.handles is not None and registered()
        assert mon.get_local_events(our_tools()[0], RUN_CODE) == (
            mon.events.PY_START | mon.events.PY_RETURN)
        for _ in range(10):
            loop.call_soon(spin, 0.001)
        await asyncio.sleep(0.03)
        report = led.report()
        assert report["handles"]["kinds"]["spin"]["runs"] == 10
        FakeAnnotation.open_now = False
        led.publish()  # the slice closed: so does the table
        assert led.handles is None and not our_tools()
        assert "handles" not in led.report()
        for t in range(6):
            if mon.get_tool(t) is None:
                assert mon.get_local_events(t, RUN_CODE) == 0
        rose = {k: v - c0.get(k, 0.0) for k, v in counters().items()}
        assert rose["server_loop_handle_seconds_spin"] >= 0.01
        assert rose["server_loop_handle_unnamed_seconds_spin"] >= 0.01
        assert rose["server_loop_handle_busy_seconds_total"] >= 0.01
        # a ledger that goes away with its table open
        FakeAnnotation.open_now = True
        led.publish()
        assert registered()
        probes.stop()
        assert not our_tools()

    drive(main)
    assert not our_tools()
    assert LoopLedger.of_this_thread() is None
