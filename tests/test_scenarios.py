"""Scenario harness: replay determinism, SLO gating, graceful drain.

Covers the harness's own contracts (the ISSUE's satellite list):
replay determinism (same seed ⇒ identical schedule hash + identical
deterministic scorecard counts), SLO-violation detection (a
deliberately impossible SLO fails the scenario — and an SLO naming an
unmeasured metric fails loudly rather than passing by vacuity),
graceful-drain unit behavior (in-flight request completes, watcher
gets the final bookmark + terminal Status, late connections are
refused), and the ``scenario.phase`` / ``server.drain`` fault-point
drills the registry lint enforces.
"""

import asyncio
import dataclasses
import threading
import time

import pytest

from kcp_tpu import faults
from kcp_tpu.scenarios import SCENARIOS, run_scenario
from kcp_tpu.scenarios.catalog import CRUD_CHURN
from kcp_tpu.scenarios.spec import SLO
from kcp_tpu.scenarios.workload import build_schedule, schedule_hash
from kcp_tpu.server.rest import RestClient
from kcp_tpu.server.server import Config
from kcp_tpu.server.threaded import ServerThread
from kcp_tpu.utils import errors
from kcp_tpu.utils.trace import REGISTRY


@pytest.fixture(autouse=True)
def _clean_injector():
    yield
    faults.clear()


def _cm(name: str, cluster: str, v: str = "") -> dict:
    return {"apiVersion": "v1", "kind": "ConfigMap",
            "metadata": {"name": name, "namespace": "default",
                         "clusterName": cluster},
            "data": {"v": v}}


TINY = dataclasses.replace(
    CRUD_CHURN, tenants=2, watchers_per_tenant=1,
    phases=tuple(dataclasses.replace(p, ops_per_tenant=8)
                 for p in CRUD_CHURN.phases))


# ---------------------------------------------------------------------------
# catalog + determinism
# ---------------------------------------------------------------------------


def test_catalog_has_the_declared_scenarios():
    # the ISSUE's six named scenarios, each with declared SLOs
    assert set(SCENARIOS) >= {"crud-churn", "noisy-neighbor",
                              "reconnect-storm", "rolling-restart",
                              "kill-primary", "crd-churn"}
    for spec in SCENARIOS.values():
        assert spec.slos, f"{spec.name} declares no SLOs"
        assert spec.phases, f"{spec.name} declares no phases"


def test_schedule_is_a_pure_function_of_seed():
    a = build_schedule(7, TINY)
    b = build_schedule(7, TINY)
    c = build_schedule(8, TINY)
    assert a == b
    assert a != c
    assert schedule_hash(7, TINY, a) == schedule_hash(7, TINY, b)
    assert schedule_hash(7, TINY, a) != schedule_hash(8, TINY, c)


def test_replay_determinism_end_to_end(tmp_path):
    """Same seed ⇒ identical schedule hash AND identical deterministic
    scorecard counts (ops, acks, final-state verification) across two
    REAL runs."""
    r1 = run_scenario(TINY, seed=1234, workdir=str(tmp_path / "a"))
    r2 = run_scenario(TINY, seed=1234, workdir=str(tmp_path / "b"))
    assert r1["passed"] and r2["passed"], (r1, r2)
    assert r1["schedule"] == r2["schedule"]
    for key in ("acked", "lost_acked_writes", "lost_watch_events",
                "unclean_stream_ends", "http_5xx"):
        assert r1["measurements"][key] == r2["measurements"][key], key


def test_slo_violation_fails_the_scenario(tmp_path):
    """A deliberately impossible SLO must fail the run; an SLO naming a
    metric that was never measured must fail loudly, not pass by
    vacuity."""
    broken = dataclasses.replace(TINY, name="crud-churn-broken", slos=(
        SLO("impossible-convergence", "p99_convergence_ms", "<=", -1.0),
        SLO("typo-metric", "no_such_metric", "==", 0),
    ))
    r = run_scenario(broken, seed=5, workdir=str(tmp_path))
    assert not r["passed"]
    rows = {row["name"]: row for row in r["slos"]}
    assert not rows["impossible-convergence"]["passed"]
    assert rows["impossible-convergence"]["observed"] > 0.0
    assert not rows["typo-metric"]["passed"]
    assert rows["typo-metric"]["error"] == "metric never measured"


def test_scenario_phase_fault_aborts_the_run(tmp_path):
    """The scenario.phase drill: an injected error at a phase boundary
    aborts the scenario, which fails with the cause on record."""
    faults.install(faults.FaultInjector("scenario.phase:error@tick=1",
                                        seed=1))
    r = run_scenario(TINY, seed=6, workdir=str(tmp_path))
    assert not r["passed"]
    assert "aborted" in r and "injected fault" in r["aborted"]


# ---------------------------------------------------------------------------
# graceful drain units
# ---------------------------------------------------------------------------


def test_drain_completes_inflight_refuses_late_and_terminates_watchers():
    """The drain contract in one pass: (1) an in-flight request —
    slowed by an injected store latency — completes and its event is
    delivered, (2) the live watcher receives the final BOOKMARK at the
    store RV plus a terminal in-stream Status, (3) a late connection is
    refused at the TCP level."""
    t = ServerThread(Config(durable=False, install_controllers=False,
                            tls=False)).start()
    addr = t.address
    c = RestClient(addr, cluster="t1")
    for i in range(3):
        c.create("configmaps", _cm(f"seed{i}", "t1"))
    c.delete("configmaps", "seed2", "default")  # rv 4; DELETED event
    # carries seed2's CREATE rv, so the stream RV trails the store RV —
    # exactly the gap the drain bookmark must close

    result: dict = {}

    async def watch_all():
        w = c.watch("configmaps", namespace="default", since_rv=0)
        evs = []
        try:
            async for ev in w:
                evs.append(ev)
        except Exception as e:  # noqa: BLE001 — the terminal Status
            return evs, e, w.last_rv
        return evs, None, w.last_rv

    th = threading.Thread(
        target=lambda: result.update(r=asyncio.run(watch_all())))
    th.start()
    time.sleep(0.4)

    faults.install(faults.FaultInjector("store.put:latency=300ms", seed=1))
    inflight: dict = {}

    def write():
        c2 = RestClient(addr, cluster="t1")
        try:
            inflight["resp"] = c2.create("configmaps",
                                         _cm("inflight", "t1"))
        except Exception as e:  # noqa: BLE001
            inflight["err"] = e
        finally:
            c2.close()

    wth = threading.Thread(target=write)
    wth.start()
    time.sleep(0.1)
    gauge_before = REGISTRY.gauge("server_draining").value
    t.drain()
    wth.join()
    th.join()
    faults.clear()

    # (1) the in-flight request completed despite arriving pre-drain
    assert "resp" in inflight, inflight.get("err")
    rv_inflight = int(inflight["resp"]["metadata"]["resourceVersion"])
    evs, err, last_rv = result["r"]
    # (2) its event was flushed to the watcher before the terminal
    assert any(e.name == "inflight" and e.rv == rv_inflight for e in evs)
    assert isinstance(err, errors.UnavailableError)
    assert "draining" in str(err)
    # ... and the final bookmark anchored the client AT the store RV
    assert last_rv == rv_inflight
    assert gauge_before == 0 and REGISTRY.gauge("server_draining").value == 0
    # (3) late connections are refused outright
    c3 = RestClient(addr, cluster="t1")
    with pytest.raises((ConnectionError, OSError)):
        c3.get("configmaps", "seed0", "default")
    c3.close()
    c.close()


def test_server_drain_fault_escalates_to_hard_stop():
    """The server.drain drill: an injected error aborts the graceful
    path (drain() returns False) and the server still stops cleanly —
    degraded shutdown, never a wedge."""
    t = ServerThread(Config(durable=False, install_controllers=False,
                            tls=False)).start()
    c = RestClient(t.address, cluster="t1")
    c.create("configmaps", _cm("x", "t1"))
    c.close()
    faults.install(faults.FaultInjector("server.drain:error@tick=1",
                                        seed=1))
    assert t.submit(t.server.drain()) is False
    faults.clear()
    t.stop()


def test_drain_flushes_replication_subscribers(tmp_path):
    """Drain on a primary flushes queued WAL records to its follower
    and ends the feed with a terminal Status; the follower's applied RV
    reaches the primary's final RV before the primary exits."""
    p = ServerThread(Config(durable=True, install_controllers=False,
                            tls=False, root_dir=str(tmp_path / "p"))).start()
    r = ServerThread(Config(role="replica", primary=p.address,
                            durable=False, install_controllers=False,
                            tls=False)).start()
    try:
        c = RestClient(p.address, cluster="t1")
        for i in range(20):
            c.create("configmaps", _cm(f"x{i}", "t1"))
        final_rv = int(c._request(
            "GET", "/replication/status")["applied_rv"])
        c.close()
        p.drain()
        rc = RestClient(r.address, cluster="t1")
        deadline = time.time() + 10
        applied = -1
        while time.time() < deadline:
            applied = int(rc._request(
                "GET", "/replication/status")["applied_rv"])
            if applied >= final_rv:
                break
            time.sleep(0.05)
        assert applied >= final_rv
        items, _ = rc.list("configmaps", namespace="default")
        assert len(items) == 20
        rc.close()
    finally:
        r.stop()
        p.stop()
