"""Deterministic fault injection (KCP_FAULTS) + degraded-mode serving.

Covers: spec parsing and seeded replayability; the store / watch / REST /
apply / device-step injection points; poison-row quarantine (retry once,
bisect, quarantine only the poison, requeue with backoff); circuit
breaker transitions and fail-fast; the RestClient stale-keep-alive retry
discipline; health-gated evacuation hysteresis for flapping clusters;
FusedCore stop idempotency; and the chaos fuzz the CI smoke drives
(seeded store 5xx + watch drops + device-step faults -> everything
surviving converges with zero lost patches).
"""

import asyncio
import os
import time

import numpy as np
import pytest

import kcp_tpu.syncer.core as core_mod
from kcp_tpu import faults
from kcp_tpu.apis.cluster import new_cluster, set_not_ready, set_ready
from kcp_tpu.client import Client, Informer, MultiClusterClient
from kcp_tpu.reconcilers.deployment import DeploymentSplitter
from kcp_tpu.reconcilers.deployment.controller import DEPLOYMENTS
from kcp_tpu.server.rest import RestClient
from kcp_tpu.store import LogicalStore
from kcp_tpu.syncer import start_syncer
from kcp_tpu.syncer.core import FusedBucket, FusedCore
from kcp_tpu.syncer.engine import CLUSTER_LABEL
from kcp_tpu.utils import circuit
from kcp_tpu.utils.errors import NotFoundError, UnavailableError
from kcp_tpu.utils.trace import REGISTRY

from helpers import wait_until

S = 16  # slot width for the direct-core harnesses

CLUSTERS_GVR = "clusters.cluster.example.dev"


@pytest.fixture(autouse=True)
def _clean_injector():
    yield
    faults.clear()


def counter(name: str) -> float:
    return REGISTRY.counter(name).value


# ---------------------------------------------------------------------------
# spec parsing + replayability
# ---------------------------------------------------------------------------


def test_spec_grammar_parses_the_issue_example():
    rules = faults.parse_spec(
        "store.put:error=0.05;watch:drop@tick=200;device.step:raise@tick=57;"
        "syncer.apply:latency=50ms;device.step:poison_row=5")
    by = {(r.point, r.action): r for r in rules}
    assert by[("store.put", "error")].value == pytest.approx(0.05)
    assert by[("watch", "drop")].at_tick == 200
    assert by[("device.step", "raise")].at_tick == 57
    assert by[("syncer.apply", "latency")].value == pytest.approx(0.05)
    assert by[("device.step", "poison_row")].value == 5
    with pytest.raises(ValueError):
        faults.parse_spec("store.put:explode")
    with pytest.raises(ValueError):
        faults.parse_spec("nonsense")
    with pytest.raises(ValueError):
        faults.parse_spec("p:error@jitter=3")


def test_seeded_schedule_is_replayable():
    def run() -> list[int]:
        inj = faults.FaultInjector("p:error=0.3", seed=7)
        out = []
        for _ in range(64):
            try:
                inj.maybe_fail("p")
                out.append(0)
            except UnavailableError:
                out.append(1)
        return out

    a, b = run(), run()
    assert a == b
    assert 0 < sum(a) < 64  # actually probabilistic, not constant


def test_tick_rule_fires_exactly_once_and_latency_returns_delay():
    inj = faults.FaultInjector("p:raise@tick=3;q:latency=50ms", seed=0)
    fired = []
    for _ in range(5):
        try:
            inj.maybe_fail("p")
            fired.append(0)
        except faults.InjectedFault:
            fired.append(1)
    assert fired == [0, 0, 1, 0, 0]
    assert inj.maybe_fail("q") == pytest.approx(0.05)
    assert inj.snapshot() == {"p": 5, "q": 1}


# ---------------------------------------------------------------------------
# store + watch injection points
# ---------------------------------------------------------------------------


def test_store_put_injection_and_metric():
    faults.install(faults.FaultInjector("store.put:error=1.0", seed=0))
    before = counter("fault_injected_total")
    store = LogicalStore()
    with pytest.raises(UnavailableError):
        store.create("configmaps", "c", {"metadata": {"name": "x"}})
    assert counter("fault_injected_total") == before + 1
    assert counter("fault_injected_store_put_total") >= 1
    faults.clear()
    store.create("configmaps", "c", {"metadata": {"name": "x"}})  # healthy


def test_store_read_verbs_are_injectable():
    # store.get:error / store.list:error / store.delete:error — every
    # store verb must fail like put under an injected 503, so chaos
    # schedules can exercise read-path and delete-path error handling
    store = LogicalStore()
    store.create("configmaps", "c", {"metadata": {"name": "x"}})
    faults.install(faults.FaultInjector(
        "store.get:error=1.0;store.list:error=1.0;store.delete:error=1.0",
        seed=0))
    with pytest.raises(UnavailableError):
        store.get("configmaps", "c", "x")
    with pytest.raises(UnavailableError):
        store.list("configmaps")
    with pytest.raises(UnavailableError):
        store.delete("configmaps", "c", "x")
    assert counter("fault_injected_store_get_total") >= 1
    assert counter("fault_injected_store_list_total") >= 1
    assert counter("fault_injected_store_delete_total") >= 1
    faults.clear()
    assert store.get("configmaps", "c", "x")["metadata"]["name"] == "x"
    assert store.list("configmaps")[0]
    store.delete("configmaps", "c", "x")  # healthy again


def test_admission_flow_fault_injects_503_before_token_accounting():
    # admission.flow:error — the flow controller's acquire is a fault
    # point; an injected 503 must surface before any token is spent
    from kcp_tpu.admission.flow import FlowController

    fc = FlowController(concurrency=4, rate=100.0)
    faults.install(faults.FaultInjector("admission.flow:error@tick=1", seed=0))
    with pytest.raises(UnavailableError):
        fc.try_acquire("tenant-a", "create")
    # the one-tick schedule is spent: the same flow admits cleanly, with
    # its full burst intact (the injected failure charged no token)
    release = fc.try_acquire("tenant-a", "create")
    assert callable(release)
    release()


def test_cluster_health_fault_reads_as_unhealthy_syncer(monkeypatch):
    # cluster.health:error — an injected fault at the pull-mode health
    # probe must flip Ready=False (feeding the splitter's evacuation
    # machinery), and clearing the schedule must let Ready recover
    from kcp_tpu.apis.cluster import is_ready, set_synced_resources
    from kcp_tpu.reconcilers.cluster import ClusterController, SyncerMode
    from kcp_tpu.reconcilers.cluster import installer as installer_mod

    async def main():
        store = LogicalStore()
        mc = MultiClusterClient(store)
        t = mc.cluster_client("tenant-1")
        cl = new_cluster("east", kubeconfig="fake://east")
        set_synced_resources(cl, ["deployments.apps"])
        t.create(CLUSTERS_GVR, cl)

        class Registry:
            def resolve(self, kubeconfig):
                return object()

        ctrl = ClusterController(mc, Registry(), mode=SyncerMode.PULL,
                                 poll_interval=30.0)
        key = ("tenant-1", "east")

        class StubImporter:
            def start(self):
                pass

            def stop(self):
                pass

        ctrl.importers[key] = StubImporter()
        monkeypatch.setattr(installer_mod, "healthcheck_syncer",
                            lambda physical: (True, ""))
        faults.install(faults.FaultInjector("cluster.health:error=1.0",
                                            seed=0))
        await ctrl._reconcile(key, t.get(CLUSTERS_GVR, "east"))
        assert not is_ready(t.get(CLUSTERS_GVR, "east")), (
            "injected health fault did not flip Ready=False")
        faults.clear()
        await ctrl._reconcile(key, t.get(CLUSTERS_GVR, "east"))
        assert is_ready(t.get(CLUSTERS_GVR, "east")), (
            "Ready did not recover after the schedule cleared")

    asyncio.run(main())


def test_watch_drop_recovers_via_informer_relist():
    async def main():
        store = LogicalStore()
        client = Client(store, "t")
        inf = Informer(client, "configmaps")
        inf.rewatch_backoff = 0.02
        await inf.start()
        client.create("configmaps", {"metadata": {"name": "a"}})
        assert await wait_until(lambda: inf.get("t", "a") is not None, 5)
        # next push kills the watch and LOSES the event — the reflector
        # loop must re-list and recover the object anyway
        faults.install(faults.FaultInjector("watch:drop@tick=1", seed=0))
        client.create("configmaps", {"metadata": {"name": "b"}})
        assert inf.get("t", "b") is None  # the event really was dropped
        assert await wait_until(lambda: inf.get("t", "b") is not None, 5), (
            "informer never recovered from the dropped watch")
        await inf.stop()

    asyncio.run(main())


# ---------------------------------------------------------------------------
# direct-core harness (open loop, from the pipeline equivalence family)
# ---------------------------------------------------------------------------


class OpenLoopOwner:
    """Open-loop SectionOwner: fixed mirrors, every patch recorded, no
    feedback — staging schedules (and so fault schedules) are identical
    across pipeline modes."""

    def __init__(self, core: FusedCore, b: int):
        self.core = core
        self.B = b
        mask = np.zeros(S, bool)
        mask[-2:] = True
        self._mask = mask
        self.up_vals = np.zeros((b, S), np.uint32)
        self.down_vals = np.zeros((b, S), np.uint32)
        self.stream: list[tuple[int, int, bool]] = []
        self.section = core.register(self, S)

    def fused_status_mask(self) -> np.ndarray:
        return self._mask

    def fused_encode(self, key: int):
        return self.up_vals[key], True, self.down_vals[key], True

    def fused_encode_many(self, keys):
        idx = np.fromiter(keys, np.int64, len(keys))
        ones = np.ones(idx.size, bool)
        return self.up_vals[idx], ones, self.down_vals[idx], ones

    def fused_apply(self, patches) -> None:
        self.stream.extend((int(k), int(c), bool(u)) for k, c, u in patches)

    def fused_overflow(self) -> None:  # pragma: no cover - fixed vocab
        raise AssertionError("vocabulary never grows in this harness")


def _stream_bytes(stream) -> bytes:
    return np.asarray(
        [(k, c, int(u)) for k, c, u in stream], np.int64).tobytes()


# ---------------------------------------------------------------------------
# poison-row quarantine
# ---------------------------------------------------------------------------


def test_poison_row_quarantine_isolates_bad_row_without_bucket_stall():
    async def main():
        # rows allocate in first-touch order: enqueue 0..29 in order so
        # key k <-> row k, then poison row 3
        faults.install(faults.FaultInjector("device.step:poison_row=3", seed=0))
        q_before = counter("quarantined_rows")
        core = FusedCore(batch_window=0.0005, pipeline="double")
        owner = OpenLoopOwner(core, 64)
        await core.start()
        bucket = owner.section.bucket
        keys = list(range(30))
        owner.up_vals[keys, 0] = 7  # diverge every row
        core.enqueue_many(owner.section, False, keys)
        # the poisoned submission fails, retries once (full upload, fails
        # again), bisects, and quarantines ONLY row 3 — after which the
        # recovery tick must deliver every co-tenant's patch
        assert await wait_until(
            lambda: bucket.stats["quarantined"] >= 1, 30), "never quarantined"
        assert await wait_until(
            lambda: {k for k, _c, _u in owner.stream} >= set(keys) - {3},
            30), f"co-tenants stalled: {sorted({k for k, _, _ in owner.stream})}"
        assert 3 not in {k for k, _c, _u in owner.stream}
        # "only the poisoned rows": every co-tenant was patched above and
        # key 3 never was — the requeue/backoff loop may re-quarantine
        # the SAME poisoned row while the fault stays active, never others
        assert counter("quarantined_rows") >= q_before + 1
        assert bucket.stats["step_failures"] >= 2  # initial + the retry
        # key 3 was requeued with backoff; lifting the fault must let the
        # level-triggered loop converge it (degraded -> healthy recovery)
        faults.clear()
        assert await wait_until(
            lambda: 3 in {k for k, _c, _u in owner.stream}, 30), (
            "quarantined key never recovered after the fault cleared")
        await core.stop()

    asyncio.run(main())


def test_systemic_step_failure_still_propagates():
    """A row-independent failure (even the empty probe fails) must NOT be
    eaten by quarantine: after the single wholesale retry it surfaces."""

    async def main():
        faults.install(faults.FaultInjector("device.step:raise", seed=0))
        core = FusedCore(batch_window=0.0005, pipeline="serial")
        owner = OpenLoopOwner(core, 64)
        await core.start()
        owner.up_vals[0, 0] = 1
        before = counter("fused_step_failures_total")
        core.enqueue(owner.section, False, 0)
        # always-on raise: submit fails, retry fails, bisection's empty
        # probe fails -> recovery refuses, batch errors, items retried by
        # the controller and eventually dropped. The loop stays alive.
        assert await wait_until(
            lambda: counter("fused_step_failures_total") >= before + 2, 30)
        assert owner.section.bucket.stats["quarantined"] == 0
        faults.clear()
        # the loop survived: fresh churn converges
        owner.up_vals[1, 0] = 2
        core.enqueue(owner.section, False, 1)
        assert await wait_until(
            lambda: 1 in {k for k, _c, _u in owner.stream}, 30)
        await core.stop()

    asyncio.run(main())


# ---------------------------------------------------------------------------
# serial-vs-double equivalence under an active fault schedule
# ---------------------------------------------------------------------------

FAULT_SCHEDULE = "device.step:raise@tick=4;device.step:poison_row=3"


async def _run_faulted_schedule(pipeline: str, seed: int, rows: int = 512,
                                steps: int = 20) -> tuple[bytes, int, int]:
    faults.install(faults.FaultInjector(FAULT_SCHEDULE, seed=99))
    core = FusedCore(batch_window=0.0005, pipeline=pipeline)
    owner = OpenLoopOwner(core, rows)
    await core.start()
    bucket = owner.section.bucket
    # pin rows 0..7 (incl. the poison) deterministically, then fuzz
    owner.up_vals[:8] = 1
    before = bucket.stats["ticks"]
    core.enqueue_many(owner.section, False, list(range(8)))
    assert await wait_until(lambda: bucket.stats["ticks"] > before, 30)
    rng = np.random.default_rng(seed)
    pool = 200
    for step in range(steps):
        n = int(rng.integers(1, 32))
        touched = rng.choice(pool, size=n, replace=False)
        owner.up_vals[touched] = rng.integers(
            1, 2**32, (n, S), dtype=np.uint32)
        before = bucket.stats["ticks"]
        core.enqueue_many(owner.section, False, touched.tolist())
        assert await wait_until(
            lambda: bucket.stats["ticks"] > before, 30), (
            f"{pipeline}: tick never ran for step {step}")
    await core.stop()
    assert not core._inflight
    return (_stream_bytes(owner.stream), bucket.stats["ticks"],
            bucket.stats["quarantined"])


@pytest.mark.parametrize("seed", [3, 17])
def test_pipeline_equivalence_holds_under_fault_schedule(seed, monkeypatch):
    """The degraded-mode machinery (retry, bisect, quarantine) must stay
    an observationally-invisible part of the pipeline: same seeded fault
    schedule -> byte-identical serial and double patch streams."""
    # keep the quarantine requeue out of the run: its wall-clock backoff
    # timing would legitimately (and irrelevantly) fork the schedules.
    # Both constants: the delay is min(base * 2**n, max), so the base
    # alone left a requeue 5 s after each quarantine, inside the run on
    # a loaded machine
    monkeypatch.setattr(core_mod, "QUARANTINE_BASE_BACKOFF", 120.0)
    monkeypatch.setattr(core_mod, "QUARANTINE_MAX_BACKOFF", 120.0)

    async def main():
        serial, serial_ticks, serial_q = await _run_faulted_schedule(
            "serial", seed)
        double, double_ticks, double_q = await _run_faulted_schedule(
            "double", seed)
        assert serial_q >= 1 and double_q >= 1  # the schedule really bit
        assert serial_ticks == double_ticks
        assert serial == double, (
            f"seed={seed}: pipelined stream diverged under faults "
            f"({len(serial)} vs {len(double)} bytes)")
        assert len(serial) > 0

    asyncio.run(main())


# ---------------------------------------------------------------------------
# full-stack chaos fuzz (the CI smoke entry point)
# ---------------------------------------------------------------------------


def _cm(name: str, data: dict) -> dict:
    return {"apiVersion": "v1", "kind": "ConfigMap",
            "metadata": {"name": name, "namespace": "default",
                         "labels": {CLUSTER_LABEL: "us-east1"}},
            "data": data}


def _create_retrying(client: Client, resource: str, obj: dict) -> None:
    for _ in range(100):
        try:
            client.create(resource, obj)
            return
        except UnavailableError:
            continue
    raise AssertionError("injected store errors never let the create through")


async def _chaos_run(n_obj: int, expect_quarantine: bool) -> None:
    kcp, phys = LogicalStore(), LogicalStore()
    up, down = Client(kcp, "tenant-1"), Client(phys, "default")
    names = [f"cm-{i:02d}" for i in range(n_obj)]
    for i, name in enumerate(names):
        _create_retrying(up, "configmaps", _cm(name, {"v": str(i)}))
    syncer = await start_syncer(up, down, ["configmaps"], "us-east1")
    bucket = syncer.engines[0]._section.bucket

    def converged() -> set[str]:
        ok = set()
        for i, name in enumerate(names):
            try:
                if down.get("configmaps", name, "default")["data"] == {
                        "v": str(i)}:
                    ok.add(name)
            except (NotFoundError, UnavailableError):
                pass
        return ok

    # under the active schedule every object EXCEPT a quarantined one
    # must converge: store 5xx retry out, dropped watches re-list, the
    # transient device-step raise retries, the poison quarantines alone
    floor = n_obj - 1 if expect_quarantine else n_obj
    assert await wait_until(lambda: len(converged()) >= floor, 120), (
        f"converged only {sorted(converged())} under faults")
    if expect_quarantine:
        assert bucket.stats["quarantined"] >= 1, "poison never quarantined"
        assert len(converged()) >= n_obj - 1, "more than the poison stalled"
    assert counter("fault_injected_total") > 0
    # lift the faults: the quarantined key's bounded-backoff requeue (and
    # any lingering retries) must converge everything — zero lost patches
    faults.clear()
    assert await wait_until(lambda: len(converged()) == n_obj, 60), (
        f"lost patches after recovery: {sorted(set(names) - converged())}")
    await syncer.stop()


def test_chaos_fuzz_store_errors_watch_drops_step_faults():
    faults.install(faults.FaultInjector(
        "store.put:error=0.05;watch:drop@tick=25;device.step:raise@tick=3;"
        "device.step:poison_row=5;syncer.apply:latency=2ms", seed=2024))

    async def main():
        await _chaos_run(24, expect_quarantine=True)

    asyncio.run(main())


def test_ci_chaos_smoke():
    """The scripts/ci.sh stage: honor an env-provided KCP_FAULTS schedule
    (seeded), else a default store-5xx + one device-step raise, and
    assert convergence with zero lost patches."""
    if os.environ.get("KCP_FAULTS"):
        assert faults.active() is not None, "env schedule did not load"
    else:
        faults.install(faults.FaultInjector(
            "store.put:error=0.05;device.step:raise@tick=5",
            seed=int(os.environ.get("KCP_FAULTS_SEED", "7"))))

    async def main():
        await _chaos_run(12, expect_quarantine=False)

    asyncio.run(main())


# ---------------------------------------------------------------------------
# satellite: dropped patch rows are counted, logged once
# ---------------------------------------------------------------------------


def test_dispatch_counts_and_logs_dropped_patch_rows(caplog):
    bucket = FusedBucket(8)
    # one patch for row 7: never allocated, no owner
    idx, code, upsync = np.array([7]), np.array([2]), np.array([False])
    before = counter("fused_dropped_patch_rows")
    with caplog.at_level("WARNING", logger="kcp_tpu.syncer.core"):
        bucket.route_patches(idx, code, upsync)
        bucket.route_patches(idx, code, upsync)
    assert counter("fused_dropped_patch_rows") == before + 2
    hits = [r for r in caplog.records if "dropping patch for row 7" in r.message]
    assert len(hits) == 1  # logged once per row, counted every time


# ---------------------------------------------------------------------------
# satellite: FusedCore.stop() is idempotent
# ---------------------------------------------------------------------------


def test_double_stop_is_idempotent_and_preserves_drain_order():
    async def main():
        core = FusedCore(batch_window=0.0005, pipeline="double")
        owner = OpenLoopOwner(core, 64)
        await core.start()
        touched = list(range(40))
        owner.up_vals[touched, 0] = 7
        core.enqueue_many(owner.section, False, touched)
        # stop with the batch possibly not even ticked: the PR-1 drain
        # ordering (controller final ticks, THEN in-flight wires) must
        # deliver everything...
        await core.stop()
        assert not core._inflight
        patched = {k for k, _c, _u in owner.stream}
        assert patched.issuperset(touched)
        # ...and stopping again (twice) is a pure no-op
        before = len(owner.stream)
        await core.stop()
        await core.stop()
        assert len(owner.stream) == before
        assert not core._inflight
        assert core._closed()

    asyncio.run(main())


def test_concurrent_stop_during_inflight_tick():
    async def main():
        core = FusedCore(batch_window=0.0005, pipeline="double")
        owner = OpenLoopOwner(core, 64)
        await core.start()
        touched = list(range(32))
        owner.up_vals[touched, 0] = 9
        core.enqueue_many(owner.section, False, touched)
        # two stops racing each other (and the in-flight tick): both must
        # return only after the full drain, without double-draining
        await asyncio.gather(core.stop(), core.stop())
        assert not core._inflight
        patched = {k for k, _c, _u in owner.stream}
        assert patched.issuperset(touched)

    asyncio.run(main())


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------


def test_circuit_breaker_transitions_and_half_open_probe():
    now = [0.0]
    cb = circuit.CircuitBreaker("peer", failure_threshold=3,
                                reset_timeout=1.0, jitter=0.0,
                                clock=lambda: now[0], seed=1)
    cb.record_failure()
    cb.record_failure()
    assert cb.state == circuit.CLOSED and cb.allow()
    cb.record_failure()  # third consecutive: trip
    assert cb.state == circuit.OPEN
    with pytest.raises(UnavailableError):
        cb.check()
    now[0] = 1.05  # past the backoff: exactly one half-open probe
    assert cb.allow()
    assert cb.state == circuit.HALF_OPEN
    assert not cb.allow()
    cb.record_failure()  # failed probe: re-open, doubled backoff
    assert cb.state == circuit.OPEN
    now[0] = 2.5
    assert not cb.allow()  # 2s backoff now: 1.05 + 2.0
    now[0] = 3.1
    assert cb.allow()
    cb.record_success()  # probe succeeded: close + reset backoff
    assert cb.state == circuit.CLOSED and cb.allow()
    assert "circuit_state" in REGISTRY.expose()
    assert counter("circuit_open_total") >= 2


def test_rest_injected_errors_trip_breaker_then_fail_fast():
    faults.install(faults.FaultInjector("rest.request:error=1.0", seed=0))
    c = RestClient("http://fake-peer:1")
    c._breaker = circuit.CircuitBreaker("test_peer", failure_threshold=2,
                                        reset_timeout=60.0)
    for _ in range(2):
        with pytest.raises(UnavailableError):
            c._request("GET", "/x")
    assert c._breaker.state == circuit.OPEN
    faults.clear()
    # open circuit: refused immediately, no socket, no timeout
    before = counter("circuit_fastfail_total")
    t0 = time.monotonic()
    with pytest.raises(UnavailableError):
        c._request("GET", "/x")
    assert time.monotonic() - t0 < 0.1
    assert counter("circuit_fastfail_total") == before + 1
    # scoped clones share the breaker (one dead peer trips all tenants)
    assert c.scoped("other")._breaker is c._breaker


# ---------------------------------------------------------------------------
# satellite: RestClient stale-keep-alive retry discipline
# ---------------------------------------------------------------------------


class FakeResponse:
    status = 200

    @staticmethod
    def read() -> bytes:
        return b"{}"


class FakeConn:
    def __init__(self, fail_send=False, fail_read=False):
        self.fail_send = fail_send
        self.fail_read = fail_read
        self.sent: list[tuple[str, str]] = []

    def request(self, method, path, body=None, headers=None):
        if self.fail_send:
            raise ConnectionResetError("stale keep-alive")
        self.sent.append((method, path))

    def getresponse(self):
        if self.fail_read:
            raise ConnectionResetError("died mid-response")
        return FakeResponse()

    def close(self):
        pass


def _faked_client(monkeypatch, fresh_conns: list) -> tuple[RestClient, list]:
    """RestClient whose fresh connections pop from ``fresh_conns``."""
    import http.client as hc

    made: list = []

    def factory(host, port, timeout=None):
        conn = fresh_conns.pop(0)
        made.append(conn)
        return conn

    monkeypatch.setattr(hc, "HTTPConnection", factory)
    return RestClient("http://fake:80"), made


@pytest.mark.parametrize("verb", ["GET", "POST", "PUT", "DELETE"])
def test_stale_keepalive_send_failure_retries_once_for_any_verb(
        monkeypatch, verb):
    good = FakeConn()
    client, made = _faked_client(monkeypatch, [good])
    client._conn = FakeConn(fail_send=True)  # the reused stale connection
    body = {"a": 1} if verb in ("POST", "PUT") else None
    assert client._request(verb, "/x", body) == {}
    assert good.sent == [(verb, "/x")]  # exactly one retry, and it stuck
    assert client._breaker.state == circuit.CLOSED


def test_fresh_connection_send_failure_does_not_retry(monkeypatch):
    client, made = _faked_client(
        monkeypatch, [FakeConn(fail_send=True), FakeConn()])
    with pytest.raises(ConnectionResetError):
        client._request("POST", "/x", {"a": 1})
    assert len(made) == 1  # the request never reached a server; no retry


def test_second_consecutive_send_failure_raises(monkeypatch):
    # retry exactly ONCE: stale conn AND its fresh replacement both dying
    client, made = _faked_client(monkeypatch, [FakeConn(fail_send=True)])
    client._conn = FakeConn(fail_send=True)
    with pytest.raises(ConnectionResetError):
        client._request("PUT", "/x", {"a": 1})
    assert len(made) == 1


def test_read_stage_failure_retries_only_get(monkeypatch):
    # GET: a response dying mid-read is safe to retry once
    good = FakeConn()
    client, made = _faked_client(monkeypatch, [good])
    client._conn = FakeConn(fail_read=True)
    assert client._request("GET", "/x") == {}
    assert good.sent == [("GET", "/x")]
    # POST: the server may have committed the write — never re-send
    client2, made2 = _faked_client(monkeypatch, [FakeConn()])
    client2._conn = FakeConn(fail_read=True)
    with pytest.raises(ConnectionResetError):
        client2._request("POST", "/x", {"a": 1})
    assert made2 == []


# ---------------------------------------------------------------------------
# health-gated evacuation: flap hysteresis + drain + readmission
# ---------------------------------------------------------------------------


def _deployment(name: str, replicas: int) -> dict:
    return {"apiVersion": "apps/v1", "kind": "Deployment",
            "metadata": {"name": name, "namespace": "default"},
            "spec": {"replicas": replicas,
                     "template": {"spec": {"containers": []}}}}


async def _eventually(pred, timeout=10.0):
    def quiet():
        try:
            return pred()
        except Exception:  # noqa: BLE001
            return False

    assert await wait_until(quiet, timeout), "condition not reached"


def test_flapping_cluster_hysteresis_then_sustained_drain_and_recovery():
    async def main():
        store = LogicalStore()
        mc = MultiClusterClient(store)
        t = mc.cluster_client("tenant-1")
        t.create(CLUSTERS_GVR, new_cluster("east"))
        t.create(CLUSTERS_GVR, new_cluster("west"))
        splitter = DeploymentSplitter(mc, evac_hysteresis=0.4)
        await splitter.start()
        t.create(DEPLOYMENTS, _deployment("web", 10))
        await _eventually(
            lambda: t.get(DEPLOYMENTS, "web--west", "default")["spec"]
            ["replicas"] == 5)
        evac_before = counter("evacuations_total")

        def flip(name: str, ready: bool) -> None:
            cl = t.get(CLUSTERS_GVR, name)
            if ready:
                set_ready(cl)
            else:
                set_not_ready(cl, "SyncerNotReady", "probe failed")
            t.update_status(CLUSTERS_GVR, cl)

        # Ready -> NotReady -> Ready within the hysteresis window: the
        # delayed health check must find it recovered — ZERO evacuations
        flip("west", False)
        await asyncio.sleep(0.15)
        flip("west", True)
        await asyncio.sleep(0.7)  # well past the window
        assert t.get(DEPLOYMENTS, "web--west", "default")["spec"]["replicas"] == 5
        assert counter("evacuations_total") == evac_before
        assert splitter._evacuated == set()

        # sustained NotReady: past the window the cluster drains — its
        # leaf goes away and the replicas land on the healthy cluster
        flip("west", False)
        await _eventually(
            lambda: t.get(DEPLOYMENTS, "web--east", "default")["spec"]
            ["replicas"] == 10, timeout=15)
        with pytest.raises(NotFoundError):
            t.get(DEPLOYMENTS, "web--west", "default")
        assert counter("evacuations_total") == evac_before + 1
        assert ("tenant-1", "west") in splitter._evacuated

        # recovery: Ready readmits the cluster and the split reconverges
        flip("west", True)
        await _eventually(
            lambda: t.get(DEPLOYMENTS, "web--west", "default")["spec"]
            ["replicas"] == 5, timeout=15)
        await _eventually(
            lambda: t.get(DEPLOYMENTS, "web--east", "default")["spec"]
            ["replicas"] == 5, timeout=15)
        assert splitter._evacuated == set()
        await splitter.stop()

    asyncio.run(main())


# ---------------------------------------------------------------------------
# replication injection points (repl.ship / repl.apply / repl.promote)
# ---------------------------------------------------------------------------


def _repl_pair(role="replica", hysteresis=0.4):
    from kcp_tpu.server.server import Config
    from kcp_tpu.server.threaded import ServerThread

    p = ServerThread(Config(durable=False, install_controllers=False,
                            tls=False)).start()
    f = ServerThread(Config(durable=False, install_controllers=False,
                            tls=False, role=role, primary=p.address,
                            repl_hysteresis_s=hysteresis)).start()
    return p, f


def _repl_applied(address: str) -> int:
    c = RestClient(address)
    try:
        return int(c._request("GET", "/replication/status")["applied_rv"])
    finally:
        c.close()


def test_repl_ship_fault_drill():
    """`repl.ship:error` kills the feed stream; the follower reconnects
    and catches up with nothing lost (resume from applied RV)."""
    faults.install(faults.FaultInjector("repl.ship:error@tick=1", seed=0))
    p, r = _repl_pair()
    try:
        pc = RestClient(p.address, cluster="t1")
        for i in range(5):
            pc.create("configmaps", {"apiVersion": "v1", "kind": "ConfigMap",
                                     "metadata": {"name": f"s{i}",
                                                  "namespace": "default",
                                                  "clusterName": "t1"}})
        assert asyncio.run(wait_until(
            lambda: _repl_applied(r.address) >= 5, 15.0))
        assert counter("fault_injected_repl_ship_total") >= 1
        pc.close()
    finally:
        faults.clear()
        r.stop()
        p.stop()


def test_repl_apply_fault_drill():
    """`repl.apply:error` drops the feed mid-apply; the reconnect
    re-resumes from the applied RV, so convergence is exact."""
    faults.install(faults.FaultInjector("repl.apply:error@tick=2", seed=0))
    p, r = _repl_pair()
    try:
        pc = RestClient(p.address, cluster="t1")
        for i in range(8):
            pc.create("configmaps", {"apiVersion": "v1", "kind": "ConfigMap",
                                     "metadata": {"name": f"a{i}",
                                                  "namespace": "default",
                                                  "clusterName": "t1"}})
        assert asyncio.run(wait_until(
            lambda: _repl_applied(r.address) >= 8, 15.0))
        assert counter("fault_injected_repl_apply_total") >= 1
        rc = RestClient(r.address, cluster="t1")
        items, rv = rc.list("configmaps", namespace="default")
        assert rv == 8 and len(items) == 8
        pc.close()
        rc.close()
    finally:
        faults.clear()
        r.stop()
        p.stop()


def test_repl_promote_fault_drill():
    """`repl.promote:error` aborts the first promotion attempt; the
    standby retries after the next probe cycle and still promotes."""
    faults.install(faults.FaultInjector("repl.promote:error@tick=1", seed=0))
    p, s = _repl_pair(role="standby", hysteresis=0.3)
    try:
        pc = RestClient(p.address, cluster="t1")
        pc.create("configmaps", {"apiVersion": "v1", "kind": "ConfigMap",
                                 "metadata": {"name": "pre",
                                              "namespace": "default",
                                              "clusterName": "t1"}})
        assert asyncio.run(wait_until(
            lambda: _repl_applied(s.address) >= 1, 15.0))
        promoted_before = counter("repl_promotions_total")
        pc.close()
        p.kill()

        def promoted() -> bool:
            try:
                c = RestClient(s.address)
                try:
                    st = c._request("GET", "/replication/status")
                finally:
                    c.close()
                return st["role"] == "primary" and st["read_only"] is None
            except Exception:
                return False

        assert asyncio.run(wait_until(promoted, 20.0))
        assert counter("fault_injected_repl_promote_total") >= 1
        assert counter("repl_promotions_total") == promoted_before + 1
    finally:
        faults.clear()
        s.stop()
        p.stop()


# ---------------------------------------------------------------------------
# group-commit window drills (store.commit_window)
# ---------------------------------------------------------------------------


def test_commit_window_forced_split_drill(tmp_path):
    """`store.commit_window:drop` forces a window split mid-fill: the
    records before the split flush as their own window, everything still
    commits, and the window counter shows the extra flush."""
    faults.install(faults.FaultInjector(
        "store.commit_window:drop@tick=2", seed=0))
    store = LogicalStore(wal_path=str(tmp_path / "split.wal"),
                         wal_backend="json")
    before = counter("store_commit_windows_total")

    async def drive():
        async def writer(i: int):
            store.create("configmaps", "c0", {
                "apiVersion": "v1", "kind": "ConfigMap",
                "metadata": {"name": f"s{i}", "namespace": "d"}})
            aw = store.commit_durable(store.resource_version)
            if aw is not None:
                await aw

        await asyncio.gather(*(writer(i) for i in range(4)))

    asyncio.run(drive())
    store.close()
    faults.clear()
    assert counter("store_commit_windows_total") - before >= 2
    restored = LogicalStore(wal_path=str(tmp_path / "split.wal"),
                            wal_backend="json")
    assert len(restored) == 4
    restored.close()


def test_commit_window_abort_drill_wraps_typed(tmp_path):
    """`store.commit_window:raise` (an InjectedFault, not an ApiError)
    aborts the flush: every writer still gets a TYPED 503 — non-API
    sync failures must not escape as bare 500s — and none of the
    window's records commit."""
    faults.install(faults.FaultInjector(
        "store.commit_window:raise", seed=0))
    wal = str(tmp_path / "abort.wal")
    store = LogicalStore(wal_path=wal, wal_backend="json")
    failures = []

    async def drive():
        async def writer(i: int):
            store.create("configmaps", "c0", {
                "apiVersion": "v1", "kind": "ConfigMap",
                "metadata": {"name": f"a{i}", "namespace": "d"}})
            try:
                await store.commit_durable(store.resource_version)
            except UnavailableError as e:
                failures.append(e)

        await asyncio.gather(*(writer(i) for i in range(3)))

    asyncio.run(drive())
    faults.clear()
    store.close()
    assert len(failures) == 3
    with open(wal) as f:
        assert [ln for ln in f if ln.strip()] == []


def test_commit_window_sync_failure_is_typed_5xx_over_http(tmp_path):
    """The HTTP half of the commit-none drill: a write whose window
    sync fails answers a typed 503 Status (the client can retry), the
    WAL carries nothing, and the next write commits normally."""
    from kcp_tpu.server.server import Config
    from kcp_tpu.server.threaded import ServerThread

    p = ServerThread(Config(durable=True, install_controllers=False,
                            tls=False,
                            root_dir=str(tmp_path / "srv"))).start()
    try:
        faults.install(faults.FaultInjector(
            "store.commit_window:error=1", seed=0))
        c = RestClient(p.address, cluster="t1")
        with pytest.raises(UnavailableError):
            c.create("configmaps", {
                "apiVersion": "v1", "kind": "ConfigMap",
                "metadata": {"name": "doomed", "namespace": "default",
                             "clusterName": "t1"}})
        faults.clear()
        c.create("configmaps", {
            "apiVersion": "v1", "kind": "ConfigMap",
            "metadata": {"name": "survivor", "namespace": "default",
                         "clusterName": "t1"}})
        c.close()
    finally:
        faults.clear()
        # kill, not stop: a graceful shutdown compacts a snapshot of the
        # in-memory map, which (exactly like a failed SERIAL append)
        # still carries the unacked object — the WAL is what the failed
        # window must not have touched
        p.kill()
    # offline replay: the failed window committed nothing; the retry did
    import importlib.util
    import os as _os

    spec = importlib.util.spec_from_file_location(
        "walreplay", _os.path.join(
            _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
            "scripts", "walreplay.py"))
    walreplay = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(walreplay)
    st = walreplay.replay(str(tmp_path / "srv" / "store.wal"))
    names = {key.decode().split("\x00")[3] for key in st.objects}
    assert names == {"survivor"}


# ---------------------------------------------------------------------------
# WAN link realism: peer-pair-scoped partition + delay, fleet solve drill
# ---------------------------------------------------------------------------


def test_link_partition_drill_directed_cut_then_heal():
    """link.partition:drop cuts ONLY the named directed pair; the heal
    counter advances on every invocation of the point, so traffic on the
    healthy reverse direction burns the partition down too."""
    faults.install(faults.FaultInjector(
        "link.partition:drop@peer=zone-a>10.0.0.2:6443@heal=3", seed=0))
    with pytest.raises(ConnectionError):
        faults.link_fault("zone-a", "10.0.0.2:6443")       # invocation 1
    # reverse direction untouched (directed spec), but counts as inv 2
    assert faults.link_fault("10.0.0.2:6443", "zone-a") == 0.0
    # invocation 3 >= heal=3: the partition has healed
    assert faults.link_fault("zone-a", "10.0.0.2:6443") == 0.0
    assert counter("fault_injected_link_partition_total") >= 1


def test_link_partition_bidirectional_wildcard_cut():
    faults.install(faults.FaultInjector(
        "link.partition:drop@peer=*<>standby", seed=0))
    for src, dst in (("primary", "standby"), ("standby", "primary")):
        with pytest.raises(ConnectionError):
            faults.link_fault(src, dst)
    # pairs not involving the standby stay connected
    assert faults.link_fault("primary", "witness") == 0.0


def test_link_delay_drill_seeded_wan_latency_with_jitter():
    """link.delay:latency on a peer pair returns base+jitter seconds,
    replayable per seed; other pairs ride free."""
    spec = "link.delay:latency=50ms@peer=repl.feed>replica@jitter=20ms"
    a = faults.FaultInjector(spec, seed=42)
    b = faults.FaultInjector(spec, seed=42)
    da = [a.link_delay("link.delay", "repl.feed", "replica")
          for _ in range(8)]
    db = [b.link_delay("link.delay", "repl.feed", "replica")
          for _ in range(8)]
    assert da == db                       # seeded => replayable
    assert all(0.05 <= d <= 0.07 for d in da)
    assert a.link_delay("link.delay", "repl.feed", "other") == 0.0


def test_fleet_solve_fault_drill_requeues_then_converges():
    """fleet.solve:error on the first dispatch: the scheduler requeues
    the dirty rows (last good assignment stands — here: none yet) and
    the retry converges to the weighted split."""
    from kcp_tpu.apis import cluster as capi
    from kcp_tpu.fleet.scheduler import FleetScheduler

    async def main():
        store = LogicalStore()
        mc = MultiClusterClient(store)
        t = mc.cluster_client("t")
        for name, cap in (("big", 300), ("small", 100)):
            obj = capi.new_cluster(name, kubeconfig=f"fake://{name}")
            capi.set_capacity(obj, cap)
            set_ready(obj)
            t.create(capi.CLUSTERS, obj)
        splitter = DeploymentSplitter(mc, backend="host")
        sched = FleetScheduler(splitter)
        faults.install(faults.FaultInjector("fleet.solve:error@tick=1",
                                            seed=0))
        await splitter.start()
        await sched.start()
        t.create(DEPLOYMENTS, deployment_obj("web", 12))
        for _ in range(500):
            try:
                if t.get(DEPLOYMENTS, "web--big",
                         "default")["spec"]["replicas"] == 9:
                    break
            except NotFoundError:
                pass
            await asyncio.sleep(0.01)
        assert t.get(DEPLOYMENTS, "web--big",
                     "default")["spec"]["replicas"] == 9
        assert t.get(DEPLOYMENTS, "web--small",
                     "default")["spec"]["replicas"] == 3
        await sched.stop()
        await splitter.stop()

    def deployment_obj(name, replicas):
        return {"apiVersion": "apps/v1", "kind": "Deployment",
                "metadata": {"name": name, "namespace": "default"},
                "spec": {"replicas": replicas,
                         "template": {"spec": {"containers": []}}}}

    asyncio.run(main())
    assert counter("fault_injected_fleet_solve_total") >= 1
