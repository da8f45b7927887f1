"""The ``k8s-rolling`` deployment at a small size on the CPU: the
Deployments of ``k8s-load`` under a location that ROLLS them
(``benchmarks/rolling_agent.py``), four to seven status writes a
convergence.

(a) the plain reference (``benchmarks/k8s_rolling_reference.py``) against
    hand-worked sequences, and the bounds a rolling update keeps;
(b) the controller against the reference, write for write, on a store
    with no syncer;
(c) the served path (``benchmarks/deploy.Deployment``) on the fused
    backend and on ``backend="host"``: every trail of statuses a watcher
    of the tenant's object saw passes ``trail_mismatches``, and both
    backends end in the stores the reference says (so in the same ones);
(d) the quiet tenant: one rollout, then nothing — the last status a
    location wrote is upstream within 2 s with no other traffic, also
    where a patch was skipped behind a pending apply
    (``kcp_sync_patches_deferred_total``);
(e) a toy controller that writes one status out of order, a torn one or
    a stale one is told by ``evidence_mismatches``.
"""

import asyncio
import copy
import json
import os
import random
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import k8s_rolling_reference as ref  # noqa: E402
from benchmarks import rolling_agent  # noqa: E402
from benchmarks.shapes import k8s_rolling as shape  # noqa: E402

# (replicas, updatedReplicas, availableReplicas, unavailableReplicas,
# Available), worked by hand from the rule in the reference's docstring
HAND = {
    (5, 7): [(9, 4, 5, 2, False), (6, 4, 6, 1, True), (9, 7, 6, 1, True),
             (7, 7, 7, 0, True)],
    (5, 3): [(3, 0, 3, 0, True), (4, 1, 3, 0, True), (3, 1, 3, 0, True),
             (4, 2, 3, 0, True), (3, 2, 3, 0, True), (4, 3, 3, 0, True),
             (3, 3, 3, 0, True)],
    (30, 40): [(50, 20, 30, 10, True), (30, 20, 30, 10, True),
               (50, 40, 30, 10, True), (40, 40, 40, 0, True)],
    (250, 200): [(150, 0, 150, 50, True), (250, 100, 150, 50, True),
                 (150, 100, 150, 50, True), (250, 200, 150, 50, True),
                 (200, 200, 200, 0, True)],
    (0, 5): [(5, 5, 0, 5, False), (5, 5, 5, 0, True)],
    (2, 1): [(1, 0, 1, 0, True), (2, 1, 1, 0, True), (1, 1, 1, 0, True)],
}


def _row(status: dict) -> tuple:
    assert status["readyReplicas"] == status["availableReplicas"]
    return (status["replicas"], status["updatedReplicas"],
            status["availableReplicas"], status["unavailableReplicas"],
            status["conditions"][0]["status"] == "True")


# ------------------------------------------------------- (a) the reference


@pytest.mark.parametrize("old,new", sorted(HAND))
def test_rollout_statuses_against_hand_worked_sequences(old, new):
    seq = ref.rollout_statuses(old, new)
    assert [_row(s) for s in seq] == HAND[(old, new)]
    assert seq[-1] == ref.final_status(new)
    assert ref.complete({"replicas": new}, seq[-1])
    assert not any(ref.complete({"replicas": new}, s) for s in seq[:-1])
    reasons = [s["conditions"][1]["reason"] for s in seq]
    assert reasons == ["ReplicaSetUpdated"] * (len(seq) - 1) + [
        "NewReplicaSetAvailable"]
    for s in seq:
        assert [c["type"] for c in s["conditions"]] == ["Available",
                                                        "Progressing"]
        assert s["conditions"][0]["reason"] == (
            "MinimumReplicasAvailable" if _row(s)[4]
            else "MinimumReplicasUnavailable")


def test_a_rollout_keeps_its_fenceposts_and_ends():
    assert [ref.fenceposts(r) for r in (0, 1, 2, 3, 4, 5, 30, 250)] == [
        (0, 1), (1, 0), (1, 0), (1, 0), (1, 1), (2, 1), (8, 7), (63, 62)]
    rng = random.Random(36)
    pairs = [(a, b) for a in range(0, 12) for b in range(0, 12)] + [
        (rng.randrange(0, 400), rng.randrange(1, 400)) for _ in range(300)]
    for old, new in pairs:
        seq = ref.rollout_statuses(old, new)
        s, u = ref.fenceposts(new)
        assert ref.complete({"replicas": new}, seq[-1]), (old, new)
        assert all(seq[i] != seq[i + 1] for i in range(len(seq) - 1))
        for st in seq:
            # never more pods than the surge allows once the old
            # ReplicaSet has been scaled, never fewer available than the
            # rolling update promises
            assert st["replicas"] <= new + s, (old, new, st)
            assert st["availableReplicas"] >= min(old, new - u), (old, new, st)
            assert st["unavailableReplicas"] == max(
                0, new - st["availableReplicas"])
    # the shares ISSUE 36 reckoned the cell's load from: status writes
    # per update of a small Deployment
    body = shape.new("deployment-000-00000000", rng, ["loc0"])
    writes = []
    for _ in range(3000):
        body = dict(body, spec=dict(body["spec"], replicas=5))
        nxt = shape.mutate(body, rng)
        writes.append(len(ref.rollout_statuses(5, nxt["spec"]["replicas"])))
    assert set(writes) == {4, 5, 7}
    assert 4.6 < sum(writes) / len(writes) < 5.0


def test_complete_is_not_ready_equals_replicas():
    first = ref.rollout_statuses(5, 3)[0]
    assert first["readyReplicas"] == 3 and first["updatedReplicas"] == 0
    assert not ref.complete({"replicas": 3}, first)
    body = shape.new("deployment-000-00000001", random.Random(1), ["loc0"])
    obj = copy.deepcopy(body)
    obj["metadata"]["clusterName"] = "t0000"
    obj["status"] = dict(first, observedGeneration=2)
    assert shape.observe(obj) != shape.want(
        dict(body, spec=dict(body["spec"], replicas=3)))


# ---------------------------------------- (b) the controller, write for write


async def _until(pred, timeout: float, what: str) -> None:
    deadline = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < deadline, what
        await asyncio.sleep(0.002)


@pytest.mark.parametrize("seed", [36, 2**31 + 36, 7, 2026])
def test_controller_writes_the_references_sequence(seed, monkeypatch):
    """50 draws of ``mutate`` a seed (200 in all), ten objects rolling at
    once, on a store with no syncer: every ``update_status`` the
    controller made, in order, is the reference's sequence for that
    change, under the generation of the copy it rolled."""
    from kcp_tpu.client import Client
    from kcp_tpu.store import LogicalStore

    monkeypatch.setattr(rolling_agent, "POD_READY_MS", 1)

    async def main():
        down = Client(LogicalStore(), "pcluster")
        wrote: dict[str, list[dict]] = {}
        sound = down.update_status

        def recording(resource, obj, namespace=""):
            wrote.setdefault(obj["metadata"]["name"], []).append(
                copy.deepcopy(obj["status"]))
            return sound(resource, obj, namespace=namespace)

        down.update_status = recording
        agent = rolling_agent.RollingDeployment(down)
        await agent.start()
        rng = random.Random(seed)
        bodies = {}
        for i in range(10):
            name = f"deployment-{i:03d}-{rng.getrandbits(32):08x}"
            bodies[name] = shape.new(name, rng, ["loc0"])
            down.create(shape.RESOURCE, bodies[name])
        before = {n: 0 for n in bodies}
        for rnd in range(6):
            if rnd:
                for name in bodies:
                    bodies[name] = shape.mutate(bodies[name], rng)
                    down.update(shape.RESOURCE, bodies[name])
            gens = {n: down.get(shape.RESOURCE, n, shape.NAMESPACE)[
                "metadata"]["generation"] for n in bodies}
            assert set(gens.values()) == {rnd + 1}
            await _until(lambda: all(
                agent._done.get((shape.NAMESPACE, n)) == g
                for n, g in gens.items()), 20, f"round {rnd} rolled")
            for name, body in bodies.items():
                got = wrote.pop(name)
                assert {s.pop("observedGeneration") for s in got} == {
                    gens[name]}
                want = ref.rollout_statuses(before[name],
                                            body["spec"]["replicas"])
                assert got == want, (name, before[name], body["spec"])
                before[name] = body["spec"]["replicas"]
                stamp = rolling_agent.STAMPS[("loc0", name, gens[name])]
                assert stamp[0] <= stamp[1]
                assert (stamp[0] < stamp[1]) == (len(want) > 1)
        assert agent.errors == 0 and not agent._walks
        # a copy deleted before its rollout's first step: the walk is
        # abandoned, nothing is written and nothing counts as an error
        name, n = sorted(bodies)[0], agent.writes
        down.update(shape.RESOURCE, shape.mutate(bodies[name], rng))
        down.delete(shape.RESOURCE, name, shape.NAMESPACE)
        await asyncio.sleep(0.05)
        assert agent.writes == n and agent.errors == 0
        assert (shape.NAMESPACE, name) not in agent._walks
        await agent.stop()

    asyncio.run(main())


def test_a_newer_generation_abandons_the_walk(monkeypatch):
    from kcp_tpu.client import Client
    from kcp_tpu.store import LogicalStore

    monkeypatch.setattr(rolling_agent, "POD_READY_MS", 30)

    async def main():
        down = Client(LogicalStore(), "pcluster")
        agent = rolling_agent.RollingDeployment(down)
        await agent.start()
        rng = random.Random(5)
        body = shape.new("deployment-000-0badc0de", rng, ["loc0"])
        down.create(shape.RESOURCE, body)
        key = (shape.NAMESPACE, body["metadata"]["name"])
        await _until(lambda: agent._done.get(key) == 1, 10, "created")
        second = shape.mutate(body, rng)
        down.update(shape.RESOURCE, second)
        await _until(lambda: key in agent._walks, 5, "the walk began")
        third = shape.mutate(second, rng)
        down.update(shape.RESOURCE, third)
        await _until(lambda: agent._done.get(key) == 3, 10, "rolled")
        got = down.get(shape.RESOURCE, key[1], key[0])
        assert ref.complete(third["spec"], got["status"])
        assert got["status"]["observedGeneration"] == 3
        assert ("loc0", key[1], 2) in rolling_agent.STAMPS
        await agent.stop()

    asyncio.run(main())


# ------------------------------------------------------------- (c) served


def _config() -> dict:
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "k8s-rolling-1k.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "k8s-load-1k.json")) as f:
        load = json.load(f)
    # k8s-load-1k's sizes and object, key for key; two cuts, each with
    # its reason; the two guarantees this deployment adds
    for key in ("logical_clusters", "locations_per_cluster",
                "resources_to_sync", "resident_per_cluster", "warm_bursts",
                "rehearsal", "server"):
        assert cfg[key] == load[key], key
    assert set(cfg["object"]) == set(load["object"])
    assert cfg["shape"] == "k8s_rolling" and len(cfg["source"]) <= 200
    assert cfg["reduced"] == ["resident_per_cluster", "pod_ready_ms"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert cfg["pod_ready_ms"] == rolling_agent.POD_READY_MS == 20
    assert set(cfg["guarantees"]) == set(load["guarantees"]) | {
        "status_order", "status_final"}
    assert (shape.AGENT, shape.AGENT_MODULE) == (
        "RollingDeployment", "benchmarks.rolling_agent")
    return {**cfg, **cfg["rehearsal"], "logical_clusters": 4,
            "resident_per_cluster": 3}


class _Watchers:
    """What ``loadgen.Session._on_event`` does, in process: ``observe``
    every event of a key that waits, ``evidence`` of the one that ends
    the wait. One informer per logical cluster, on the server's loop."""

    def __init__(self, dep):
        from kcp_tpu.client import Informer

        self.waiting: dict[tuple[str, str], dict] = {}
        self.informers = []
        mc = dep.srv.server.client
        for tenant in dep.tenants:
            inf = Informer(mc.cluster_client(tenant), shape.RESOURCE)
            inf.add_handler(self._on_event)
            dep.srv.submit(inf.start())
            self.informers.append(inf)

    def _on_event(self, etype, old, new):
        if etype == "DELETED" or new is None:
            return
        key = (new["metadata"]["clusterName"], new["metadata"]["name"])
        rec = self.waiting.get(key)
        if rec is None or shape.observe(new) != rec["want"]:
            return
        del self.waiting[key]
        rec["evidence"] = shape.evidence(new)
        rec["seen"] = time.monotonic()

    def stop(self, dep):
        for inf in self.informers:
            dep.srv.submit(inf.stop())


@pytest.mark.parametrize("backend", ["tpu", "host"])
def test_served_path_equals_the_reference(backend, tmp_path, monkeypatch):
    from benchmarks import compare, deploy

    from kcp_tpu.reconcilers import cluster as cluster_pkg
    from kcp_tpu.server.rest import RestClient
    from kcp_tpu.syncer.engine import BatchSyncEngine

    made = []
    sound = BatchSyncEngine.__init__

    def init(self, *a, **kw):
        sound(self, *a, **kw)
        made.append(self.backend)

    monkeypatch.setattr(BatchSyncEngine, "__init__", init)
    if backend == "host":
        # steered here, in the test: the Server has no such option
        controller = cluster_pkg.ClusterController
        monkeypatch.setattr(
            cluster_pkg, "ClusterController",
            lambda *a, **kw: controller(*a, backend="host", **kw))
    seed = 2**31 + 36
    dep = deploy.Deployment(_config(), seed, str(tmp_path))
    rng = random.Random(seed)
    ops: list[dict] = []
    bodies = dict(dep.population)
    client = watchers = None
    trips0 = _counter(TIMELINE["restatus"])
    try:
        dep.bring_up(say=lambda _m: None)
        assert made and set(made) == {backend}
        assert dep.agent_class() is rolling_agent.RollingDeployment
        client = RestClient(dep.srv.address)
        watchers = _Watchers(dep)

        def write(kind, tenant, name, body=None):
            client.cluster = tenant
            rec = {"kind": kind, "key": [tenant, name], "body": body,
                   "sent": time.monotonic(), "acked": None, "seen": None}
            ops.append(rec)
            if kind != "delete":
                rec["want"] = shape.want(body)
                watchers.waiting[(tenant, name)] = rec
            if kind == "create":
                client.create(shape.RESOURCE, body)
            elif kind == "update":
                client.update(shape.RESOURCE, body)
            else:
                client.delete(shape.RESOURCE, name, shape.NAMESPACE)
            rec["acked"] = time.monotonic()
            if body is None:
                bodies.pop((tenant, name))
                return
            bodies[(tenant, name)] = body
            # as the benchmark's generators: never write an object whose
            # last write has not converged yet
            deadline = time.monotonic() + 30
            while rec["seen"] is None:
                assert time.monotonic() < deadline, (kind, tenant, name)
                time.sleep(0.005)

        for i in range(30):
            keys = sorted(bodies)
            tenant, name = keys[rng.randrange(len(keys))]
            u = rng.random()
            if u < 0.15:
                tenant = dep.tenants[rng.randrange(len(dep.tenants))]
                name = f"{shape.PREFIX}-n{i:03d}-{rng.getrandbits(32):08x}"
                write("create", tenant, name,
                      shape.new(name, rng, dep.locations))
            elif u < 0.25:
                write("delete", tenant, name)
            else:
                write("update", tenant, name,
                      shape.mutate(bodies[(tenant, name)], rng))
        state, uncertain = ref.final_state(dep.population, ops)
        assert not uncertain and state == bodies

        # every trail a watcher saw, and the object that ended its wait
        bad = compare.evidence_mismatches(dep, ops)
        assert not bad, "\n".join(bad)
        trails = [r["evidence"]["trail"] for r in ops if r["kind"] == "update"]
        assert trails and all(len(t) >= 2 for t in trails)
        assert max(len(t) for t in trails) >= 4  # several trips a write
        # 20 ms apart, the later statuses of a rollout are trips of
        # their own on either backend
        assert _counter(TIMELINE["restatus"]) - trips0 >= 2 * len(trails)
        # both backends end in the stores the reference says
        by_tenant, skip, n_uncertain = compare.expected(dep, ops)
        assert n_uncertain == 0
        up, down, _waited = compare.drain(dep, by_tenant, skip, 60.0)
        assert not up + down, "\n".join(up + down)
        assert dep.agent_errors() == 0
    finally:
        if watchers is not None:
            watchers.stop(dep)
        if client is not None:
            client.close()
        dep.stop()


# ------------------------------------------------------ (d) the quiet tenant


def _counter(name: str) -> float:
    """A counter's value, a histogram's count."""
    from kcp_tpu.utils.trace import REGISTRY

    v = REGISTRY.snapshot().get(name, 0.0)
    return v["count"] if isinstance(v, dict) else v


TIMELINE = {"deferred": "kcp_sync_patches_deferred_total",
            "upsyncs": "kcp_sync_status_upsyncs_total",
            "repeats": "kcp_sync_status_upsync_repeats_total",
            "upstatus": "convergence_upstatus_seconds",
            "restatus": "convergence_restatus_seconds",
            "stage": "convergence_stage_seconds"}


@pytest.mark.parametrize("backend,slow_apply_ms", [
    ("tpu", 0), ("tpu", 5), ("host", 0)])
def test_the_quiet_tenant_gets_its_last_status(backend, slow_apply_ms,
                                               monkeypatch):
    """One logical cluster, one Deployment, one syncer: a rollout, then
    nothing at all until the final status is upstream — within 2 s of the
    location showing it — and at once the next rollout, so that the
    tenant's spec write meets the previous status trip's patches in
    flight. Before ISSUE 36 a patch skipped behind a pending apply of
    ANOTHER decision (the spec patch behind a stale status upsync) was
    lost until some other key's event made a tick: 26 of 200 such rollouts
    never reached the location."""
    from kcp_tpu import faults
    from kcp_tpu.client import Client
    from kcp_tpu.store import LogicalStore
    from kcp_tpu.syncer import start_syncer

    monkeypatch.setattr(rolling_agent, "POD_READY_MS", 2)
    reps = 40 if backend == "tpu" else 15

    async def main():
        up = Client(LogicalStore(), "tenant")
        down = Client(LogicalStore(), "pcluster")
        syncer = await start_syncer(up, down, [shape.RESOURCE], "loc0",
                                    backend=backend)
        agent = rolling_agent.RollingDeployment(down)
        await agent.start()
        rng = random.Random(36)
        body = shape.new("deployment-000-0000abcd", rng, ["loc0"])
        name = body["metadata"]["name"]
        before = {k: _counter(n) for k, n in TIMELINE.items()}
        up.create(shape.RESOURCE, body)
        try:
            for rep in range(reps + 1):
                if rep:
                    body = shape.mutate(body, rng)
                    up.update(shape.RESOURCE, body)
                t0 = time.monotonic()
                t_down = None
                while True:
                    now = time.monotonic()
                    if t_down is None:
                        try:
                            d = down.get(shape.RESOURCE, name, shape.NAMESPACE)
                        except Exception:  # noqa: BLE001 — not created yet
                            d = {}
                        if ((d.get("spec") or {}) == body["spec"]
                                and ref.complete(d["spec"], d.get("status"))):
                            t_down = now
                    o = up.get(shape.RESOURCE, name, shape.NAMESPACE)
                    if shape.observe(o) == shape.want(body):
                        break
                    # the first turn compiles the fused step
                    assert now - t0 < (90 if rep == 0 else 10), (
                        f"rollout {rep}: the spec never reached the location"
                        if t_down is None else f"rollout {rep}: lost")
                    assert t_down is None or now - t_down < 2.0, (
                        f"rollout {rep}: the final status is downstream for "
                        f"2 s and not upstream: {o.get('status')}")
                    await asyncio.sleep(0.002)
                assert ref.status_mismatches(body["spec"]["replicas"],
                                             o["status"]) == []
            # the echo of the last status write retires the key's entry
            await _until(lambda: not syncer.engines[0]._dirty, 5,
                         "the timeline's entry retired")
        finally:
            await agent.stop()
            await syncer.stop()
        assert agent.errors == 0
        return {k: _counter(TIMELINE[k]) - v for k, v in before.items()}

    if slow_apply_ms:
        faults.install(faults.FaultInjector(
            f"syncer.apply:latency={slow_apply_ms}ms", seed=36))
    try:
        rise = asyncio.run(main())
    finally:
        faults.clear()
    # the timeline follows the key: one first trip a tenant write (the
    # echo of a LATER status write opens no timeline of its own), every
    # other upsync a later trip or a repeat of the same status
    assert rise["stage"] == rise["upstatus"] == reps + 1, rise
    assert (rise["upstatus"] + rise["restatus"] + rise["repeats"]
            == rise["upsyncs"]), rise
    if backend == "host":
        # at least the final status of each rollout is a trip of its own
        # (on the fused backend, 2 ms apart, a status that arrives while
        # the previous one's apply is pending rides that apply)
        assert rise["restatus"] >= reps
        assert rise["deferred"] == 0  # the host backend applies in its tick
    elif slow_apply_ms:
        # the test is known to reach the branch
        assert rise["deferred"] > 0


def test_a_skipped_patch_of_another_decision_is_rearmed():
    """``fused_apply`` alone: a patch behind a pending apply of the same
    decision is covered by it; one of another decision re-enqueues the
    key when the pending apply ends."""
    from kcp_tpu.client import Client
    from kcp_tpu.ops.diff import DECISION_UPDATE
    from kcp_tpu.store import LogicalStore
    from kcp_tpu.syncer.engine import BatchSyncEngine

    class Core:
        def __init__(self):
            self.enqueued = []

        def enqueue(self, section, side, key):
            self.enqueued.append(key)

    async def main():
        eng = BatchSyncEngine(Client(LogicalStore(), "t"),
                              Client(LogicalStore(), "p"), shape.RESOURCE,
                              "loc0", backend="tpu", core=Core())
        eng._apply_q = asyncio.Queue()
        eng._section = object()
        key, other = ("default", "a"), ("default", "b")
        before = _counter("kcp_sync_patches_deferred_total")
        eng.fused_apply([(key, 0, True), (other, 0, True)])
        eng.fused_apply([(key, 0, True)])                # same: covered
        assert eng._apply_pending == {key: (0, True), other: (0, True)}
        eng.fused_apply([(key, DECISION_UPDATE, False)])  # another decision
        assert eng._apply_pending[key] not in ((0, True),
                                               (DECISION_UPDATE, False))
        assert _counter("kcp_sync_patches_deferred_total") - before == 2
        assert eng._apply_q.qsize() == 2
        worker = asyncio.create_task(eng._apply_worker())
        await eng._apply_q.join()
        worker.cancel()
        assert eng.core.enqueued == [key]
        assert not eng._apply_pending

    asyncio.run(main())


# ---------------------------------------------------- (e) a toy controller


def _stored(body: dict, status: dict | None, generation: int) -> dict:
    out = copy.deepcopy(body)
    out["metadata"].update(uid="0b1e0f9c-5d7e", resourceVersion="41",
                           generation=generation, clusterName="t0000")
    if status is not None:
        out["status"] = dict(status, observedGeneration=generation)
    return out


ORDERS = {
    "in-order": (lambda s: s, False),
    "skipping": (lambda s: s[:1] + s[-1:], False),
    "repeating": (lambda s: s[:2] + s[1:2] + s[2:], False),
    "one-out-of-order": (lambda s: [s[1], s[0]] + s[2:], True),
    "stale": (lambda s: s[:2] + ["before"] + s[2:], True),
    "torn": (lambda s: s[:1] + [dict(s[1], updatedReplicas=99)] + s[1:], True),
    "not-the-final-one-last": (lambda s: s[:-1] + ["final-counters"], True),
}


@pytest.mark.parametrize("order", sorted(ORDERS))
def test_a_toy_controller_is_held_to_the_order(order):
    """A store, a watcher that observes as the load generator does, and a
    toy controller that writes the reference's statuses in an order of
    its own: only the level-triggered orders pass."""
    from kcp_tpu.client import Client, Informer
    from kcp_tpu.store import LogicalStore

    arrange, told = ORDERS[order]
    rng = random.Random(4)
    old = shape.new("deployment-000-00000e0e", rng, ["loc0"])
    body = shape.mutate(old, rng)
    r0, r = old["spec"]["replicas"], body["spec"]["replicas"]
    seq = ref.rollout_statuses(r0, r)
    assert len(seq) >= 4
    plan = arrange(list(seq))

    async def main():
        up = Client(LogicalStore(), "t0000")
        seen = {}

        def on_event(etype, _old, new):
            if new is not None and not seen and (
                    shape.observe(new) == shape.want(body)):
                seen.update(shape.evidence(new))

        inf = Informer(up, shape.RESOURCE)
        inf.add_handler(on_event)
        up.create(shape.RESOURCE, old)
        cur = up.get(shape.RESOURCE, old["metadata"]["name"], shape.NAMESPACE)
        cur["status"] = dict(ref.final_status(r0), observedGeneration=1)
        up.update_status(shape.RESOURCE, cur, namespace=shape.NAMESPACE)
        await inf.start()
        up.update(shape.RESOURCE, body)
        for st in plan:
            if st == "before":
                st = ref.final_status(r0)
            elif st == "final-counters":
                # ends the wait by the counters, under a condition the
                # final status does not carry
                st = dict(seq[-1], conditions=seq[-2]["conditions"])
            cur = up.get(shape.RESOURCE, old["metadata"]["name"],
                         shape.NAMESPACE)
            cur["status"] = dict(st, observedGeneration=2)
            up.update_status(shape.RESOURCE, cur, namespace=shape.NAMESPACE)
            await asyncio.sleep(0.005)
            if seen:
                break
        await _until(lambda: bool(seen), 5, "the wait ended")
        await inf.stop()
        return seen

    seen = asyncio.run(main())
    bad = shape.evidence_mismatches(body, seen, None, ["loc0"])
    assert bool(bad) == told, (order, bad, seen["trail"])


def test_trail_mismatches_by_hand():
    body = {"spec": {"replicas": 7}}
    seq = [dict(s, observedGeneration=3) for s in ref.rollout_statuses(5, 7)]
    before = dict(ref.final_status(5), observedGeneration=2)
    assert ref.trail_mismatches(5, body, [before] + seq) == []
    assert ref.trail_mismatches(5, body, [before, seq[1], seq[3]]) == []
    assert ref.trail_mismatches(5, body, [before] + seq[:3])  # not final
    assert ref.trail_mismatches(5, body, [before, seq[2], seq[1], seq[3]])
    assert ref.trail_mismatches(5, body, [None] + seq)  # a status was there
    assert ref.trail_mismatches(5, body, [before, seq[0], before] + seq[1:])
    back = [before, seq[0], dict(seq[1], observedGeneration=2)] + seq[2:]
    assert ref.trail_mismatches(5, body, back)  # the generation went back
    create = {"spec": {"replicas": 5}}
    made = [dict(s, observedGeneration=1) for s in ref.rollout_statuses(0, 5)]
    assert ref.trail_mismatches(0, create, [None] + made) == []
    assert ref.trail_mismatches(0, create, [None, made[0], None, made[1]])
    # the whole-object and store comparisons are k8s-load's, under this
    # rule's final status
    rng = random.Random(2)
    b = shape.new("deployment-000-000000aa", rng, ["loc0"])
    good = _stored(b, ref.final_status(b["spec"]["replicas"]), 4)
    assert ref.object_mismatches(b, good, copy=True) == []
    assert ref.store_mismatches("t", {"a": b}, {"a": good}, copy=True) == []
    mid = _stored(b, ref.rollout_statuses(0, b["spec"]["replicas"])[0], 4)
    assert ref.object_mismatches(b, mid) and ref.store_mismatches(
        "t", {"a": b}, {"a": mid})
    inside = copy.deepcopy(good)
    inside["spec"]["template"]["spec"]["containers"][0]["env"][0]["value"] = "x"
    assert "['template']" in ref.object_mismatches(b, inside)[0]
    behind = copy.deepcopy(good)
    behind["status"]["observedGeneration"] = 3
    assert ref.object_mismatches(b, behind) == []  # upstream cannot tell
    assert ref.object_mismatches(b, behind, copy=True)
    assert ref.store_mismatches("t", {"a": b}, {}) == [
        "t/a: acknowledged, not held"]
    assert ref.store_mismatches("t", {}, {"a": good}) == [
        "t/a: held but deleted or never written"]
