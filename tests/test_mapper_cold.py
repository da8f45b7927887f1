"""A cold full sync at a small size on the CPU: the logical clusters are
populated FIRST and their locations registered AFTER (``Cluster`` objects
created against a store that already holds every resident and against
empty ``fake://`` locations), with live creates in flight — the path a
control plane takes after a restart, which ``mapper-1k-50k.cold`` times.

(a) every case, sized to cross a row doubling (64 -> 128 -> 256), a
    segment doubling (8 -> 16) or a patch-capacity overflow inside the
    sync, ends equal to the plain reference
    (``benchmarks/cold_sync_reference.py``) object for object, with
    ``fused_step_failures_total`` and ``quarantined_rows`` unmoved;
(b) what the deployment adds to the program is counted: the three growth
    counters, the uploaded bytes, the initial-list rows, the syncer
    starts and the ``kcp.cluster.reconcile`` section;
(c) the tick's ``compile`` phase times the first dispatch of a set of
    shapes once and a known one never;
(d) the compile listener hears a compile, and is absent without jax.
"""

import os
import random
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import cold_sync_reference as ref  # noqa: E402
from benchmarks.agents import StatusEcho  # noqa: E402
from kcp_tpu.apis import cluster as capi  # noqa: E402
from kcp_tpu.obs import runtime  # noqa: E402
from kcp_tpu.physical import PhysicalRegistry  # noqa: E402
from kcp_tpu.server import Config, RestClient  # noqa: E402
from kcp_tpu.server.threaded import ServerThread  # noqa: E402
from kcp_tpu.syncer import core as fused  # noqa: E402
from kcp_tpu.utils.trace import REGISTRY  # noqa: E402

LOC = "loc0"
COUNTERS = ("fused_fleet_row_growths_total", "fused_fleet_segment_growths_total",
            "fused_fleet_patch_growths_total",
            "fused_fleet_state_upload_bytes_total",
            "kcp_sync_initial_rows_total", "fused_step_failures_total",
            "quarantined_rows", "cluster_syncer_start_seconds_count",
            "fused_compile_seconds_count", "fused_fleet_ticks_total",
            "server_loop_self_seconds_kcp_cluster_reconcile")


def body(name: str, rng: random.Random) -> dict:
    return {"apiVersion": "v1", "kind": "ConfigMap",
            "metadata": {"name": name, "namespace": "default",
                         "labels": {ref.CLUSTER_LABEL: LOC}},
            "data": {"k0": f"{rng.getrandbits(64):016x}", "gen": "0"}}


def snapshot() -> dict:
    snap = REGISTRY.snapshot()
    return {k: float(snap.get(k, 0.0)) for k in COUNTERS}


def fake(tenant: str) -> str:
    return f"fake://{tenant}-{LOC}"


def cold_sync(n_tenants: int, per_tenant: int, n_live: int = 6) -> dict:
    """Populate, start the locations' controllers, THEN register every
    location over REST while live creates go on; wait for the statuses;
    return the stores as read, the reference's inputs and the counters'
    rise."""
    rng = random.Random(n_tenants * 1000 + per_tenant)
    tenants = [f"t{i:03d}" for i in range(n_tenants)]
    population = {(t, f"cm-{j:03d}"): body(f"cm-{j:03d}", rng)
                  for t in tenants for j in range(per_tenant)}
    registry = PhysicalRegistry()
    before = snapshot()
    srv = ServerThread(
        Config(durable=False, tls=False, install_controllers=True,
               auto_publish_apis=True, resources_to_sync=["configmaps"],
               syncer_mode="push"), registry=registry).start(timeout=120)
    agents = []
    try:
        mc = srv.server.client

        def populate():
            for (tenant, _name), obj in population.items():
                mc.cluster_client(tenant).create("configmaps", obj)

        srv.call(populate)
        agents = [StatusEcho(registry.resolve(fake(t))) for t in tenants]

        async def start_agents():
            for a in agents:
                await a.start()

        srv.submit(start_agents())
        assert not REGISTRY.snapshot().get("fused_fleet_ticks_total", 0.0) \
            - before["fused_fleet_ticks_total"], "a tick before any location"

        rest = RestClient(srv.address)
        live: dict = {}
        for i, tenant in enumerate(tenants):
            rest.cluster = tenant
            rest.create(capi.CLUSTERS.storage_name,
                        capi.new_cluster(LOC, fake(tenant)))
            if i < n_live:  # a tenant's own write, while the sync runs
                obj = body(f"live-{i:03d}", rng)
                rest.create("configmaps", obj)
                live[(tenant, obj["metadata"]["name"])] = obj
        rest.close()
        wanted = {**population, **live}

        def read():
            up = {}
            for tenant in tenants:
                items, _rv = mc.cluster_client(tenant).list("configmaps")
                up.update({(tenant, o["metadata"]["name"]): o for o in items})
            down = {(t, LOC): registry.resolve(fake(t)).list("configmaps")[0]
                    for t in tenants}
            return up, down

        deadline = time.monotonic() + 90.0
        while True:
            up, down = srv.call(read)
            registered = [(t, LOC) for t in tenants]
            if not ref.upstream_mismatches(wanted, registered, up):
                break
            assert time.monotonic() < deadline, ref.upstream_mismatches(
                wanted, registered, up)[:3]
            time.sleep(0.05)
        up, down = srv.call(read)
        after = snapshot()
    finally:
        async def stop_agents():
            for a in agents:
                await a.stop()

        if agents:
            srv.submit(stop_agents())
        srv.stop()
    return {"population": population, "live": live, "up": up, "down": down,
            "registered": [(t, LOC) for t in tenants],
            "agent_errors": sum(a.errors for a in agents),
            "rise": {k: after[k] - before[k] for k in COUNTERS}}


# (tenants, residents each, patch capacity floor, what it must cross)
CASES = {
    "rows-64-128": (3, 30, None, {"fused_fleet_row_growths_total": 1}),
    "rows-64-128-256": (5, 40, None, {"fused_fleet_row_growths_total": 2}),
    "segments-8-16": (10, 4, None, {"fused_fleet_segment_growths_total": 1}),
    "patch-overflow": (3, 30, 8, {"fused_fleet_patch_growths_total": 1}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_cold_sync_ends_equal_to_the_reference(case, monkeypatch):
    n_tenants, per_tenant, patch_floor, crossed = CASES[case]
    if patch_floor is not None:
        monkeypatch.setattr(fused, "MIN_PATCH_CAPACITY", patch_floor)
    got = cold_sync(n_tenants, per_tenant)
    expected = ref.expected_downstream(got["population"], got["registered"],
                                       got["live"])
    assert sum(map(len, expected.values())) == (
        n_tenants * per_tenant + len(got["live"]))
    assert ref.downstream_mismatches(expected, got["down"]) == []
    assert ref.upstream_mismatches({**got["population"], **got["live"]},
                                   got["registered"], got["up"]) == []
    rise = got["rise"]
    assert got["agent_errors"] == 0
    assert rise["fused_step_failures_total"] == 0
    assert rise["quarantined_rows"] == 0
    for name, least in crossed.items():
        assert rise[name] >= least, (name, rise)
    # every resident was staged by an initial list's replay, at least once
    assert rise["kcp_sync_initial_rows_total"] >= n_tenants * per_tenant
    assert rise["cluster_syncer_start_seconds_count"] == n_tenants
    assert rise["fused_fleet_state_upload_bytes_total"] > 0
    assert rise["fused_compile_seconds_count"] >= 1
    assert rise["server_loop_self_seconds_kcp_cluster_reconcile"] > 0


def test_reference_tells_a_missing_a_foreign_and_a_doubled_object():
    rng = random.Random(7)
    pop = {("t0", "a"): body("a", rng), ("t0", "b"): body("b", rng),
           ("t1", "c"): body("c", rng)}
    expected = ref.expected_downstream(pop, [("t0", LOC)])
    assert set(expected) == {("t0", LOC)} and set(expected[("t0", LOC)]) == {
        "a", "b"}
    sound = {("t0", LOC): [pop[("t0", "a")], pop[("t0", "b")]]}
    assert ref.downstream_mismatches(expected, sound) == []
    wrong = dict(pop[("t0", "b")], data={"k0": "x", "gen": "0"})
    for stores, said in (
            ({("t0", LOC): [pop[("t0", "a")]]}, "b not downstream"),
            ({("t0", LOC): sound[("t0", LOC)] + [pop[("t1", "c")]]},
             "c downstream, not placed there"),
            ({("t0", LOC): sound[("t0", LOC)] + [pop[("t0", "a")]]}, "a twice"),
            ({("t0", LOC): [pop[("t0", "a")], wrong]}, "b downstream {"),
            ({**sound, ("t1", LOC): [pop[("t1", "c")]]},
             "c downstream, not placed there")):
        assert any(said in m for m in ref.downstream_mismatches(
            expected, stores)), said
    up = {k: dict(v, status=ref.status_for(v)) for k, v in pop.items()}
    assert ref.upstream_mismatches(pop, [("t0", LOC), ("t1", LOC)], up) == []
    up[("t0", "a")] = dict(pop[("t0", "a")], status={"observedGen": "9"})
    assert len(ref.upstream_mismatches(pop, [("t0", LOC)], up)) == 1


class _Owner:
    """The least a section's owner is: rows of one constant slot."""

    def fused_status_mask(self):
        import numpy as np

        return np.zeros(64, bool)

    def fused_encode(self, key):
        import numpy as np

        v = np.zeros(64, np.uint32)
        v[0] = hash(key) & 0xFFFF
        return v, True, v, True

    def fused_apply(self, patches):
        pass


def test_compile_phase_times_a_new_shape_once_and_a_known_one_never():
    """Ticks of one shape: the first is the ``compile`` phase, the rest
    are ``step_dispatch``; growth past 64 rows is a new shape, once."""
    import asyncio

    def counts():
        snap = REGISTRY.snapshot()
        return (snap.get("fused_compile_seconds_count", 0.0),
                snap.get("fused_step_dispatch_seconds_count", 0.0),
                snap.get("fused_fleet_row_growths_total", 0.0))

    async def main():
        core = fused.FusedCore(pipeline="serial")
        section = core.register(_Owner(), 64)
        await core.start()
        try:
            async def tick(keys):
                n = core._fleet.stats["ticks"]
                core.enqueue_many(section, False, keys)
                for _ in range(2000):
                    if core._fleet.stats["ticks"] > n:
                        return
                    await asyncio.sleep(0.005)
                raise AssertionError("no tick")

            c0, d0, g0 = counts()
            await tick(["a"])            # a full upload at B = 64: new
            c1, d1, _ = counts()
            assert (c1 - c0, d1 - d0) == (1, 0)
            await tick(["b"])            # a delta of the same width: known
            await tick(["c"])
            await tick(["d"])
            c2, d2, g2 = counts()
            assert (c2 - c1, d2 - d1, g2 - g0) == (0, 3, 0)
            many = [f"k{i}" for i in range(70)]
            await tick(many)             # past 64 rows: B = 128, new
            c3, d3, g3 = counts()
            assert (c3 - c2, d3 - d2, g3 - g2) == (1, 0, 1)
            await tick(many)             # 70 rows in one delta: d = 128, new
            await tick(many)             # known
            await tick(["a", "b"])       # d = 64 at B = 128: the upload's
            c4, d4, g4 = counts()
            assert (c4 - c3, d4 - d3, g4 - g3) == (1, 2, 0)
        finally:
            await core.stop()

    asyncio.run(main())


def test_compile_listener_hears_a_compile():
    import jax
    import jax.numpy as jnp

    runtime.RuntimeProbes._hear_compiles()
    assert runtime.RuntimeProbes._compiles_heard
    snap = REGISTRY.snapshot()
    n0 = snap.get("jax_backend_compile_seconds_count", 0.0)
    salt = random.Random().randrange(1 << 30)
    jax.jit(lambda x: x * salt + 1)(jnp.arange(7)).block_until_ready()
    snap = REGISTRY.snapshot()
    assert snap["jax_backend_compile_seconds_count"] >= n0 + 1
    assert snap["jax_backend_compile_seconds"]["mean"] > 0
    # another event of jax.monitoring is not a compile
    n1 = snap["jax_backend_compile_seconds_count"]
    runtime._on_compile("/jax/core/compile/jaxpr_trace_duration", 1.0)
    assert REGISTRY.snapshot()["jax_backend_compile_seconds_count"] == n1


def test_compile_listener_is_absent_without_jax(monkeypatch):
    """A process that never imported jax (a storage frontend, the load
    generator) registers nothing and imports nothing."""
    monkeypatch.setattr(runtime.RuntimeProbes, "_compiles_heard", False)
    monkeypatch.setitem(sys.modules, "jax", None)
    runtime.RuntimeProbes._hear_compiles()
    assert runtime.RuntimeProbes._compiles_heard is False
