"""The SERVED mesh at a small size on the CPU: one ``Server`` with
``Config(mesh="4")`` — what ``kcp start --mesh 4`` builds, REST, store,
watch, cluster controller, syncer engines and applier around the fused
core — on four of conftest's eight virtual devices, with ``fake://``
locations and the benchmark's ``StatusEcho`` controllers: the deployment
``mesh4-1k.steady`` times on four real chips.

(a) seeded creates, updates and deletes over REST for six logical
    clusters end with every downstream store and every upstream status
    equal to an oracle kept in this file (a dict and three verbs; nothing
    of ``kcp_tpu.ops`` or ``kcp_tpu.models``), and equal to what the
    single-device server gives under the same seed;
(b) the resident state lies on four devices in shards of ``B / 4`` rows;
    ``fused_fleet_mesh_shards`` reads 4 (1 without a mesh) and
    ``fused_fleet_put_bytes_total`` rises by the bytes of a tick's ONE
    packed array (the ack lane in its tail rows) times 4 (times 1
    without a mesh) every tick, and ``fused_fleet_puts_total`` by 4 (1);
(c) the fleet grows past a power of two WHILE serving on the mesh
    (row-factor padding, a sharded full upload) and loses no staged row,
    mask stamp or patch;
(d) the benchmark's topology (``benchmarks/mesh_deploy.py``) says the
    shards, and refuses a state that lies on fewer devices than asked.
"""

import os
import random
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import mesh_deploy  # noqa: E402
from benchmarks.agents import StatusEcho  # noqa: E402
from kcp_tpu.apis import cluster as capi  # noqa: E402
from kcp_tpu.models.reconcile_model import ack_lane_rows  # noqa: E402
from kcp_tpu.physical import PhysicalRegistry  # noqa: E402
from kcp_tpu.server import Config, RestClient  # noqa: E402
from kcp_tpu.server.threaded import ServerThread  # noqa: E402
from kcp_tpu.syncer.core import MIN_EVENTS, FusedCore  # noqa: E402
from kcp_tpu.utils.errors import ConflictError  # noqa: E402
from kcp_tpu.utils.trace import REGISTRY  # noqa: E402

LOC = "loc0"
LABEL = "kcp.dev/cluster"
COUNTERS = ("fused_fleet_put_bytes_total", "fused_fleet_puts_total",
            "fused_fleet_ticks_total",
            "fused_fleet_row_growths_total",
            "fused_fleet_state_upload_bytes_total",
            "fused_step_failures_total", "quarantined_rows")


def body(name: str, rng: random.Random, gen: int = 0) -> dict:
    return {"apiVersion": "v1", "kind": "ConfigMap",
            "metadata": {"name": name, "namespace": "default",
                         "labels": {LABEL: LOC}},
            "data": {"k0": f"{rng.getrandbits(64):016x}",
                     "k1": f"{rng.getrandbits(64):016x}", "gen": str(gen)}}


def snapshot() -> dict:
    snap = REGISTRY.snapshot()
    return {k: float(snap.get(k, 0.0)) for k in COUNTERS}


def fake(tenant: str) -> str:
    return f"fake://{tenant}-{LOC}"


class Oracle:
    """What every store must hold: the objects by (tenant, name) with the
    data last written, and for each the status its location's controller
    writes for THAT data."""

    def __init__(self):
        self.data: dict[tuple[str, str], dict] = {}

    def create(self, tenant: str, obj: dict) -> None:
        assert (tenant, obj["metadata"]["name"]) not in self.data
        self.data[(tenant, obj["metadata"]["name"])] = dict(obj["data"])

    def update(self, tenant: str, obj: dict) -> None:
        assert (tenant, obj["metadata"]["name"]) in self.data
        self.data[(tenant, obj["metadata"]["name"])] = dict(obj["data"])

    def delete(self, tenant: str, name: str) -> None:
        del self.data[(tenant, name)]

    def expected(self) -> dict:
        """{(tenant, name): (data, status)}: upstream and, a logical
        cluster's objects in its one location, downstream alike."""
        return {key: (data, {"observedGen": data["gen"]})
                for key, data in self.data.items()}


def served(mesh: str, seed: int, n_tenants: int = 6, residents: int = 5,
           writes: int = 40, creates_each: int = 0) -> dict:
    """One server with ``Config(mesh=mesh)``; residents, then ``writes``
    seeded REST operations (90/5/5 as the cell's traffic, over tenants
    drawn uniformly), then ``creates_each`` more creates a tenant (the
    growth case); wait until every store reads as the oracle says."""
    rng = random.Random(seed)
    tenants = [f"t{i:03d}" for i in range(n_tenants)]
    oracle = Oracle()
    registry = PhysicalRegistry()
    before = snapshot()
    srv = ServerThread(
        Config(durable=False, tls=False, install_controllers=True,
               auto_publish_apis=True, resources_to_sync=["configmaps"],
               syncer_mode="push", mesh=mesh), registry=registry
    ).start(timeout=120)
    agents = []
    try:
        mc = srv.server.client
        rest = RestClient(srv.address)

        def write(kind: str, tenant: str, obj: dict) -> None:
            rest.cluster = tenant
            if kind == "create":
                rest.create("configmaps", obj)
                oracle.create(tenant, obj)
            elif kind == "update":
                while True:  # the status upsync writes the object too
                    have = rest.get("configmaps", obj["metadata"]["name"],
                                    "default")
                    have["data"] = obj["data"]
                    try:
                        rest.update("configmaps", have)
                        break
                    except ConflictError:
                        continue
                oracle.update(tenant, obj)
            else:
                rest.delete("configmaps", obj["metadata"]["name"], "default")
                oracle.delete(tenant, obj["metadata"]["name"])

        for tenant in tenants:
            rest.cluster = tenant
            rest.create(capi.CLUSTERS.storage_name,
                        capi.new_cluster(LOC, fake(tenant)))
        agents = [StatusEcho(registry.resolve(fake(t))) for t in tenants]

        async def start_agents():
            for a in agents:
                await a.start()

        srv.submit(start_agents())
        for tenant in tenants:
            for j in range(residents):
                write("create", tenant, body(f"cm-{j:03d}", rng))
        fresh = 0
        for _ in range(writes):
            tenant = tenants[rng.randrange(n_tenants)]
            mine = sorted(n for t, n in oracle.data if t == tenant)
            roll = rng.random()
            if roll < 0.05 or not mine:
                fresh += 1
                write("create", tenant, body(f"new-{fresh:03d}", rng))
            elif roll < 0.10:
                name = mine[rng.randrange(len(mine))]
                write("delete", tenant, body(name, rng))
            else:
                name = mine[rng.randrange(len(mine))]
                gen = int(oracle.data[(tenant, name)]["gen"]) + 1
                write("update", tenant, body(name, rng, gen))
        for tenant in tenants:
            for j in range(creates_each):
                write("create", tenant, body(f"grow-{j:03d}", rng))
        rest.close()

        def read():
            up, down = {}, {}
            for tenant in tenants:
                items, _rv = mc.cluster_client(tenant).list("configmaps")
                up.update({(tenant, o["metadata"]["name"]):
                           (o.get("data"), o.get("status")) for o in items})
                items, _rv = registry.resolve(fake(tenant)).list("configmaps")
                down.update({(tenant, o["metadata"]["name"]):
                             (o.get("data"), o.get("status")) for o in items})
            return up, down

        want = oracle.expected()
        deadline = time.monotonic() + 90.0
        while True:
            up, down = srv.call(read)
            if up == want and down == want:
                break
            assert time.monotonic() < deadline, (
                {k: (up.get(k), down.get(k), want.get(k))
                 for k in set(up) | set(down) | set(want)
                 if not up.get(k) == down.get(k) == want.get(k)})
            time.sleep(0.05)

        def layout():
            (core,) = [c for c in FusedCore._instances.values()
                       if c._loop is srv._loop]
            fleet = core._fleet
            shards = fleet._state.up_vals.addressable_shards
            width = fleet.S + 2
            per_tick = (MIN_EVENTS
                        + ack_lane_rows(fleet.ack_capacity, width)) * width * 4
            return {"B": fleet.B, "S": fleet.S, "per_tick_bytes": per_tick,
                    "devices": sorted({sh.device.id for sh in shards}),
                    "shard_rows": sorted({sh.data.shape[0] for sh in shards}),
                    "gauge": REGISTRY.snapshot()["fused_fleet_mesh_shards"]}

        lay = srv.call(layout)
        after = snapshot()
    finally:
        async def stop_agents():
            for a in agents:
                await a.stop()

        if agents:
            srv.submit(stop_agents())
        srv.stop()
    return {"want": want, "up": up, "down": down, "layout": lay,
            "agent_errors": sum(a.errors for a in agents),
            "rise": {k: after[k] - before[k] for k in COUNTERS}}


def sound(got: dict) -> None:
    assert got["up"] == got["want"] and got["down"] == got["want"]
    assert got["agent_errors"] == 0
    assert got["rise"]["fused_step_failures_total"] == 0
    assert got["rise"]["quarantined_rows"] == 0
    assert got["rise"]["fused_fleet_ticks_total"] >= 1


@pytest.fixture(scope="module")
def pair():
    """The same seed through the mesh server and the single-device one."""
    return served("4", seed=46), served("", seed=46)


def test_served_mesh_equals_the_oracle_and_the_single_device(pair):
    mesh, single = pair
    sound(mesh)
    sound(single)
    assert len(mesh["want"]) >= 25
    assert mesh["want"] == single["want"]  # the same seed, the same writes
    assert mesh["up"] == single["up"] and mesh["down"] == single["down"]


def test_state_lies_on_four_devices_in_quarters(pair):
    mesh, single = pair
    lay = mesh["layout"]
    assert len(lay["devices"]) == 4
    assert lay["shard_rows"] == [lay["B"] // 4]
    assert len(single["layout"]["devices"]) == 1
    assert single["layout"]["shard_rows"] == [single["layout"]["B"]]
    assert (lay["B"], lay["S"]) == (single["layout"]["B"],
                                    single["layout"]["S"])


def test_gauge_and_put_bytes_count_the_replication(pair):
    mesh, single = pair
    assert mesh["layout"]["gauge"] == 4 and single["layout"]["gauge"] == 1
    for got, devices in ((mesh, 4), (single, 1)):
        rise, lay = got["rise"], got["layout"]
        # no tick of this size carries more than MIN_EVENTS events, so
        # every tick puts ONE array — the packed wire with the floor's ack
        # lane in its tail rows — to every device of the mesh
        assert rise["fused_fleet_put_bytes_total"] == (
            rise["fused_fleet_ticks_total"] * lay["per_tick_bytes"] * devices)
        # and nothing else: the plain syncer stages no placement lane
        assert rise["fused_fleet_puts_total"] == (
            rise["fused_fleet_ticks_total"] * devices)
    assert mesh["layout"]["per_tick_bytes"] == single["layout"]["per_tick_bytes"]


def test_growth_past_a_power_of_two_while_serving_on_the_mesh():
    got = served("4", seed=47, residents=5, writes=20, creates_each=8)
    sound(got)
    lay, rise = got["layout"], got["rise"]
    assert len(got["want"]) >= 6 * 13 - 3
    assert rise["fused_fleet_row_growths_total"] >= 1 and lay["B"] >= 128
    assert len(lay["devices"]) == 4 and lay["shard_rows"] == [lay["B"] // 4]
    # the growth's full upload went up sharded: the whole state once more
    assert rise["fused_fleet_state_upload_bytes_total"] > 2 * 64 * lay["S"] * 4


def test_topology_says_the_shards_and_refuses_fewer_devices(tmp_path):
    from benchmarks import run as runmod

    config = dict(runmod.resolve("mesh4-1k.steady", rehearse=True)[2],
                  mesh="2x1", warm_bursts=[])
    dep = mesh_deploy.Deployment(config, 7, str(tmp_path))
    try:
        dep.start()
        dep.register()
        dep.populate()
        dep.start_agents()
        dep.settle()
        fleet = dep.fleet()
        assert fleet["shards"] == 2 and fleet["shard_rows"] == fleet["B"] // 2
        assert fleet["on"] == ["cpu"] and fleet["live"] == len(dep.population)
        dep.cfg = dict(config, mesh="4x1")  # asked: four; the state: two
        with pytest.raises(RuntimeError, match="lies on 2 device"):
            dep.fleet()
    finally:
        dep.stop()
    assert mesh_deploy.mesh_devices("4x1") == 4
    assert mesh_deploy.mesh_devices("4x2") == 8
