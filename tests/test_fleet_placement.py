"""Fleet placement control plane: solver determinism, inventory FSM,
scheduler loop (kcp_tpu/fleet/).

- batched-vs-host differential fuzz: the jitted [W x P] bin-pack and its
  numpy twin must produce byte-identical assignments across seeds x
  skewed capacities x partition patterns (eligibility holes), plus the
  bin-pack invariants (conservation, capacity-positivity, spread).
- inventory hysteresis property test at 10k workspaces under a virtual
  clock: flaps inside the window move NOTHING (version frozen);
  sustained outages evacuate exactly once; readmission reconverges; the
  delta journal routes re-solves to exactly the touched workspaces.
- FleetScheduler end-to-end: capacity-weighted leafs through the
  DeploymentSplitter's apply conventions, zero churn under flap,
  evacuation + readmission reconvergence, locality preference.
"""

import asyncio

import numpy as np
import pytest

from kcp_tpu.apis import cluster as capi
from kcp_tpu.client import MultiClusterClient
from kcp_tpu.fleet.inventory import ClusterInventory
from kcp_tpu.fleet.scheduler import FleetScheduler
from kcp_tpu.fleet.solver import (DEFAULT_LOCALITY_WEIGHT, FleetSolver,
                                  solve_batched, solve_host, solve_sharded)
from kcp_tpu.physical import ChurnDriver
from kcp_tpu.reconcilers.deployment import DeploymentSplitter
from kcp_tpu.reconcilers.deployment.controller import DEPLOYMENTS
from kcp_tpu.store import LogicalStore
from kcp_tpu.utils.trace import REGISTRY


def deployment(name, replicas, ns="default", labels=None):
    d = {"apiVersion": "apps/v1", "kind": "Deployment",
         "metadata": {"name": name, "namespace": ns},
         "spec": {"replicas": replicas,
                  "template": {"spec": {"containers": []}}}}
    if labels:
        d["metadata"]["labels"] = dict(labels)
    return d


def ready_cluster(name, cap, region="", alloc=None):
    obj = capi.new_cluster(name, kubeconfig=f"fake://{name}")
    capi.set_capacity(obj, cap, allocatable=alloc, region=region)
    capi.set_ready(obj)
    return obj


async def eventually(pred, timeout=5.0):
    loop = asyncio.get_event_loop()
    end = loop.time() + timeout
    while loop.time() < end:
        try:
            if pred():
                return
        except Exception:
            pass
        await asyncio.sleep(0.01)
    raise AssertionError("condition not reached")


# ---------------------------------------------------------------------------
# solver: batched-vs-host differential fuzz
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_solver_differential_fuzz_device_equals_host(seed):
    """Seeds x skewed capacities x partition patterns: the device program
    and the numpy twin must agree byte-for-byte, and every assignment
    must satisfy the bin-pack invariants."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    for _ in range(25):
        W = int(rng.integers(1, 48))
        P = int(rng.integers(1, 24))
        demand = rng.integers(0, 2000, W).astype(np.int32)
        # partition patterns: candidate holes + zero-capacity clusters
        cand = rng.random((W, P)) < rng.uniform(0.2, 1.0)
        alloc = np.maximum(
            0, np.round(64 * rng.lognormal(0.0, rng.uniform(0.2, 2.0), P))
        ).astype(np.int32)
        region = rng.integers(0, 5, P).astype(np.int32)
        home = rng.integers(0, 5, W).astype(np.int32)
        spread = int(rng.integers(0, 6))
        lw = int(rng.choice([0, 64, DEFAULT_LOCALITY_WEIGHT]))
        dev = np.asarray(solve_batched(demand, cand, alloc, region, home,
                                       jnp.int32(spread), jnp.int32(lw)))
        host = solve_host(demand, cand, alloc, region, home, spread, lw)
        assert np.array_equal(dev, host)
        elig = cand & (alloc > 0)[None, :]
        placeable = elig.any(axis=-1)
        assert (host.sum(axis=-1)[placeable] == demand[placeable]).all()
        assert (host[~placeable] == 0).all()
        assert ((host > 0) <= elig).all()  # never onto dead capacity
        if spread:
            assert ((host > 0).sum(axis=-1) <= spread).all()


def test_per_workspace_solves_equal_the_batched_assignment():
    """Rows are independent: the pre-fleet splitter's loop (one host
    solve per workspace) and ONE device batch give byte-identical
    assignments at the splitter's shape (8 clusters a workspace,
    lognormal-skewed capacity), never above demand, never onto a
    non-candidate."""
    rng = np.random.default_rng(17)
    W, P, spread = 300, 8, 2
    demand = rng.integers(0, 48, W).astype(np.int32)
    alloc = np.clip(rng.lognormal(3.0, 1.2, P), 1, 30000).astype(np.int32)
    cand = rng.random((W, P)) < 0.9
    region = rng.integers(0, 4, P).astype(np.int32)
    home = rng.integers(-1, 4, W).astype(np.int32)
    dev = FleetSolver(spread=spread).solve(
        demand, cand, alloc, region, home).copy()
    per = np.stack([
        solve_host(demand[i:i + 1], cand[i:i + 1], alloc, region,
                   home[i:i + 1], spread)[0] for i in range(W)])
    assert np.array_equal(dev, per)
    assert not (dev.sum(axis=1) > demand).any()
    assert dev[~cand].sum() == 0


def test_solver_prefers_home_region_then_capacity():
    # two regions; the home region has less capacity but wins on locality
    cand = np.ones((1, 3), bool)
    alloc = np.array([100, 400, 50], np.int32)
    region = np.array([0, 1, 0], np.int32)  # cols 0,2 in region 0
    home = np.array([0], np.int32)
    out = solve_host(np.array([10], np.int32), cand, alloc, region, home,
                     spread=2, locality_weight=DEFAULT_LOCALITY_WEIGHT)
    # spread=2 picks the two home-region clusters despite col 1's size
    assert out[0, 1] == 0 and out[0, 0] + out[0, 2] == 10
    # weighted by allocatable: 100 vs 50 -> the bigger one gets more
    assert out[0, 0] > out[0, 2]
    # with locality off, raw capacity wins
    out = solve_host(np.array([10], np.int32), cand, alloc, region, home,
                     spread=1, locality_weight=0)
    assert out[0, 1] == 10


def test_solver_deterministic_tie_break_is_column_order():
    cand = np.ones((1, 4), bool)
    alloc = np.full(4, 7, np.int32)  # all tied
    zeros = np.zeros(4, np.int32)
    out = solve_host(np.array([1], np.int32), cand, alloc, zeros,
                     np.zeros(1, np.int32), spread=1)
    assert out[0].tolist() == [1, 0, 0, 0]  # lowest column wins ties


def test_incremental_resolve_matches_full_and_skips_untouched():
    rng = np.random.default_rng(42)
    W, P = 200, 16
    demand = rng.integers(0, 500, W).astype(np.int32)
    cand = rng.random((W, P)) < 0.8
    alloc = rng.integers(1, 300, P).astype(np.int32)
    region = rng.integers(0, 3, P).astype(np.int32)
    home = rng.integers(0, 3, W).astype(np.int32)
    s = FleetSolver(backend="tpu")
    s.solve(demand, cand, alloc, region, home)
    # flip a few rows' candidate sets; re-solve ONLY those
    changed = [3, 77, 150]
    for r in changed:
        cand[r] = rng.random(P) < 0.5
    inc = s.solve(demand, cand, alloc, region, home, rows=changed).copy()
    assert np.array_equal(
        inc, solve_host(demand, cand, alloc, region, home))
    assert s.stats["rows_solved"] == W + len(changed)
    assert s.stats["rows_skipped"] == W - len(changed)


def test_solver_sharded_by_mesh_matches_host():
    from kcp_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(n_devices=1, slots=1)
    rng = np.random.default_rng(7)
    W, P = 33, 9  # deliberately not a multiple of the row factor
    demand = rng.integers(0, 100, W).astype(np.int32)
    cand = rng.random((W, P)) < 0.7
    alloc = rng.integers(0, 200, P).astype(np.int32)
    region = rng.integers(0, 2, P).astype(np.int32)
    home = rng.integers(0, 2, W).astype(np.int32)
    out = solve_sharded(mesh, demand, cand, alloc, region, home, spread=3)
    assert np.array_equal(
        out, solve_host(demand, cand, alloc, region, home, spread=3))


# ---------------------------------------------------------------------------
# inventory: hysteresis FSM + delta routing (virtual clock, 10k workspaces)
# ---------------------------------------------------------------------------


def _mk_cluster(name, ready, cap=64, region=""):
    obj = capi.new_cluster(name, kubeconfig=f"fake://{name}")
    capi.set_capacity(obj, cap, region=region)
    if ready:
        capi.set_ready(obj)
    else:
        capi.set_not_ready(obj, capi.REASON_SYNCER_NOT_READY, "down")
    return obj


def test_inventory_hysteresis_property_10k_workspaces():
    """10k workspaces x 4 pclusters under a virtual clock: flaps inside
    the window are invisible (no version bump -> zero churn routed), and
    sustained outages evacuate exactly the outaged registrations, whose
    workspaces — and ONLY those — come back from delta_since."""
    now = [0.0]
    inv = ClusterInventory(evac_hysteresis=5.0, clock=lambda: now[0])
    names = [f"pc-{i}" for i in range(4)]
    W = 10_000
    for w in range(W):
        ws = f"ws-{w:05d}"
        for name in names:
            inv.observe(ws, _mk_cluster(name, ready=True))
    v0 = inv.version
    view = inv.view()
    assert view.candidates.shape == (W, 4) and view.candidates.all()

    rng = np.random.default_rng(0)
    flap_set = {int(x) for x in rng.choice(W, 1000, replace=False)}
    out_set = {f"ws-{int(x):05d}" for x in rng.choice(W, 500, replace=False)}

    # flaps: NotReady then Ready again inside the window
    for w in flap_set:
        inv.observe(f"ws-{w:05d}", _mk_cluster("pc-1", ready=False))
    now[0] += 2.0  # < hysteresis
    for w in flap_set:
        inv.observe(f"ws-{w:05d}", _mk_cluster("pc-1", ready=True))
    now[0] += 10.0
    assert inv.tick() == []                      # nothing ripened
    assert inv.version == v0                     # ZERO churn by construction
    assert inv.delta_since(v0) == (set(), v0)

    # sustained outages: evacuate exactly once, exactly those
    for ws in out_set:
        inv.observe(ws, _mk_cluster("pc-2", ready=False))
    assert inv.version == v0                     # still quiet inside window
    now[0] += 5.0
    evacuated = inv.tick()
    assert {ws for ws, _ in evacuated} == out_set
    assert all(name == "pc-2" for _, name in evacuated)
    assert inv.tick() == []                      # idempotent
    changed, v1 = inv.delta_since(v0)
    assert changed == out_set                    # delta routes ONLY the outaged
    rows = [inv.row_of(ws) for ws in out_set]
    assert not inv.view().candidates[rows, 2].any()

    # readmission reconverges: Ready clears evacuation and re-lists
    for ws in out_set:
        inv.observe(ws, _mk_cluster("pc-2", ready=True))
    changed, _ = inv.delta_since(v1)
    assert changed == out_set
    assert inv.view().candidates.all()
    assert inv.pending() == 0


def test_inventory_capacity_delta_routes_all_registered_workspaces():
    inv = ClusterInventory(evac_hysteresis=5.0, clock=lambda: 0.0)
    for ws in ("a", "b"):
        inv.observe(ws, _mk_cluster("pc-0", ready=True, cap=64))
    inv.observe("c", _mk_cluster("pc-9", ready=True, cap=64))
    v = inv.version
    # pc-0's allocatable halves: a and b must re-solve, c must not
    obj = _mk_cluster("pc-0", ready=True, cap=64)
    obj["status"]["allocatable"] = {capi.CAPACITY_KEY: 32}
    inv.observe("a", obj)
    changed, _ = inv.delta_since(v)
    assert changed == {"a", "b"}
    view = inv.view()
    assert view.alloc[view.names.index("pc-0")] == 32


def test_inventory_journal_compaction_forces_full_resync():
    inv = ClusterInventory(clock=lambda: 0.0)
    inv.observe("ws", _mk_cluster("pc-0", ready=True))
    stale = inv.version
    for i in range(9000):  # blow past the journal window
        inv.observe("ws", _mk_cluster("pc-0", ready=True, cap=64 + i))
    changed, v = inv.delta_since(stale)
    assert changed is None and v == inv.version  # resync-all sentinel
    assert inv.delta_since(v) == (set(), v)


def test_churn_driver_is_replayable():
    a = ChurnDriver(64, seed=3, ticks=32)
    b = ChurnDriver(64, seed=3, ticks=32)
    assert a.capacity.tolist() == b.capacity.tolist()
    assert a.region == b.region
    for t in range(32):
        assert a.ready_at(t) == b.ready_at(t)
        assert a.allocatable_at(t) == b.allocatable_at(t)
    assert a.flap_count() == b.flap_count() > 0
    c = ChurnDriver(64, seed=4, ticks=32)
    assert (c.flap_count() != a.flap_count()
            or c.capacity.tolist() != a.capacity.tolist())


# ---------------------------------------------------------------------------
# scheduler: solver decisions through the splitter's leaf conventions
# ---------------------------------------------------------------------------


def test_fleet_scheduler_weighted_split_and_locality():
    async def main():
        store = LogicalStore()
        mc = MultiClusterClient(store)
        t = mc.cluster_client("t")
        t.create(capi.CLUSTERS, ready_cluster("big", 300, "us-east"))
        t.create(capi.CLUSTERS, ready_cluster("small", 100, "us-east"))
        t.create(capi.CLUSTERS, ready_cluster("far", 900, "eu-west"))
        splitter = DeploymentSplitter(mc, backend="host")
        sched = FleetScheduler(splitter, spread=2,
                               locality_weight=DEFAULT_LOCALITY_WEIGHT)
        assert splitter.place is False
        await splitter.start()
        await sched.start()
        # home region us-east: spread=2 picks big+small despite far's size
        t.create(DEPLOYMENTS, deployment(
            "web", 12, labels={capi.REGION_LABEL: "us-east"}))
        await eventually(lambda: t.get(
            DEPLOYMENTS, "web--big", "default")["spec"]["replicas"] == 9)
        assert t.get(DEPLOYMENTS, "web--small",
                     "default")["spec"]["replicas"] == 3
        items, _ = t.list(DEPLOYMENTS)
        assert "web--far" not in {o["metadata"]["name"] for o in items}
        # leaf conventions are the splitter's own
        leaf = t.get(DEPLOYMENTS, "web--big", "default")
        assert leaf["metadata"]["labels"]["kcp.dev/cluster"] == "big"
        assert leaf["metadata"]["labels"]["kcp.dev/owned-by"] == "web"
        assert leaf["metadata"]["ownerReferences"][0]["name"] == "web"
        # status fan-in still flows through the splitter's aggregation
        leaf["status"] = {"replicas": 9, "updatedReplicas": 9,
                          "readyReplicas": 9, "availableReplicas": 9,
                          "unavailableReplicas": 0}
        t.update_status(DEPLOYMENTS, leaf)
        await eventually(lambda: t.get(DEPLOYMENTS, "web", "default")
                         .get("status", {}).get("readyReplicas") == 9)
        await sched.stop()
        await splitter.stop()
    asyncio.run(main())


def test_fleet_scheduler_flap_zero_churn_then_evacuation_and_readmission():
    async def main():
        store = LogicalStore()
        mc = MultiClusterClient(store)
        t = mc.cluster_client("t")
        t.create(capi.CLUSTERS, ready_cluster("big", 300))
        t.create(capi.CLUSTERS, ready_cluster("small", 100))
        splitter = DeploymentSplitter(mc, backend="host",
                                      evac_hysteresis=0.3)
        sched = FleetScheduler(splitter)
        await splitter.start()
        await sched.start()
        t.create(DEPLOYMENTS, deployment("web", 12))
        await eventually(lambda: t.get(
            DEPLOYMENTS, "web--big", "default")["spec"]["replicas"] == 9)
        churn0 = REGISTRY.counter("placement_churn_total").value
        solves0 = sched.solver.stats["solves"]

        def flip(name, ready):
            obj = t.get(capi.CLUSTERS, name)
            if ready:
                capi.set_ready(obj)
            else:
                capi.set_not_ready(obj, capi.REASON_SYNCER_NOT_READY, "x")
            t.update_status(capi.CLUSTERS, obj)

        # flap inside the window: ZERO churn, ZERO re-solves
        flip("big", False)
        await asyncio.sleep(0.1)
        flip("big", True)
        await asyncio.sleep(0.5)
        assert REGISTRY.counter("placement_churn_total").value == churn0
        assert sched.solver.stats["solves"] == solves0
        assert t.get(DEPLOYMENTS, "web--big",
                     "default")["spec"]["replicas"] == 9

        # sustained: evacuate -> everything moves to small, leaf drained
        flip("big", False)
        await eventually(lambda: t.get(
            DEPLOYMENTS, "web--small", "default")["spec"]["replicas"] == 12)
        items, _ = t.list(DEPLOYMENTS)
        assert "web--big" not in {o["metadata"]["name"] for o in items}
        assert ("t", "big") in splitter._evacuated

        # readmission reconverges to the weighted split
        flip("big", True)
        await eventually(lambda: t.get(
            DEPLOYMENTS, "web--big", "default")["spec"]["replicas"] == 9)
        assert t.get(DEPLOYMENTS, "web--small",
                     "default")["spec"]["replicas"] == 3
        assert splitter._evacuated == set()
        # bounded migration: evac = update+drain, readmit = create+update
        assert REGISTRY.counter("placement_churn_total").value - churn0 == 4
        await sched.stop()
        await splitter.stop()
    asyncio.run(main())


def test_fleet_scheduler_churn_driver_reconverges():
    """A seeded flap storm over a small fleet: after it heals, the live
    assignment equals the host twin's answer for the final fleet state."""
    async def main():
        store = LogicalStore()
        mc = MultiClusterClient(store)
        t = mc.cluster_client("t")
        drv = ChurnDriver(6, seed=11, ticks=8, flap_rate=0.2,
                          outage_rate=0.0, base_capacity=64, skew=0.8)
        drv.seed_fleet(t)
        splitter = DeploymentSplitter(mc, backend="host",
                                      evac_hysteresis=0.25)
        sched = FleetScheduler(splitter)
        await splitter.start()
        await sched.start()
        t.create(DEPLOYMENTS, deployment("web", 40))
        await eventually(
            lambda: t.get(DEPLOYMENTS, "web--pc-0000", "default") is not None)
        for tick in range(drv.ticks):
            drv.apply(t, tick)
            await asyncio.sleep(0.02)
        drv.apply(t, drv.ticks)  # heal (past-end = all Ready)
        await asyncio.sleep(0.6)
        alloc = np.asarray(drv.allocatable_at(drv.ticks), np.int32)
        want = solve_host(np.array([40], np.int32),
                          np.ones((1, drv.n), bool), alloc,
                          np.zeros(drv.n, np.int32), np.zeros(1, np.int32))
        for i, name in enumerate(drv.names):
            if want[0, i]:
                assert t.get(DEPLOYMENTS, f"web--{name}", "default")[
                    "spec"]["replicas"] == int(want[0, i])
        await sched.stop()
        await splitter.stop()
    asyncio.run(main())


# ---------------------------------------------------------------------------
# the fused core's placement lane: a retired row rides the placement-leaves
# swap (syncer/core.py free_pl_row), driven tick by tick with no loop
# ---------------------------------------------------------------------------

class _PlacementOwner:
    """What `FusedBucket.route_placement` hands its owner, kept."""

    def __init__(self):
        self.applied: list[tuple[object, list[int]]] = []

    def placement_apply(self, applies):
        self.applied.extend((k, c.tolist()) for k, c in applies)


def _placement_core(mesh_devices):
    from kcp_tpu.parallel.mesh import make_mesh
    from kcp_tpu.syncer.core import FusedCore

    mesh = (make_mesh(n_devices=mesh_devices, slots=1)
            if mesh_devices else None)
    core = FusedCore(mesh=mesh)
    owner = _PlacementOwner()
    return core, core.register_placement(owner, 8), owner


def _dispatch(core, submitted):
    wire, meta = submitted
    core._fleet.dispatch(np.asarray(wire), meta)


def _one_tick(core):
    _dispatch(core, core._fleet.submit())


SPLIT_11_OVER_3 = [5, 3, 3, 0, 0, 0, 0, 0]
# no mesh, and the suite's virtual CPU devices as a 4x1 serving mesh: the
# swap's two leaves then go up sharded by rows over four devices
MESHES = pytest.mark.parametrize("mesh_devices", [0, 4], ids=["one", "mesh4"])


@MESHES
def test_a_reused_placement_row_emits_a_split_equal_to_the_retired_ones(
        mesh_devices):
    """The hazard `free_pl_row` guards: the device's `current[row]` keeps
    the retired root's split, so an equal split on the re-used row would
    never come back dirty. One tick with the row's zeroed inputs zeroes
    it — no full upload, no other row handed back."""
    core, b, owner = _placement_core(mesh_devices)
    b.stage_placement("stays", 7, 2)
    b.stage_placement("old", 11, 3)
    _one_tick(core)
    assert sorted(owner.applied) == [("old", SPLIT_11_OVER_3),
                                     ("stays", [4, 3, 0, 0, 0, 0, 0, 0])]
    row, uploads = b.pl_rows["old"], b.stats["full_uploads"]
    retired0 = REGISTRY.counter("fused_placement_rows_retired_total").value
    del owner.applied[:]

    b.free_pl_row("old")
    b.free_pl_row("old")  # gone already: finds no row, counts nothing
    assert REGISTRY.counter(
        "fused_placement_rows_retired_total").value - retired0 == 1
    assert b._pl_retiring == [row] and b._pl_free == [] and b.dirty
    _one_tick(core)
    # the row's own zero emission met no key; "stays" was not re-emitted
    assert owner.applied == []
    assert b._pl_free == [row] and b._pl_retiring == []
    assert b.stats["full_uploads"] == uploads

    b.stage_placement("new", 11, 3)
    assert b.pl_rows["new"] == row
    _one_tick(core)
    assert owner.applied == [("new", SPLIT_11_OVER_3)]
    assert b.stats["full_uploads"] == uploads
    # level-triggered: a quiet tick hands nothing back
    b._pl_staged = True
    _one_tick(core)
    assert owner.applied == [("new", SPLIT_11_OVER_3)]


@MESHES
def test_a_root_created_in_the_retirements_tick_window_takes_another_row(
        mesh_devices):
    """Retire and create between two ticks: the retired row is not free
    yet, so the new root gets a fresh one and is placed by the tick that
    zeroes the old one; the old row comes free only when THAT tick's wire
    has been dispatched — not at its submit."""
    core, b, owner = _placement_core(mesh_devices)
    b.stage_placement("old", 11, 3)
    _one_tick(core)
    row, uploads = b.pl_rows["old"], b.stats["full_uploads"]
    del owner.applied[:]

    b.free_pl_row("old")
    b.stage_placement("new", 11, 3)
    assert b.pl_rows["new"] != row
    submitted = core._fleet.submit()
    assert b._pl_retiring == [] and b._pl_free == []  # submitted, in flight
    b.stage_placement("newer", 11, 3)
    assert b.pl_rows["newer"] not in (row, b.pl_rows["new"])
    _dispatch(core, submitted)
    assert owner.applied == [("new", SPLIT_11_OVER_3)]
    assert b._pl_free == [row]
    _one_tick(core)
    assert owner.applied[1:] == [("newer", SPLIT_11_OVER_3)]
    b.stage_placement("last", 11, 3)
    assert b.pl_rows["last"] == row
    _one_tick(core)
    assert owner.applied[2:] == [("last", SPLIT_11_OVER_3)]
    assert b.stats["full_uploads"] == uploads


@MESHES
def test_puts_total_counts_the_wire_and_a_placement_swaps_two(mesh_devices):
    """`fused_fleet_puts_total`: every `jax.device_put` a submit makes,
    times the devices it writes — ONE a tick for the packed wire (the ack
    lane rides in it), two more on a tick with a placement-leaves swap;
    a full upload's state leaves are not the put phase's."""
    core, b, _owner = _placement_core(mesh_devices)
    devices = mesh_devices or 1
    puts = REGISTRY.counter("fused_fleet_puts_total")
    ticks = REGISTRY.counter("fused_fleet_ticks_total")
    b.stage_placement("a", 7, 2)
    p0, t0 = puts.value, ticks.value
    _one_tick(core)  # the first tick uploads the whole state
    assert b.stats["full_uploads"] == 1
    assert puts.value - p0 == devices
    b.stage_placement("b", 11, 3)
    p1 = puts.value
    _one_tick(core)  # replicas + avail + the wire
    assert puts.value - p1 == 3 * devices
    core._fleet.mark_stale()
    p2 = puts.value
    _one_tick(core)  # stale again: the wire alone
    assert puts.value - p2 == devices
    assert ticks.value - t0 == 3


def test_wires_in_flight_across_a_retirement_name_no_live_root():
    """Two wires cross a retirement: one submitted BEFORE it with the
    root's real split, one after it with the row's zero emission. Both
    are dispatched with the row keyless — nothing reaches the owner, and
    a root that takes the row afterwards sees its own split only."""
    core, b, owner = _placement_core(0)
    b.stage_placement("stays", 6, 3)
    _one_tick(core)
    del owner.applied[:]
    b.stage_placement("old", 11, 3)
    before = core._fleet.submit()      # carries old's [5, 3, 3]
    b.free_pl_row("old")
    b.stage_placement("other", 9, 3)   # cannot take old's row
    after = core._fleet.submit()       # carries old's row, all zeros
    row = after[1].pl_retired[0][1][0]
    assert after[1].pl_retired == ((b, [row]),) and before[1].pl_retired == ()
    _dispatch(core, before)
    assert owner.applied == [] and b._pl_free == []
    _dispatch(core, after)
    assert owner.applied == [("other", [3, 3, 3, 0, 0, 0, 0, 0])]
    assert b._pl_free == [row]
    b.stage_placement("new", 11, 3)
    _one_tick(core)
    assert owner.applied[1:] == [("new", SPLIT_11_OVER_3)]


def test_a_full_upload_for_another_reason_releases_the_retiring_rows():
    """Row growth (`_pl_grow`) rebuilds the resident state with `current`
    zeroed: the retiring rows ride that tick like any other and come free
    at its dispatch; the rows that stay are handed back once, as a full
    upload always did."""
    core, b, owner = _placement_core(0)
    for i in range(8):
        b.stage_placement(f"r{i}", 8 + i, 2)
    _one_tick(core)
    assert b.R == 8 and len(owner.applied) == 8
    uploads = b.stats["full_uploads"]
    rows = sorted(b.pl_rows[k] for k in ("r1", "r5"))
    b.free_pl_row("r1")
    b.free_pl_row("r5")
    # no free row while two retire: the ninth root grows the lane
    b.stage_placement("r8", 16, 2)
    assert b.R == 16 and b.pl_rows["r8"] == 8 and b._stale
    del owner.applied[:]
    _one_tick(core)
    assert b.stats["full_uploads"] == uploads + 1
    assert sorted(b._pl_free) == rows and b._pl_retiring == []
    assert sorted(k for k, _c in owner.applied) == [
        "r0", "r2", "r3", "r4", "r6", "r7", "r8"]
    b.stage_placement("again", 13, 2)
    assert b.pl_rows["again"] in rows
    del owner.applied[:]
    _one_tick(core)
    assert owner.applied == [("again", [7, 6, 0, 0, 0, 0, 0, 0])]


def test_a_failed_submit_keeps_the_retiring_rows_for_the_retry():
    """The rows leave `_pl_retiring` only when the step has accepted the
    tick that carries them: a submit that raises leaves them for the
    retry (a full upload), whose dispatch releases them."""
    from kcp_tpu import faults

    core, b, owner = _placement_core(0)
    b.stage_placement("old", 11, 3)
    _one_tick(core)
    row = b.pl_rows["old"]
    b.free_pl_row("old")
    faults.install(faults.FaultInjector("device.step:raise", seed=0))
    try:
        with pytest.raises(faults.InjectedFault):
            core._fleet.submit()
    finally:
        faults.clear()
    assert b._pl_retiring == [row] and b._pl_free == []
    core._fleet.mark_stale()  # what FusedCore._retick does after a failure
    _one_tick(core)
    assert b._pl_free == [row] and b._pl_retiring == []


def test_full_upload_ticks_pct_reads_zero_where_no_tick_was_stale():
    """The benchmark's reader of how often a tick rebuilt the resident
    state: the count of the `full_upload` phase per hundred fleet ticks.
    The histogram is registered with the phases, so a window with no
    full upload reads 0 (not None), and a parent reads like a change."""
    import importlib

    read = importlib.import_module(
        "benchmarks.layer_metrics.full_upload_ticks_pct").read
    parent = {"fused_fleet_ticks_total": 2000.0,
              "fused_full_upload_seconds": 3.1,
              "fused_full_upload_seconds_count": 600.0}
    assert read({"registry": parent}) == pytest.approx(30.0)
    quiet = dict(parent, fused_full_upload_seconds=0.0,
                 fused_full_upload_seconds_count=0.0)
    assert read({"registry": quiet}) == 0.0
    assert read({"registry": dict(parent, fused_fleet_ticks_total=0.0)}) is None
    assert read({"registry": {}}) is None
    # and on the live registry: a retirement's tick is not a stale one
    core, b, _owner = _placement_core(0)
    b.stage_placement("old", 11, 3)
    _one_tick(core)
    snap0 = REGISTRY.snapshot()
    b.free_pl_row("old")
    _one_tick(core)
    snap1 = REGISTRY.snapshot()
    rise = {k: snap1[k] - snap0[k] for k in
            ("fused_fleet_ticks_total", "fused_full_upload_seconds_count")}
    assert rise["fused_fleet_ticks_total"] == 1
    assert read({"registry": rise}) == 0.0
