"""Deployment splitter: split, no-clusters condition, status fan-in."""

import asyncio

import pytest

from kcp_tpu.apis.cluster import new_cluster
from kcp_tpu.client import MultiClusterClient
from kcp_tpu.reconcilers.deployment import DeploymentSplitter
from kcp_tpu.reconcilers.deployment.controller import DEPLOYMENTS
from kcp_tpu.store import LogicalStore
from kcp_tpu.utils.trace import REGISTRY


def deployment(name, replicas, ns="default"):
    return {
        "apiVersion": "apps/v1",
        "kind": "Deployment",
        "metadata": {"name": name, "namespace": ns},
        "spec": {"replicas": replicas, "template": {"spec": {"containers": []}}},
    }


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


@pytest.mark.parametrize("backend", ["tpu", "host"])
def test_split_and_aggregate(backend):
    async def main():
        store = LogicalStore()
        mc = MultiClusterClient(store)
        tenant = mc.cluster_client("tenant-1")
        tenant.create("clusters.cluster.example.dev", new_cluster("us-east1"))
        tenant.create("clusters.cluster.example.dev", new_cluster("us-west1"))

        splitter = DeploymentSplitter(mc, backend=backend)
        await splitter.start()

        tenant.create(DEPLOYMENTS, deployment("web", 10))
        # reference split: 2 clusters, 10 replicas -> first gets base+rest
        await eventually(lambda: tenant.get(DEPLOYMENTS, "web--us-east1", "default"))
        east = tenant.get(DEPLOYMENTS, "web--us-east1", "default")
        west = tenant.get(DEPLOYMENTS, "web--us-west1", "default")
        assert east["spec"]["replicas"] == 5
        assert west["spec"]["replicas"] == 5
        assert east["metadata"]["labels"]["kcp.dev/cluster"] == "us-east1"
        assert east["metadata"]["labels"]["kcp.dev/owned-by"] == "web"
        assert east["metadata"]["ownerReferences"][0]["name"] == "web"

        # leaf status flows up, summed, conditions from first leaf
        for leaf_name, ready in (("web--us-east1", 5), ("web--us-west1", 4)):
            leaf = tenant.get(DEPLOYMENTS, leaf_name, "default")
            leaf["status"] = {
                "replicas": 5, "updatedReplicas": 5, "readyReplicas": ready,
                "availableReplicas": ready, "unavailableReplicas": 5 - ready,
                "conditions": [{"type": "Available", "status": "True"}],
            }
            tenant.update_status(DEPLOYMENTS, leaf)
        await eventually(
            lambda: tenant.get(DEPLOYMENTS, "web", "default").get("status", {}).get("readyReplicas") == 9
        )
        root = tenant.get(DEPLOYMENTS, "web", "default")
        assert root["status"]["replicas"] == 10
        assert root["status"]["unavailableReplicas"] == 1
        assert root["status"]["conditions"] == [{"type": "Available", "status": "True"}]
        await splitter.stop()
    asyncio.run(main())


def test_remainder_goes_to_first_cluster():
    async def main():
        store = LogicalStore()
        mc = MultiClusterClient(store)
        t = mc.cluster_client("t")
        for name in ("a-cl", "b-cl", "c-cl"):
            t.create("clusters.cluster.example.dev", new_cluster(name))
        splitter = DeploymentSplitter(mc)
        await splitter.start()
        t.create(DEPLOYMENTS, deployment("api", 10))
        await eventually(lambda: t.get(DEPLOYMENTS, "api--c-cl", "default"))
        counts = [t.get(DEPLOYMENTS, f"api--{c}", "default")["spec"]["replicas"]
                  for c in ("a-cl", "b-cl", "c-cl")]
        assert counts == [4, 3, 3]  # whole remainder on the first
        await splitter.stop()
    asyncio.run(main())


def test_no_clusters_sets_progressing_false():
    async def main():
        store = LogicalStore()
        mc = MultiClusterClient(store)
        t = mc.cluster_client("empty-tenant")
        splitter = DeploymentSplitter(mc)
        await splitter.start()
        t.create(DEPLOYMENTS, deployment("web", 3))
        await eventually(
            lambda: (t.get(DEPLOYMENTS, "web", "default").get("status", {}).get("conditions")
                     or [{}])[0].get("reason") == "NoRegisteredClusters"
        )
        await splitter.stop()
    asyncio.run(main())


def test_labelled_deployment_without_owner_is_nobodys_leaf():
    """A tenant's own Deployment labelled for a cluster (the plain
    syncer's object) has no root: its events enqueue nothing, so the
    splitter runs no pass for them and keeps no timer."""
    async def main():
        store = LogicalStore()
        mc = MultiClusterClient(store)
        t = mc.cluster_client("t1")
        t.create("clusters.cluster.example.dev", new_cluster("east"))
        splitter = DeploymentSplitter(mc, backend="host")
        await splitter.start()
        own = deployment("own", 3)
        own["metadata"]["labels"] = {"kcp.dev/cluster": "east"}
        t.create(DEPLOYMENTS, own)
        got = t.get(DEPLOYMENTS, "own", "default")
        got["status"] = {"readyReplicas": 3}
        t.update_status(DEPLOYMENTS, got)
        key = ("t1", "default", "own")
        await eventually(lambda: (splitter.informer.cache[key].get("status")
                                  == {"readyReplicas": 3}))
        await asyncio.sleep(0.1)  # a queued item would have run by now
        assert splitter.stats["ticks"] == 0
        assert not splitter._agg_t0 and not splitter._split_t0
        # a root beside it still splits, and its leaf still aggregates
        t.create(DEPLOYMENTS, deployment("web", 4))
        await eventually(lambda: t.get(DEPLOYMENTS, "web--east", "default"))
        leaf = t.get(DEPLOYMENTS, "web--east", "default")
        leaf["status"] = {"readyReplicas": 4, "replicas": 4}
        t.update_status(DEPLOYMENTS, leaf)
        await eventually(lambda: t.get(DEPLOYMENTS, "web", "default")
                         .get("status", {}).get("readyReplicas") == 4)
        await splitter.stop()
    asyncio.run(main())


def test_tenancy_isolation_between_logical_clusters():
    async def main():
        store = LogicalStore()
        mc = MultiClusterClient(store)
        t1 = mc.cluster_client("t1")
        t2 = mc.cluster_client("t2")
        t1.create("clusters.cluster.example.dev", new_cluster("east"))
        # t2 has NO clusters
        splitter = DeploymentSplitter(mc)
        await splitter.start()
        t1.create(DEPLOYMENTS, deployment("a", 4))
        t2.create(DEPLOYMENTS, deployment("a", 4))
        await eventually(lambda: t1.get(DEPLOYMENTS, "a--east", "default"))
        # t2's deployment must not split into t1's cluster
        await eventually(
            lambda: (t2.get(DEPLOYMENTS, "a", "default").get("status", {}).get("conditions")
                     or [{}])[0].get("reason") == "NoRegisteredClusters"
        )
        items, _ = t2.list(DEPLOYMENTS)
        assert [o["metadata"]["name"] for o in items] == ["a"]
        await splitter.stop()
    asyncio.run(main())


def test_rebalance_mode_adapts_to_cluster_changes():
    async def main():
        store = LogicalStore()
        mc = MultiClusterClient(store)
        t = mc.cluster_client("t")
        t.create("clusters.cluster.example.dev", new_cluster("east"))
        splitter = DeploymentSplitter(mc, rebalance=True)
        await splitter.start()
        t.create(DEPLOYMENTS, deployment("web", 6))
        await eventually(
            lambda: t.get(DEPLOYMENTS, "web--east", "default")["spec"]["replicas"] == 6
        )
        # a second cluster arrives: replicas re-split 3/3
        t.create("clusters.cluster.example.dev", new_cluster("west"))
        await eventually(
            lambda: t.get(DEPLOYMENTS, "web--west", "default")["spec"]["replicas"] == 3
            and t.get(DEPLOYMENTS, "web--east", "default")["spec"]["replicas"] == 3
        )
        await splitter.stop()
    asyncio.run(main())


def test_placement_rides_the_fused_serving_core():
    """VERDICT r3 item 5: with backend=tpu the split is computed by the
    FusedCore's flagship step (placement lanes + wire segment), not a
    separate split_replicas_jit call — and a sync engine sharing the
    loop shares the same bucket/program."""
    from kcp_tpu.syncer.core import FusedCore

    async def main():
        store = LogicalStore()
        mc = MultiClusterClient(store)
        tenant = mc.cluster_client("tenant-1")
        for name in ("a", "b", "c"):
            tenant.create("clusters.cluster.example.dev", new_cluster(name))

        splitter = DeploymentSplitter(mc)
        await splitter.start()
        core = FusedCore.for_current_loop()
        assert splitter.core is core
        bucket = splitter._pbucket
        assert bucket is core.bucket(64)
        assert bucket.placement_owner is splitter

        tenant.create(DEPLOYMENTS, deployment("web", 11))
        await eventually(lambda: tenant.get(DEPLOYMENTS, "web--c", "default"))
        # remainder->first parity through the device lane: 11 over 3
        assert tenant.get(DEPLOYMENTS, "web--a", "default")["spec"]["replicas"] == 5
        assert tenant.get(DEPLOYMENTS, "web--b", "default")["spec"]["replicas"] == 3
        assert tenant.get(DEPLOYMENTS, "web--c", "default")["spec"]["replicas"] == 3
        assert splitter.stats["fused_placements"] >= 1
        assert bucket.stats["ticks"] >= 1
        assert bucket.R >= 8  # placement rows materialized in the state
        await splitter.stop()

    asyncio.run(main())


def test_fused_placement_apply_failure_retries_from_cache():
    """A failed fused apply must not be lost: counts are cached and the
    root requeues rate-limited (re-staging identical inputs would not
    re-dirty the device row)."""

    async def main():
        store = LogicalStore()
        mc = MultiClusterClient(store)
        tenant = mc.cluster_client("tenant-1")
        tenant.create("clusters.cluster.example.dev", new_cluster("east"))

        splitter = DeploymentSplitter(mc)
        real_apply = splitter._apply_placement
        fails = {"n": 2}

        def flaky(*args, **kwargs):
            if fails["n"] > 0:
                fails["n"] -= 1
                raise RuntimeError("injected apply failure")
            return real_apply(*args, **kwargs)

        splitter._apply_placement = flaky
        await splitter.start()
        tenant.create(DEPLOYMENTS, deployment("web", 4))
        await eventually(
            lambda: tenant.get(DEPLOYMENTS, "web--east", "default"), timeout=10)
        assert tenant.get(DEPLOYMENTS, "web--east", "default")["spec"]["replicas"] == 4
        assert fails["n"] == 0
        await splitter.stop()

    asyncio.run(main())


def test_flap_inside_hysteresis_is_zero_churn_and_replans_touch_one_workspace():
    """A Ready flap inside the evacuation window moves NOTHING (no
    resolves, no churn), and a sustained outage replans only the flapped
    cluster's workspace — the other tenant's leafs are never rewritten."""
    from kcp_tpu.apis.cluster import (CLUSTERS, REASON_SYNCER_NOT_READY,
                                      set_not_ready, set_ready)
    from kcp_tpu.utils.trace import REGISTRY

    async def main():
        store = LogicalStore()
        mc = MultiClusterClient(store)
        t1, t2 = mc.cluster_client("t1"), mc.cluster_client("t2")
        for t, names in ((t1, ("east", "west")), (t2, ("solo",))):
            for name in names:
                obj = new_cluster(name)
                set_ready(obj)
                t.create(CLUSTERS, obj)
        splitter = DeploymentSplitter(mc, evac_hysteresis=0.3)
        await splitter.start()
        t1.create(DEPLOYMENTS, deployment("web", 8))
        t1.create(DEPLOYMENTS, deployment("api", 4))
        t2.create(DEPLOYMENTS, deployment("db", 2))
        await eventually(lambda: t1.get(DEPLOYMENTS, "web--east", "default"))
        await eventually(lambda: t2.get(DEPLOYMENTS, "db--solo", "default"))

        def flip(ready):
            obj = t1.get(CLUSTERS, "east")
            if ready:
                set_ready(obj)
            else:
                set_not_ready(obj, REASON_SYNCER_NOT_READY, "flap")
            t1.update_status(CLUSTERS, obj)

        resolves0 = REGISTRY.counter("placement_resolves_total").value
        churn0 = REGISTRY.counter("placement_churn_total").value
        other_rv = t2.get(DEPLOYMENTS, "db--solo",
                          "default")["metadata"]["resourceVersion"]

        # flap: NotReady then Ready again inside the 0.3s window
        flip(False)
        await asyncio.sleep(0.1)
        flip(True)
        await asyncio.sleep(0.5)  # past the window: the check found Ready
        assert REGISTRY.counter("placement_resolves_total").value == resolves0
        assert REGISTRY.counter("placement_churn_total").value == churn0

        # sustained: ONLY t1's two roots re-resolve; t2's leaf untouched
        flip(False)
        await eventually(lambda: t1.get(
            DEPLOYMENTS, "web--west", "default")["spec"]["replicas"] == 8)
        await eventually(lambda: t1.get(
            DEPLOYMENTS, "api--west", "default")["spec"]["replicas"] == 4)
        assert (REGISTRY.counter("placement_resolves_total").value
                - resolves0) == 2
        assert t2.get(DEPLOYMENTS, "db--solo",
                      "default")["metadata"]["resourceVersion"] == other_rv
        await splitter.stop()

    asyncio.run(main())


# ---------------------------------------------- the Cluster informer's index


def _scan_clusters_for(splitter, lc):
    """The scan `_clusters_for` made before the index: every registered
    cluster read, the workspace's kept, evacuated ones dropped, by name."""
    return sorted(
        (c for c in splitter.cluster_informer.list()
         if c["metadata"].get("clusterName", "") == lc
         and not splitter.inventory.is_evacuated(lc, c["metadata"]["name"])),
        key=lambda c: c["metadata"]["name"],
    )


def _cluster_counter(name):
    return REGISTRY.counter(name).value


@pytest.mark.parametrize("backend", ["tpu", "host"])
def test_indexed_clusters_for_equals_the_scan(backend):
    """Adds, deletes and status updates over workspaces that reuse
    cluster NAMES, one cluster evacuated and readmitted, one workspace
    with none: after every step the index hands `_clusters_for` the same
    clusters in the same order as a scan of the whole informer."""
    from kcp_tpu.apis.cluster import (CLUSTERS, REASON_SYNCER_NOT_READY,
                                      set_not_ready, set_ready)

    async def main():
        store = LogicalStore()
        mc = MultiClusterClient(store)
        tenants = {lc: mc.cluster_client(lc) for lc in ("w1", "w2", "w3", "bare")}
        splitter = DeploymentSplitter(mc, backend=backend, evac_hysteresis=0.2)
        await splitter.start()

        def names(lc):
            return [c["metadata"]["name"] for c in splitter._clusters_for(lc)]

        async def same(want: dict):
            # the informer has caught up when the scan reads what was written
            await eventually(lambda: all(
                [c["metadata"]["name"] for c in _scan_clusters_for(splitter, lc)]
                == want.get(lc, []) for lc in tenants))
            for lc in tenants:
                assert splitter._clusters_for(lc) == _scan_clusters_for(splitter, lc)
                assert names(lc) == want.get(lc, [])

        def add(lc, name):
            obj = new_cluster(name)
            set_ready(obj)
            tenants[lc].create(CLUSTERS, obj)

        def flip(lc, name, ready):
            obj = tenants[lc].get(CLUSTERS, name)
            if ready:
                set_ready(obj)
            else:
                set_not_ready(obj, REASON_SYNCER_NOT_READY, "down")
            tenants[lc].update_status(CLUSTERS, obj)

        await same({})
        # the same names in three workspaces, created out of name order
        for lc in ("w1", "w2", "w3"):
            for name in ("west", "east", "north"):
                add(lc, name)
        add("w2", "south")
        await same({"w1": ["east", "north", "west"],
                    "w2": ["east", "north", "south", "west"],
                    "w3": ["east", "north", "west"]})
        # a delete in one workspace leaves its namesakes where they are
        tenants["w1"].delete(CLUSTERS, "north")
        await same({"w1": ["east", "west"],
                    "w2": ["east", "north", "south", "west"],
                    "w3": ["east", "north", "west"]})
        # status updates re-index the object under the same bucket; a
        # NotReady held past the window evacuates w2/east and only it
        flip("w3", "east", False)
        flip("w3", "east", True)
        flip("w2", "east", False)
        await eventually(lambda: splitter.inventory.is_evacuated("w2", "east"))
        await same({"w1": ["east", "west"],
                    "w2": ["north", "south", "west"],
                    "w3": ["east", "north", "west"]})
        # the evacuated cluster is still in the index: the filter, not
        # the bucket, keeps it out — and Ready lets it in again
        assert len(splitter.cluster_informer.index("by_workspace", "w2")) == 4
        flip("w2", "east", True)
        await eventually(
            lambda: not splitter.inventory.is_evacuated("w2", "east"))
        tenants["w1"].delete(CLUSTERS, "east")
        tenants["w1"].delete(CLUSTERS, "west")
        add("w1", "north")
        await same({"w1": ["north"],
                    "w2": ["east", "north", "south", "west"],
                    "w3": ["east", "north", "west"]})
        # a workspace with no cluster: an empty bucket, the reference's
        # NoRegisteredClusters as before
        tenants["bare"].create(DEPLOYMENTS, deployment("web", 3))
        await eventually(
            lambda: (tenants["bare"].get(DEPLOYMENTS, "web", "default")
                     .get("status", {}).get("conditions")
                     or [{}])[0].get("reason") == "NoRegisteredClusters")
        await splitter.stop()
    asyncio.run(main())


def test_a_lookup_reads_its_workspaces_clusters_not_the_fleets():
    """125 workspaces x 8 clusters: `_clusters_for` reads 8 candidates a
    call (`splitter_cluster_candidates_total`), whatever the fleet's
    size, and one lookup is one `splitter_cluster_lookups_total`."""
    from kcp_tpu.apis.cluster import CLUSTERS

    async def main():
        store = LogicalStore()
        mc = MultiClusterClient(store)
        for w in range(125):
            t = mc.cluster_client(f"ws-{w}")
            for c in range(8):
                t.create(CLUSTERS, new_cluster(f"loc-{c}"))
        splitter = DeploymentSplitter(mc, backend="host")
        await splitter.start()
        assert len(splitter.cluster_informer.list()) == 1000
        for lc in ("ws-0", "ws-77", "ws-124"):
            lookups0 = _cluster_counter("splitter_cluster_lookups_total")
            read0 = _cluster_counter("splitter_cluster_candidates_total")
            got = splitter._clusters_for(lc)
            assert [c["metadata"]["name"] for c in got] == [
                f"loc-{c}" for c in range(8)]
            assert all(c["metadata"]["clusterName"] == lc for c in got)
            assert _cluster_counter("splitter_cluster_lookups_total") - lookups0 == 1
            assert _cluster_counter("splitter_cluster_candidates_total") - read0 == 8
        # an unknown workspace reads nothing at all
        read0 = _cluster_counter("splitter_cluster_candidates_total")
        assert splitter._clusters_for("nobody") == []
        assert _cluster_counter("splitter_cluster_candidates_total") == read0
        await splitter.stop()
    asyncio.run(main())


async def _fused_splitter_with_roots(replicas):
    """A fused-path splitter over two clusters with one placed root a
    replica count, settled: every leaf written, nothing queued, the
    bucket's state resident."""
    from kcp_tpu.apis.cluster import CLUSTERS

    store = LogicalStore()
    mc = MultiClusterClient(store)
    t = mc.cluster_client("t")
    for name in ("a", "b"):
        t.create(CLUSTERS, new_cluster(name))
    splitter = DeploymentSplitter(mc)
    await splitter.start()
    for i, n in enumerate(replicas):
        t.create(DEPLOYMENTS, deployment(f"r{i}", n))
    for i in range(len(replicas)):
        await eventually(lambda: t.get(DEPLOYMENTS, f"r{i}--b", "default"))
    await _settled(splitter)
    return t, splitter


async def _settled(splitter):
    b = splitter._pbucket
    await eventually(lambda: splitter._apply_q.empty() and not b.dirty
                     and not b._pl_retiring and not splitter.core._inflight)
    await asyncio.sleep(0.1)


def test_a_retired_root_re_emits_no_resident_roots_row():
    """A root deleted on the fused path retires ITS placement row through
    the placement-leaves swap: the resident state is not rebuilt, the
    device hands back no resident root's row (the retired row's own
    all-zero emission finds no key), nothing is looked up and no leaf is
    rewritten. (Until PR 49 it was the opposite: a rebuild with `current`
    zeroed, and EVERY remaining row handed back to be answered with
    nothing to write.)"""

    async def main():
        t, splitter = await _fused_splitter_with_roots((4, 5, 6, 7))
        b = splitter._pbucket
        rows0 = _cluster_counter("splitter_placement_rows_total")
        applied0 = _cluster_counter("splitter_fused_placements_total")
        assert applied0 >= 4 and rows0 >= applied0
        lookups0 = _cluster_counter("splitter_cluster_lookups_total")
        retired0 = _cluster_counter("fused_placement_rows_retired_total")
        invalid0 = _cluster_counter("splitter_placement_invalidations_total")
        uploads0, ticks0 = b.stats["full_uploads"], b.stats["ticks"]
        row = b.pl_rows[("t", "default", "r0")]
        rvs = {f"r{i}--{c}": t.get(DEPLOYMENTS, f"r{i}--{c}",
                                   "default")["metadata"]["resourceVersion"]
               for i in (1, 2, 3) for c in "ab"}

        t.delete(DEPLOYMENTS, "r0", "default")
        await eventually(lambda: ("t", "default", "r0") not in b.pl_rows)
        assert row not in b._pl_free  # retiring: not free before its tick
        splitter.core.kick()
        await eventually(lambda: row in b._pl_free)
        await _settled(splitter)
        assert b.stats["ticks"] > ticks0
        assert b.stats["full_uploads"] == uploads0
        assert _cluster_counter("fused_placement_rows_retired_total") - retired0 == 1
        assert _cluster_counter("splitter_placement_rows_total") == rows0
        assert _cluster_counter("splitter_fused_placements_total") == applied0
        # not even one: the retired root's own pass of the splitter's
        # tick is answered at `root is None`, ahead of the lookup
        assert _cluster_counter("splitter_cluster_lookups_total") == lookups0
        assert _cluster_counter(
            "splitter_placement_invalidations_total") == invalid0
        for leaf, rv in rvs.items():
            assert t.get(DEPLOYMENTS, leaf,
                         "default")["metadata"]["resourceVersion"] == rv
        await splitter.stop()

    asyncio.run(main())


def test_a_root_with_the_retired_roots_split_is_placed_on_its_row():
    """The hazard the rebuild was there for, end to end: the device's
    `current[row]` held the retired root's split, so a root with an EQUAL
    split on the re-used row would never come back dirty. The tick that
    carried the row's zeroed inputs zeroed it: the new root is placed, by
    the device, with no full upload and no rejected counts."""

    async def main():
        t, splitter = await _fused_splitter_with_roots((4, 7))
        b = splitter._pbucket
        row = b.pl_rows[("t", "default", "r1")]
        t.delete(DEPLOYMENTS, "r1", "default")
        for c in "ab":
            t.delete(DEPLOYMENTS, f"r1--{c}", "default")
        await eventually(lambda: ("t", "default", "r1") not in b.pl_rows)
        splitter.core.kick()
        await eventually(lambda: b._pl_free == [row])
        await _settled(splitter)
        uploads0 = b.stats["full_uploads"]
        applied0 = _cluster_counter("splitter_fused_placements_total")
        rows0 = _cluster_counter("splitter_placement_rows_total")
        invalid0 = _cluster_counter("splitter_placement_invalidations_total")

        t.create(DEPLOYMENTS, deployment("twin", 7))
        await eventually(lambda: t.get(DEPLOYMENTS, "twin--b", "default"))
        assert b.pl_rows[("t", "default", "twin")] == row
        assert [t.get(DEPLOYMENTS, f"twin--{c}", "default")["spec"]["replicas"]
                for c in "ab"] == [4, 3]
        await _settled(splitter)
        assert b.stats["full_uploads"] == uploads0
        assert _cluster_counter("splitter_fused_placements_total") - applied0 == 1
        assert _cluster_counter("splitter_placement_rows_total") - rows0 == 1
        assert _cluster_counter(
            "splitter_placement_invalidations_total") == invalid0
        await splitter.stop()

    asyncio.run(main())


def test_rollouts_that_retire_and_create_together_reject_no_counts():
    """What the rollout cell does: roots retired and created in the same
    tick windows, equal replica counts among them, wires in flight across
    the retirements. Every root is placed by the device, none of the
    all-zero emissions reaches the applier as a live root's counts
    (`splitter_placement_invalidations_total` stays), and the resident
    state is uploaded again only where the lane grows."""

    async def main():
        t, splitter = await _fused_splitter_with_roots((6, 6, 6, 6))
        b = splitter._pbucket
        invalid0 = _cluster_counter("splitter_placement_invalidations_total")
        applied0 = _cluster_counter("splitter_fused_placements_total")
        uploads0, lane0 = b.stats["full_uploads"], b.R
        for gen in range(6):
            old, new = f"r{gen % 4}" if gen < 4 else f"n{gen - 4}", f"n{gen}"
            t.delete(DEPLOYMENTS, old, "default")
            t.create(DEPLOYMENTS, deployment(new, 6))
            await eventually(lambda: t.get(DEPLOYMENTS, f"{new}--b", "default"))
            assert [t.get(DEPLOYMENTS, f"{new}--{c}", "default")["spec"]["replicas"]
                    for c in "ab"] == [3, 3]
        await _settled(splitter)
        assert _cluster_counter(
            "splitter_placement_invalidations_total") == invalid0
        assert _cluster_counter("splitter_fused_placements_total") - applied0 == 6
        assert b.R == lane0 and b.stats["full_uploads"] == uploads0
        # four resident roots: no more rows than those and the retiring
        assert b._pl_next <= 6 and len(b.pl_rows) == 4
        await splitter.stop()

    asyncio.run(main())
