"""Everything a deployment is made of is found by name: the manifest's
cells resolve, a controller and a topology come from the modules the
configuration names, the generator sees the configuration and the readers
see the generator, and the open loop can burst without moving a schedule
that does not."""

import hashlib
import importlib
import json
import os
import types
from collections import Counter

import pytest

from benchmarks import agents, deploy, run, shapes
from benchmarks.generators import open_loop
from benchmarks.layer_metrics import burst_drain_p50_ms, rows_per_tick

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
MANIFEST = run.load_json(run.REPO, "BENCHMARK.json")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
ACCEPTED = {"syncer-1k": ("StatusEcho", "configmap"),
            "splitter-125x8": ("DeploymentReady", "deployment"),
            "k8s-load-1k": ("DeploymentReady", "k8s_deployment")}
MIX = {"update": 0.9, "create": 0.05, "delete": 0.05}
BURST = {"every_s": 2.0, "size": 320, "spread_ms": 100}
# sha256 of repr() of the schedule of a 51 s run (5 + 51 + 2 s) over 1,000
# tenants, taken on the parent of the PR that added bursts (PR 34's commit)
PARENT_DIGESTS = {
    ("steady", 1): "e60bb5a39e3733839bdd6a2e6a89f59f0335089234784df679c5850d4da678fa",
    ("steady", 2147487102): "442262b3a67ea84b11f29bf62a6b651650e7bce399fc42b3fbcaccb7c6b5be4b",
    ("load-churn", 1): "bbf42ad67eeeaea4c63ad60c3e5ed01fca499d94b59f2274a2a253f710cf7cdc",
    ("load-churn", 2147487102): "28462b37922a6effdd2c314a5a64566f03f08fcf6d34c2014f3c6d2a6eb5d23d",
}


def traffic(name: str) -> dict:
    return run.load_json(BENCH, "traffic", name + ".json")


def unstarted(config: dict):
    """A deployment of the configuration's rehearsal size, never started."""
    cfg = dict(config, **config["rehearsal"])
    dep = deploy.load(cfg)(cfg, 3, "/nonexistent")
    dep.srv = types.SimpleNamespace(address="http://127.0.0.1:1")
    return dep


# ------------------------------------------------------------ the schedule

@pytest.mark.parametrize("name,seed", sorted(PARENT_DIGESTS))
def test_a_schedule_without_bursts_is_the_parents_value_for_value(name, seed):
    tr = traffic(name)
    assert "burst" not in tr
    s = open_loop.schedule(seed, tr["rate_per_s"], tr["mix"],
                           tr["warmup_s"] + 51 + tr["cooldown_s"], 1000)
    assert all(b is None for *_four, b in s)
    digest = hashlib.sha256(repr([x[:4] for x in s]).encode()).hexdigest()
    assert digest == PARENT_DIGESTS[(name, seed)]


def test_burst_count_sizes_and_spacing():
    s = open_loop.schedule(2**31 + 5, 240, MIX, 58.0, 1000, BURST)
    by_burst: dict[int, list[float]] = {}
    for due, _k, _t, _p, b in s:
        if b is not None:
            by_burst.setdefault(b, []).append(due)
    assert sorted(by_burst) == list(range(29))  # at 0, 2, ..., 56 s
    for b, dues in by_burst.items():
        assert len(dues) == 320
        assert 2.0 * b <= min(dues) and max(dues) <= 2.0 * b + 0.100
    dues = [d for d, *_ in s]
    assert dues == sorted(dues) and dues[-1] <= 58.0


def test_burst_keeps_the_mean_rate_and_the_exact_mix():
    plain = open_loop.schedule(9, 240, MIX, 58.0, 1000)
    burst = open_loop.schedule(9, 240, MIX, 58.0, 1000, BURST)
    assert len(burst) == len(plain) == 240 * 58
    assert (Counter(k for _d, k, *_ in burst)
            == Counter(k for _d, k, *_ in plain))
    background = [x for x in burst if x[4] is None]
    assert len(background) == 80 * 58
    assert {t for _d, _k, t, _p, _b in burst} <= set(range(1000))


def test_burst_schedule_is_pure_in_the_seed_and_seeds_offer_equal_work():
    a = open_loop.schedule(2**31 + 17, 240, MIX, 58.0, 1000, BURST)
    b = open_loop.schedule(2**31 + 17, 240, MIX, 58.0, 1000, dict(BURST))
    c = open_loop.schedule(2**31 + 18, 240, MIX, 58.0, 1000, BURST)
    assert a == b and a != c
    assert Counter(x[4] for x in a) == Counter(x[4] for x in c)
    assert Counter(x[1] for x in a) == Counter(x[1] for x in c)


def test_a_burst_that_does_not_fit_or_outnumbers_the_rate_is_refused():
    assert open_loop.burst_starts(BURST, 4.1) == [0.0, 2.0]
    assert open_loop.burst_starts(BURST, 2.05) == [0.0]  # the second would not end inside
    assert open_loop.burst_starts(None, 58.0) == []
    with pytest.raises(ValueError):
        open_loop.schedule(1, 100, MIX, 58.0, 10, BURST)
    with pytest.raises(ValueError):
        open_loop.burst_starts(dict(BURST, every_s=0), 58.0)


# ------------------------------------------------------------ the manifest

@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_by_name(cell):
    manifest, entry, config, tr = run.resolve(cell)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    kind = importlib.import_module(f"benchmarks.generators.{tr['kind']}")
    assert callable(kind.prepare) and callable(kind.run)
    shape = shapes.load(config["shape"])
    assert issubclass(deploy.load(config), deploy.Deployment)
    assert hasattr(shape, "AGENT") and hasattr(shape, "RESOURCE")
    for section, package in (("end_to_end", "end_to_end"),
                             ("per_layer", "layer_metrics")):
        names = run.metric_names(manifest, section, cell)
        assert names, f"{cell} reports nothing of {section}"
        for name in names:
            reader = importlib.import_module(f"benchmarks.{package}.{name}")
            assert callable(reader.read)
    assert "setup_s" in run.metric_names(manifest, "end_to_end", cell)
    _m, _e, small, small_tr = run.resolve(cell, rehearse=True)
    assert small["logical_clusters"] < config["logical_clusters"]
    assert small_tr["kind"] == tr["kind"]


def test_every_name_in_the_manifest_names_something():
    cells, configs = set(CELLS), {c["name"] for c in MANIFEST["configs"]}
    assert len(cells) == len(CELLS) == 4
    end_to_end = {m["name"] for m in MANIFEST["end_to_end"]}
    for w in MANIFEST["workloads"]:
        assert w["config"] in configs
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert set(m.get("workloads", ())) <= cells, m["name"]
        assert len(m["name"]) <= 64
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in end_to_end
        moved = next(e for e in MANIFEST["end_to_end"]
                     if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for name in [c["name"] for c in MANIFEST["configs"]] + CELLS:
        assert len(name) <= 64


def test_the_new_traffic_files_are_the_ones_the_issue_names():
    """``flood`` is data without a cell yet (PERF.md, section 7: the core
    keeps a row per name ever seen, so its B doubles inside the window);
    benchmarks/tests/data/toy_manifest.json rehearses it."""
    burst, flood = traffic("burst"), traffic("flood")
    assert (burst["kind"], burst["rate_per_s"]) == \
        ("open_loop", traffic("steady")["rate_per_s"])
    assert burst["mix"] == traffic("steady")["mix"]
    assert burst["burst"]["every_s"] == 2.0
    assert 160 <= burst["burst"]["size"] <= 480
    # a blocking sender carries one write at a time: fewer senders than a
    # burst holds would keep part of every burst inside the generator
    assert burst["senders"] >= burst["burst"]["size"]
    # the burst's shape has no public source: the file says so
    assert {"burst.size", "burst.spread_ms", "burst.every_s"} <= set(burst["assumed"])
    assert (flood["kind"], flood["clients"], flood["keep_last"]) == \
        ("closed_loop", 64, True)
    for tr in (burst, flood):
        assert (tr["warmup_s"], tr["cooldown_s"], tr["deadline_s"]) == (5, 2, 10)


# ------------------------------------------- controller and topology by name

@pytest.mark.parametrize("name", sorted(ACCEPTED))
def test_accepted_configurations_resolve_as_they_always_did(name):
    entry = next(c for c in MANIFEST["configs"] if c["name"] == name)
    config = run.load_json(run.REPO, entry["file"])
    agent, shape = ACCEPTED[name]
    assert config["shape"] == shape and "deployment" not in config
    assert deploy.load(config) is deploy.Deployment
    dep = unstarted(config)
    assert dep.agent_class() is getattr(agents, agent)
    assert not hasattr(dep.shape, "AGENT_MODULE")


def test_a_shape_names_the_module_of_its_controller():
    from benchmarks.tests import toy_agents

    config = run.load_json(HERE, "data", "toy-controller.json")
    dep = unstarted(config)
    assert dep.shape.__name__ == "benchmarks.tests.toy_shape"
    assert dep.agent_class() is toy_agents.ToyEcho
    assert not hasattr(agents, "ToyEcho")


# what run.py, sweep.py, compare.py and controls.py call on a topology:
# benchmarks/README.md's list, name for name
TOPOLOGY_OFFERS = {"bring_up", "loadgen", "population", "tenants", "locations",
                   "shape", "srv", "downstream", "fleet", "agent_errors",
                   "counters0", "stop"}


def test_a_configuration_names_the_module_of_its_topology():
    from benchmarks.tests import toy_topology

    config = run.load_json(HERE, "data", "toy-topology.json")
    assert deploy.load(config) is toy_topology.Deployment


def test_the_toy_topology_offers_the_readmes_list_and_nothing_else():
    """It shares no class with deploy.Deployment, so its rehearsal (a whole
    run through run.py and compare.py) holds the list to what they call."""
    from benchmarks.tests import toy_topology

    assert not issubclass(toy_topology.Deployment, deploy.Deployment)
    config = run.load_json(HERE, "data", "toy-topology.json")
    dep = toy_topology.Deployment(config, 3, "/nonexistent")
    public = {n for n in set(dir(dep)) if not n.startswith("_")}
    assert public == TOPOLOGY_OFFERS
    with open(os.path.join(BENCH, "README.md")) as f:
        readme = f.read()
    offers = readme[readme.index("**What a topology offers**"):
                    readme.index("**What a generator kind is told**")]
    for name in TOPOLOGY_OFFERS:
        assert f"`{name}" in offers, name
    dep.population = {("t", "n"): {}}
    assert dep.population == {("t", "n"): {}}


def test_run_and_sweep_name_no_deployment_class():
    for name in ("run.py", "sweep.py"):
        with open(os.path.join(BENCH, name)) as f:
            assert "deploy.Deployment" not in f.read()


# ---------------------------- generator <- configuration, readers <- generator

def test_the_generator_is_told_the_whole_configuration():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "syncer-1k")
    config = run.load_json(run.REPO, entry["file"])
    dep = unstarted(config)
    tr = traffic("burst")
    spec = dep.loadgen_spec(tr, 2**31 + 3, 51)
    assert spec["config"] == dep.cfg
    assert spec["config"]["guarantees"] == config["guarantees"]
    assert (spec["server"], spec["shape"], spec["seed"], spec["seconds"],
            spec["tenants"], spec["per_tenant"], spec["locations"],
            spec["traffic"]) == ("http://127.0.0.1:1", "configmap", 2**31 + 3,
                                 51.0, 6, 5, ["loc0"], tr)
    json.dumps(spec)  # it crosses to the generator's process as JSON


def test_readers_are_handed_what_the_generator_returned():
    out = {"records": [{"kind": "update"}], "skipped": 2, "offered_per_s": 240,
           "t_start": 12.5, "watch_events": 7}
    assert run.generator_extras(out) == {"skipped": 2, "offered_per_s": 240,
                                         "t_start": 12.5, "watch_events": 7}


# ------------------------------------------------------------ the two readers

def test_rows_per_tick_divides_the_two_rises_or_reads_nothing():
    ctx = {"registry": {"fused_encoded_rows_total": 900.0,
                        "fused_fleet_ticks_total": 150.0}}
    assert rows_per_tick.read(ctx) == 6.0
    assert rows_per_tick.read({"registry": {"fused_fleet_ticks_total": 3.0}}) is None
    assert rows_per_tick.read({"registry": {"fused_encoded_rows_total": 5.0,
                                            "fused_fleet_ticks_total": 0.0}}) is None


def op(kind, due, seen, burst=None):
    rec = {"kind": kind, "due": due, "seen": seen}
    if burst is not None:
        rec["burst"] = burst
    return rec


def test_burst_drain_is_last_seen_minus_first_due_median_over_bursts():
    ops = [op("update", 10.002, 10.300, 5), op("create", 10.000, 10.450, 5),
           op("delete", 10.001, None, 5),  # a delete is not waited for
           op("update", 12.000, 12.800, 6), op("update", 12.009, 12.500, 6),
           op("update", 14.000, 14.100, 7),
           op("update", 11.000, 11.900)]   # background: in no burst
    got = burst_drain_p50_ms.read({"ops": ops, "beyond_ms": 10_000.0})
    assert got == pytest.approx(450.0)     # of 100, 450, 800


def test_burst_drain_counts_an_undrained_burst_as_beyond_and_no_burst_as_none():
    ops = [op("update", 10.0, None, 1), op("update", 10.0, 10.2, 1),
           op("update", 12.0, 12.1, 2)]
    assert burst_drain_p50_ms.read({"ops": ops[:2], "beyond_ms": 10_000.0}) == 10_000.0
    assert burst_drain_p50_ms.read({"ops": ops, "beyond_ms": 10_000.0}) == pytest.approx(100.0)
    plain = [op("update", 1.0, 1.1), op("delete", 2.0, None)]
    assert burst_drain_p50_ms.read({"ops": plain, "beyond_ms": 10_000.0}) is None
    assert burst_drain_p50_ms.read({"ops": [], "beyond_ms": 10_000.0}) is None


def test_loadgen_share_is_mean_lateness_over_mean_convergence():
    from benchmarks.layer_metrics import loadgen_share_pct

    def sent(kind, due, at, seen):
        return {"kind": kind, "due": due, "sent": at, "seen": seen}

    ops = [sent("update", 10.0, 10.1, 11.0), sent("create", 12.0, 12.3, 13.0),
           sent("delete", 13.0, 13.9, None),    # not timed
           sent("update", 14.0, 14.2, None),    # never converged: not in the means
           dict(sent("update", 15.0, 15.9, 16.0), aux=True)]
    assert loadgen_share_pct.read({"ops": ops}) == pytest.approx(20.0)  # 0.2 s of 1.0 s
    assert loadgen_share_pct.read({"ops": ops[2:4]}) is None
    assert loadgen_share_pct.read({"ops": []}) is None
