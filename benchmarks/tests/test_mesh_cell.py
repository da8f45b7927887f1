"""The ``mesh4-1k.steady`` cell: its configuration and manifest entries,
its five readers over a recorded rise and a synthetic trace with known
collective events, and whole runs tiny on FOUR virtual CPU devices with
its ``rehearsal`` block (both kinds of run come out correct, both
controls ``correct: false``)."""

import importlib
import json
import os
from types import SimpleNamespace as NS

import pytest

from benchmarks import collective_ops, mesh_deploy, opcount
from benchmarks import run as runmod
from test_rehearsal import result
from test_rehearsal import run as run_cell

CELL = "mesh4-1k.steady"
CONTROL = "syncer-1k.steady"
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NEW = ["mesh_step_roofline", "mesh_collective_pct", "tick_put_ms",
       "tick_step_dispatch_ms", "put_bytes_per_tick"]
FOUR = "--xla_force_host_platform_device_count=4"


def reader(name):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}")


@pytest.fixture
def run(monkeypatch):
    """test_rehearsal's run of the cell, with four virtual CPU devices in
    the child (which takes this process's environment)."""
    monkeypatch.setenv("XLA_FLAGS", FOUR)
    return lambda *extra, **kw: run_cell(*extra, cell=CELL, **kw)


def test_manifest_and_configuration():
    manifest, cell, config, traffic = runmod.resolve(CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        4, "mesh4-1k", "mesh-steady")
    assert [w["name"] for w in manifest["workloads"]][-1] == CELL
    assert len(manifest["workloads"]) == 8 and len(manifest["configs"]) == 7
    # one four-chip cell of eight: inside "at most half"
    assert [w["name"] for w in manifest["workloads"] if w["chips"] == 4] == [CELL]
    entry = manifest["configs"][-1]
    assert entry["name"] == "mesh4-1k" and entry["file"].endswith(
        "configs/mesh4-1k.json")
    assert entry["reduced"] == config["reduced"] == ["resident_per_cluster"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    for part in ("cluster-mapper.md:21-24", "BASELINE.json configs[1]",
                 "north_star", "kcp start --mesh", "docs/operations.md:48-50"):
        assert part in entry["source"], part
    assert config["deployment"] == "benchmarks.mesh_deploy"
    assert config["mesh"] == "4x1" and mesh_deploy.mesh_devices("4x1") == 4
    # the control differs by the mesh and nothing else
    _m, _c, control, steady = runmod.resolve(CONTROL)
    same = ("shape", "logical_clusters", "locations_per_cluster",
            "resources_to_sync", "resident_per_cluster", "warm_bursts",
            "rehearsal")
    assert {k: config[k] for k in same} == {k: control[k] for k in same}
    assert set(config) - set(control) == {"deployment", "mesh"}
    assert set(config["guarantees"]) - set(control["guarantees"]) == {
        "sharded_state", "single_device_equivalence"}
    for k, v in control["guarantees"].items():
        assert config["guarantees"][k] == v
    assert set(config["assumed"]) - set(control["assumed"]) == {"layout", "mesh"}
    # the traffic is steady's, value for value, but for the rate's cap
    keys = ("kind", "mix", "tenants", "warmup_s", "cooldown_s", "deadline_s",
            "senders", "rehearsal")
    assert {k: traffic[k] for k in keys} == {k: steady[k] for k in keys}
    assert traffic["rate_per_s"] <= steady["rate_per_s"] == 240
    assert traffic["rate_per_s"] % 10 == 0 and "sweep" in traffic["rate_source"]
    # the five new readers are the manifest's last five
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert [m["name"] for m in manifest["per_layer"]][-5:] == NEW
    for name in NEW[:2]:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["layer"] == "fused step"
        assert by_name[name]["source"] == "device_trace"
    for name in NEW[2:]:
        assert by_name[name]["workloads"] == [CONTROL, CELL]
        assert by_name[name]["layer"] == "syncer core, host side"
        assert by_name[name]["source"] == "program_counter"
    assert all(by_name[n]["moves"] == "converge_p50_ms" for n in NEW)
    # every accepted reader of the control reads here too, but the one
    # chip's roofline
    here = set(runmod.metric_names(manifest, "per_layer", CELL))
    there = set(runmod.metric_names(manifest, "per_layer", CONTROL))
    assert there - here == {"fused_step_roofline"}
    assert here - there == {"mesh_step_roofline", "mesh_collective_pct"}
    assert set(runmod.metric_names(manifest, "end_to_end", CELL)) == {
        "converge_p50_ms", "setup_s"}
    for m in manifest["per_layer"]:
        for k in m:
            assert k in {"name", "unit", "better", "source", "layer", "moves",
                         "workloads"}, (m["name"], k)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        assert len(f.read()) < 64 * 1024


def test_mesh_step_roofline_is_a_quarter_of_one_chips_formula():
    tr = {"steps": 40, "step_seconds_total": 40 * 0.4e-3,
          "planes": [f"/device:TPU:{i}" for i in range(4)]}
    fleet = {"B": 16384, "S": 64, "shards": 4, "shard_rows": 4096}
    ctx = {"trace": tr, "fleet": fleet, "device_kind": "TPU v5 lite"}
    whole = reader("fused_step_roofline").read(ctx)
    assert reader("mesh_step_roofline").read(ctx) == pytest.approx(whole / 4)
    assert whole / 4 == pytest.approx(
        opcount.step_roofline_pct(4096, 64, 0.4e-3, "TPU v5 lite"))
    # a topology that does not say its shards, or an untraced run: nothing
    bare = dict(ctx, fleet={"B": 16384, "S": 64})
    assert reader("mesh_step_roofline").read(bare) is None
    assert reader("mesh_step_roofline").read(dict(ctx, trace=None)) is None


def plane(name, modules, ops):
    ev = lambda n, a, d: NS(name=n, start_ns=a, duration_ns=d)  # noqa: E731
    return NS(name=name, lines=[
        NS(name="XLA Modules", events=[ev(*m) for m in modules]),
        NS(name="XLA Ops", events=[ev(*o) for o in ops]),
        NS(name="Async XLA Ops", events=[ev("%all-reduce-start.9", 0, 10**6)])])


def test_collective_reducer_on_known_events():
    step = "jit_reconcile_step_fleet(123)"
    dev = [plane(f"/device:TPU:{i}",
                 [(step, 1000, 1000), (step, 5000, 1000),
                  ("jit_other(9)", 8000, 1000)],
                 [("%fusion.1 = u32[4096,64]", 1000, 500),
                  ("%all-reduce.3 = u32[8]", 1500, 100),
                  ("%collective-permute-start.1 = (u32[64])", 1600, 20),
                  ("%collective-permute-done.1 = u32[64]", 1700, 30),
                  ("%all-reduce.3 = u32[8]", 5500, 150),
                  ("%all-gather.2 = u32[16]", 8100, 400),   # another program
                  ("%all-reduce.7 = u32[8]", 3000, 999)])   # between steps
           for i in range(4)]
    got = collective_ops.reduce_planes(
        dev + [plane("/host:CPU", [(step, 0, 10)], [("%all-reduce.1", 0, 10)])])
    assert got["planes"] == 4 and got["steps"] == 8
    assert got["step_seconds"] == pytest.approx(8000e-9)
    assert got["collective_seconds"] == pytest.approx(4 * 300e-9)
    assert got["by_kind"] == {"all-reduce": pytest.approx(4 * 250e-9),
                              "collective-permute": pytest.approx(4 * 50e-9)}
    # 300 of 2,000 ns a plane
    assert 100 * got["collective_seconds"] / got["step_seconds"] == (
        pytest.approx(15.0))
    assert collective_ops.reduce_planes([plane("/host:CPU", [], [])]) is None
    assert collective_ops.kind_of("%reduce-scatter.1 = ...") == "reduce-scatter"
    assert collective_ops.kind_of("%all-to-all.4") == "all-to-all"
    assert collective_ops.kind_of("%fusion.3 = ...") is None
    # the recorded one-chip trace holds no collective: the share is 0
    tiny = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "tiny_tpu.xplane.pb")
    one = collective_ops.reduce(tiny, step_prefix="jit__lambda")
    assert one["steps"] == 3 and one["collective_seconds"] == 0.0
    assert reader("mesh_collective_pct").read({"trace": None}) is None


def test_counter_readers_and_a_registry_without_the_counters():
    rise = {"fused_fleet_ticks_total": 200.0,
            "fused_put_seconds": 0.3, "fused_put_seconds_count": 200.0,
            "fused_step_dispatch_seconds": 0.2,
            "fused_step_dispatch_seconds_count": 199.0,
            "fused_fleet_put_bytes_total": 200 * 83968.0}
    ctx = {"registry": rise}
    assert reader("tick_put_ms").read(ctx) == pytest.approx(1.5)
    assert reader("tick_step_dispatch_ms").read(ctx) == pytest.approx(1.0)
    assert reader("put_bytes_per_tick").read(ctx) == pytest.approx(83968.0)
    # the parent has the two histograms and not the counter
    parent = {"registry": {k: v for k, v in rise.items()
                           if k != "fused_fleet_put_bytes_total"}}
    assert reader("put_bytes_per_tick").read(parent) is None
    assert reader("tick_put_ms").read(parent) == pytest.approx(1.5)
    for name in NEW[2:]:
        assert reader(name).read({"registry": {}}) is None, name
        assert reader(name).read(
            {"registry": {"fused_fleet_ticks_total": 0.0}}) is None, name


@pytest.mark.parametrize("trace", (0, 1))
def test_sound_run_on_four_virtual_devices_is_correct(run, trace):
    rc, lines, err = run("--platform", "cpu", "--rehearse", trace=trace,
                         seed=2**31 + 46 + trace)
    assert rc == 0, err[-2000:]
    r = result(lines)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["device"] == dict(r["device"], platform="cpu", count=4)
    assert any("topology benchmarks.mesh_deploy.Deployment" in l for l in lines)
    checks = [l.split("] ", 1)[-1] for l in lines if "] check " in l]
    assert len(checks) == 8 and all(c.endswith(" ok") for c in checks)
    if trace:
        m = r["metrics"]
        # the device_trace readers find no TPU plane on the CPU: left out
        assert set(NEW[2:]) <= set(m) and "fused_step_roofline" not in m
        # 64 events of 66 words and 1,024 acks, to four devices, a tick
        assert m["put_bytes_per_tick"]["value"] == 4 * (64 * 66 + 1024) * 4
        assert m["compiles_in_window"]["value"] == 0
    else:
        assert set(r["metrics"]) == {"setup_s", "converge_p50_ms"}


def test_a_run_on_one_device_ends_with_no_result(monkeypatch):
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    rc, lines, err = run_cell("--platform", "cpu", "--rehearse", cell=CELL)
    assert rc != 0 and "the cell needs 4" in err
    assert not any(l.startswith("{") for l in lines)


@pytest.mark.parametrize("control", ("corrupt-downstream", "drop-downstream"))
def test_controls_are_not_correct(run, control):
    rc, lines, err = run("--platform", "cpu", "--rehearse", "--control", control)
    assert rc == 0, err[-2000:]
    r = result(lines)
    assert r["correct"] is False
    assert r["checks"]["downstream_mismatches"]["ok"] is False
    assert (r["failed"] > 0) == (control == "drop-downstream")
