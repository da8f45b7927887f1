"""The ``syncer-churn-1k.flood`` cell (PR 51): its configuration and
manifest entries are the ones the issue names (found by name, wherever
later PRs leave them in their lists), its traffic is ``flood.json`` as
it stands, its three readers read a toy registry (the stated values
where the counters are, nothing where they are not: the parent), and
whole runs tiny on the CPU with the configuration's ``rehearsal`` block
come out correct with ``B`` where it was, both controls
``correct: false``."""

import importlib
import json
import os

import pytest

from benchmarks import run as runmod
from test_rehearsal import result
from test_rehearsal import run as run_cell

CELL = "syncer-churn-1k.flood"
CONFIG = "syncer-churn-1k"
CONTROL = "syncer-1k.steady"
ROLLOUT = "splitter-125x8.rollout"
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NEW = {"row_reuse_pct": ("%", "higher"),
       "row_growths_in_window": ("count", "lower"),
       "row_retire_ms": ("ms", "lower")}
SPLITTER_ONLY = {"split_ms", "aggregate_ms", "clusters_per_lookup",
                 "placement_applied_pct"}
BESIDES = {"compiles_in_window", "fused_step_roofline", "converge_p99_ms",
           "converge_accounted_pct", "compile_s_in_window"}
COLD_ONLY = {"growth_stall_pct", "loadgen_cpu_pct"}

# a window of a program that retires rows: 17,000 new keys, 40 of them
# past the high-water mark, every retirement 12 ms from gone to free
CHANGE = {"fused_rows_reused_total": 16960.0, "fused_rows_fresh_total": 40.0,
          "fused_rows_retired_total": 17000.0, "fused_rows_held_back": 3.0,
          "fused_fleet_row_growths_total": 0.0,
          "fused_row_retire_seconds": 204.0,
          "fused_row_retire_seconds_count": 17000.0}
# its parent: a row a name, so B doubled once in the window
PARENT = {"fused_fleet_row_growths_total": 1.0,
          "fused_fleet_ticks_total": 6000.0}


def reader(name):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}")


def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_and_its_configuration_are_the_ones_the_issue_names():
    m, cell, config, traffic = runmod.resolve(CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "flood",
                    "chips": 1, "why": cell["why"]}
    assert 0 < len(cell["why"]) <= 200
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"] == "benchmarks/configs/syncer-churn-1k.json"
    assert entry["reduced"] == config["reduced"] == ["resident_per_cluster"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    for part in ("BASELINE.json configs[1]", "ConfigMap churn",
                 "docs/cluster-mapper.md:21-24", "metadata.generateName"):
        assert part in entry["source"], part
    assert [c["file"] for c in m["configs"]].count(entry["file"]) == 1
    # syncer-1k size for size, on the default topology
    _m, _c, control, _t = runmod.resolve(CONTROL)
    for key in ("shape", "logical_clusters", "locations_per_cluster",
                "resources_to_sync", "resident_per_cluster", "warm_bursts",
                "server", "rehearsal", "reduced", "reduced_why"):
        assert config[key] == control[key], key
    assert "deployment" not in config and "1:" in config["chips"]
    # its guarantees: syncer-1k's four word for word, and two more
    for name, text in control["guarantees"].items():
        assert config["guarantees"][name] == text
    assert set(config["guarantees"]) - set(control["guarantees"]) == {
        "deletion", "bounded_state"}
    assert "16,384" in config["guarantees"]["bounded_state"]
    for name, text in control["assumed"].items():
        assert config["assumed"][name] == text
    assert set(config["assumed"]) - set(control["assumed"]) == {
        "names", "lifetime", "clients", "held_by"}
    assert "row_growths_in_window" in config["assumed"]["held_by"]
    # the traffic, as PR 35 left it
    assert traffic == {"kind": "closed_loop", "clients": 64,
                       "tenants": "uniform", "warmup_s": 5, "cooldown_s": 2,
                       "deadline_s": 10, "keep_last": True,
                       "rehearsal": {"clients": 4, "warmup_s": 1,
                                     "cooldown_s": 1}}


def test_the_metrics_the_cell_reports():
    m = manifest()
    end_to_end = runmod.metric_names(m, "end_to_end", CELL)
    assert end_to_end == ["converge_p50_ms", "converged_per_s", "setup_s"]
    rate = next(e for e in m["end_to_end"] if e["name"] == "converged_per_s")
    assert rate["workloads"][:2] == [ROLLOUT, CELL]
    mine = set(runmod.metric_names(m, "per_layer", CELL))
    rollout = set(runmod.metric_names(m, "per_layer", ROLLOUT))
    assert mine == (rollout - SPLITTER_ONLY) | BESIDES
    assert not mine & COLD_ONLY and set(NEW) <= mine
    by_name = {p["name"]: p for p in m["per_layer"]}
    for name in mine:
        assert by_name[name]["moves"] in end_to_end, name
        assert callable(reader(name).read)
    # appended to each list, nothing else of an entry changed
    for name in mine - set(NEW):
        assert by_name[name]["workloads"].index(CELL) >= 1, name


def test_the_three_new_entries():
    by_name = {p["name"]: p for p in manifest()["per_layer"]}
    for name, (unit, better) in NEW.items():
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better,
            "source": "program_counter", "layer": "syncer core, host side",
            "moves": "converged_per_s", "workloads": [CELL, ROLLOUT]}
        assert os.path.exists(os.path.join(
            REPO, "benchmarks", "layer_metrics", name + ".py"))


def test_row_reuse_pct(capsys):
    read = reader("row_reuse_pct").read
    assert read({"registry": CHANGE}) == pytest.approx(100.0 * 16960 / 17000)
    said = capsys.readouterr().out
    assert "16960 reused + 40 fresh" in said and "17000 retired" in said
    assert read({"registry": PARENT}) is None
    assert read({"registry": {}}) is None
    # the counters are there and no key was new in the window
    assert read({"registry": dict(CHANGE, fused_rows_reused_total=0.0,
                                  fused_rows_fresh_total=0.0)}) is None
    # every new key past the high-water mark (a tenant that really grows)
    assert read({"registry": dict(CHANGE, fused_rows_reused_total=0.0)}) == 0.0


def test_row_growths_in_window_reads_parent_and_change_alike(capsys):
    read = reader("row_growths_in_window").read
    fleet = {"B": 32768, "S": 64}
    assert read({"registry": PARENT, "fleet": fleet}) == 1.0
    assert "B=32768 S=64" in capsys.readouterr().out
    assert read({"registry": CHANGE, "fleet": {"B": 16384, "S": 64}}) == 0.0
    assert read({"registry": CHANGE}) == 0.0
    assert read({"registry": {}}) is None


def test_row_retire_ms_is_the_histograms_mean():
    read = reader("row_retire_ms").read
    assert read({"registry": CHANGE}) == pytest.approx(12.0)
    assert read({"registry": PARENT}) is None
    quiet = dict(CHANGE, fused_row_retire_seconds=0.0,
                 fused_row_retire_seconds_count=0.0)
    assert read({"registry": quiet}) is None


def test_a_rehearsed_flood_is_correct_and_its_rows_come_back():
    rc, lines, err = run_cell("--platform", "cpu", "--rehearse", cell=CELL,
                              trace=1)
    assert rc == 0, err[-2000:]
    r = result(lines)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert m["row_growths_in_window"] == 0 and m["compiles_in_window"] == 0
    assert m["full_upload_ticks_pct"] == 0 and m["row_reuse_pct"] >= 95
    assert m["row_retire_ms"] > 0
    assert any("fleet state:" in l and "B=64 " in l for l in lines)


@pytest.mark.parametrize("control", ("corrupt-downstream", "drop-downstream"))
def test_a_flood_with_the_timed_path_broken_is_not_correct(control):
    rc, lines, err = run_cell("--platform", "cpu", "--rehearse", "--control",
                              control, cell=CELL)
    assert rc == 0, err[-2000:]
    r = result(lines)
    assert r["correct"] is False
    assert r["checks"]["downstream_mismatches"]["ok"] is False
