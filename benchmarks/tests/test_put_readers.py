"""``puts_per_tick`` (PR 53) on a hand-made ``ctx``: the rises of the two
counters as ``deploy.rise`` yields them; nothing on a program without the
counter (the parent); the manifest names it — found by NAME, wherever
later PRs leave it in the list — beside ``put_bytes_per_tick``."""

import importlib
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELLS = ["syncer-1k.steady", "mesh4-1k.steady", "splitter-125x8.rollout"]


def reader(name):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}").read


def test_puts_per_tick_divides_the_two_rises_or_reads_nothing(capsys):
    read = reader("puts_per_tick")
    # one chip: the packed wire, the ack lane inside it
    steady = {"fused_fleet_ticks_total": 9000.0,
              "fused_fleet_puts_total": 9000.0}
    assert read({"registry": steady}) == pytest.approx(1.0)
    assert capsys.readouterr().out.count("[layer] fused_fleet_puts_total") == 1
    # a 4x1 mesh: the one array to four devices
    mesh = dict(steady, fused_fleet_puts_total=36000.0)
    assert read({"registry": mesh}) == pytest.approx(4.0)
    # a rollout: the placement-leaves swap's two on nearly every tick
    rollout = {"fused_fleet_ticks_total": 2000.0,
               "fused_fleet_puts_total": 2000.0 + 2 * 1900.0}
    assert read({"registry": rollout}) == pytest.approx(2.9)
    # the parent: no such counter, whatever else its registry holds
    parent = {"fused_fleet_ticks_total": 9000.0,
              "fused_fleet_put_bytes_total": 9000.0 * 21120.0}
    assert read({"registry": parent}) is None
    assert read({"registry": {}}) is None
    # a window without a tick
    assert read({"registry": dict(steady, fused_fleet_ticks_total=0.0)}) is None
    # and the parent's byte reader reads the change alike
    assert reader("put_bytes_per_tick")({"registry": parent}) == 21120.0


def test_the_manifest_names_it_beside_put_bytes_per_tick():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    new, beside = per_layer["puts_per_tick"], per_layer["put_bytes_per_tick"]
    for key in ("layer", "moves", "source", "better"):
        assert new[key] == beside[key]
    assert new == dict(new, unit="puts", better="lower",
                       layer="syncer core, host side",
                       moves="converge_p50_ms", source="program_counter",
                       workloads=CELLS)
    assert set(new) == {"name", "unit", "better", "source", "layer", "moves",
                        "workloads"}
