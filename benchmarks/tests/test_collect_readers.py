"""The four readers of the completion wake (PR 47), each on a hand-made
``ctx``: the rises of the tick's histograms and of the collect's
counters as ``deploy.rise`` yields them; ``tick_wire_wait_ms`` reads a
program without the wake too (the pair's own before and after), the
other three read nothing there; the manifest names all four, together, in
every cell."""

import importlib
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NEW = ["tick_wire_wait_ms", "wire_ready_ms", "collect_lag_ms",
       "collect_woken_pct"]

# 1,000 ticks of 4.5 ms of wall: 2.0 ms in the eight phases, 2.5 between
PARENT = {"fused_fleet_ticks_total": 1000.0,
          "fused_tick_seconds": 4.5, "fused_tick_seconds_count": 1000.0,
          "fused_encode_seconds": 0.8, "fused_pack_seconds": 0.1,
          "fused_full_upload_seconds": 0.0, "fused_put_seconds": 0.5,
          "fused_step_dispatch_seconds": 0.4, "fused_compile_seconds": 0.0,
          "fused_collect_wait_seconds": 0.05, "fused_dispatch_seconds": 0.15,
          "fused_collect_ready_total": 990.0}
# the same ticks with the wake: 1.3 ms between, 0.9 of it the device's
# answer and 0.3 the wake's turn; 950 collects woken, 50 by the depth rule
CHANGE = dict(PARENT, fused_tick_seconds=3.3,
              fused_wire_ready_seconds=0.9,
              fused_wire_ready_seconds_count=1000.0,
              fused_collect_lag_seconds=0.285,
              fused_collect_lag_seconds_count=950.0,
              fused_collect_woken_total=950.0,
              fused_collect_depth_total=50.0)


def reader(name):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}")


def test_tick_wire_wait_reads_parent_and_change_alike(capsys):
    read = reader("tick_wire_wait_ms").read
    assert read({"registry": PARENT}) == pytest.approx(2.5)
    assert read({"registry": CHANGE}) == pytest.approx(1.3)
    assert capsys.readouterr().out.count("[layer] tick_wire_wait") == 2
    # every phase the program names is taken off: a compile, a full upload
    cold = dict(PARENT, fused_compile_seconds=1.0,
                fused_full_upload_seconds=0.5)
    assert read({"registry": cold}) == pytest.approx(1.0)
    assert read({"registry": {}}) is None
    assert read({"registry": dict(PARENT, fused_fleet_ticks_total=0.0)}) is None


def test_wire_ready_is_the_histograms_mean_and_nothing_on_the_parent():
    read = reader("wire_ready_ms").read
    assert read({"registry": CHANGE}) == pytest.approx(0.9)
    assert read({"registry": PARENT}) is None
    # the CPU backend registers the histogram and never observes it
    quiet = dict(PARENT, fused_wire_ready_seconds=0.0,
                 fused_wire_ready_seconds_count=0.0)
    assert read({"registry": quiet}) is None


def test_collect_lag_is_a_mean_over_the_wakes_that_collected():
    read = reader("collect_lag_ms").read
    assert read({"registry": CHANGE}) == pytest.approx(0.3)
    assert read({"registry": PARENT}) is None


def test_collect_woken_pct_counts_wakes_per_hundred_ticks():
    read = reader("collect_woken_pct").read
    assert read({"registry": CHANGE}) == pytest.approx(95.0)
    assert read({"registry": PARENT}) is None
    # the counter is there and did not rise (the CPU backend): 0, not None
    assert read({"registry": dict(PARENT, fused_collect_woken_total=0.0)}) == 0.0
    assert read({"registry": {"fused_collect_woken_total": 3.0}}) is None


def test_the_manifest_names_the_four_readers_in_every_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = [w["name"] for w in manifest["workloads"]][:8]
    names = [m["name"] for m in manifest["per_layer"]]
    # appended after everything PR 46 left, together and in this order
    # (what a later PR appends behind them is not this test's to pin)
    at = names.index(NEW[0])
    assert names[at:at + 4] == NEW and at >= names.index("put_bytes_per_tick")
    for m in manifest["per_layer"][at:at + 4]:
        assert m["workloads"][:8] == cells, m["name"]
        assert m["layer"] == "syncer core, host side"
        assert m["moves"] == "converge_p50_ms"
        assert m["source"] == "program_counter"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.exists(os.path.join(
            REPO, "benchmarks", "layer_metrics", m["name"] + ".py"))
    assert [(m["unit"], m["better"])
            for m in manifest["per_layer"][at:at + 4]] == [
        ("ms", "lower")] * 3 + [("%", "higher")]
