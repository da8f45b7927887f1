"""``encode_sections_per_stage`` (PR 52) on a hand-made ``ctx``: the
rises of the two counters as ``deploy.rise`` yields them; nothing on a
program without them (the parent); the manifest names it beside
``encode_us_per_row``, in the same cells."""

import importlib
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_sections_per_stage_divides_the_two_rises_or_reads_nothing(capsys):
    read = importlib.import_module(
        "benchmarks.layer_metrics.encode_sections_per_stage").read
    # a flood: 51 s of ticks, 50 one-key sections each, one bucket
    flood = {"fused_encoded_rows_total": 21000.0,
             "fused_encoded_sections_total": 20000.0,
             "fused_stage_batches_total": 400.0}
    assert read({"registry": flood}) == pytest.approx(50.0)
    assert capsys.readouterr().out.count(
        "[layer] fused_encoded_sections_total") == 1
    # the parent: neither counter
    assert read({"registry": {"fused_encoded_rows_total": 21000.0}}) is None
    assert read({"registry": {}}) is None
    # a window that staged nothing
    quiet = dict(flood, fused_encoded_sections_total=0.0,
                 fused_stage_batches_total=0.0)
    assert read({"registry": quiet}) is None


def test_the_manifest_names_it_in_the_cells_of_encode_us_per_row():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    new, beside = (per_layer["encode_sections_per_stage"],
                   per_layer["encode_us_per_row"])
    for key in ("layer", "moves", "workloads", "source"):
        assert new[key] == beside[key]
    assert new["unit"] == "sections"
