"""The readers PR 29 added, each on a hand-made ``ctx``; the idle
attribution on hand-made intervals and on the recorded TPU trace (which
holds no ``kcp.*`` annotation: None)."""

import importlib
import os

import pytest

from benchmarks import host_annotations, phase_means

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# reader -> (histogram it reads, counter it divides by)
MEANS = {f"conv_{p}_ms": (f"convergence_{p}_seconds", None)
         for p in phase_means.PHASES}
MEANS.update({
    "request_admission_ms": ("request_admission_seconds", None),
    "request_commit_ms": ("request_commit_seconds", None),
    "request_finish_ms": ("request_finish_seconds", None),
    "loop_lag_ms": ("server_loop_lag_seconds", None),
    "tick_wall_ms": ("fused_tick_seconds", "fused_fleet_ticks_total"),
    "split_ms": ("splitter_split_seconds", None),
    "aggregate_ms": ("splitter_aggregate_seconds", None),
})


def reader(name):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}")


@pytest.mark.parametrize("name", sorted(MEANS))
def test_mean_reader(name):
    hist, over = MEANS[name]
    ctx = {"registry": {hist: 0.6, over or hist + "_count": 200.0}}
    assert reader(name).read(ctx) == pytest.approx(3.0)  # 600 ms / 200
    # the parent's program: the histogram or its count is not there
    assert reader(name).read({"registry": {}}) is None
    assert reader(name).read({"registry": {hist: 0.6}}) is None
    # a window in which nothing was observed
    assert reader(name).read({"registry": {hist: 0.0, over or hist + "_count": 0.0}}) is None


def test_accounted_pct():
    reg = {}
    for i, p in enumerate(phase_means.PHASES):
        reg[f"convergence_{p}_seconds"] = 0.001 * (i + 1) * 10  # 10 obs each
        reg[f"convergence_{p}_seconds_count"] = 10.0
    ctx = {"registry": reg, "timed": [40.0, 50.0]}  # mean due->seen 45 ms
    # means 1..8 ms sum to 36 ms
    assert reader("converge_accounted_pct").read(ctx) == pytest.approx(80.0)
    assert reader("converge_accounted_pct").read(dict(ctx, timed=[])) is None
    del reg["convergence_observe_seconds_count"]
    assert reader("converge_accounted_pct").read(ctx) is None


def test_interval_helpers():
    m = host_annotations.merged([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert m == [(0, 3), (5, 8)]
    assert host_annotations.gaps_of(m) == [(3, 5)]
    assert host_annotations.overlap([(0, 10), (20, 30)],
                                    [(5, 25), (28, 40)]) == 5 + 5 + 2


def test_attribution_of_idle_time():
    s = 1e9  # intervals are ns
    busy = [(0.0, 1 * s), (3 * s, 4 * s), (4.5 * s, 5 * s)]  # gaps 2 s, 0.5 s
    by_name = {"kcp.gc": [(1.5 * s, 2.5 * s)],
               "kcp.tick": [(2.25 * s, 3.5 * s), (4.75 * s, 6 * s)]}
    got = host_annotations.attribute([busy], by_name, top=10)
    assert got["idle_s"] == pytest.approx(2.5)
    # under gc or a tick: 1.5..3.0 of the first gap, nothing of the second
    assert got["attributed_s"] == pytest.approx(1.5)
    (start, length, under), second = got["gaps"]
    assert (start, length) == (pytest.approx(1.0), pytest.approx(2.0))
    assert under == {"kcp.gc": pytest.approx(1.0),
                     "kcp.tick": pytest.approx(0.75)}
    assert second[2] == {}
    assert got["annotations"]["kcp.tick"] == pytest.approx(2.5)


def test_recorded_tpu_trace_has_no_annotations():
    path = os.path.join(DATA, "tiny_tpu.xplane.pb")
    assert host_annotations.read(path) is None
    # and the reader leaves the metric out where there is no traced run
    assert reader("idle_attributed_pct").read({"trace": None}) is None
