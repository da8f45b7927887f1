"""The ``k8s-rolling-1k.churn`` cell: its configuration and manifest
entries, its five readers on hand-made input, and whole runs tiny on the
CPU with its ``rehearsal`` block (both kinds of run come out correct,
both controls ``correct: false``, the statuses a tenant saw on the way
are part of ``converged_for_wrong_values``)."""

import importlib
import json
import os

import pytest

from benchmarks import run as runmod
from benchmarks.shapes import k8s_rolling as shape
from test_rehearsal import result, run

CELL = "k8s-rolling-1k.churn"
TWIN = "k8s-load-1k.churn"
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NEW = {"status_trips_per_write", "status_trip_ms", "upsync_deferred_pct",
       "controller_span_ms", "system_overhead_p50_ms"}


def reader(name):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}")


def test_manifest_and_configuration():
    manifest, cell, config, traffic = runmod.resolve(CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "k8s-rolling-1k", "rolling-churn")
    assert [w["name"] for w in manifest["workloads"]][-1] == CELL
    assert len(manifest["workloads"]) == 5 and len(manifest["configs"]) == 4
    assert all(w["chips"] == 1 for w in manifest["workloads"])
    entry = manifest["configs"][-1]
    assert entry["name"] == "k8s-rolling-1k" and entry["file"].endswith(
        "configs/k8s-rolling-1k.json")
    assert entry["reduced"] == config["reduced"] == [
        "resident_per_cluster", "pod_ready_ms"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert "rolling.go" in entry["source"] and "perf-tests" in entry["source"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert {"status_order", "status_final"} <= set(config["guarantees"])
    twin = json.load(open(os.path.join(REPO, "benchmarks", "traffic",
                                       "load-churn.json")))
    assert set(traffic) == set(twin) and "burst" not in traffic
    for key in ("kind", "mix", "tenants", "warmup_s", "cooldown_s",
                "deadline_s", "senders", "rehearsal"):
        assert traffic[key] == twin[key], key
    assert traffic["rate_per_s"] % 10 == 0 and "knee" in traffic["rate_source"]
    # every per-layer metric the twin reports but the accounted share
    # (the eight phases describe the first trip only), and the new five
    names = set(runmod.metric_names(manifest, "per_layer", CELL))
    twins = set(runmod.metric_names(manifest, "per_layer", TWIN))
    assert names - twins == NEW - {"status_trips_per_write"}
    assert twins - names == {"converge_accounted_pct"}
    assert set(runmod.metric_names(manifest, "end_to_end", CELL)) == {
        "converge_p50_ms", "setup_s"}
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert [m["name"] for m in manifest["per_layer"]][-5:] == [
        "status_trips_per_write", "status_trip_ms", "upsync_deferred_pct",
        "controller_span_ms", "system_overhead_p50_ms"]
    assert all(by_name[n]["moves"] == "converge_p50_ms" for n in NEW)
    assert by_name["status_trips_per_write"]["workloads"] == [TWIN, CELL]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        assert len(f.read()) < 64 * 1024


def test_counter_readers():
    rise = {"kcp_sync_status_upsyncs_total": 378.0,
            "request_admission_seconds_count": 82.0,
            "kcp_sync_patches_deferred_total": 186.0,
            "convergence_restatus_seconds": 2.343,
            "convergence_restatus_seconds_count": 257.0}
    ctx = {"registry": rise}
    assert reader("status_trips_per_write").read(ctx) == pytest.approx(378 / 82)
    assert reader("upsync_deferred_pct").read(ctx) == pytest.approx(
        100 * 186 / 378)
    assert reader("status_trip_ms").read(ctx) == pytest.approx(
        1e3 * 2.343 / 257)
    # the parent's program has no such counter or histogram
    for name in ("status_trips_per_write", "upsync_deferred_pct",
                 "status_trip_ms"):
        assert reader(name).read({"registry": {
            "request_admission_seconds_count": 82.0}}) is None


def test_span_readers(monkeypatch):
    from benchmarks import rolling_agent

    body = shape.new("deployment-000-000000aa", __import__("random").Random(1),
                     ["loc0"])

    def op(name, gen, due, seen, kind="update"):
        return {"kind": kind, "key": ["t0001", name], "due": due, "seen": seen,
                "body": body,
                "evidence": {"status": {"observedGeneration": gen}}}

    monkeypatch.setattr(rolling_agent, "STAMPS", {
        ("loc0", "a", 2): [10.00, 10.07], ("loc0", "b", 5): [20.0, 20.10],
        ("loc0", "c", 3): [30.0, 30.06], ("loc0", "d", 1): [1.0, 1.02]})
    ctx = {"ops": [op("a", 2, 9.98, 10.08), op("b", 5, 19.99, 20.14),
                   op("c", 3, 29.97, 30.09),
                   op("d", 1, 0.9, 1.1, kind="create"),   # updates only
                   op("e", 9, 40.0, 40.2),                # no walk stamped
                   op("a", 2, 50.0, None)]}               # never converged
    assert reader("controller_span_ms").read(ctx) == pytest.approx(
        (70 + 100 + 60) / 3)
    assert reader("system_overhead_p50_ms").read(ctx) == pytest.approx(50.0)
    # a controller that answers once stamps nothing: nothing to read
    monkeypatch.setattr(rolling_agent, "STAMPS", {})
    assert reader("controller_span_ms").read(ctx) is None
    assert reader("system_overhead_p50_ms").read(ctx) is None


@pytest.mark.parametrize("trace", (0, 1))
def test_sound_run_is_correct(trace):
    rc, lines, err = run("--platform", "cpu", "--rehearse", cell=CELL,
                         trace=trace, seed=2**31 + 36 + trace)
    assert rc == 0, err[-2000:]
    r = result(lines)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["platform"] == "cpu"
    assert any("agents started (benchmarks.rolling_agent.RollingDeployment)"
               in l for l in lines)
    checks = [l.split("] ", 1)[-1] for l in lines if "] check " in l]
    assert len(checks) == 8 and all(c.endswith(" ok") for c in checks)
    if trace:
        m = r["metrics"]
        assert NEW | {"conv_upstatus_ms", "tick_host_ms"} <= set(m)
        assert "converge_accounted_pct" not in m
        assert 3 <= m["status_trips_per_write"]["value"] <= 6
        assert m["controller_span_ms"]["value"] >= 60  # three waits of 20 ms
        assert m["system_overhead_p50_ms"]["value"] > 0
    else:
        assert set(r["metrics"]) == {"setup_s", "converge_p50_ms"}


def test_the_twin_reads_about_one_trip_a_write():
    rc, lines, err = run("--platform", "cpu", "--rehearse", cell=TWIN,
                         trace=1, seed=2**31 + 38)
    assert rc == 0, err[-2000:]
    m = result(lines)["metrics"]
    assert 0.8 <= m["status_trips_per_write"]["value"] <= 1.3
    assert not (NEW - {"status_trips_per_write"}) & set(m)


def test_a_value_corrupted_inside_a_list_is_not_correct():
    rc, lines, err = run("--platform", "cpu", "--rehearse", "--control",
                         "corrupt-downstream", cell=CELL)
    assert rc == 0, err[-2000:]
    r = result(lines)
    assert r["correct"] is False
    assert r["failed"] == 0  # every rollout ends: only whole specs tell
    assert r["checks"]["downstream_mismatches"]["ok"] is False
    assert r["checks"]["converged_for_wrong_values"]["ok"] is True


def test_dropped_downstream_write_is_not_correct():
    rc, lines, err = run("--platform", "cpu", "--rehearse", "--control",
                         "drop-downstream", cell=CELL)
    assert rc == 0, err[-2000:]
    r = result(lines)
    assert r["correct"] is False and r["failed"] > 0
    assert r["checks"]["downstream_mismatches"]["ok"] is False
