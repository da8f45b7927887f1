"""The ``mapper-1k-50k.cold`` cell: its configuration and manifest
entries, its nine readers over a recorded rise and recorded records, the
topology's twelve names, and whole runs tiny on the CPU with its
``rehearsal`` block (both kinds of run come out correct, both controls
``correct: false``)."""

import importlib
import json
import os

import pytest

from benchmarks import mapper_deploy
from benchmarks import run as runmod
from test_rehearsal import result, run

CELL = "mapper-1k-50k.cold"
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NEW = ["full_sync_s", "sync_rate_per_s", "first_synced_ms",
       "live_converge_p50_ms", "cluster_start_ms", "compile_s_in_window",
       "growth_stall_pct", "initial_rows_pct", "loadgen_cpu_pct"]
NOT_HERE = {"ack_p50_ms", "loadgen_late_p95_ms", "conv_write_ms",
            "conv_propagate_ms", "converge_accounted_pct",
            "request_admission_ms", "request_commit_ms", "request_finish_ms",
            "write_body_bytes", "wal_bytes_per_write", "copies_per_write",
            "loop_ms_per_write", "status_trips_per_write"}


def reader(name):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}")


def test_manifest_and_configuration():
    manifest, cell, config, traffic = runmod.resolve(CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "mapper-1k-50k", "cold")
    assert [w["name"] for w in manifest["workloads"]][-1] == CELL
    assert len(manifest["workloads"]) == 7 and len(manifest["configs"]) == 6
    assert all(w["chips"] == 1 for w in manifest["workloads"])
    entry = manifest["configs"][-1]
    assert entry["name"] == "mapper-1k-50k" and entry["file"].endswith(
        "configs/mapper-1k-50k.json")
    assert entry["reduced"] == config["reduced"] == []
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert "cluster-mapper.md:21-24" in entry["source"]
    # the source's 50,000 resources, not cut; nothing warmed
    assert config["logical_clusters"] * config["resident_per_cluster"] == 50_000
    assert config["warm_bursts"] == [] and config["shape"] == "configmap"
    assert config["deployment"] == "benchmarks.mapper_deploy"
    assert {"full_sync", "status", "live_writes_during_the_sync",
            "durability", "read_your_writes", "downsync",
            "upsync"} == set(config["guarantees"])
    assert {"object", "locations", "controller", "cold",
            "compile_cache"} <= set(config["assumed"])
    assert config["rehearsal"] == {"logical_clusters": 10,
                                   "resident_per_cluster": 8}
    assert traffic["kind"] == "cold_sync" and traffic["register_due_s"] == 1.0
    assert traffic["register_senders"] == 32
    assert traffic["live"] == {"rate_per_s": 20, "senders": 8, "aux": True}
    assert (traffic["warmup_s"], traffic["cooldown_s"]) == (2, 2)
    # the nine new readers are the manifest's last nine, this cell's alone
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert [m["name"] for m in manifest["per_layer"]][-9:] == NEW
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "converge_p50_ms"
    names = set(runmod.metric_names(manifest, "per_layer", CELL))
    assert set(NEW) <= names and not names & NOT_HERE
    # the roofline reader divides the FINAL B's bytes by a step timed while
    # B was still growing: it would read high here (PERF.md §4), so not here
    assert "fused_step_roofline" not in names
    assert {"tick_host_ms", "step_device_ms", "compiles_in_window", "conv_stage_ms", "conv_observe_ms",
            "rows_per_tick", "loop_busy_pct", "loop_stalled_pct",
            "idle_attributed_pct", "converge_p99_ms"} <= names
    assert set(runmod.metric_names(manifest, "end_to_end", CELL)) == {
        "converge_p50_ms", "setup_s"}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["converged_per_s"]["workloads"] == ["splitter-125x8.rollout"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        assert len(f.read()) < 64 * 1024


def test_topology_offers_the_twelve_names():
    dep = mapper_deploy.Deployment(
        dict(runmod.resolve(CELL, rehearse=True)[2]), 5, "/nonexistent")
    for name in ("bring_up", "loadgen", "population", "tenants", "locations",
                 "shape", "srv", "downstream", "fleet", "agent_errors",
                 "counters0", "stop"):
        assert hasattr(dep, name), name
    assert len(dep.population) == 80 and len(dep.tenants) == 10
    spec = dep.__class__.loadgen_spec
    assert spec is not mapper_deploy.deploy.Deployment.loadgen_spec


def records():
    """Ten residents registered at t = 101: seen 2..10 s later, one
    never; three live creates, one of them before the window."""
    ops = [{"kind": "sync", "key": ["t0", f"r{i}"], "due": 101.0,
            "seen": 101.0 + 2 + i if i < 9 else None, "aux": False}
           for i in range(10)]
    live = [{"kind": "create", "key": ["t0", f"l{i}"], "due": due,
             "seen": due + lat, "aux": True}
            for i, (due, lat) in enumerate(((99.0, 9.0), (105.0, 0.3),
                                            (120.0, 0.1), (130.0, 0.2)))]
    return ops, live


def test_generator_readers():
    ops, live = records()
    ctx = {"ops": ops, "all_ops": ops + live, "window": (100.0, 151.0),
           "beyond_ms": 120e3, "seconds": 51.0,
           "generator": {"sync_cpu_s": 3.0, "sync_wall_s": 12.0}}
    assert reader("full_sync_s").read(ctx) == 120.0   # one never seen
    assert reader("first_synced_ms").read(ctx) == 2000.0
    # between the first (10 %) and the ninth (90 %): 8 residents in 8 s
    assert reader("sync_rate_per_s").read(ctx) == pytest.approx(1.0)
    # the live create due before the window is not in it
    assert reader("live_converge_p50_ms").read(ctx) == pytest.approx(200.0)
    assert reader("loadgen_cpu_pct").read(ctx) == pytest.approx(25.0)
    for o in ops:
        o["seen"] = o["seen"] or 111.5
    assert reader("full_sync_s").read(ctx) == pytest.approx(10.5)
    # another cell's records: nothing to read
    other = dict(ctx, ops=[dict(o, kind="update") for o in ops],
                 all_ops=[dict(o, aux=False) for o in live], generator={})
    for name in ("full_sync_s", "sync_rate_per_s", "first_synced_ms",
                 "live_converge_p50_ms", "loadgen_cpu_pct"):
        assert reader(name).read(other) is None, name


def test_counter_readers():
    ops, _live = records()
    for o in ops:
        o["seen"] = o["seen"] or 111.0  # the sync took 10 s of the window
    rise = {"cluster_syncer_start_seconds": 0.5,
            "cluster_syncer_start_seconds_count": 10.0,
            "cluster_syncer_restarts_total": 1.0,
            "jax_backend_compile_seconds": 4.25,
            "jax_backend_compile_seconds_count": 17.0,
            "fused_full_upload_seconds": 1.5,
            "fused_full_upload_seconds_count": 30.0,
            "fused_compile_seconds": 2.5,
            "fused_compile_seconds_count": 16.0,
            "fused_fleet_row_growths_total": 9.0,
            "fused_fleet_segment_growths_total": 7.0,
            "fused_fleet_patch_growths_total": 3.0,
            "fused_fleet_state_upload_bytes_total": 5e8,
            "kcp_sync_initial_rows_total": 50.0,
            "fused_encoded_rows_total": 200.0}
    ctx = {"registry": rise, "ops": ops, "window": (100.0, 151.0),
           "beyond_ms": 120e3, "compiles": 17}
    assert reader("cluster_start_ms").read(ctx) == pytest.approx(50.0)
    assert reader("compile_s_in_window").read(ctx) == pytest.approx(4.25)
    assert reader("growth_stall_pct").read(ctx) == pytest.approx(40.0)
    assert reader("initial_rows_pct").read(ctx) == pytest.approx(25.0)
    # a sync that outlasts the window is cut at the window's end
    late = dict(ctx, window=(100.0, 106.0))
    assert reader("growth_stall_pct").read(late) == pytest.approx(80.0)
    # the parent's program has none of the counters: nothing, no raise
    bare = dict(ctx, registry={"fused_encoded_rows_total": 200.0,
                               "fused_full_upload_seconds": 1.5})
    for name in ("cluster_start_ms", "compile_s_in_window",
                 "growth_stall_pct", "initial_rows_pct"):
        assert reader(name).read(bare) is None, name


@pytest.mark.parametrize("trace", (0, 1))
def test_sound_run_is_correct(trace):
    rc, lines, err = run("--platform", "cpu", "--rehearse", cell=CELL,
                         trace=trace, seed=2**31 + 44 + trace)
    assert rc == 0, err[-2000:]
    r = result(lines)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] == 80
    assert r["device"]["platform"] == "cpu"
    assert any("topology benchmarks.mapper_deploy.Deployment" in l
               for l in lines)
    assert any("residents populated (no Cluster exists)" in l for l in lines)
    checks = [l.split("] ", 1)[-1] for l in lines if "] check " in l]
    assert len(checks) == 8 and all(c.endswith(" ok") for c in checks)
    if trace:
        m = r["metrics"]
        assert set(NEW) <= set(m) and not set(m) & NOT_HERE
        assert m["compiles_in_window"]["value"] >= 1  # nothing is warmed
        assert m["growth_stall_pct"]["value"] > 0
        assert 0 < m["full_sync_s"]["value"] < 20
        assert m["first_synced_ms"]["value"] <= 1e3 * m["full_sync_s"]["value"]
        assert 15 <= m["initial_rows_pct"]["value"] <= 60
        long_passes = [l for l in lines if l.startswith("[layer] loop stalls")]
        assert long_passes and "kcp.tick.compile" in long_passes[0]
    else:
        assert set(r["metrics"]) == {"setup_s", "converge_p50_ms"}


def test_corrupted_downstream_copy_is_not_correct():
    rc, lines, err = run("--platform", "cpu", "--rehearse", "--control",
                         "corrupt-downstream", cell=CELL)
    assert rc == 0, err[-2000:]
    r = result(lines)
    assert r["correct"] is False
    assert r["failed"] == 0  # every resident got its status: only values tell
    assert r["checks"]["downstream_mismatches"]["ok"] is False
    assert r["checks"]["converged_for_wrong_values"]["ok"] is True


def test_dropped_downstream_write_is_not_correct():
    rc, lines, err = run("--platform", "cpu", "--rehearse", "--control",
                         "drop-downstream", cell=CELL)
    assert rc == 0, err[-2000:]
    r = result(lines)
    assert r["correct"] is False and r["failed"] > 0
    assert r["checks"]["downstream_mismatches"]["ok"] is False
