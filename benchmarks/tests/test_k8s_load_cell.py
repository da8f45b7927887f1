"""The ``k8s-load-1k.churn`` cell: its configuration and manifest
entries, its five readers over a recorded rise of the registry, and
whole runs tiny on the CPU (both kinds of run come out correct, both
controls ``correct: false``)."""

import importlib
import json
import os

import pytest

from benchmarks import run as runmod
from benchmarks.shapes import k8s_deployment as shape
from test_rehearsal import result, run

CELL = "k8s-load-1k.churn"
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# what a traced rehearsal of the cell printed for its window (CPU, seed
# 2147483726, 4 s at 20/s: 73 write requests, 5 of them deletes). Byte and
# row COUNTS do not depend on the platform; the seconds are the CPU's and
# stand here only as a divisor's partner.
RISE = {"write_request_body_bytes_total": 123586.0,
        "request_admission_seconds_count": 73.0,
        "wal_appended_bytes_total": 289488.0,
        "watch_stream_bytes_total": 312533.0,
        "watch_stream_events_total": 144.0,
        "fused_encode_seconds": 0.133351,
        "fused_encoded_rows_total": 259.0}
READERS = {"write_body_bytes": 123586 / 73, "wal_bytes_per_write": 289488 / 73,
           "watch_bytes_per_event": 312533 / 144,
           "encode_us_per_row": 1e6 * 0.133351 / 259}


def reader(name):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}")


@pytest.mark.parametrize("name", sorted(READERS))
def test_ratio_reader(name):
    assert reader(name).read({"registry": RISE}) == pytest.approx(READERS[name])
    # the parent's program has no such counter; an idle window no divisor
    assert reader(name).read({"registry": {}}) is None
    idle = {k: (v if k.endswith(("bytes_total", "_seconds")) else 0.0)
            for k, v in RISE.items()}
    assert reader(name).read({"registry": idle}) is None


def test_slot_fill_reads_the_gauge_as_it_stands(monkeypatch):
    from benchmarks import deploy

    monkeypatch.setattr(deploy, "registry_snapshot",
                        lambda: {"encoder_slot_vocab_max": 35.0})
    assert reader("slot_fill_pct").read({"fleet": {"S": 64}}) == pytest.approx(
        100 * 35 / 64)
    assert reader("slot_fill_pct").read({"fleet": None}) is None
    monkeypatch.setattr(deploy, "registry_snapshot", lambda: {})
    assert reader("slot_fill_pct").read({"fleet": {"S": 64}}) is None


def test_manifest_and_configuration():
    manifest, cell, config, traffic = runmod.resolve(CELL)
    assert cell["chips"] == 1 and cell["config"] == "k8s-load-1k"
    entry = {c["name"]: c for c in manifest["configs"]}["k8s-load-1k"]
    assert entry["reduced"] == config["reduced"] == ["resident_per_cluster"]
    assert entry["source"] == config["source"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert config["shape"] == "k8s_deployment" and shape.AGENT == "DeploymentReady"
    assert config["logical_clusters"] == 1000
    assert config["resources_to_sync"] == [shape.RESOURCE]
    assert set(config["guarantees"]) >= {"durability", "read_your_writes",
                                         "downsync", "upsync"}
    assert traffic["kind"] == "open_loop" and traffic["senders"] == 96
    assert traffic["mix"] == {"update": 0.9, "create": 0.05, "delete": 0.05}
    assert traffic["rate_per_s"] % 10 == 0 and traffic["rate_source"]
    # every per-layer metric the first open-loop cell reports, and the new five
    names = set(runmod.metric_names(manifest, "per_layer", CELL))
    assert names == set(runmod.metric_names(manifest, "per_layer",
                                            "syncer-1k.steady"))
    assert names >= set(READERS) | {"slot_fill_pct", "ack_p50_ms",
                                    "converge_p90_ms", "converge_p95_ms",
                                    "loadgen_late_p95_ms"}
    assert set(runmod.metric_names(manifest, "end_to_end", CELL)) == {
        "converge_p50_ms", "setup_s"}
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        assert len(f.read()) < 64 * 1024


def test_rehearsal_block_is_laid_over_the_configuration():
    _m, _c, config, traffic = runmod.resolve(CELL, rehearse=True)
    full = json.load(open(os.path.join(REPO, "benchmarks", "configs",
                                       "k8s-load-1k.json")))
    assert full["rehearsal"] == {"logical_clusters": 6,
                                 "resident_per_cluster": 5, "warm_bursts": [4]}
    assert (config["logical_clusters"], config["resident_per_cluster"],
            config["warm_bursts"]) == (6, 5, [4])
    assert config["shape"] == full["shape"] and traffic["rate_per_s"] == 20


@pytest.mark.parametrize("trace", (0, 1))
def test_sound_run_is_correct(trace):
    rc, lines, err = run("--platform", "cpu", "--rehearse", cell=CELL,
                         trace=trace, seed=2**31 + 31 + trace)
    assert rc == 0, err[-2000:]
    r = result(lines)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["platform"] == "cpu"
    checks = [l.split("] ", 1)[-1] for l in lines if "] check " in l]
    assert len(checks) == 8 and all(c.endswith(" ok") for c in checks)
    if trace:
        m = r["metrics"]
        assert set(READERS) | {"slot_fill_pct", "tick_host_ms"} <= set(m)
        assert 1500 < m["write_body_bytes"]["value"] < 1800
        assert m["watch_bytes_per_event"]["value"] > 1800
        assert m["wal_bytes_per_write"]["value"] > m["write_body_bytes"]["value"]
        assert m["slot_fill_pct"]["value"] == pytest.approx(100 * 35 / 64)
    else:
        assert set(r["metrics"]) == {"setup_s", "converge_p50_ms"}


def test_a_value_corrupted_inside_a_list_is_not_correct():
    rc, lines, err = run("--platform", "cpu", "--rehearse", "--control",
                         "corrupt-downstream", cell=CELL)
    assert rc == 0, err[-2000:]
    r = result(lines)
    assert r["correct"] is False
    assert r["failed"] == 0  # every write converged: only whole specs tell
    bad = [l for l in lines if "downstream_mismatches" in l and "FAILED" in l]
    assert bad and "['template']" in bad[0]
    assert any("rest_readback_mismatches=0 " in l for l in lines)


def test_dropped_downstream_write_is_not_correct():
    rc, lines, err = run("--platform", "cpu", "--rehearse", "--control",
                         "drop-downstream", cell=CELL)
    assert rc == 0, err[-2000:]
    r = result(lines)
    assert r["correct"] is False and r["failed"] > 0
