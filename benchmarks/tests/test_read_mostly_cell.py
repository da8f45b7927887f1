"""The ``k8s-load-read-1k.read-mostly`` cell (PR 54): its configuration
and manifest entries are the ones the issue names (found by NAME,
wherever later PRs leave them in their lists), its writes are
``load-churn.json``'s value for value and its write schedule is
``open_loop``'s for the same seed, its read schedule is a pure function
of the seed (digests for two seeds), every new reader gives a number on
a toy context of the change and nothing on one without the stamps or the
counters (the parent), and whole runs tiny on the CPU come out correct,
traced and untraced, with every read judged."""

import hashlib
import importlib
import json
import os
from collections import Counter

import pytest

from benchmarks import k8s_load_read_reference as ref
from benchmarks import read_deploy, run as runmod
from benchmarks.generators import open_loop, read_mostly
from test_rehearsal import result
from test_rehearsal import run as run_cell

CELL = "k8s-load-read-1k.read-mostly"
CONFIG = "k8s-load-read-1k"
CONTROL = "k8s-load-1k.churn"
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MIX = {"get": 0.70, "list_selector": 0.15, "list_table": 0.10,
       "relist_watch": 0.04, "list_all_paged": 0.01}
HOST = {"read_get_p50_ms": ("ms", "lower", "read path"),
        "read_get_p99_ms": ("ms", "lower", "read path"),
        "read_list_p99_ms": ("ms", "lower", "read path"),
        "read_list_all_p99_ms": ("ms", "lower", "read path"),
        "watch_open_p99_ms": ("ms", "lower", "read path"),
        "reads_per_s": ("ops/s", "higher", "read path"),
        "read_late_p95_ms": ("ms", "lower", "load generator"),
        "read_undetermined_pct": ("%", "lower", "read path")}
COUNTED = {"loop_ms_per_read": ("ms", "lower", "read path"),
           "read_bytes_per_request": ("bytes", "lower", "read path"),
           "list_cache_hit_pct": ("%", "higher", "read path"),
           "list_scanned_per_returned": ("count", "lower", "read path"),
           "plan_watches_per_flush": ("count", "lower",
                                      "watch fan-out and informer")}
SCHEDULE_DIGESTS = {
    1: "a853a77d767acc230f3164b0a314b36eda6e0c2877a7d91c86c8d299c2f7678b",
    2**31 + 17: "52210798cdebd9e9ef123dec83286e75dbc0d22e57d738c1dd8c119ebdeaea52",
}


def reader(name):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}")


def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


# ------------------------------------------------------------- the entries


def test_the_cell_and_its_configuration_are_the_ones_the_issue_names():
    m, cell, config, traffic = runmod.resolve(CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "read-mostly",
                    "chips": 1, "why": cell["why"]}
    assert 0 < len(cell["why"]) <= 200
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"] == "benchmarks/configs/k8s-load-read-1k.json"
    assert entry["reduced"] == config["reduced"] == ["resident_per_cluster"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    for part in ("perf-tests clusterloader2/testing/load",
                 "api_call_latency.md", "YCSB workloads/workloadb", "KEP-365"):
        assert part in entry["source"], part
    assert [c["file"] for c in m["configs"]].count(entry["file"]) == 1
    # k8s-load-1k key for key, but the shape and the topology
    _m, _c, control, churn = runmod.resolve(CONTROL)
    for key in ("logical_clusters", "locations_per_cluster", "object",
                "resources_to_sync", "resident_per_cluster", "warm_bursts",
                "server", "rehearsal", "reduced"):
        assert config[key] == control[key], key
    assert config["shape"] == "k8s_load_read"
    assert config["deployment"] == "benchmarks.read_deploy"
    assert "deployment" not in control and config["chips"].startswith("1:")
    for name, text in control["guarantees"].items():
        assert config["guarantees"][name] == text
    new = set(config["guarantees"]) - set(control["guarantees"])
    assert new == {"get_after_ack", "list_snapshot", "paged_list_snapshot",
                   "list_then_watch", "table", "held_by"}
    held = config["guarantees"]["held_by"]
    assert set(held) == new - {"held_by"} | {"why_these_checks"}
    assert "converged_for_wrong_values" in held["get_after_ack"]
    for name in new - {"held_by"}:
        assert "agent_errors" in held[name], name
    assert set(control["assumed"]) < set(config["assumed"])
    for key in ("read_shares", "watch_hold_s", "limit", "read_to_write"):
        assert key in config["assumed"], key
    assert "690 KB" in config["reduced_why"]["resident_per_cluster"]
    # the traffic: load-churn's writes value for value, then the reads
    for key in set(churn) - {"kind", "rate_source", "rehearsal"}:
        assert traffic[key] == churn[key], key
    for key, value in churn["rehearsal"].items():
        assert traffic["rehearsal"][key] == value, key
    assert traffic["kind"] == "read_mostly" and traffic["read_mix"] == MIX
    assert traffic["watch_hold_s"] == 20 and traffic["limit"] == 500
    assert 0 < traffic["read_rate_per_s"] <= 2280
    assert traffic["read_rate_per_s"] % 50 == 0
    assert "sweep" in traffic["rate_source"]


def test_the_metrics_the_cell_reports():
    m = manifest()
    assert runmod.metric_names(m, "end_to_end", CELL) == ["converge_p50_ms",
                                                          "setup_s"]
    mine = set(runmod.metric_names(m, "per_layer", CELL))
    control = set(runmod.metric_names(m, "per_layer", CONTROL))
    assert mine == control | set(HOST) | set(COUNTED)
    assert not control & (set(HOST) | set(COUNTED))
    by_name = {p["name"]: p for p in m["per_layer"]}
    for name in control:  # appended to each list, nothing else changed
        assert by_name[name]["workloads"].index(CELL) >= 1, name
        assert callable(reader(name).read)
    for table, source in ((HOST, "host_clock"), (COUNTED, "program_counter")):
        for name, (unit, better, layer) in table.items():
            assert by_name[name] == {
                "name": name, "unit": unit, "better": better,
                "source": source, "layer": layer, "moves": "converge_p50_ms",
                "workloads": [CELL]}, name
            assert callable(reader(name).read)


# ----------------------------------------------------------- the schedules


@pytest.mark.parametrize("seed", sorted(SCHEDULE_DIGESTS))
def test_the_read_schedule_is_a_pure_function_of_the_seed(seed):
    s = read_mostly.read_schedule(seed, 500, MIX, 58.0, 1000)
    assert s == read_mostly.read_schedule(seed, 500, dict(MIX), 58.0, 1000)
    assert hashlib.sha256(repr(s).encode()).hexdigest() == SCHEDULE_DIGESTS[seed]
    assert len(s) == 500 * 58
    verbs = Counter(v for _d, v, _t, _p in s)
    assert verbs == {v: round(share * len(s)) for v, share in MIX.items()}
    dues = [d for d, *_ in s]
    assert dues == sorted(dues) and 0.0 <= dues[0] and dues[-1] <= 58.0
    assert {t for _d, _v, t, _p in s} <= set(range(1000))


def test_every_reader_takes_its_share_and_the_walkers_only_walks():
    s = read_mostly.read_schedule(3, 500, MIX, 58.0, 1000)
    parts = [read_mostly.share(s, i, 5, 2) for i in range(5)]
    assert sorted(x for p in parts for x in p) == sorted(s)
    for p in parts[:3]:
        assert p and all(v != "list_all_paged" for _d, v, _t, _p in p)
    for p in parts[3:]:
        assert p and all(v == "list_all_paged" for _d, v, _t, _p in p)
    assert read_mostly.share(s, 0, 1, 0) == s  # one process takes it all
    # the walkers follow from the walk rate: each under half busy, at
    # least one, never every process
    tr = {"read_rate_per_s": 500, "read_mix": MIX, "read_procs": 5,
          "warmup_s": 5, "cooldown_s": 2}
    assert read_mostly.walkers(tr) == 2  # 5 walks/s x 0.2 s, half busy
    assert read_mostly.walkers(dict(tr, read_procs=1)) == 0
    assert read_mostly.walkers(dict(tr, read_procs=2)) == 1
    assert read_mostly.walkers(dict(tr, read_rate_per_s=20)) == 1
    assert read_mostly.walkers(dict(tr, read_mix={"get": 1.0})) == 0
    assert read_mostly.planned(tr, 51.0) == len(s)
    with pytest.raises(ValueError):
        read_mostly.read_schedule(1, 10, {"patch": 1.0}, 5.0, 3)


def test_a_seed_offers_this_cell_the_writes_it_offers_the_control():
    """``read_mostly.prepare`` hands the writes to ``open_loop.prepare``
    and ``open_loop.run``: same function, same salts, same parameters."""
    _m, _c, _cfg, traffic = runmod.resolve(CELL)
    _m, _c, _cfg, churn = runmod.resolve(CONTROL)
    seed, length = 2**31 + 23, 58.0
    mine = open_loop.schedule(seed, traffic["rate_per_s"], traffic["mix"],
                              length, 1000, traffic.get("burst"))
    theirs = open_loop.schedule(seed, churn["rate_per_s"], churn["mix"],
                                length, 1000, churn.get("burst"))
    assert mine == theirs and len(mine) == 120 * 58
    src = open(read_mostly.__file__).read()
    assert "open_loop.prepare(session, spec)" in src
    assert "open_loop.run(probing, plan, spec, t_start)" in src


def test_which_writes_are_probed_is_a_pure_function_of_what_they_write():
    body = {"metadata": {"annotations": {
        "deployment.kubernetes.io/revision": "7"}}}
    picks = [read_mostly.sampled(f"t{i:04d}", "deployment-000", body, 10)
             for i in range(2000)]
    assert 150 < sum(picks) < 250
    assert picks == [read_mostly.sampled(f"t{i:04d}", "deployment-000",
                                         body, 10) for i in range(2000)]
    assert not read_mostly.sampled("t0000", "x", None, 10)  # a delete
    assert not read_mostly.sampled("t0000", "x", body, 0)


# -------------------------------------------------------------- the readers


def stamp(verb, due, sent_late=0.0005, took=0.002, **more):
    return dict({"verb": verb, "scope": read_mostly.SCOPES[verb],
                 "tenant": "t0001", "name": None, "due": due,
                 "sent": due + sent_late, "done": due + sent_late + took,
                 "bytes": 2100, "items": 1, "pages": 0, "status": 200,
                 "restarts": 0, "error": None, "undetermined": False}, **more)


def toy_reads():
    reads = read_deploy.Reads()
    for i in range(100):
        reads.append(stamp("get", 10.0 + i * 0.1, took=0.001 + i * 1e-5))
    for i in range(40):
        reads.append(stamp("list_selector" if i % 2 else "list_table",
                           10.0 + i * 0.2, took=0.003, items=3, pages=1,
                           undetermined=i == 0))
    for i in range(12):
        reads.append(stamp("list_all_paged", 10.0 + i, took=0.5, pages=6,
                           items=3000, bytes=6_300_000))
        reads.append(stamp("relist_watch", 10.0 + i, took=0.003, items=3,
                           watch={"sent": 10.1 + i, "head": 10.1015 + i,
                                  "hold_end": 20.0, "closed": 20.2,
                                  "events": 4}))
    reads.append(stamp("get", 5.0))   # before the window
    reads.append(stamp("get", 30.0))  # after it
    reads.append(stamp("get", 12.0, error="ConnectionError: reset"))
    return reads


REGISTRY_OF_THE_CHANGE = {
    "server_loop_busy_seconds_total": 40.0, "server_loop_idle_seconds_total": 11.0,
    "server_loop_passes_total": 90000.0, "server_loop_cpu_seconds_total": 39.0,
    "server_loop_self_seconds_kcp_read_get": 1.0,
    "server_loop_self_seconds_kcp_read_list": 1.5,
    "server_loop_self_seconds_kcp_read_page": 3.0,
    "server_loop_self_seconds_kcp_read_table": 2.0,
    "server_loop_self_seconds_kcp_watch_open": 0.3,
    "server_loop_self_seconds_kcp_watch_close": 0.2,
    "server_loop_self_seconds_kcp_store_fanout": 4.0,
    "read_requests_total_get": 20000.0, "read_requests_total_list": 5000.0,
    "read_requests_total_page": 1500.0, "read_requests_total_table": 2500.0,
    "read_response_bytes_total": 2.9e9,
    "list_cache_lookups_total": 5000.0, "list_cache_hits_total": 50.0,
    "store_list_scanned_total": 2_000_000.0, "store_list_returned_total": 800000.0,
    "store_fanout_plan_rebuilds_total": 2000.0,
    "store_fanout_plan_watches_total": 3_000_000.0,
    "store_emit_seconds_count": 20000.0,
}
REGISTRY_OF_THE_PARENT = {
    k: v for k, v in REGISTRY_OF_THE_CHANGE.items()
    if not k.startswith(("read_", "list_cache_", "store_fanout_plan_",
                         "server_loop_self_seconds_kcp_read",
                         "server_loop_self_seconds_kcp_watch_"))}


def ctx(reads=None, registry=None):
    return {"window": (10.0, 22.0), "seconds": 12.0,
            "generator": {} if reads is None else {"reads": reads},
            "registry": registry if registry is not None else {}}


def test_the_host_clock_readers_read_the_stamps(capsys):
    c = ctx(toy_reads())
    assert reader("read_get_p50_ms").read(c) == pytest.approx(1.49, abs=0.02)
    assert reader("read_get_p99_ms").read(c) == pytest.approx(1.98, abs=0.02)
    assert "100 answered of 101 due" in capsys.readouterr().out
    assert reader("read_list_p99_ms").read(c) == pytest.approx(3.0)
    assert reader("read_list_all_p99_ms").read(c) == pytest.approx(500.0)
    assert reader("watch_open_p99_ms").read(c) == pytest.approx(1.5)
    assert reader("reads_per_s").read(c) == pytest.approx(164 / 12.0)
    assert "'list_all_paged': 12" in capsys.readouterr().out
    assert reader("read_late_p95_ms").read(c) == pytest.approx(0.5)
    assert reader("read_undetermined_pct").read(c) == pytest.approx(100 / 165)


@pytest.mark.parametrize("name", sorted(HOST))
def test_a_run_without_read_stamps_gives_nothing(name):
    assert reader(name).read(ctx()) is None
    assert reader(name).read(ctx(read_deploy.Reads())) is None


def test_the_counter_readers_on_the_change(capsys):
    c = ctx(registry=REGISTRY_OF_THE_CHANGE)
    assert reader("loop_ms_per_read").read(c) == pytest.approx(
        1e3 * 8.0 / 29000)
    said = capsys.readouterr().out
    assert "page 2.0000 1500" in said and "kcp_watch_open 0.3000 s" in said
    assert reader("read_bytes_per_request").read(c) == pytest.approx(1e5)
    assert reader("list_cache_hit_pct").read(c) == pytest.approx(1.0)
    assert reader("list_scanned_per_returned").read(c) == pytest.approx(2.5)
    assert reader("plan_watches_per_flush").read(c) == pytest.approx(150.0)
    assert "2000 rebuilds walked 3e+06 watches (1500.0 a rebuild)" in \
        capsys.readouterr().out


@pytest.mark.parametrize("name", sorted(set(COUNTED)
                                        - {"list_scanned_per_returned"}))
def test_the_counter_readers_on_a_program_without_the_counters(name):
    assert reader(name).read(ctx(registry=REGISTRY_OF_THE_PARENT)) is None
    assert reader(name).read(ctx(registry={})) is None


def test_list_scanned_per_returned_reads_the_parent_too():
    read = reader("list_scanned_per_returned").read
    assert read(ctx(registry=REGISTRY_OF_THE_PARENT)) == pytest.approx(2.5)
    assert read(ctx(registry={})) is None


# ---------------------------------------------------- the judge's hand-over


def test_the_handle_judges_the_files_and_counts_what_is_wrong(tmp_path):
    import random

    from benchmarks.shapes import k8s_load_read as shape

    rng = random.Random(1)
    body = shape.new("deployment-000-aa", rng, ["loc0"])
    newer = shape.mutate(body, rng)
    pop = {("t0001", "deployment-000-aa"): body}
    records = [{"kind": "update", "key": ["t0001", "deployment-000-aa"],
                "body": newer, "sent": 1.0, "acked": 1.01, "rv": 90}]

    def item(b, rv):
        return ["t0001", "default", "deployment-000-aa", rv, ref.digest(b)]

    sound = stamp("get", 2.0, name="deployment-000-aa",
                  answer={"view": item(newer, 91)})
    stale = stamp("get", 2.0, name="deployment-000-aa",
                  answer={"view": item(body, 80)})
    failed = stamp("list_selector", 2.0, error="RuntimeError: LIST answered 500",
                   answer=None)
    slow = stamp("get", 2.0, took=11.0, name="deployment-000-aa",
                 answer={"view": item(newer, 91)})
    path = tmp_path / "reads-0.jsonl"
    path.write_text("".join(json.dumps(r) + "\n"
                            for r in (sound, stale, failed, slow)))
    reads, verdict = read_deploy.judge_files(
        ref.WriteLog(pop, records), [str(path)], 10.0)
    assert repr(reads) == "<4 reads>" and "answer" not in reads[0]
    assert {k: verdict[k] for k in ("judged", "mismatches", "errors", "late")
            } == {"judged": 4, "mismatches": 1, "errors": 1, "late": 1}
    assert "no longer admitted" in verdict["examples"]["mismatches"][0]

    # the handle holds the lines against the schedule: 1/s for 4 + 1 + 1
    # seconds plans six reads, the file holds four, so two were LOST and
    # count like the mismatch, the error and the late one
    class Inner:
        traffic = {"read_rate_per_s": 1, "warmup_s": 1, "cooldown_s": 1,
                   "deadline_s": 10.0}
        seconds = 4.0

        def result(self, timeout):
            return {"records": records, "read_files": [str(path)]}

    class Dep:
        population, read_problems = pop, 0

    out = read_deploy.JudgedLoadGen(Inner(), Dep).result(1.0)
    assert out["read_verdict"]["planned"] == 6
    assert out["read_verdict"]["lost"] == 2 and Dep.read_problems == 5


# ------------------------------------------------------------- whole runs


@pytest.mark.parametrize("trace", (0, 1))
def test_a_rehearsed_run_is_correct_and_every_read_is_judged(trace):
    rc, lines, err = run_cell("--platform", "cpu", "--rehearse", cell=CELL,
                              trace=trace, seed=2**31 + 29)
    assert rc == 0, err[-2000:]
    r = result(lines)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert all(c["ok"] for c in r["checks"].values())
    judged = next(l for l in lines if l.startswith("reads: "))
    assert " 0 mismatches, 0 errors, 0 past the deadline" in judged
    assert int(judged.split()[1]) > 200
    assert any("'probed': " in l and "'probed': 0" not in l for l in lines)
    m = {k: v["value"] for k, v in r["metrics"].items()}
    if not trace:
        assert set(m) == {"converge_p50_ms", "setup_s"}
        return
    for name in set(HOST) | set(COUNTED):
        if name in ("read_list_all_p99_ms", "watch_open_p99_ms"):
            continue  # under ten samples in four seconds
        assert name in m, name
    assert m["reads_per_s"] > 40 and m["loop_ms_per_read"] > 0
    assert m["list_cache_hit_pct"] < 50 and m["read_bytes_per_request"] > 1000
    assert any("section leaks 0" in l for l in lines)
