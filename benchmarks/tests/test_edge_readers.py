"""The nine readers of PR 56 on hand-made ``ctx``: the two histogram
means, the join of the generator's stamps against the program's edge log
(``benchmarks/edge_join.py``: the rule, the horizon, the None cases) and
the handle table's three shares (``benchmarks/handle_table.py``);
nothing on a program without their source (the parent); the manifest
names all nine — found by NAME, wherever later PRs leave them."""

import importlib
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NINE = ["syncer-1k.steady", "splitter-125x8.rollout", "k8s-load-1k.churn",
        "syncer-1k.burst", "k8s-rolling-1k.churn", "frontend-1k.steady",
        "mesh4-1k.steady", "syncer-churn-1k.flood",
        "k8s-load-read-1k.read-mostly"]
TEN = NINE[:6] + ["mapper-1k-50k.cold"] + NINE[6:]


def reader(name):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}").read


# ------------------------------------------------------------ histograms


def test_the_two_means_read_their_histograms_or_nothing(capsys):
    reg = {"convergence_ingress_seconds": 0.6,
           "convergence_ingress_seconds_count": 1200.0,
           "http_ingress_wake_seconds": 0.3,
           "http_ingress_wake_seconds_count": 3000.0}
    assert reader("conv_ingress_ms")({"registry": reg}) == pytest.approx(0.5)
    assert reader("ingress_wake_ms")({"registry": reg}) == pytest.approx(0.1)
    assert capsys.readouterr().out.count("[layer] ") == 2
    parent = {"convergence_write_seconds": 1.0,
              "convergence_write_seconds_count": 1200.0}
    for name in ("conv_ingress_ms", "ingress_wake_ms"):
        assert reader(name)({"registry": parent}) is None


# ------------------------------------------------------------------ join


def kept(name: str) -> bool:
    return name.startswith("k")


def op(i, name, *, kind="update", due=None, late=0.001, ack=0.004,
       seen=0.020, aux=False):
    due = 100.0 + i * 0.01 if due is None else due
    sent = due + late
    return {"kind": kind, "key": ["t1", name], "due": due, "sent": sent,
            "acked": None if ack is None else sent + ack,
            "seen": None if seen is None else due + seen, "aux": aux}


def records(o, *, wire_in=0.0005, ingress=0.0002, out=0.0003, frame=0.002):
    """What the program logs for operation ``o``: its request, the spec
    echo's frame and the status' frame."""
    rx = o["sent"] + wire_in
    t0 = rx + ingress
    recs = [("req", "t1", o["key"][1], rx, t0, o["acked"] - out)]
    if o["seen"] is not None:
        recs.append(("frame", "t1", o["key"][1], t0 + 0.001, t0 + 0.0015))
        recs.append(("frame", "t1", o["key"][1], o["seen"] - frame - 0.001,
                     o["seen"] - frame))
    return recs


def join_ctx(n=300, **over):
    ops = [op(i, f"k{i}") for i in range(n)]
    ops += [op(n + i, f"x{i}") for i in range(50)]  # keys the log drops
    ops += [op(n + 60, "k-del", kind="delete", seen=None),
            op(n + 61, "k-aux", aux=True),
            op(n + 62, "k-refused", ack=None, seen=None)]
    log = [("frame", "t0", "k-old", 1.0, 2.0)]  # the log's oldest record
    for o in ops[:n]:
        log += records(o)
    # the same key written again later: outside [sent, acked] and after seen
    log.append(("req", "t1", "k0", 900.0, 900.1, 900.2))
    log.append(("frame", "t1", "k0", 900.3, 900.4))
    reg = {f"convergence_{p}_seconds": 0.3 for p in (
        "write", "propagate", "stage", "tick", "patch", "downstream",
        "upstatus", "observe")}
    reg.update({k + "_count": 300.0 for k in list(reg)})
    ctx = {"ops": ops, "edges": log, "edge_kept": kept, "registry": reg}
    ctx.update(over)
    return ctx


def test_the_join_reads_the_four_intervals(capsys):
    ctx = join_ctx()
    assert reader("wire_in_p50_ms")(ctx) == pytest.approx(0.5)
    assert reader("ack_out_p50_ms")(ctx) == pytest.approx(0.3)
    assert reader("frame_out_p50_ms")(ctx) == pytest.approx(2.0)
    # (1 + 0.5 + 0.2 + 2) ms of a mean due->seen of 20 ms
    assert reader("converge_edges_pct")(ctx) == pytest.approx(18.5)
    out = capsys.readouterr().out
    assert "300 of 300 operations of kept keys found their record" in out
    # the four means, the eight phase means (8 x 1 ms) and the rest
    assert "the eight phase means of the window" in out
    assert "= 8.0000 ms: 40.00%; left of a hundred 41.50%" in out
    # the join was made once for the four readers
    assert ctx["_edge_join"]["records"] == len(ctx["edges"])


def test_a_retried_request_gives_its_first_rx_and_its_last_t_out():
    ctx = join_ctx()
    o = ctx["ops"][0]
    first, second = o["sent"] + 0.0001, o["sent"] + 0.002
    ctx["edges"] = [r for r in ctx["edges"] if r[2] != "k0"] + [
        ("req", "t1", "k0", first, first + 0.0001, first + 0.0005),
        ("req", "t1", "k0", second, second + 0.0001, o["acked"] - 0.0001)]
    from benchmarks import edge_join

    row = edge_join.joined(ctx)["ops"][0]
    assert row["rx"] == first and row["t_out"] == o["acked"] - 0.0001
    assert "t_handed" not in row  # its frames went with the filter


def test_the_join_says_why_it_reads_nothing(capsys):
    # too few operations
    few = join_ctx(n=150)
    for name in ("wire_in_p50_ms", "ack_out_p50_ms", "frame_out_p50_ms",
                 "converge_edges_pct"):
        assert reader(name)(few) is None
    assert "under 200 operations" in capsys.readouterr().out
    # enough operations, too few of them found: a fifth of the requests
    # and of the status frames are not in the log
    holes = join_ctx()
    lost = {f"k{i}" for i in range(0, 300, 5)}
    holes["edges"] = [r for r in holes["edges"] if r[2] not in lost]
    for name in ("wire_in_p50_ms", "frame_out_p50_ms", "converge_edges_pct"):
        assert reader(name)(holes) is None
    assert "240 of 300 operations of kept keys" in capsys.readouterr().out
    # operations sent before the log's oldest record are beyond its
    # horizon: left out of both counts, and said
    late = join_ctx()
    late["edges"] = [("frame", "t0", "k-old", 100.9, 101.0)] + late["edges"][1:]
    assert reader("wire_in_p50_ms")(late) == pytest.approx(0.5)
    assert "200 of 200 operations" in capsys.readouterr().out
    assert late["_edge_join"]["beyond"] == 100
    # a program without the log: the parent
    import kcp_tpu.obs as obs

    parent = join_ctx()
    del parent["edges"]
    saved = obs.edges
    try:
        del obs.edges
        assert reader("wire_in_p50_ms")(parent) is None
        assert reader("converge_edges_pct")(parent) is None
    finally:
        obs.edges = saved


def test_the_join_reads_the_programs_own_log():
    """No ``ctx["edges"]``: the readers ask the program, and keep the
    keys its rule keeps."""
    from kcp_tpu import obs
    from kcp_tpu.obs import trace

    names = [f"own-{i}" for i in range(4000)
             if obs.edge_kept(f"own-{i}")][:250]
    ops = [op(i, n) for i, n in enumerate(names)]
    ops += [op(999, next(f"own-{i}" for i in range(4000)
                         if not obs.edge_kept(f"own-{i}")))]
    trace._EDGES.clear()
    try:
        obs.edge_append(("frame", "t0", "own-old", 1.0, 2.0))
        for o in ops[:-1]:
            for rec in records(o):
                obs.edge_append(rec)
        ctx = {"ops": ops, "registry": {}}
        assert reader("wire_in_p50_ms")(ctx) == pytest.approx(0.5)
        assert len(ctx["_edge_join"]["ops"]) == 250
    finally:
        trace._EDGES.clear()


# ---------------------------------------------------------- handle table


def handle_ctx(**over):
    """A slice of 4 s busy: 3.6 s inside handles, 1.0 s of it unnamed."""
    reg = {
        "server_loop_handle_busy_seconds_total": 4.0,
        "server_loop_handle_seconds__SelectorSocketTransport__read_ready": 0.4,
        "server_loop_handle_unnamed_seconds__SelectorSocketTransport__read_ready": 0.1,
        "server_loop_handle_seconds__UnixSelectorEventLoop__read_from_self": 0.2,
        "server_loop_handle_unnamed_seconds__UnixSelectorEventLoop__read_from_self": 0.1,
        "server_loop_handle_seconds_task_HttpServer__serve": 2.0,
        "server_loop_handle_unnamed_seconds_task_HttpServer__serve": 0.6,
        "server_loop_handle_seconds_task_FusedCore__tick_loop": 0.8,
        "server_loop_handle_unnamed_seconds_task_FusedCore__tick_loop": 0.1,
        "server_loop_handle_seconds_RuntimeProbes__beat": 0.2,
        "server_loop_handle_unnamed_seconds_RuntimeProbes__beat": 0.1,
        "server_loop_busy_seconds_total": 40.0,
        "request_admission_seconds_count": 12000.0}
    reg.update(over)
    return {"registry": reg}


def test_the_handle_tables_three_shares(capsys):
    ctx = handle_ctx()
    assert reader("loop_read_handles_pct")(ctx) == pytest.approx(100 * 0.4 / 3.6)
    assert reader("loop_wake_handles_pct")(ctx) == pytest.approx(100 * 0.2 / 3.6)
    assert reader("loop_unnamed_task_pct")(ctx) == pytest.approx(70.0)
    out = capsys.readouterr().out
    assert "coverage 90.0%" in out and "unnamed 1.0000 s (25.0% of busy)" in out
    # a kind's ms a write: its share of the slice's busy seconds times
    # the window's 40 s / 12,000 writes
    assert "task_HttpServer__serve 1.6667 | 0.5000" in out


@pytest.mark.parametrize("name", ["loop_read_handles_pct",
                                  "loop_wake_handles_pct",
                                  "loop_unnamed_task_pct"])
def test_no_table_no_reading(name):
    # the parent's registry; an untraced window (no slice: no rise)
    assert reader(name)({"registry": {
        "server_loop_busy_seconds_total": 40.0}}) is None
    flat = {k: 0.0 for k in handle_ctx()["registry"]}
    assert reader(name)({"registry": flat}) is None


# -------------------------------------------------------------- manifest


def test_the_manifest_names_the_nine():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    cells = [w["name"] for w in manifest["workloads"]]
    assert [c for c in cells if c in TEN] == TEN
    want = {
        "conv_ingress_ms": ("ms", "program_counter", "REST write path", NINE),
        "ingress_wake_ms": ("ms", "program_counter", "REST write path", NINE),
        "wire_in_p50_ms": ("ms", "host_clock", "end to end, accounted", NINE),
        "ack_out_p50_ms": ("ms", "host_clock", "end to end, accounted", NINE),
        "frame_out_p50_ms": ("ms", "host_clock", "end to end, accounted",
                             NINE),
        "converge_edges_pct": ("%", "host_clock", "end to end, accounted",
                               NINE),
        "loop_read_handles_pct": ("%", "program_counter",
                                  "Python runtime of the server process", TEN),
        "loop_wake_handles_pct": ("%", "program_counter",
                                  "Python runtime of the server process", TEN),
        "loop_unnamed_task_pct": ("%", "program_counter",
                                  "Python runtime of the server process", TEN),
    }
    for name, (unit, source, layer, workloads) in want.items():
        assert per_layer[name] == {
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": layer, "moves": "converge_p50_ms",
            "workloads": workloads}, name
        importlib.import_module(f"benchmarks.layer_metrics.{name}")
    # the nine cells are those that report ack_p50_ms, the layers are
    # the accepted names
    assert per_layer["ack_p50_ms"]["workloads"] == NINE
    assert per_layer["converge_accounted_pct"]["layer"] == \
        "end to end, accounted"
    assert per_layer["loop_unnamed_pct"]["layer"] == \
        "Python runtime of the server process"
