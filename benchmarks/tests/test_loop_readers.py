"""The six readers of the serving loop's ledger (PR 42), each on a
hand-made ``ctx``: the rises of the ledger's counters as
``deploy.rise`` yields them, None on the parent's registry, the printed
tables; the device's idle time under ``kcp.loop.select`` on hand-made
planes and on the recorded TPU trace (which holds no such annotation:
None)."""

import importlib
import os
import re
import types

import pytest

from benchmarks.layer_metrics import idle_host_waiting_pct

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def reader(name):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}")


def ledger_ctx(**over):
    """A 50 s window: 30 s busy (24 on the CPU; 18 + 6 named), 20 idle."""
    reg = {"server_loop_busy_seconds_total": 30.0,
           "server_loop_idle_seconds_total": 20.0,
           "server_loop_cpu_seconds_total": 24.0,
           "server_loop_passes_total": 300000.0,
           "server_loop_long_passes_total": 4.0,
           "server_loop_long_pass_seconds_total": 1.0,
           "server_loop_section_leaks_total": 0.0,
           "server_loop_self_seconds_kcp_store_fanout": 18.0,
           "server_loop_self_seconds_kcp_tick_encode": 6.0,
           "server_loop_lag_seconds": 3.0,  # a histogram's sum: no slot
           "request_admission_seconds_count": 12000.0}
    reg.update(over)
    return {"registry": reg, "seconds": 50.0, "window": (1000.0, 1050.0)}


PARENT = {"registry": {"server_loop_lag_seconds": 3.0,
                       "server_loop_lag_seconds_count": 1000.0,
                       "request_admission_seconds_count": 12000.0},
          "seconds": 50.0, "window": (1000.0, 1050.0), "trace": None}

VALUES = {"loop_busy_pct": 60.0,  # 30 of 50
          "loop_ms_per_write": 2.5,  # 30 s over 12,000 writes
          "loop_offcpu_pct": 20.0,  # 6 of 30
          "loop_unnamed_pct": 20.0,  # 30 - 24 named, of 30
          "loop_stalled_pct": 2.0}  # 1 s of 50


@pytest.mark.parametrize("name", sorted(VALUES))
def test_ledger_reader(name, capsys):
    assert reader(name).read(ledger_ctx()) == pytest.approx(VALUES[name])
    assert capsys.readouterr().out.startswith("[layer] loop")
    # the parent's program has no ledger: nothing to read, nothing raised
    assert reader(name).read(PARENT) is None
    # a window in which the loop made no pass
    assert reader(name).read(ledger_ctx(
        server_loop_busy_seconds_total=0.0,
        server_loop_passes_total=0.0)) is None


def test_ms_per_write_needs_writes_and_prints_sections_largest_first(capsys):
    assert reader("loop_ms_per_write").read(
        ledger_ctx(request_admission_seconds_count=0.0)) is None
    capsys.readouterr()
    reader("loop_ms_per_write").read(ledger_ctx())
    out = capsys.readouterr().out
    table = re.findall(r"(\w+) (\d+\.\d+) (\d+\.\d)%", out.split("): ", 1)[1])
    assert table == [("kcp_store_fanout", "1.5000", "60.0"),
                     ("kcp_tick_encode", "0.5000", "20.0"),
                     ("unnamed", "0.5000", "20.0")]
    # self times and the unnamed rest add up to the value
    assert sum(float(ms) for _, ms, _ in table) == pytest.approx(2.5)


def test_busy_prints_the_ledgers_other_slots(capsys):
    reader("loop_busy_pct").read(ledger_ctx())
    out = capsys.readouterr().out
    assert "busy 30.0000 s + idle 20.0000 s = 50.0000 s of a window of 50 s" in out
    assert "300000 passes, mean pass 100.0 us" in out and "cpu 24.0000 s" in out


def test_stalled_prints_the_rings_passes_of_the_window(capsys, monkeypatch):
    from kcp_tpu.obs import runtime

    ring = [{"start": 990.0, "wall_s": 0.3, "sections": []},
            {"start": 1012.5, "wall_s": 0.25,
             "sections": [["kcp.wal.sync", 0.2], ["kcp.gc", 0.04]]},
            {"start": 1050.0, "wall_s": 0.09, "sections": []}]
    monkeypatch.setattr(runtime, "long_passes", lambda: ring)
    assert reader("loop_stalled_pct").read(ledger_ctx()) == pytest.approx(2.0)
    out = capsys.readouterr().out
    assert "4 passes of 50 ms or more, 1.0000 s of the window; 1 of them" in out
    assert "(12.5, 0.25, [('kcp.wal.sync', 0.2), ('kcp.gc', 0.04)])" in out
    # a program that keeps no ring (the parent's obs.runtime): no pass
    monkeypatch.delattr(runtime, "long_passes")
    assert reader("loop_stalled_pct").ring(ledger_ctx()) == []


# ---- idle_host_waiting_pct: hand-made planes, ns


def plane(name, **lines):
    def ev(n, a, b):
        return types.SimpleNamespace(name=n, start_ns=a, duration_ns=b - a)
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln.replace("_", " "),
                              events=[ev(*e) for e in evs])
        for ln, evs in lines.items()])


def fake_profile(monkeypatch, planes):
    import jax.profiler

    monkeypatch.setattr(
        jax.profiler.ProfileData, "from_file",
        staticmethod(lambda path: types.SimpleNamespace(planes=planes)))


def test_minus():
    minus = idle_host_waiting_pct.minus
    assert minus([(0, 10), (20, 30), (40, 50)],
                 [(2, 3), (5, 25), (45, 60)]) == [(0, 2), (3, 5), (25, 30), (40, 45)]
    assert minus([(0, 10)], []) == [(0, 10)]
    assert minus([(0, 10)], [(0, 10)]) == []
    assert minus([(0, 10)], [(-5, 2), (8, 12)]) == [(2, 8)]


def test_waiting_is_idle_under_select_and_nothing_else(monkeypatch):
    from benchmarks import reduce_trace

    op_line = sorted(reduce_trace.OP_LINES)[0].replace(" ", "_")
    # the device runs 0-100 and 1100-1200: 1,000 ns idle between them
    device = plane("/device:TPU:0", **{op_line: [("step", 0, 100),
                                                 ("step", 1100, 1200)]})
    host = plane("/host:CPU", loop=[
        ("kcp.loop.select", 50, 400),  # 300 of it in the gap ...
        ("kcp.tick", 400, 600),
        ("kcp.loop.select", 700, 1000),
        ("kcp.gc", 900, 1000)],  # ... 100 of this select under a gc
        other=[("kcp.remote.call", 350, 420),  # another thread's, over select
               ("not.ours", 0, 1200)])
    fake_profile(monkeypatch, [device, host])
    idle, alone, other = idle_host_waiting_pct.waiting("x")
    assert idle == pytest.approx(1000e-9)
    # select alone: 100-350 and 700-900 = 450; named work: 350-600, 900-1000
    assert alone == pytest.approx(450e-9)
    assert other == pytest.approx(350e-9)
    # a program without the ledger annotates no select: nothing to read
    fake_profile(monkeypatch, [device, plane("/host:CPU", loop=[
        ("kcp.tick", 400, 600)])])
    assert idle_host_waiting_pct.waiting("x") is None
    # no device plane with operations (a rehearsal on the CPU)
    fake_profile(monkeypatch, [host])
    assert idle_host_waiting_pct.waiting("x") is None


def test_waiting_reader_on_the_recorded_trace_and_without_one(monkeypatch,
                                                               tmp_path):
    # the recorded TPU trace holds no kcp.loop.select annotation
    assert idle_host_waiting_pct.waiting(
        os.path.join(DATA, "tiny_tpu.xplane.pb")) is None
    assert idle_host_waiting_pct.read(PARENT) is None  # an untraced run
    # a traced run whose slice is gone or empty
    monkeypatch.setattr(idle_host_waiting_pct, "TRACE_DIR", str(tmp_path))
    assert idle_host_waiting_pct.read(dict(PARENT, trace={"busy_s": 1})) is None


def test_waiting_reader_prints_the_three_parts(monkeypatch, capsys, tmp_path):
    monkeypatch.setattr(idle_host_waiting_pct, "TRACE_DIR", str(tmp_path))
    monkeypatch.setattr(idle_host_waiting_pct.reduce_trace, "find_xplane",
                        lambda d: "x")
    monkeypatch.setattr(idle_host_waiting_pct, "waiting",
                        lambda path: (4.0, 1.0, 2.5))
    assert idle_host_waiting_pct.read(
        dict(PARENT, trace={"busy_s": 1})) == pytest.approx(25.0)
    out = capsys.readouterr().out
    assert "1.0000 s of 4.0000 s idle lie under kcp.loop.select" in out
    assert "2.5000 s under another" in out and "0.5000 s, under unnamed" in out


def test_the_manifest_names_the_six_readers_in_every_cell():
    import json

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as fh:
        manifest = json.load(fh)
    cells = [w["name"] for w in manifest["workloads"]]
    mine = [m for m in manifest["per_layer"]
            if m["name"] in set(VALUES) | {"idle_host_waiting_pct"}]
    assert [m["name"] for m in mine] == [
        "loop_busy_pct", "loop_ms_per_write", "loop_offcpu_pct",
        "loop_unnamed_pct", "loop_stalled_pct", "idle_host_waiting_pct"]
    assert mine == manifest["per_layer"][-6:]
    for m in mine:
        assert m["layer"] == "Python runtime of the server process"
        assert m["moves"] == "converge_p50_ms" and m["workloads"] == cells
        reader(m["name"])  # found by name
