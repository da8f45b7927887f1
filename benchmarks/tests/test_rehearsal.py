"""Drive whole runs tiny on the CPU (the harness's look for a chip lifted
by ``--platform cpu --rehearse``): a sound run of each cell comes out
correct, traced and untraced; with the timed path broken underneath
(benchmarks/controls.py) ``correct`` comes out false; with no accelerator
and no lift the run exits non-zero and prints no result."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELLS = ("syncer-1k.steady", "splitter-125x8.rollout", "syncer-1k.burst")
TOY_MANIFEST = "benchmarks/tests/data/toy_manifest.json"


def run(*extra: str, seed: int = 5, cell: str = CELLS[0], trace: int = 0):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--workload", cell, "--seed", str(seed), "--seconds", "4",
         "--trace", str(trace), *extra],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    lines = [l for l in p.stdout.splitlines() if l.strip()]
    return p.returncode, lines, p.stderr


def result(lines: list[str]) -> dict:
    return json.loads(lines[-1])


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", (0, 1))
def test_sound_run_is_correct(cell, trace):
    rc, lines, err = run("--platform", "cpu", "--rehearse", cell=cell,
                         trace=trace, seed=2**31 + 11)
    assert rc == 0, err[-2000:]
    r = result(lines)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["platform"] == "cpu"
    assert set(r) >= {"correct", "attempted", "failed", "metrics", "device"}
    if trace:
        assert r["device"]["busy_s"] > 0 and r["device"]["window_s"] > 0
        assert "tick_host_ms" in r["metrics"] and "ack_p50_ms" in r["metrics"]
    else:
        assert "setup_s" in r["metrics"] and "converge_p50_ms" in r["metrics"]
    assert any(l.split("] ", 1)[-1].startswith("check ") for l in lines)
    assert list(r)[-1] == "checks" and all(c["ok"] for c in r["checks"].values())
    assert err.rstrip().splitlines()[-1].startswith("check ")
    if trace:
        assert "rows_per_tick" in r["metrics"]
        assert ("burst_drain_p50_ms" in r["metrics"]) == cell.endswith(".burst")


@pytest.mark.parametrize("cell", CELLS)
def test_corrupted_downstream_copy_is_not_correct(cell):
    rc, lines, err = run("--platform", "cpu", "--rehearse", "--control",
                         "corrupt-downstream", cell=cell)
    assert rc == 0, err[-2000:]
    r = result(lines)
    assert r["correct"] is False
    assert r["failed"] == 0  # every write converged: only values tell
    assert any("downstream_mismatches" in l and "FAILED" in l for l in lines)


@pytest.mark.parametrize("cell", (CELLS[0],) + CELLS[2:])
def test_dropped_downstream_write_is_not_correct(cell):
    rc, lines, err = run("--platform", "cpu", "--rehearse", "--control",
                         "drop-downstream", cell=cell)
    assert rc == 0, err[-2000:]
    r = result(lines)
    assert r["correct"] is False and r["failed"] > 0
    assert r["checks"]["downstream_mismatches"]["ok"] is False


def test_a_flood_with_dropped_downstream_writes_is_not_correct():
    """``traffic/flood.json`` has no cell in BENCHMARK.json yet; the toy
    manifest drives it, sound below and broken here. A client blocks on
    its dropped create for the whole deadline, longer than this tiny
    window, so none need fall due inside it: only the stores tell."""
    rc, lines, err = run("--platform", "cpu", "--rehearse", "--manifest",
                         TOY_MANIFEST, "--control", "drop-downstream",
                         cell="toy-topology.flood")
    assert rc == 0, err[-2000:]
    r = result(lines)
    assert r["correct"] is False
    assert r["checks"]["downstream_mismatches"]["ok"] is False


@pytest.mark.parametrize("cell,said", (
    ("toy-controller.burst",
     "agents started (benchmarks.tests.toy_agents.ToyEcho)"),
    ("toy-topology.flood", "topology benchmarks.tests.toy_topology.Deployment")))
def test_a_controller_and_a_topology_in_modules_of_their_own_run(cell, said):
    """The seams a later deployment comes in by, each driven through a
    whole run by files under benchmarks/tests alone."""
    rc, lines, err = run("--platform", "cpu", "--rehearse", "--manifest",
                         TOY_MANIFEST, cell=cell, trace=1)
    assert rc == 0, err[-2000:]
    r = result(lines)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert any(said in l for l in lines)
    assert "rows_per_tick" in r["metrics"]


def test_no_accelerator_no_result():
    rc, lines, _err = run()
    assert rc != 0
    assert not any(l.startswith("{") for l in lines)
