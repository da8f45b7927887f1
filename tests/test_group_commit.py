"""Group commit: the write-path commit window.

The contract under test: grouping is a LATENCY/THROUGHPUT transform,
never a semantic one — a seeded concurrent CRUD workload produces a
byte-identical final state, byte-identical per-cluster event streams,
and a byte-identical WAL whether writes commit one record at a time
(serial, the A/B reference) or one window at a time, on BOTH durability
backends; a window that dies before its sync fails every writer with a
typed 5xx and commits NONE of its records; and a primary killed
mid-window never acknowledged a write its WAL does not carry (the
zero-acked-write-loss drill, group-commit edition).
"""

import asyncio
import importlib.util
import json
import os
import threading
import time

import pytest

from kcp_tpu import faults
from kcp_tpu.native import available as native_available
from kcp_tpu.server.rest import RestClient
from kcp_tpu.server.server import Config
from kcp_tpu.server.threaded import ServerThread
from kcp_tpu.store.store import LogicalStore
from kcp_tpu.utils.errors import ApiError, UnavailableError
from kcp_tpu.utils.trace import REGISTRY

from helpers import wait_until

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _walreplay():
    spec = importlib.util.spec_from_file_location(
        "walreplay", os.path.join(REPO, "scripts", "walreplay.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _clean_injector():
    yield
    faults.clear()


def counter(name: str) -> float:
    return REGISTRY.counter(name).value


def _cm(cluster: str, name: str, step: int) -> dict:
    return {"apiVersion": "v1", "kind": "ConfigMap",
            "metadata": {"name": name, "namespace": "default",
                         "clusterName": cluster,
                         "labels": {"step": str(step % 3)}},
            "data": {"v": str(step)}}


# ---------------------------------------------------------------------------
# differential fuzz: grouped vs serial, both backends
# ---------------------------------------------------------------------------


class _FakeUUID:
    def __init__(self, i: int):
        self.i = i

    @property
    def hex(self) -> str:
        return f"{self.i:032x}"

    def __str__(self) -> str:
        return f"00000000-0000-4000-8000-{self.i:012x}"


def _run_workload(tmp_path, backend: str, grouped: bool, monkeypatch):
    """One seeded concurrent CRUD pass; returns (state, events, wal
    bytes, replayed objects). Writers interleave identically in both
    modes (they never await durability mid-stream), and uids/timestamps
    are pinned, so any divergence is the group-commit transform leaking
    semantics."""
    import itertools

    from kcp_tpu.store import store as store_mod

    seq = itertools.count()
    monkeypatch.setattr(store_mod.uuid, "uuid4",
                        lambda: _FakeUUID(next(seq)))
    wal = str(tmp_path / f"{backend}-{'g' if grouped else 's'}.wal")
    store = LogicalStore(wal_path=wal, wal_backend=backend,
                         clock=lambda: 0.0, group_commit=grouped)
    watches = {c: store.watch("configmaps", c) for c in ("c0", "c1")}

    async def drive():
        async def writer(wi: int):
            cluster = f"c{wi % 2}"
            for step in range(12):
                name = f"w{wi}-{step % 4}"
                kind = (wi + step) % 3
                try:
                    if kind == 0:
                        store.create("configmaps", cluster,
                                     _cm(cluster, name, step))
                    elif kind == 1:
                        cur = store.get("configmaps", cluster, name,
                                        "default")
                        cur["data"] = {"v": str(step)}
                        store.update("configmaps", cluster, cur, "default")
                    else:
                        store.delete("configmaps", cluster, name, "default")
                except ApiError:
                    pass  # seeded collisions (exists/not-found) are data
                await asyncio.sleep(0)

        await asyncio.gather(*(writer(i) for i in range(6)))

    asyncio.run(drive())
    events = {
        c: [(e.type, e.name, e.rv, json.dumps(e.object, sort_keys=True))
            for e in w.drain()]
        for c, w in watches.items()
    }
    items, rv = store.list("configmaps")
    state = (rv, json.dumps(items, sort_keys=True))
    store.close()
    with open(wal, "rb") as f:
        wal_bytes = f.read()
    st = _walreplay().replay(wal)
    return state, events, wal_bytes, (st.rv, dict(st.objects))


@pytest.mark.parametrize("backend", ["json", "native"])
def test_grouped_vs_serial_differential(tmp_path, backend, monkeypatch):
    if backend == "native" and not native_available():
        pytest.skip("native library unavailable")
    serial = _run_workload(tmp_path, backend, grouped=False,
                           monkeypatch=monkeypatch)
    grouped = _run_workload(tmp_path, backend, grouped=True,
                            monkeypatch=monkeypatch)
    assert grouped[0] == serial[0], "final state diverged"
    assert grouped[1] == serial[1], "per-cluster event streams diverged"
    assert grouped[2] == serial[2], "WAL bytes diverged"
    assert grouped[3] == serial[3], "offline WAL replay diverged"


def test_backends_replay_to_the_same_store(tmp_path, monkeypatch):
    """The grouped workload's replayed object map is identical across
    the JSON-lines and native binary formats (modulo the container)."""
    if not native_available():
        pytest.skip("native library unavailable")
    j = _run_workload(tmp_path, "json", grouped=True,
                      monkeypatch=monkeypatch)
    n = _run_workload(tmp_path, "native", grouped=True,
                      monkeypatch=monkeypatch)
    assert j[0] == n[0], "final store state diverged across backends"
    assert j[3] == n[3], "replayed WAL state diverged across backends"


# ---------------------------------------------------------------------------
# window bounds
# ---------------------------------------------------------------------------


def test_window_size_bound_splits(tmp_path, monkeypatch):
    monkeypatch.setenv("KCP_COMMIT_WINDOW_MAX", "4")
    store = LogicalStore(wal_path=str(tmp_path / "b.wal"),
                         wal_backend="json")
    before = counter("store_commit_windows_total")

    async def drive():
        for i in range(10):
            store.create("configmaps", "c0", _cm("c0", f"n{i}", i))
        aw = store.commit_durable(store.resource_version)
        if aw is not None:
            await aw

    asyncio.run(drive())
    store.close()
    # 10 writes with a 4-row bound: 2 size-split windows + the tail
    assert counter("store_commit_windows_total") - before >= 3
    s2 = LogicalStore(wal_path=str(tmp_path / "b.wal"), wal_backend="json")
    assert len(s2) == 10 and s2.resource_version == 10
    s2.close()


def test_linger_window_flushes(tmp_path, monkeypatch):
    monkeypatch.setenv("KCP_COMMIT_WINDOW_US", "2000")

    async def drive(store):
        store.create("configmaps", "c0", _cm("c0", "one", 0))
        aw = store.commit_durable(store.resource_version)
        assert aw is not None
        high = await aw  # resolves at the linger-timer flush
        assert high == 1

    store = LogicalStore(wal_path=str(tmp_path / "l.wal"),
                         wal_backend="json")
    asyncio.run(drive(store))
    store.close()


def test_sync_context_stays_serial(tmp_path):
    """No running loop = nothing to drive a window flush: writes take
    the serial append path and are durable on return."""
    wal = str(tmp_path / "s.wal")
    store = LogicalStore(wal_path=wal, wal_backend="json")
    store.create("configmaps", "c0", _cm("c0", "one", 0))
    assert store.commit_durable(1) is None
    with open(wal) as f:
        assert len([ln for ln in f if ln.strip()]) == 1
    store.close()


def test_group_commit_off_is_serial(tmp_path):
    store = LogicalStore(wal_path=str(tmp_path / "o.wal"),
                         wal_backend="json", group_commit=False)

    async def drive():
        store.create("configmaps", "c0", _cm("c0", "one", 0))
        assert store.commit_durable(1) is None

    asyncio.run(drive())
    store.close()


# ---------------------------------------------------------------------------
# KCP_WAL_SYNC policy
# ---------------------------------------------------------------------------


def test_wal_sync_fsync_is_metered(tmp_path, monkeypatch):
    monkeypatch.setenv("KCP_WAL_SYNC", "fsync")
    before = counter("wal_sync_total")
    store = LogicalStore(wal_path=str(tmp_path / "f.wal"),
                         wal_backend="json")
    store.create("configmaps", "c0", _cm("c0", "one", 0))
    store.close()
    assert counter("wal_sync_total") - before >= 1


def test_wal_sync_off_still_replays(tmp_path, monkeypatch):
    monkeypatch.setenv("KCP_WAL_SYNC", "off")
    wal = str(tmp_path / "n.wal")
    store = LogicalStore(wal_path=wal, wal_backend="json")
    store.create("configmaps", "c0", _cm("c0", "one", 0))
    store.close()  # close flushes python's buffer even with sync off
    s2 = LogicalStore(wal_path=wal, wal_backend="json")
    assert len(s2) == 1
    s2.close()


def test_wal_sync_rejects_unknown_mode(tmp_path, monkeypatch):
    monkeypatch.setenv("KCP_WAL_SYNC", "sideways")
    from kcp_tpu.utils.errors import InvalidError

    with pytest.raises(InvalidError):
        LogicalStore(wal_path=str(tmp_path / "x.wal"), wal_backend="json")


# ---------------------------------------------------------------------------
# failed windows commit none (store-level determinism; the HTTP-typed
# drill lives in tests/test_faults.py alongside the other fault drills)
# ---------------------------------------------------------------------------


def test_failed_window_fails_every_writer_and_commits_none(tmp_path):
    wal = str(tmp_path / "fail.wal")
    store = LogicalStore(wal_path=wal, wal_backend="json")
    # probability-1 (not @tick): the split check at every record append
    # advances the same point's schedule, so a tick-pinned rule would be
    # consumed by an append instead of the flush
    faults.install(faults.FaultInjector(
        "store.commit_window:error=1", seed=0))
    failures: list[BaseException] = []

    async def drive():
        async def writer(i: int):
            store.create("configmaps", "c0", _cm("c0", f"w{i}", i))
            try:
                await store.commit_durable(store.resource_version)
            except UnavailableError as e:
                failures.append(e)

        await asyncio.gather(*(writer(i) for i in range(6)))

    asyncio.run(drive())
    faults.clear()
    # every writer of the window saw the typed 503; none of its records
    # reached the WAL
    assert len(failures) == 6
    with open(wal) as f:
        assert [ln for ln in f if ln.strip()] == []
    # the store recovers: the next write commits durably
    store.create("configmaps", "c0", _cm("c0", "after", 0))
    store.close()
    st = _walreplay().replay(wal)
    assert len(st.objects) == 1


# ---------------------------------------------------------------------------
# HTTP end to end: semi-sync batching + kill-mid-window
# ---------------------------------------------------------------------------


def _hammer(address: str, n_writers: int, per_writer: int,
            cluster: str = "t1") -> list[str]:
    """Concurrent HTTP writers; returns the names of ACKED creates."""
    acked: list[str] = []
    lock = threading.Lock()

    def work(wi: int) -> None:
        c = RestClient(address, cluster=cluster)
        try:
            for j in range(per_writer):
                name = f"gw{wi}-{j}"
                try:
                    c.create("configmaps", _cm(cluster, name, j))
                except Exception:
                    return  # 5xx / dead server: unacked, by definition
                with lock:
                    acked.append(name)
        finally:
            c.close()

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_writers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return acked


def test_semi_sync_window_acks_batch_over_http(tmp_path):
    """Primary + standby with group commit: concurrent writers all ack,
    the standby converges, and the commit-window + batched-ack counters
    prove the path actually grouped."""
    p = ServerThread(Config(durable=True, install_controllers=False,
                            tls=False,
                            root_dir=str(tmp_path / "p"))).start()
    s = ServerThread(Config(durable=True, install_controllers=False,
                            tls=False, role="standby", primary=p.address,
                            repl_hysteresis_s=5.0,
                            root_dir=str(tmp_path / "s"))).start()
    try:
        pc = RestClient(p.address, cluster="t1")
        pc.create("configmaps", _cm("t1", "warm", 0))
        pc.close()
        assert asyncio.run(wait_until(
            lambda: _applied_rv(s.address) >= 1, 15.0))
        win0 = counter("store_commit_windows_total")
        ack0 = counter("repl_ack_batched_total")
        acked = _hammer(p.address, n_writers=8, per_writer=6)
        assert len(acked) == 48
        assert counter("store_commit_windows_total") > win0
        # at least one window parked >1 writer on the shared standby ack
        assert counter("repl_ack_batched_total") > ack0
        # semi-sync held: the standby has every acked write
        assert asyncio.run(wait_until(
            lambda: _applied_rv(s.address) >= 49, 15.0))
    finally:
        s.stop()
        p.stop()


def _applied_rv(address: str) -> int:
    c = RestClient(address)
    try:
        return int(c._request("GET", "/replication/status")["applied_rv"])
    finally:
        c.close()


def test_kill_mid_window_loses_no_acked_write(tmp_path, monkeypatch):
    """SIGKILL-equivalent death mid-storm with group commit + fsync:
    the restarted WAL carries EVERY acked write (an unsynced window was
    never acked — that is the whole point of releasing acks only after
    the window's sync)."""
    monkeypatch.setenv("KCP_WAL_SYNC", "fsync")
    root = tmp_path / "kill"
    p = ServerThread(Config(durable=True, install_controllers=False,
                            tls=False, root_dir=str(root))).start()
    acked: list[str] = []
    storm = threading.Thread(
        target=lambda: acked.extend(_hammer(p.address, 6, 40)))
    storm.start()
    time.sleep(0.4)  # mid-storm
    p.kill()
    storm.join(timeout=30)
    st = _walreplay().replay(str(root / "store.wal"))
    have = {key.decode().split("\x00")[3] for key in st.objects}
    lost = [n for n in acked if n not in have]
    assert not lost, f"{len(lost)} acked writes missing after kill: {lost[:5]}"
