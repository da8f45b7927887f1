"""A sync row follows the LIVE object: ``Section.retire`` gives a row
back when both sides of its key are gone, the row is held back until
the wire that carried its last events has been dispatched, and the next
key takes it from the free list (``syncer/core.py``).

Two kinds of test. Served ones run real engines over in-process stores
(the flood's lifecycle: create -> status seen -> delete, names used
once). Staged ones drive a ``FusedCore(pipeline="serial")`` or its fleet
batch by hand with scripted owners, so that a wire can be held back
between its submit and its dispatch.
"""

from __future__ import annotations

import asyncio
import logging
import time
from collections import Counter

import numpy as np
import pytest

from kcp_tpu.client import Client
from kcp_tpu.models.reconcile_model import unpack_seg_counts
from kcp_tpu.ops.diff import DECISION_DELETE, DECISION_UPDATE
from kcp_tpu.ops.encode import BucketOverflow
from kcp_tpu.parallel.mesh import make_mesh
from kcp_tpu.store import LogicalStore
from kcp_tpu.syncer import start_syncer
from kcp_tpu.syncer.core import MIN_ROWS, FusedCore
from kcp_tpu.syncer.engine import CLUSTER_LABEL
from kcp_tpu.utils.trace import REGISTRY


def counter(name: str) -> float:
    return REGISTRY.snapshot().get(name, 0.0)


def cm(name: str, data: dict, label: str) -> dict:
    return {"apiVersion": "v1", "kind": "ConfigMap",
            "metadata": {"name": name, "namespace": "default",
                         "labels": {CLUSTER_LABEL: label}},
            "data": data}


async def until(pred, what: str, timeout: float = 20.0) -> None:
    deadline = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < deadline, f"never: {what}"
        await asyncio.sleep(0.002)


def get(client: Client, name: str):
    try:
        return client.get("configmaps", name, "default")
    except Exception:  # noqa: BLE001 — NotFound: absent
        return None


# ------------------------------------------------------------ served churn


async def churn(backend: str, rounds: int, clients: int = 6, mesh=None) -> dict:
    """``rounds`` lifecycles of a name used once, over two engines that
    share a core: create upstream -> the location has it -> the location
    writes its status -> the status is seen upstream -> delete upstream
    -> the location has lost it. ``clients`` pipelines an engine, so the
    live count stays put. Returns what the run decided and left."""
    kcp, phys = LogicalStore(), LogicalStore()
    up, down = Client(kcp, "t"), Client(phys, "p")
    labels = ("c1", "c2")
    syncers = [await start_syncer(up, down, ["configmaps"], label,
                                  backend=backend, mesh=mesh)
               for label in labels]
    engines = [s.engines[0] for s in syncers]
    applied: Counter = Counter()
    for eng in engines:
        real = eng._apply_decision

        def recording(key, decision, upsync, real=real):
            done = real(key, decision, upsync)
            if done:
                applied[(key, decision)] += 1
            return done

        eng._apply_decision = recording
    for label in labels:  # residents: they outlive the churn
        for i in range(3):
            up.create("configmaps", cm(f"res-{label}-{i}", {"v": str(i)}, label))
    await until(lambda: len(down.list("configmaps")[0]) == 6, "residents down")
    marks: dict = {}
    per_client = rounds // (clients * len(labels))

    async def pipeline(label: str, c: int) -> None:
        for n in range(per_client):
            name = f"cm-{label}-{c:02d}-{n:05d}"
            up.create("configmaps", cm(name, {"gen": str(n)}, label))
            await until(lambda: get(down, name) is not None, f"{name} down")
            dobj = get(down, name)
            assert dobj["data"] == {"gen": str(n)}
            dobj["status"] = {"observedGen": str(n)}
            down.update_status("configmaps", dobj)
            await until(lambda: (get(up, name) or {}).get("status")
                        == {"observedGen": str(n)}, f"{name} status up")
            up.delete("configmaps", name, "default")
            await until(lambda: get(down, name) is None, f"{name} gone")
            if backend == "tpu" and n == per_client // 10 and c == 0:
                b = engines[0]._section.bucket
                marks[label] = (b.B, b._next, b.stats["full_uploads"])

    await asyncio.gather(*(pipeline(label, c) for label in labels
                           for c in range(clients)))
    out = {"applied": set(applied),
           "down": {o["metadata"]["name"]: (o["data"], o.get("status"))
                    for o in down.list("configmaps")[0]},
           "up": {o["metadata"]["name"]: (o["data"], o.get("status"))
                  for o in up.list("configmaps")[0]}}
    if backend == "tpu":
        # the last deletes' rows come back with the tick after them
        await until(lambda: all(len(e._section.rows) == 3 for e in engines)
                    and not engines[0]._section.bucket._held,
                    "the last rows retired and released")
        b = engines[0]._section.bucket
        assert engines[1]._section.bucket is b
        # what the engine keeps per key went with the keys
        for eng in engines:
            assert set(eng._section.rows) == set(eng._section.row_keys.values())
            for table in (eng._dirty, eng._reports, eng._apply_failures,
                          eng._apply_pending):
                assert all(k[1].startswith("res-") for k in table), table
        out.update(bucket=b, marks=marks, owned=len(b.row_owner),
                   free=len(b._free), uploads=b.stats["full_uploads"],
                   state=engines[0].core._fleet._state)
    for s in syncers:
        await s.stop()
    return out


def check_churn(got: dict, host: dict, rounds: int, retired0: float,
                reused0: float, fresh0: float, reuse: float = 0.95) -> None:
    assert got["applied"] == host["applied"]
    assert got["down"] == host["down"] and got["up"] == host["up"]
    assert sorted(got["down"]) == sorted(
        f"res-{label}-{i}" for label in ("c1", "c2") for i in range(3))
    b = got["bucket"]
    # the rows in use never came near the first power of two, where a
    # row per NAME would have doubled B five times
    assert b.B == MIN_ROWS and b._next < MIN_ROWS
    for b_mark, next_mark, uploads_mark in got["marks"].values():
        assert (b.B, got["uploads"]) == (b_mark, uploads_mark)
        assert b._next <= next_mark + 4, (b._next, next_mark)
    assert counter("fused_rows_retired_total") - retired0 == rounds
    reused = counter("fused_rows_reused_total") - reused0
    fresh = counter("fused_rows_fresh_total") - fresh0
    assert reused + fresh == rounds + 6 and fresh == b._next
    assert reused / (reused + fresh) >= reuse
    assert got["owned"] == 6 and got["free"] == b._next - 6
    assert not b._retiring and not b._held


def test_names_used_once_keep_the_rows_of_the_live_set():
    rounds = 2004
    retired0 = counter("fused_rows_retired_total")
    reused0 = counter("fused_rows_reused_total")
    fresh0 = counter("fused_rows_fresh_total")
    got = asyncio.run(churn("tpu", rounds))
    host = asyncio.run(churn("host", rounds))
    check_churn(got, host, rounds, retired0, reused0, fresh0)


def test_the_same_churn_on_a_4x1_mesh():
    rounds = 240
    retired0 = counter("fused_rows_retired_total")
    reused0 = counter("fused_rows_reused_total")
    fresh0 = counter("fused_rows_fresh_total")
    mesh = make_mesh(n_devices=4, tenants=4, slots=1)
    got = asyncio.run(churn("tpu", rounds, mesh=mesh))
    host = asyncio.run(churn("host", rounds))
    check_churn(got, host, rounds, retired0, reused0, fresh0, reuse=0.8)
    assert got["bucket"].mesh is mesh
    assert len(got["state"].up_vals.sharding.device_set) == 4


def test_a_key_keeps_its_row_until_the_downstream_delete_has_landed():
    async def main():
        kcp, phys = LogicalStore(), LogicalStore()
        up, down = Client(kcp, "t"), Client(phys, "p")
        syncer = await start_syncer(up, down, ["configmaps"], "c1",
                                    backend="tpu")
        eng = syncer.engines[0]
        section, bucket = eng._section, eng._section.bucket
        key = ("default", "slow")
        hold = asyncio.Event()
        real = eng._apply_async

        async def held(k, code, upsync):
            if code == DECISION_DELETE:
                await hold.wait()
            return await real(k, code, upsync)

        eng._apply_async = held
        up.create("configmaps", cm("slow", {"v": "1"}, "c1"))
        await until(lambda: get(down, "slow") is not None, "created down")
        row = section.rows[key]
        retired0 = counter("fused_rows_retired_total")
        ticks0 = bucket.stats["ticks"]
        up.delete("configmaps", "slow", "default")
        # ticks that see the up side gone and the down side standing
        up.create("configmaps", cm("other", {"v": "1"}, "c1"))
        await until(lambda: get(down, "other") is not None
                    and bucket.stats["ticks"] >= ticks0 + 2, "ticks went by")
        assert get(down, "slow") is not None
        assert section.rows[key] == row and bucket.row_owner[row] is section
        assert not bucket._retiring and not bucket._held
        assert not bucket.up_exists[row] and bucket.down_exists[row]
        assert counter("fused_rows_retired_total") == retired0
        hold.set()
        await until(lambda: get(down, "slow") is None, "deleted down")
        await until(lambda: key not in section.rows and row in bucket._free,
                    "the row retired and released")
        assert counter("fused_rows_retired_total") == retired0 + 1
        assert row not in bucket.row_owner and not bucket._held
        for table in (eng._dirty, eng._reports, eng._apply_failures,
                      eng._apply_pending):
            assert key not in table
        # the key written again gets a row like any new key: this one, LIFO
        up.create("configmaps", cm("slow", {"v": "2"}, "c1"))
        await until(lambda: (get(down, "slow") or {}).get("data") == {"v": "2"},
                    "created again")
        assert section.rows[key] == row
        await syncer.stop()

    asyncio.run(main())


# ------------------------------------------------------ staged, by hand


class Owner:
    """A scripted SectionOwner: ``objs[key] = (up_vals | None,
    down_vals | None)``, every patch recorded."""

    def __init__(self, core: FusedCore, s: int = 16, status=slice(-2, None)):
        self.core, self.S = core, s
        self._mask = np.zeros(s, bool)
        self._mask[status] = True
        self.objs: dict = {}
        self.stream: list[tuple] = []
        self.retired: list = []
        self.overflow_at: int | None = None
        self.section = core.register(self, s)

    def fused_status_mask(self) -> np.ndarray:
        return self._mask

    def fused_encode(self, key):
        if self.overflow_at is not None:
            raise BucketOverflow(self.overflow_at)
        up, down = self.objs.get(key, (None, None))
        zeros = np.zeros(self.S, np.uint32)
        return (zeros if up is None else up, up is not None,
                zeros if down is None else down, down is not None)

    def fused_apply(self, patches) -> None:
        self.stream.extend((k, int(c), bool(u)) for k, c, u in patches)

    def fused_retired(self, keys) -> None:
        self.retired.extend(keys)

    def fused_overflow(self) -> None:
        """As the engine does: a wider encoder, another section in the
        wider bucket, the old one released; the replay is the test's."""
        old, self.S = self.section, self.overflow_at
        self.overflow_at = None
        mask = np.zeros(self.S, bool)
        mask[: self._mask.shape[0]] = self._mask
        self._mask = mask
        self.objs = {k: tuple(None if v is None else
                              np.concatenate([v, np.zeros(self.S - v.shape[0],
                                                          np.uint32)])
                              for v in sides)
                     for k, sides in self.objs.items()}
        self.section = self.core.register(self, self.S)
        old.release()


def vals(s: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(1, 2**32, s, dtype=np.uint32)


def both(s: int, seed: int, differ: int | None = None):
    """An object present on both sides, equal but for slot ``differ``."""
    up = vals(s, seed)
    down = up.copy()
    if differ is not None:
        down[differ] ^= np.uint32(1)
    return up, down


def stage(core: FusedCore, owner: Owner, keys, sides: int = 3) -> None:
    core._encode_section(owner.section, dict.fromkeys(keys, sides))


def test_a_patch_in_flight_for_a_retired_key_never_reaches_the_next_occupant(caplog):
    core = FusedCore(pipeline="serial")
    fleet = core._fleet
    o = Owner(core)
    bucket = o.section.bucket
    o.objs["a"] = both(o.S, 1, differ=0)        # spec differs: UPDATE a tick
    o.objs["keep"] = both(o.S, 2)
    stage(core, o, ["a", "keep"])
    fleet.dispatch(np.asarray((w := fleet.submit())[0]), w[1])
    assert o.stream == [("a", DECISION_UPDATE, False)]
    row_a = o.section.rows["a"]
    # wire 0 is level-triggered: it names row_a again, and is held back
    o.objs["keep"] = both(o.S, 3)
    stage(core, o, ["keep"])
    wire0, meta0 = fleet.submit()
    assert not meta0.rows_retired
    # both sides of "a" go; its row is retired and rides wire 1
    del o.objs["a"]
    stage(core, o, ["a"])
    assert "a" not in o.section.rows and row_a not in bucket.row_owner
    assert o.retired == ["a"] and bucket._retiring == [row_a]
    wire1, meta1 = fleet.submit()
    assert meta1.rows_retired == ((bucket, [row_a]),)
    assert not bucket._retiring and row_a in bucket._held
    # a key that arrives now must not get row_a: two wires can name it
    o.objs["b"] = both(o.S, 4, differ=1)
    stage(core, o, ["b"])
    row_b = o.section.rows["b"]
    assert row_b != row_a and row_a not in bucket._free
    wire2, meta2 = fleet.submit()
    dropped0 = counter("fused_dropped_patch_rows")
    del o.stream[:]
    with caplog.at_level(logging.WARNING, logger="kcp_tpu.syncer.core"):
        fleet.dispatch(np.asarray(wire0), meta0)
    # the old key's patch: dropped, counted, not logged, applied to nobody
    assert o.stream == []
    assert counter("fused_dropped_patch_rows") == dropped0 + 1
    assert not caplog.records and row_a not in bucket._dropped_logged
    assert row_a not in bucket._free
    fleet.dispatch(np.asarray(wire1), meta1)
    assert bucket._free == [row_a] and not bucket._held
    assert counter("fused_dropped_patch_rows") == dropped0 + 1
    fleet.dispatch(np.asarray(wire2), meta2)
    assert o.stream == [("b", DECISION_UPDATE, False)]
    # the next key takes the row, and only its own decisions come back
    o.objs["c"] = (vals(o.S, 5), None)
    stage(core, o, ["c"])
    assert o.section.rows["c"] == row_a
    del o.stream[:]
    fleet.dispatch(np.asarray((w := fleet.submit())[0]), w[1])
    assert sorted(o.stream) == [("b", DECISION_UPDATE, False), ("c", 1, False)]
    assert fleet.stats["full_uploads"] == 1  # the first tick's, none since


def test_a_row_passes_to_a_section_with_another_mask_and_segment():
    core = FusedCore(pipeline="serial")
    fleet = core._fleet
    a = Owner(core, status=slice(-2, None))
    b = Owner(core, status=slice(0, 2))
    bucket = a.section.bucket
    assert b.section.bucket is bucket and a.section.seg != b.section.seg
    for i in range(3):
        a.objs[f"a{i}"] = both(a.S, 10 + i)
        b.objs[f"b{i}"] = both(b.S, 20 + i)
    stage(core, a, list(a.objs))
    stage(core, b, list(b.objs))
    fleet.dispatch(np.asarray((w := fleet.submit())[0]), w[1])
    row = a.section.rows["a1"]
    del a.objs["a1"]
    stage(core, a, ["a1"])
    fleet.dispatch(np.asarray((w := fleet.submit())[0]), w[1])
    assert bucket._free == [row]
    # slot 0 is status to B and spec to A; slot -1 the other way round
    b.objs["new"] = both(b.S, 30, differ=0)
    stage(core, b, ["new"])
    assert b.section.rows["new"] == row and bucket.row_owner[row] is b.section
    assert (bucket.status_mask[row] == b._mask).all()
    wire, meta = fleet.submit()
    host_wire = np.asarray(wire)
    fleet.dispatch(host_wire, meta)
    assert a.stream == [] and b.stream == [("new", 0, True)]
    counts = unpack_seg_counts(host_wire, meta.k, meta.r_total, meta.p,
                               meta.seg_capacity)
    assert counts[a.section.seg] == 2 and counts[b.section.seg] == 4
    # and on the device: the row's mask and segment are B's
    assert (np.asarray(fleet._state.status_mask[row]) == b._mask).all()
    assert int(fleet._seg_ids[row]) == b.section.seg
    # spec churn of the new occupant is spec churn
    up, down = b.objs["new"]
    down = down.copy()
    down[-1] ^= np.uint32(1)
    b.objs["new"] = (up, down)
    del b.stream[:]
    stage(core, b, ["new"], sides=2)
    fleet.dispatch(np.asarray((w := fleet.submit())[0]), w[1])
    assert b.stream == [("new", DECISION_UPDATE, True)]
    assert fleet.stats["full_uploads"] == 1


def test_a_retirement_meets_growth_overflow_and_quarantine_in_one_tick():
    core = FusedCore(pipeline="serial")
    fleet = core._fleet
    o = Owner(core)
    mover = Owner(core)
    bucket = o.section.bucket
    n = MIN_ROWS - 2
    for i in range(n):
        o.objs[i] = both(o.S, 100 + i)
    mover.objs.update(m0=both(mover.S, 300, differ=2), m1=both(mover.S, 301))
    assert core._tick([(0, False, k, o.section) for k in o.objs]
                      + [(1, False, k, mover.section) for k in mover.objs],
                      time.monotonic()) == []
    assert bucket.B == MIN_ROWS and bucket._next == MIN_ROWS
    assert mover.stream == [("m0", DECISION_UPDATE, False)]
    uploads0 = fleet.stats["full_uploads"]
    row_gone, row_bad = o.section.rows[0], o.section.rows[5]
    # one tick: key 0 goes (retired), key 5's row is quarantined and its
    # key comes back (as _requeue_quarantined brings it), key 1 changes,
    # two new keys arrive (the second needs row 64: B doubles), and the
    # other owner's vocabulary outgrows the bucket
    del o.objs[0]
    key5, sec5 = fleet.quarantine_row(row_bad)
    assert (key5, sec5) == (5, o.section) and 5 not in o.section.rows
    o.objs[1] = both(o.S, 901, differ=3)
    o.objs["n1"] = both(o.S, 902, differ=4)
    o.objs["n2"] = (vals(o.S, 903), None)
    mover.overflow_at = 32
    old_section = mover.section
    del o.stream[:], mover.stream[:]
    core._tick([(0, False, k, o.section) for k in (0, 1, 5, "n1", "n2")]
               + [(0, True, k, o.section) for k in (0, 1)]
               + [(1, False, "m0", old_section)],
               time.monotonic())
    assert bucket.B == 2 * MIN_ROWS
    assert fleet.stats["full_uploads"] == uploads0 + 1
    assert old_section.released and mover.section.bucket is core.buckets[32]
    # serial pipeline: the tick collected its own wire, so the retired
    # row is free already; the quarantined and the released rows went
    # straight to the free list
    assert o.retired == [0] and not bucket._retiring and not bucket._held
    assert row_gone in bucket._free and 0 not in o.section.rows
    assert o.section.rows[5] == row_bad            # LIFO: its own row again
    assert (o.section.rows["n1"], o.section.rows["n2"]) == (MIN_ROWS,
                                                           MIN_ROWS + 1)
    assert sorted(o.stream, key=str) == sorted(
        [(1, DECISION_UPDATE, False), ("n1", DECISION_UPDATE, False),
         ("n2", 1, False)], key=str)
    # the replay of the moved owner, as the engine enqueues it
    core._tick([(1, False, k, mover.section) for k in mover.objs],
               time.monotonic())
    assert ("m0", DECISION_UPDATE, False) in mover.stream
    # no staged row was lost: the device holds every live key's mirrors
    b_base = dict(zip(map(id, fleet._members), fleet._bases))
    st = fleet._state
    for owner in (o, mover):
        sec = owner.section
        base = b_base[id(sec.bucket)]
        assert set(sec.rows) == set(owner.objs)
        for key, (up, down) in owner.objs.items():
            r = base + sec.rows[key]
            assert bool(st.up_exists[r]) and bool(st.down_exists[r]) == (down is not None)
            assert (np.asarray(st.up_vals[r])[: owner.S] == up).all()
            if down is not None:
                assert (np.asarray(st.down_vals[r])[: owner.S] == down).all()
    gone = b_base[id(bucket)] + row_gone
    assert not bool(st.up_exists[gone]) and not bool(st.down_exists[gone])
    assert not np.asarray(st.up_vals[gone]).any()
    # and the freed row is the next one taken
    o.objs["n3"] = both(o.S, 904)
    core._tick([(0, False, "n3", o.section)], time.monotonic())
    assert o.section.rows["n3"] == row_gone


@pytest.mark.parametrize("sides", [1, 2])
def test_a_key_unknown_to_its_section_with_nothing_on_either_side_takes_no_row(sides):
    core = FusedCore(pipeline="serial")
    o = Owner(core)
    o.objs["live"] = both(o.S, 1)
    fresh0 = counter("fused_rows_fresh_total")
    stage(core, o, ["ghost", "live"], sides=sides)
    assert list(o.section.rows) == ["live"] and o.retired == []
    assert counter("fused_rows_fresh_total") == fresh0 + 1
    stage(core, o, ["ghost"], sides=sides)
    assert list(o.section.rows) == ["live"] and o.section.bucket._next == 1
