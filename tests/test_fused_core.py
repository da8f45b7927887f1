"""FusedCore serving tests: the served program IS the benched program.

Covers the round-2 integration seams:
- engines with different slot vocabularies sharing ONE fused bucket
  (per-row status masks)
- the pipelined applier: ticks keep running while applies are in flight
- patch-set overflow -> capacity doubling + level-triggered retick
- encoder vocabulary overflow -> bucket migration + row replay
"""

import asyncio
import time

import pytest
from helpers import device_mirror_equal

from kcp_tpu.client import Client
from kcp_tpu.store import LogicalStore
from kcp_tpu.syncer import start_syncer
from kcp_tpu.syncer.core import FusedCore
from kcp_tpu.syncer.engine import CLUSTER_LABEL


def cm(name, data, label="c1", ns="default", kind="ConfigMap"):
    return {
        "apiVersion": "v1",
        "kind": kind,
        "metadata": {"name": name, "namespace": ns, "labels": {CLUSTER_LABEL: label}},
        "data": data,
    }


async def eventually(pred, timeout=8.0, interval=0.01):
    deadline = asyncio.get_event_loop().time() + timeout
    while True:
        try:
            if pred():
                return
        except Exception:
            pass
        if asyncio.get_event_loop().time() > deadline:
            raise AssertionError("condition not reached")
        await asyncio.sleep(interval)


def test_engines_share_one_fused_bucket():
    """Two engines (different GVRs, different vocabularies) must land in
    the same schema bucket and still compute independent decisions."""

    async def main():
        kcp, phys = LogicalStore(), LogicalStore()
        up, down = Client(kcp, "t"), Client(phys, "p")
        # seed widgets so discovery serves the type
        up.create("widgets", cm("seed", {"w": "0"}, label="nope", kind="Widget"))
        s1 = await start_syncer(up, down, ["configmaps"], "c1", backend="tpu")
        s2 = await start_syncer(up, down, ["widgets"], "c1", backend="tpu")

        core = s1.engines[0].core
        assert core is s2.engines[0].core, "engines must share the per-loop core"
        assert len(core.buckets) == 1, "same slot capacity -> same bucket"
        bucket = core.buckets[64]
        assert len(bucket.sections) >= 2

        up.create("configmaps", cm("a", {"k": "v"}))
        up.create("widgets", cm("w", {"x": "1"}, kind="Widget"))
        await eventually(lambda: down.get("configmaps", "a", "default"))
        await eventually(lambda: down.get("widgets", "w", "default"))

        # a status written downstream arrives upstream (its own event
        # hands it to the applier; the tick that carries the row is the
        # backstop), and the row's two sides end equal on the device,
        # each row under its own engine's status mask
        dobj = down.get("widgets", "w", "default")
        dobj["status"] = {"ready": True}
        down.update_status("widgets", dobj)
        await eventually(
            lambda: up.get("widgets", "w", "default").get("status") == {"ready": True}
        )
        await eventually(
            lambda: device_mirror_equal(s2.engines[0], ("default", "w")))
        # the configmap row must not have been disturbed
        assert down.get("configmaps", "a", "default")["data"] == {"k": "v"}
        assert up.get("configmaps", "a", "default").get("status") is None

        assert bucket.stats["ticks"] >= 2
        await s1.stop()
        await s2.stop()

    asyncio.run(main())


def test_tick_independent_of_apply_latency():
    """The VERDICT #3 criterion: with slow applies in flight, other keys
    keep converging — the tick loop never waits on the applier."""

    async def main():
        kcp, phys = LogicalStore(), LogicalStore()
        up, down = Client(kcp, "t"), Client(phys, "p")
        syncer = await start_syncer(up, down, ["configmaps"], "c1", backend="tpu")
        eng = syncer.engines[0]

        real_apply = eng._apply_decision
        SLOW = 0.3

        async def slow_apply(key, code, upsync):
            if key[1].startswith("slow-"):
                await asyncio.sleep(SLOW)
            return real_apply(key, code, upsync)

        eng._apply_async = slow_apply

        # occupy 3 of the 4 applier workers with slow keys
        for i in range(3):
            up.create("configmaps", cm(f"slow-{i}", {"v": "1"}))
        await asyncio.sleep(0.05)
        t0 = time.monotonic()
        up.create("configmaps", cm("fast", {"v": "1"}))
        await eventually(lambda: down.get("configmaps", "fast", "default"),
                         timeout=SLOW)
        fast_latency = time.monotonic() - t0
        assert fast_latency < SLOW, (
            f"fast key took {fast_latency:.3f}s — tick blocked on slow applies"
        )
        # the slow keys land eventually too
        await eventually(lambda: all(
            down.get("configmaps", f"slow-{i}", "default") for i in range(3)))
        await syncer.stop()

    asyncio.run(main())


def test_patch_overflow_reticks_until_converged():
    """More actionable rows than patch capacity: the core doubles the
    capacity and re-ticks; level-triggering loses nothing."""

    async def main():
        kcp, phys = LogicalStore(), LogicalStore()
        up, down = Client(kcp, "t"), Client(phys, "p")
        syncer = await start_syncer(up, down, ["configmaps"], "c1", backend="tpu")
        eng = syncer.engines[0]
        bucket = eng._section.bucket
        bucket.patch_capacity = 16  # force overflow with 100 creates

        for i in range(100):
            up.create("configmaps", cm(f"cm-{i}", {"v": str(i)}))
        await eventually(
            lambda: len(down.list("configmaps")[0]) == 100, timeout=15)
        assert bucket.stats["overflows"] >= 1
        assert bucket.patch_capacity > 16
        await syncer.stop()

    asyncio.run(main())


def test_vocabulary_overflow_migrates_bucket():
    """An object with >64 leaf paths overflows the default bucket; the
    engine re-registers at 128 slots and replays its rows."""

    async def main():
        kcp, phys = LogicalStore(), LogicalStore()
        up, down = Client(kcp, "t"), Client(phys, "p")
        syncer = await start_syncer(up, down, ["configmaps"], "c1", backend="tpu")
        eng = syncer.engines[0]

        up.create("configmaps", cm("small", {"k": "v"}))
        await eventually(lambda: down.get("configmaps", "small", "default"))

        wide = cm("wide", {f"field-{i}": str(i) for i in range(70)})
        up.create("configmaps", wide)
        await eventually(lambda: down.get("configmaps", "wide", "default"))
        assert eng.enc.capacity >= 128
        assert eng._section.bucket.S >= 128
        # the pre-overflow object survived the migration
        assert down.get("configmaps", "small", "default")["data"] == {"k": "v"}

        # post-migration sync still works both ways
        obj = up.get("configmaps", "small", "default")
        obj["data"] = {"k": "v2"}
        up.update("configmaps", obj)
        await eventually(
            lambda: down.get("configmaps", "small", "default")["data"] == {"k": "v2"})
        await syncer.stop()

    asyncio.run(main())


def test_core_refcount_across_syncers():
    """The per-loop core starts once and stops with its last engine."""

    async def main():
        kcp, phys = LogicalStore(), LogicalStore()
        up, down = Client(kcp, "t"), Client(phys, "p")
        s1 = await start_syncer(up, down, ["configmaps"], "c1", backend="tpu")
        core = FusedCore.for_current_loop()
        assert core is s1.engines[0].core
        s2 = await start_syncer(up, down, ["configmaps"], "c2", backend="tpu")
        await s1.stop()
        # core still serves s2
        up.create("configmaps", cm("x", {"a": "b"}, label="c2"))
        await eventually(lambda: down.get("configmaps", "x", "default"))
        await s2.stop()
        assert core._refs == 0

    asyncio.run(main())


def test_ack_lane_unit_padding_never_clobbers_row_zero():
    """The converged-row acks lane: padding entries (-1) must scatter
    NOTHING — a clip-to-zero implementation would overwrite row 0 (racing
    its genuine ack, or reverting it outright) — while a real ack copies
    the up mirror into the down mirror exactly."""
    import jax
    import numpy as np

    from kcp_tpu.models.reconcile_model import (
        example_state,
        reconcile_step_packed,
    )

    base = example_state(b=64, s=16, r=8, p=8, l=4, c=8)
    # force row 0 divergent so any padding write to it is detectable
    down = np.asarray(base.down_vals).copy()
    down[0] = 12345
    base = base._replace(down_vals=down, down_exists=np.asarray(base.down_exists).copy())
    packed = np.zeros((8, 16 + 2), np.uint32)
    step = jax.jit(reconcile_step_packed, static_argnames=("patch_capacity",))

    # 1. padding-only acks: row 0 must stay divergent (nothing scattered)
    state = jax.tree.map(jax.device_put, base)
    pad_only = np.full(8, -1, np.int32)
    s1, _ = step(state, jax.device_put(packed), jax.device_put(pad_only),
                 patch_capacity=16)
    np.testing.assert_array_equal(np.asarray(s1.down_vals)[0], down[0])

    # 2. a real ack for row 0 among padding: down becomes exactly up
    state = jax.tree.map(jax.device_put, base)
    acks = np.full(8, -1, np.int32)
    acks[0] = 0
    s2, _ = step(state, jax.device_put(packed), jax.device_put(acks),
                 patch_capacity=16)
    np.testing.assert_array_equal(np.asarray(s2.down_vals)[0],
                                  np.asarray(base.up_vals)[0])
    assert bool(np.asarray(s2.down_exists)[0])


def test_ack_lane_compresses_feedback_and_stays_correct():
    """End-to-end: the downstream echo of an applied sync rides the acks
    lane (bucket.stats['acked'] grows) and the loop still converges both
    an update and a subsequent delete."""

    async def main():
        kcp, phys = LogicalStore(), LogicalStore()
        up, down = Client(kcp, "t"), Client(phys, "p")
        syncer = await start_syncer(up, down, ["configmaps"], "c1", backend="tpu")
        bucket = syncer.engines[0]._section.bucket

        for i in range(16):
            up.create("configmaps", cm(f"cm-{i}", {"v": str(i)}))
        await eventually(lambda: len(down.list("configmaps")[0]) == 16)
        # the downstream creates echo back as down-side events whose
        # encoding equals the up mirror -> acks, not full entries
        await eventually(lambda: bucket.stats["acked"] > 0)

        obj = up.get("configmaps", "cm-3", "default")
        obj["data"] = {"v": "updated"}
        up.update("configmaps", obj)
        await eventually(
            lambda: down.get("configmaps", "cm-3", "default")["data"]["v"] == "updated")

        up.delete("configmaps", "cm-5", "default")
        from kcp_tpu.utils.errors import NotFoundError

        def gone():
            try:
                down.get("configmaps", "cm-5", "default")
                return False
            except NotFoundError:
                return True

        await eventually(gone)
        await syncer.stop()

    asyncio.run(main())


def test_idle_flush_head_guard_survives_collect_failure():
    """If a tick's depth-based collect pops the in-flight head while the
    waiter thread is still blocked on it, the wake that follows must not
    collect that wire a second time nor take the next head before it is
    ready: every wire is collected once, in submit order (the
    eager-collect review finding, kept through the poll's replacement
    by a completion wake)."""

    async def main():
        import threading

        import numpy as np

        from kcp_tpu.syncer.core import FusedCore

        core = FusedCore(batch_window=0.0005)
        core._eager_collect = True  # force the eager path on CPU

        collected = []

        class FakeFleet:
            def dispatch(self, wire, meta):
                collected.append(int(np.asarray(wire)[0]))
                return False

        class FakeWire:
            """Ready when its event is set: ``__array__`` blocks as a
            device array's host copy does."""

            def __init__(self, tag):
                self.tag = tag
                self.done = threading.Event()

            def is_ready(self):
                return self.done.is_set()

            def __array__(self, dtype=None, copy=None):
                assert self.done.wait(5.0)
                return np.array([self.tag])

        core._fleet = FakeFleet()
        wire_a, wire_b = FakeWire(1), FakeWire(2)
        core._inflight = [(wire_a, (0, 8)), (wire_b, (0, 8))]
        # park the waiter thread on the head
        core._watch(wire_a)
        core._watch(wire_b)
        await asyncio.sleep(0.005)
        assert len(core._inflight) == 2  # blocked, nothing collected yet
        # the tick's own collect pops wire_a while the waiter is parked
        # on it (the depth rule's blocking fetch)
        head = core._inflight.pop(0)
        wire_a.done.set()
        core._collect(*head)
        # wire_a's wake must leave wire_b, which is not ready, in flight
        await asyncio.sleep(0.01)
        assert collected == [1], collected
        assert len(core._inflight) == 1
        wire_b.done.set()
        for _ in range(20):
            await asyncio.sleep(0.002)
            if not core._inflight:
                break
        assert collected == [1, 2], collected
        assert not core._inflight
        assert core._flush_task is None  # no timer-driven task on this path
        core._started = True
        await core.stop()
        assert core._waiter is None

    asyncio.run(main())


def test_mask_stamp_wire_entry_updates_device_mask():
    """A MASK_STAMP entry (flag bit 8) must scatter into the per-row
    status mask and NOT apply as a delta; the stamped row's status-only
    divergence then decides upsync, not UPDATE (the fuzz-found bug)."""
    import jax
    import numpy as np

    from kcp_tpu.models.reconcile_model import (
        MASK_STAMP_BIT,
        example_state,
        reconcile_step_packed,
        unpack_patches,
    )

    s = 16
    base = example_state(b=64, s=s, r=8, p=8, l=4, c=8, dirty_frac=0.0)
    # per-row mask form (the serving core's), all-False for row 3
    mask = np.zeros((64, s), bool)
    down = np.asarray(base.down_vals).copy()
    down[3, s - 1] ^= 1  # row 3 diverges in the last slot only
    base = base._replace(status_mask=mask, down_vals=down)
    state = jax.tree.map(jax.device_put, base)

    # without a stamp: the divergence reads as spec churn -> UPDATE
    packed = np.zeros((8, s + 2), np.uint32)
    step = jax.jit(reconcile_step_packed, static_argnames=("patch_capacity",))
    state1, wire = step(state, jax.device_put(packed), None, patch_capacity=16)
    idx, code, upsync, _, _ = unpack_patches(np.asarray(wire))
    assert idx.tolist() == [3] and code.tolist() == [2] and not upsync[0]

    # with a stamp marking the last slot as status: upsync, not UPDATE
    stamp = np.zeros((8, s + 2), np.uint32)
    stamp[0, s - 1] = 1  # mask row: last slot is status
    stamp[0, s] = 3  # row index
    stamp[0, s + 1] = 4 | MASK_STAMP_BIT
    state2, wire = step(state1, jax.device_put(stamp), None, patch_capacity=16)
    idx, code, upsync, _, _ = unpack_patches(np.asarray(wire))
    assert idx.tolist() == [3] and code.tolist() == [0] and bool(upsync[0])
    # the stamp did not corrupt the mirrors (it is not a delta)
    np.testing.assert_array_equal(np.asarray(state2.down_vals), down)


# ---------------------------------------------------------------------------
# the ack lane rides in the packed wire: ONE array a tick crosses to the
# device (models/reconcile_model.py WireBuffers / split_ack_lane)
# ---------------------------------------------------------------------------

def _two_array_fleet_step(state, seg_ids, packed, acks, patch_capacity,
                          seg_capacity):
    """The fleet step as it was while the ack lane crossed as a second
    array — this test's reference: the event wire and the lane apart."""
    import jax.numpy as jnp

    from kcp_tpu.models.reconcile_model import (
        apply_seg_stamps,
        reconcile_step_packed,
    )

    seg_ids = apply_seg_stamps(seg_ids, packed)
    new_state, wire = reconcile_step_packed(state, packed, acks,
                                            patch_capacity)
    counts = jnp.zeros(seg_capacity, jnp.int32).at[seg_ids].add(
        new_state.up_exists.astype(jnp.int32), mode="drop")
    return new_state, seg_ids, jnp.concatenate([wire, counts])


# a tick: (event rows d, ack capacity, full entries, acks, mask stamps)
LANE_CASES = {
    # (a) nothing acked: the lane is padding from end to end
    "padding-only-lane": (14, [(8, 32, 5, 0, 0), (8, 32, 8, 0, 0)]),
    # (b) everything a tick can carry, in one tick
    "acks-rows-and-stamps": (14, [(16, 32, 6, 9, 4), (16, 32, 3, 30, 2)]),
    # (c) the sticky capacity doubles between two ticks of one run
    "capacity-doubles": (14, [(8, 16, 2, 16, 1), (8, 32, 2, 17, 0),
                              (8, 64, 1, 40, 1)]),
    # (d) the tick of a full upload: MIN_EVENTS empty rows, an empty lane
    "full-upload-tick": (14, [(64, 32, 0, 0, 0), (8, 32, 3, 4, 1)]),
    # (e) S + 2 = 18 divides neither 32 nor 100: the lane's last row is
    # padded, and a lane shorter than one row is too
    "width-divides-nothing": (16, [(8, 8, 2, 7, 1), (8, 32, 4, 20, 2),
                                   (16, 100, 5, 61, 3)]),
}


@pytest.mark.parametrize("case", sorted(LANE_CASES))
def test_one_array_step_equals_the_two_array_step(case):
    """The fleet step fed ONE array (the ack lane in the tail rows of the
    packed wire, laid out by WireBuffers) leaves the bit-identical state,
    segment lane and wire that the two-array step leaves, tick after
    tick, each run carrying its own resident state forward."""
    import jax
    import numpy as np

    from kcp_tpu.models.reconcile_model import (
        MASK_STAMP_BIT,
        SEG_NONE,
        SEG_SHIFT,
        WireBuffers,
        ack_lane_rows,
        example_state,
        reconcile_step_fleet,
    )

    s, ticks = LANE_CASES[case]
    b, k, segs = 128, 64, 8
    rng = np.random.default_rng(sum(case.encode()))
    base = example_state(b=b, s=s, r=8, p=8, l=4, c=8, seed=3,
                         dirty_frac=0.2)
    base = base._replace(status_mask=np.zeros((b, s), bool))
    one = jax.jit(reconcile_step_fleet,
                  static_argnames=("ack_capacity", "patch_capacity",
                                   "seg_capacity"))
    two = jax.jit(_two_array_fleet_step,
                  static_argnames=("patch_capacity", "seg_capacity"))
    st1 = st2 = jax.tree.map(jax.device_put, base)
    seg1 = seg2 = jax.device_put(np.full(b, SEG_NONE, np.int32))
    bufs = WireBuffers(depth=2)
    for d, cap, n_full, n_acks, n_stamps in ticks:
        slot, packed, acks = bufs.acquire(d, s + 2, cap)
        assert packed.shape == (d + ack_lane_rows(cap, s + 2), s + 2)
        assert acks.shape == (cap,) and acks.base is not None
        assert not packed[:d].any() and (acks == -1).all()
        rows = rng.permutation(b)[:n_full + n_acks + n_stamps]
        full, acked, stamped = np.split(rows, [n_full, n_full + n_acks])
        packed[:n_full, :s] = rng.integers(1, 2**32, (n_full, s),
                                           dtype=np.uint32)
        packed[:n_full, s] = full
        packed[:n_full, s + 1] = 4 | rng.integers(0, 4, n_full)  # exists, side
        m = slice(n_full, n_full + n_stamps)
        packed[m, :s] = rng.random((n_stamps, s)) < 0.3
        packed[m, s] = stamped
        packed[m, s + 1] = (4 | MASK_STAMP_BIT
                            | (rng.integers(0, segs, n_stamps) << SEG_SHIFT))
        acks[:n_acks] = acked
        # what the two arrays of the parent's tick would have held
        events, lane = packed[:d].copy(), acks.copy()
        st1, seg1, wire1 = one(st1, seg1, jax.device_put(packed),
                               ack_capacity=cap, patch_capacity=k,
                               seg_capacity=segs)
        st2, seg2, wire2 = two(st2, seg2, jax.device_put(events),
                               jax.device_put(lane), patch_capacity=k,
                               seg_capacity=segs)
        bufs.commit(slot, wire1)
        np.testing.assert_array_equal(np.asarray(wire1), np.asarray(wire2))
        np.testing.assert_array_equal(np.asarray(seg1), np.asarray(seg2))
        for name, x, y in zip(st1._fields, st1, st2):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=name)
        if n_acks:  # the lane did its work: an acked row's down IS its up
            r = int(acked[0])
            np.testing.assert_array_equal(np.asarray(st1.down_vals)[r],
                                          np.asarray(st1.up_vals)[r])
    assert bufs.reuse_waits == 0


def test_the_lane_in_the_wire_converges_like_the_host_engine():
    """The differential run: the served engine (acks riding the packed
    wire) and the host-backend engine converge one seeded random op
    sequence to the same state."""
    from test_differential_fuzz import _run_backend

    async def main():
        assert await _run_backend("tpu", 53) == await _run_backend("host", 53)

    asyncio.run(main())
