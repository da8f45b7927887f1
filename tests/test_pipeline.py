"""Double-buffered tick pipeline: serial-vs-pipelined equivalence.

The pipelined loop ("double": 2-deep in-flight window, overlapped queue
drain, double-buffered wire staging) must be an OBSERVATIONALLY
invisible optimization: over an identical randomized churn schedule it
must emit the byte-identical patch stream the serial loop emits — no
reordered, duplicated, or dropped decisions — the same invariant the
differential fuzz family protects for the decision math itself. Plus
the lifecycle half: shutting down with steps in flight must deliver
every submitted tick's patches (stop drains the controller BEFORE the
in-flight wires, or the last window is silently lost).
"""

import asyncio

import numpy as np
import pytest

from kcp_tpu.models.reconcile_model import WireBuffers, ack_lane_rows
from kcp_tpu.syncer.core import MIN_EVENTS, PIPELINE_DEPTH, FusedCore

from helpers import wait_until

S = 16  # slot width (one shared bucket)


class RecordingOwner:
    """Open-loop SectionOwner: a fixed mirror array pair, every patch
    recorded, NO feedback — so both pipeline modes see an identical
    staging schedule and the patch streams are comparable byte for byte.
    (A closed loop would legitimately diverge: apply timing shifts which
    churn lands before which tick.)"""

    def __init__(self, core, b: int):
        self.core = core
        self.B = b
        mask = np.zeros(S, bool)
        mask[-2:] = True
        self._mask = mask
        self.up_vals = np.zeros((b, S), np.uint32)
        self.down_vals = np.zeros((b, S), np.uint32)
        self.stream: list[tuple[int, int, bool]] = []
        self.dispatches = 0
        self.section = core.register(self, S)

    def fused_status_mask(self) -> np.ndarray:
        return self._mask

    def fused_encode(self, key: int):
        return self.up_vals[key], True, self.down_vals[key], True

    def fused_encode_many(self, keys):
        idx = np.fromiter(keys, np.int64, len(keys))
        ones = np.ones(idx.size, bool)
        return self.up_vals[idx], ones, self.down_vals[idx], ones

    def fused_apply(self, patches) -> None:
        self.dispatches += 1
        self.stream.extend((int(k), int(c), bool(u)) for k, c, u in patches)

    def fused_overflow(self) -> None:  # pragma: no cover - fixed vocab
        raise AssertionError("pipeline fuzz vocabulary never grows")


def _stream_bytes(stream) -> bytes:
    return np.asarray(
        [(k, c, int(u)) for k, c, u in stream], np.int64).tobytes()


async def _run_schedule(pipeline: str, seed: int, rows: int = 512,
                        steps: int = 30, bufs=None) -> tuple[bytes, int]:
    """Drive one deterministic churn schedule in lockstep (one enqueued
    batch per tick) and return the fully-drained patch stream. ``bufs``
    replaces the fleet's wire staging (the reuse gate's test)."""
    core = FusedCore(batch_window=0.0005, pipeline=pipeline)
    if bufs is not None:
        core._fleet._wire_bufs = bufs
    owner = RecordingOwner(core, rows)
    await core.start()
    bucket = owner.section.bucket
    rng = np.random.default_rng(seed)
    # churn pool < MIN_PATCH_CAPACITY so the level-triggered re-patches
    # never overflow the wire (overflow reticks at mode-dependent times,
    # which would legitimately fork the schedules)
    pool = 200
    for step in range(steps):
        n = int(rng.integers(1, 32))
        touched = rng.choice(pool, size=n, replace=False)
        owner.up_vals[touched] = rng.integers(
            1, 2**32, (n, S), dtype=np.uint32)
        before = bucket.stats["ticks"]
        self_keys = touched.tolist()
        core.enqueue_many(owner.section, False, self_keys)
        assert await wait_until(
            lambda: bucket.stats["ticks"] > before, 10), (
            f"{pipeline}: tick never ran for step {step}")
    await core.stop()
    # stop() must leave nothing in flight
    assert not core._inflight
    return _stream_bytes(owner.stream), bucket.stats["ticks"]


@pytest.mark.parametrize("seed", [1, 9, 27])
def test_pipelined_vs_serial_equivalence_fuzz(seed):
    """Byte-identical patch streams over a randomized churn schedule:
    pipelining must not reorder, duplicate, or drop decisions."""

    async def main():
        serial, serial_ticks = await _run_schedule("serial", seed)
        double, double_ticks = await _run_schedule("double", seed)
        # lockstep drove one staged batch per tick in both modes
        assert serial_ticks == double_ticks
        assert serial == double, (
            f"seed={seed}: pipelined patch stream diverged from serial "
            f"({len(serial)} vs {len(double)} bytes)")
        assert len(serial) > 0, "schedule produced no patches — vacuous"

    asyncio.run(main())


def test_shutdown_drains_inflight_steps():
    """No tick is lost with steps in flight: churn enqueued and never
    awaited must still deliver its patches through stop()'s shutdown
    drain (controller final ticks first, THEN the in-flight wires)."""

    async def main():
        core = FusedCore(batch_window=0.0005, pipeline="double")
        owner = RecordingOwner(core, 64)
        await core.start()
        touched = list(range(40))
        owner.up_vals[touched, 0] = 7  # diverge 40 rows
        core.enqueue_many(owner.section, False, touched)
        # stop IMMEDIATELY: the batch may not even have ticked yet; the
        # controller's shutdown drain must run it, and the wire it puts
        # in flight must be collected by stop's inflight drain
        await core.stop()
        assert not core._inflight
        patched = {k for k, _c, _u in owner.stream}
        assert patched.issuperset(touched), (
            f"lost {sorted(set(touched) - patched)} in shutdown")

    asyncio.run(main())


def test_serial_mode_never_leaves_wires_inflight():
    """pipeline="serial" is the A/B reference: every tick fetches its
    own wire before returning (depth 0), so nothing pipelines."""

    async def main():
        core = FusedCore(batch_window=0.0005, pipeline="serial")
        assert core.fetch_depth == 0
        assert not core.controller.overlap_drain
        owner = RecordingOwner(core, 64)
        await core.start()
        for step in range(5):
            owner.up_vals[step, 1] = step + 1
            before = owner.section.bucket.stats["ticks"]
            core.enqueue(owner.section, False, step)
            assert await wait_until(
                lambda: owner.section.bucket.stats["ticks"] > before, 10)
            assert not core._inflight, "serial mode left a wire in flight"
        await core.stop()

    asyncio.run(main())


def test_pipeline_modes_validated_and_metered():
    """Mode plumbing: bad modes rejected; the double-mode run exposes
    the per-stage occupancy metrics on the /metrics registry."""
    with pytest.raises(ValueError):
        FusedCore(pipeline="triple")

    async def main():
        core = FusedCore(batch_window=0.0005, pipeline="double")
        assert core.fetch_depth == PIPELINE_DEPTH
        assert core.controller.overlap_drain
        owner = RecordingOwner(core, 64)
        await core.start()
        bucket = owner.section.bucket
        for step in range(8):
            owner.up_vals[step, 1] = step + 1
            before = bucket.stats["ticks"]
            core.enqueue(owner.section, False, step)
            assert await wait_until(
                lambda: bucket.stats["ticks"] > before, 10)
        await core.stop()

    asyncio.run(main())
    from kcp_tpu.utils.trace import REGISTRY

    exposition = REGISTRY.expose()
    assert "fused_pipeline_depth_bucket" in exposition
    assert "fused_pipeline_window" in exposition
    # ticks ran through the fetch path, so exactly one of the ready/
    # blocked counters must have counted them
    assert ("fused_collect_ready_total" in exposition
            or "fused_collect_blocked_total" in exposition)


class _Lagging:
    """A device array stand-in that is not ready until waited on; around
    a real array (``arr``) the wait is the real one."""

    def __init__(self, arr=None):
        self.arr, self.waited = arr, False

    def is_ready(self) -> bool:
        return self.waited

    def block_until_ready(self) -> None:
        if self.arr is not None:
            self.arr.block_until_ready()
        self.waited = True


def test_staging_reuse_waits_for_the_step_that_read_the_buffer():
    """A staging buffer is handed out again only after the step that
    consumed it has finished: its output is committed with the puts. The
    CPU backend's device_put is zero-copy, so the put's own readiness says
    nothing — gated on the puts alone, a reused buffer lost a whole tick's
    events under load (found by chip_smoke.py's closed loop, PR 25)."""
    from kcp_tpu.models.reconcile_model import WireBuffers

    bufs = WireBuffers(depth=2)
    slot, packed, acks = bufs.acquire(64, S + 2, 8)
    # one buffer a slot: the ack lane is a view of the array's tail row
    assert packed.shape == (64 + 1, S + 2) and acks.base is not None
    assert np.shares_memory(acks, packed[64:])
    put, step_out = _Lagging(), _Lagging()
    put.waited = True  # the transfer is long done; the step is not
    bufs.commit(slot, put, step_out)
    packed[:] = 7
    bufs.acquire(64, S + 2, 8)  # the other slot: no wait
    assert not step_out.waited and bufs.reuse_waits == 0
    slot2, packed2, acks2 = bufs.acquire(64, S + 2, 8)
    assert slot2 == slot and packed2 is packed
    assert step_out.waited and bufs.reuse_waits == 1
    # reset only after the wait: event rows zero, the lane all padding
    assert not packed[:64].any() and (acks2 == -1).all()


def test_serving_core_commits_the_step_output_with_the_puts():
    """The submit path gates staging reuse on (packed, wire) — the ONE
    array it put, the ack lane in its tail rows, and the output of the
    step that read it — over one slot more than the in-flight window, so
    on a backend that keeps pace the gate never waits."""
    async def main():
        core = FusedCore(batch_window=0.0005)
        owner = RecordingOwner(core, 64)
        await core.start()
        owner.up_vals[:8] = 5
        core.enqueue_many(owner.section, False, list(range(8)))
        bucket = owner.section.bucket
        assert await wait_until(lambda: bucket.stats["ticks"] >= 1, 10)
        bufs = core._fleet._wire_bufs
        assert bufs.depth == PIPELINE_DEPTH + 1
        committed = [p for p in bufs._pending if p is not None]
        assert committed and all(len(p) == 2 for p in committed)
        packed_d, wire = committed[0]
        fleet = core._fleet
        assert wire.ndim == 1 and packed_d.shape == (
            MIN_EVENTS + ack_lane_rows(fleet.ack_capacity, S + 2), S + 2)
        assert np.asarray(packed_d).dtype == np.uint32
        # the lane crossed inside it, padding where nothing was acked
        lane = np.asarray(packed_d)[MIN_EVENTS:].reshape(-1)
        assert (lane[:fleet.ack_capacity].view(np.int32) == -1).all()
        await core.stop()
        assert bufs.reuse_waits == 0

    asyncio.run(main())


class _OneSlot(WireBuffers):
    """A single staging slot: every tick is handed the buffer of the tick
    before it, whose step's output is held not-ready."""

    def __init__(self):
        super().__init__(depth=1)
        self.handed: list[int] = []

    def acquire(self, *a):
        slot, packed, acks = super().acquire(*a)
        self.handed.append(id(packed))
        return slot, packed, acks

    def commit(self, slot, *arrays):
        # read not-ready until the gate has really waited for them (the
        # CPU backend would mostly have finished)
        super().commit(slot, *(_Lagging(a) for a in arrays))


def test_a_slot_handed_out_before_its_step_is_ready_blocks_and_loses_nothing():
    """One buffer a slot, the ack lane inside it: a slot that comes round
    again while the step that read it is still running BLOCKS on that
    step's output (`reuse_waits` rises) before the buffer is reset, and
    the run's patch stream is the serial reference's byte for byte — no
    tick's events lost to a buffer refilled under the step (the loss the
    WireBuffers docstring describes, found with only the put gating)."""

    async def main():
        bufs = _OneSlot()
        held, ticks = await _run_schedule("double", 27, bufs=bufs)
        serial, serial_ticks = await _run_schedule("serial", 27)
        assert ticks == serial_ticks and held == serial and len(held) > 0
        # ONE buffer a slot (no second per-slot list), handed out again
        # wherever the tick's shape is the one before's ...
        assert len(bufs._packed) == 1 and not hasattr(bufs, "_acks")
        assert len(set(bufs.handed)) < len(bufs.handed) == ticks
        # ... and every hand-out after the first waited for the put and
        # for the consuming step's output
        assert bufs.reuse_waits == 2 * (ticks - 1)

    asyncio.run(main())
