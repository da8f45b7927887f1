"""Fleet-wide ragged batching: the one device path, against an oracle.

The fleet batch (syncer/core.py FleetBatch) packs every schema bucket's
rows into ONE pipelined device program per tick; the buckets only stage
on the host. Over a seeded churn schedule spanning several buckets it
must emit, per owner, byte for byte the stream a few lines of numpy
predict from the owners' mirrors (no kcp_tpu.ops / kcp_tpu.models in the
prediction); it must preserve the PR 2 poison-row semantics
(segment-scoped bisection quarantining ONLY the poison rows), the PR 1
shutdown-drain ordering, and it must feed the admission quota ledger
from the device-side per-segment counters.
"""

import asyncio

import jax
import numpy as np
import pytest

from kcp_tpu import faults
from kcp_tpu.syncer.core import FusedCore

from helpers import wait_until


class Owner:
    """Open-loop SectionOwner at a chosen slot width: fixed mirrors,
    every patch recorded, NO feedback — so the stream is a function of
    the staging schedule alone and compares byte-for-byte with the
    oracle (the test_pipeline.py RecordingOwner pattern,
    width-parameterized)."""

    def __init__(self, core, b: int, s: int):
        self.core = core
        self.B, self.S = b, s
        mask = np.zeros(s, bool)
        mask[-2:] = True
        self._mask = mask
        self.up_vals = np.zeros((b, s), np.uint32)
        self.down_vals = np.zeros((b, s), np.uint32)
        self.stream: list[tuple[int, int, bool]] = []
        self.section = core.register(self, s)

    def fused_status_mask(self) -> np.ndarray:
        return self._mask

    def fused_encode(self, key: int):
        return self.up_vals[key], True, self.down_vals[key], True

    def fused_encode_many(self, keys):
        idx = np.fromiter(keys, np.int64, len(keys))
        ones = np.ones(idx.size, bool)
        return self.up_vals[idx], ones, self.down_vals[idx], ones

    def fused_apply(self, patches) -> None:
        self.stream.extend((int(k), int(c), bool(u)) for k, c, u in patches)

    def fused_overflow(self) -> None:  # pragma: no cover - fixed vocab
        raise AssertionError("fleet fuzz vocabulary never grows")


class LedgerOwner(Owner):
    """Owner that accounts to the quota ledger (the engine seam)."""

    def __init__(self, core, b, s, ledger_key):
        self._ledger_key = ledger_key
        super().__init__(core, b, s)

    def fused_ledger_key(self):
        return self._ledger_key


def _stream_bytes(stream) -> bytes:
    return np.asarray(
        [(k, c, int(u)) for k, c, u in stream], np.int64).tobytes()


WIDTHS = (16, 32)  # two slot widths -> two schema buckets
# bucket layouts of the fuzz: owner widths, the last a 3-row straggler.
# "two-width" is the ragged case (a straggler sharing the narrow bucket);
# "one-64" is the single 64-slot bucket every benchmark cell runs
LAYOUTS = {"two-width": (16, 32, 16), "one-64": (64, 64, 64)}
UPDATE = 2  # ops/diff.py: both sides exist and a non-status slot differs


class Oracle:
    """What one owner's stream must be, from its mirrors alone. The step
    is level-triggered: every tick reports, in row order (rows are
    allocated in first-touch order and never freed here), each resident
    row whose mirrors differ — code UPDATE when a non-status slot
    differs, ``upsync`` when a status slot does."""

    def __init__(self, owner: Owner):
        self.o = owner
        self.order: dict[int, None] = {}  # resident keys, row order
        self.stream: list[tuple[int, int, bool]] = []

    def touch(self, keys) -> None:
        self.order.update(dict.fromkeys(keys))

    def tick(self) -> None:
        keys = np.fromiter(self.order, np.int64, len(self.order))
        neq = self.o.up_vals[keys] != self.o.down_vals[keys]
        spec = (neq & ~self.o._mask).any(axis=1)
        status = (neq & self.o._mask).any(axis=1)
        self.stream.extend(
            (int(k), UPDATE if sp else 0, bool(st))
            for k, sp, st in zip(keys, spec, status) if sp or st)


def _churn(rng, o: Owner, pool: int) -> tuple[list[int], list[int]]:
    """Mutate a few of one owner's keys, one op each: a new upstream
    row, upstream status churn, downstream convergence (the acks lane:
    the event equals the resident upstream row) or downstream spec
    drift. Returns the keys whose (up, down) side to enqueue."""
    hi = min(pool, o.B)
    n = int(rng.integers(1, min(16, hi + 1)))
    ups, downs = [], []
    for key, op in zip(rng.choice(hi, size=n, replace=False).tolist(),
                       rng.integers(0, 4, n).tolist()):
        if op == 0:
            o.up_vals[key] = rng.integers(1, 2**32, o.S, dtype=np.uint32)
        elif op == 1:
            o.up_vals[key, -2:] = rng.integers(1, 2**32, 2, dtype=np.uint32)
        elif op == 2:
            o.down_vals[key] = o.up_vals[key]
        else:
            o.down_vals[key, 0] = rng.integers(1, 2**32, dtype=np.uint32)
        (ups if op < 2 else downs).append(key)
    return ups, downs


async def _run_schedule(seed: int, layout: str = "two-width", steps: int = 15,
                        mesh=None):
    """Drive one deterministic multi-bucket churn schedule in lockstep
    (all owners enqueue, then wait for every bucket to tick once) and
    return the owners (streams fully drained), their oracles and the
    stopped core."""
    core = FusedCore(batch_window=0.0005, pipeline="double", mesh=mesh)
    widths = LAYOUTS[layout]
    owners = [Owner(core, 256, w) for w in widths[:-1]]
    # a 3-row straggler section sharing a bucket: the ragged case the
    # fleet batch exists for
    owners.append(Owner(core, 3, widths[-1]))
    oracles = [Oracle(o) for o in owners]
    await core.start()
    buckets = list(core.buckets.values())
    assert len(buckets) == len(set(widths)), "one bucket per width"
    rng = np.random.default_rng(seed)
    pool = 100  # < patch capacity so level-triggered re-patches never overflow
    for step in range(steps):
        before = [b.stats["ticks"] for b in buckets]
        for o, oracle in zip(owners, oracles):
            ups, downs = _churn(rng, o, pool)
            core.enqueue_many(o.section, False, ups)
            core.enqueue_many(o.section, True, downs)
            oracle.touch(ups + downs)
        assert await wait_until(
            lambda: all(b.stats["ticks"] > t for b, t in zip(buckets, before)),
            10), f"tick never ran for step {step}"
        for oracle in oracles:
            oracle.tick()
    await core.stop()
    assert not core._inflight
    return owners, oracles, core


@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("seed", [2, 11, 29])
def test_ragged_vs_per_bucket_differential_fuzz(seed, layout):
    """Byte-identical per-owner patch streams, fleet vs the numpy oracle,
    across the bucket layout (including a 3-row straggler section):
    fleet packing must not reorder, duplicate, drop, or cross-route
    decisions."""

    async def main():
        steps = 15
        owners, oracles, core = await _run_schedule(seed, layout, steps)
        # the lockstep drove one staged batch per tick, and the whole
        # fleet rode ONE dispatch per tick, not one per bucket: the
        # oracle's one emission per step is the right count
        assert core._fleet.stats["ticks"] == steps
        assert [b.stats["ticks"] for b in core.buckets.values()] == (
            [steps] * len(core.buckets))
        for i, (o, oracle) in enumerate(zip(owners, oracles)):
            got, want = _stream_bytes(o.stream), _stream_bytes(oracle.stream)
            assert got == want, (
                f"seed={seed}: owner {i} stream diverged from the oracle "
                f"({len(o.stream)} vs {len(oracle.stream)} patches)")
        streams = [p for o in owners for p in o.stream]
        # not vacuous: updates, status-only upsyncs and the acks lane ran
        assert any(c == UPDATE for _k, c, _u in streams)
        assert any(c == 0 and u for _k, c, u in streams)
        assert core._fleet.stats["acked"] > 0

    asyncio.run(main())


def test_buckets_hold_no_device_state():
    """A bucket is host staging, the fleet batch the one device owner:
    after a two-bucket core has ticked, nothing a bucket holds is a
    device array or a compiled function, and every tick was ONE device
    program for both buckets."""

    async def main():
        _owners, _oracles, core = await _run_schedule(7, steps=4)
        assert len(core.buckets) == 2
        fleet = core._fleet
        assert isinstance(fleet._state.up_vals, jax.Array)
        assert fleet.stats["ticks"] == 4
        jitted = type(fleet._step)
        for b in core.buckets.values():
            assert b.stats["ticks"] == fleet.stats["ticks"]
            for name, value in vars(b).items():
                assert not any(isinstance(x, (jax.Array, jitted))
                               for x in jax.tree.leaves(value)), name

    asyncio.run(main())


def test_fleet_on_mesh_matches_unsharded_fleet():
    """The same schedule on an 8-device (virtual) tenants mesh emits the
    byte-identical streams the single-device fleet emits, and the fleet
    state actually carries the canonical row sharding."""
    from kcp_tpu.parallel.mesh import SLOTS_AXIS, TENANTS_AXIS, make_mesh

    async def main():
        single, _, _ = await _run_schedule(5)
        mesh = make_mesh(n_devices=8, tenants=8, slots=1)
        meshed, oracles, core = await _run_schedule(5, mesh=mesh)
        spec = core._fleet._state.up_vals.sharding.spec
        assert tuple(spec) == (TENANTS_AXIS, SLOTS_AXIS), spec
        # fleet rows pad to the row factor: 8-way mesh -> B % 8 == 0
        assert core._fleet.B % 8 == 0 and core._fleet.B > 0
        assert ([_stream_bytes(o.stream) for o in meshed]
                == [_stream_bytes(o.stream) for o in single]), (
            "mesh-sharded fleet diverged from single-device")
        assert ([_stream_bytes(o.stream) for o in meshed]
                == [_stream_bytes(o.stream) for o in oracles])

    asyncio.run(main())


# ---------------------------------------------------------------------------
# poison-row quarantine: segment-scoped bisection
# ---------------------------------------------------------------------------


def test_fleet_poison_quarantine_is_segment_scoped(monkeypatch):
    """device.step:poison_row=3 poisons bucket-LOCAL row 3 of every
    bucket. The fleet bisection must isolate within segments and
    quarantine ONLY those rows: every co-tenant in every bucket still
    converges."""
    # keep the wall-clock requeue backoff out of the run
    monkeypatch.setattr("kcp_tpu.syncer.core.QUARANTINE_BASE_BACKOFF", 0.001)

    async def main():
        faults.install(faults.FaultInjector("device.step:poison_row=3",
                                            seed=0))
        try:
            core = FusedCore(batch_window=0.0005, pipeline="double")
            owners = [Owner(core, 64, w) for w in WIDTHS]
            await core.start()
            fleet = core._fleet
            keys = list(range(30))
            for o in owners:
                o.up_vals[keys, 0] = 7  # diverge rows 0..29 in BOTH buckets
                core.enqueue_many(o.section, False, keys)
            # the poisoned fleet submission fails, retries once (full
            # re-upload, fails again), bisects BY SEGMENT, and
            # quarantines only local row 3 of each poisoned bucket
            assert await wait_until(
                lambda: fleet.stats["quarantined"] >= 2, 30), (
                "fleet never quarantined both buckets' poison rows")
            for i, o in enumerate(owners):
                assert await wait_until(
                    lambda o=o: {k for k, _c, _u in o.stream}
                    >= set(keys) - {3}, 30), (
                    f"owner {i} co-tenants stalled")
                assert 3 not in {k for k, _c, _u in o.stream}
                assert o.section.bucket.stats["quarantined"] >= 1
            assert fleet.stats["step_failures"] >= 2
            # lifting the fault lets the level-triggered loop recover
            # the quarantined keys (requeued with backoff)
            faults.clear()
            for o in owners:
                assert await wait_until(
                    lambda o=o: 3 in {k for k, _c, _u in o.stream}, 30), (
                    "quarantined key never recovered after the fault cleared")
            await core.stop()
        finally:
            faults.clear()

    asyncio.run(main())


def test_fleet_systemic_failure_still_propagates():
    """A row-independent failure (the empty probe fails too) must not be
    eaten by segment quarantine: after the single wholesale retry it
    surfaces, and the loop survives."""

    async def main():
        faults.install(faults.FaultInjector("device.step:raise", seed=0))
        try:
            core = FusedCore(batch_window=0.0005, pipeline="serial")
            owner = Owner(core, 64, 16)
            await core.start()
            owner.up_vals[0, 0] = 1
            before = core._fleet.stats["step_failures"]
            core.enqueue(owner.section, False, 0)
            assert await wait_until(
                lambda: core._fleet.stats["step_failures"] >= before + 2, 30)
            assert core._fleet.stats["quarantined"] == 0
            faults.clear()
            owner.up_vals[1, 0] = 2
            core.enqueue(owner.section, False, 1)
            assert await wait_until(
                lambda: 1 in {k for k, _c, _u in owner.stream}, 30)
            await core.stop()
        finally:
            faults.clear()

    asyncio.run(main())


# ---------------------------------------------------------------------------
# shutdown drain
# ---------------------------------------------------------------------------


def test_fleet_shutdown_drains_inflight_window():
    """No tick is lost with fleet wires in flight: churn across several
    buckets enqueued and never awaited must still deliver every owner's
    patches through stop()'s shutdown drain (PR 1 ordering: controller
    final ticks first, THEN the in-flight fleet wires)."""

    async def main():
        core = FusedCore(batch_window=0.0005, pipeline="double")
        owners = [Owner(core, 64, w) for w in WIDTHS]
        await core.start()
        touched = list(range(40))
        for o in owners:
            o.up_vals[touched, 0] = 9
            core.enqueue_many(o.section, False, touched)
        await core.stop()
        assert not core._inflight
        for i, o in enumerate(owners):
            patched = {k for k, _c, _u in o.stream}
            assert patched.issuperset(touched), (
                f"owner {i} lost {sorted(set(touched) - patched)} in shutdown")

    asyncio.run(main())


# ---------------------------------------------------------------------------
# device-side per-segment counters -> quota ledger
# ---------------------------------------------------------------------------


def test_fleet_segment_counts_feed_quota_ledger():
    """The fused step's per-segment live-row counts reach the attached
    quota ledger (admission accounting rides the batch), agree with the
    ledger's usage when accounting is correct, and flag drift when not."""
    from kcp_tpu.admission.quota import QuotaLedger

    async def main():
        ledger = QuotaLedger()
        core = FusedCore(batch_window=0.0005)
        core.ledger = ledger
        o1 = LedgerOwner(core, 64, 16, ("c1", "configmaps"))
        o2 = LedgerOwner(core, 64, 32, ("c2", "widgets"))
        await core.start()
        o1.up_vals[:10, 0] = 1
        o2.up_vals[:4, 0] = 1
        core.enqueue_many(o1.section, False, list(range(10)))
        core.enqueue_many(o2.section, False, list(range(4)))
        assert await wait_until(
            lambda: ledger.device_usage_of("c1", "configmaps") == 10
            and ledger.device_usage_of("c2", "widgets") == 4, 10), (
            ledger.snapshot())
        # ledger usage agrees -> the recount fast path may skip the host
        # walk for limited keys
        for _ in range(10):
            ledger.record("configmaps", "c1", +1)
        for _ in range(4):
            ledger.record("widgets", "c2", +1)
        ledger.set_hard("c1", "configmaps", 100)
        ledger.set_hard("c2", "widgets", 100)
        # a fresh tick re-reports the counts after the limits landed
        core.enqueue(o1.section, False, 0)
        await asyncio.sleep(0.05)
        assert ledger.device_counts_agree(60.0)
        # drift (an uncounted write) breaks agreement -> host recount runs
        ledger.record("configmaps", "c1", +1)
        assert not ledger.device_counts_agree(60.0)
        await core.stop()

    asyncio.run(main())


def test_fleet_patch_overflow_doubles_member_capacity():
    """Fleet overflow pools member budgets: overflow doubles every
    member's patch capacity and the level-triggered retick converges."""

    async def main():
        core = FusedCore(batch_window=0.0005)
        owners = [Owner(core, 64, w) for w in WIDTHS]
        for o in owners:
            o.section.bucket.patch_capacity = 8  # force overflow
        await core.start()
        keys = list(range(40))
        for o in owners:
            o.up_vals[keys, 0] = 3
            core.enqueue_many(o.section, False, keys)
        for o in owners:
            assert await wait_until(
                lambda o=o: {k for k, _c, _u in o.stream} >= set(keys), 30)
        assert core._fleet.stats["overflows"] >= 1
        assert all(o.section.bucket.patch_capacity > 8 for o in owners)
        await core.stop()

    asyncio.run(main())
