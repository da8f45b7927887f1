"""The device-count lane from a collected fleet wire to the quota ledger.

``FusedCore._publish_fleet_counts`` runs on every collect of every tick,
so it must not pay a Python turn per tenant: it caches a segment ->
ledger slot map and hands ``QuotaLedger.ingest_device_counts`` arrays.
These tests hold that vector pass to the per-section loop and the
per-key ledger walk it replaced (kept here as the oracle), and count
that a collect's work does not grow with the number of sections."""

from __future__ import annotations

import sys
import types

import numpy as np
import pytest

from kcp_tpu.admission import quota
from kcp_tpu.admission.quota import UNLIMITED, QuotaLedger
from kcp_tpu.syncer.core import FusedCore
from kcp_tpu.utils.trace import REGISTRY

SLOTS = 16
UPDATES = "fused_fleet_ledger_updates_total"
REBUILDS = "fused_fleet_ledger_map_rebuilds_total"
DRIFT = "quota_device_drift_total"
GAUGE = "quota_usage_device"


class StubOwner:
    """A section owner that is nobody's engine: the lane reads only
    ``fused_ledger_key`` (and a section's constructor the status mask)."""

    def fused_status_mask(self) -> np.ndarray:
        return np.zeros(SLOTS, bool)


class KeyedOwner(StubOwner):
    calls = 0  # fused_ledger_key calls, all instances

    def __init__(self, key):
        self._key = key

    def fused_ledger_key(self):
        KeyedOwner.calls += 1
        return self._key


class Oracle:
    """The lane as it stood before the vector pass: the core's loop over
    every registered section and the ledger's walk over every key, with
    the two counters and the gauge as plain numbers."""

    def __init__(self, ledger: QuotaLedger, clock):
        self.ledger = ledger  # read for usage and limits only
        self.clock = clock
        self.segments: dict[int, object] = {}
        self.device_counts: dict[tuple, int] = {}
        self.stamp = float("-inf")
        self.gauge = None
        self.drift = 0
        self.updates = 0

    def publish(self, seg_counts: np.ndarray) -> None:
        counts: dict[tuple, int] = {}
        released = []
        for seg, section in self.segments.items():
            if section.released:
                released.append(seg)
                continue
            if seg >= seg_counts.shape[0]:
                continue
            keyfn = getattr(section.owner, "fused_ledger_key", None)
            key = keyfn() if keyfn is not None else None
            if key is None:
                continue
            counts[key] = counts.get(key, 0) + int(seg_counts[seg])
        for seg in released:
            del self.segments[seg]
        if counts:
            self.ingest(counts)
            self.updates += 1

    def ingest(self, counts: dict) -> None:
        for key, n in counts.items():
            self.device_counts[key] = int(n)
            if self.ledger.usage_of(*key) != n:
                self.drift += 1
        self.stamp = self.clock()
        self.gauge = sum(counts.values())

    def agree(self, max_age: float) -> bool:
        if self.clock() - self.stamp > max_age:
            return False
        limited = [k for k, (_u, _r, hard) in self.ledger.snapshot().items()
                   if hard != UNLIMITED]
        if not limited:
            return False
        return all(self.device_counts.get(k) == self.ledger.usage_of(*k)
                   for k in limited)


def _rise(before: dict, name: str) -> float:
    return REGISTRY.snapshot().get(name, 0) - before.get(name, 0)


def _register(core: FusedCore, oracle: Oracle | None, owner) -> object:
    section = core.register(owner, SLOTS)
    if oracle is not None:
        oracle.segments[section.seg] = section
    return section


@pytest.fixture
def clock(monkeypatch):
    """A clock the test advances, for the ledger and the oracle alike."""
    now = [1000.0]
    monkeypatch.setattr(
        quota, "time", types.SimpleNamespace(monotonic=lambda: now[0]))
    return now


@pytest.mark.parametrize("seed", range(8))
def test_vector_lane_equals_per_section_loop(seed, clock):
    """Random register / release / collect sequences over sections that
    share a key, have no ``fused_ledger_key``, return None, or sit beyond
    the wire's segment capacity: after every collect the ledger's device
    lane, both counters and the gauge are what the loop would have left."""
    rng = np.random.default_rng(seed)
    # a small ledger, so that interning grows its arrays mid-sequence
    ledger = QuotaLedger(cap=4)
    core = FusedCore()
    core.ledger = ledger
    oracle = Oracle(ledger, lambda: clock[0])
    before = REGISTRY.snapshot()
    keys = [(f"c{i}", res) for i in range(12)
            for res in ("configmaps", "deployments.apps")]
    live: list = []
    collects = 0
    for step in range(240):
        op = rng.choice(["register", "release", "collect", "collect",
                         "usage", "settle", "limit", "clock"])
        if op == "register" or step < 4:
            kind = rng.choice(["keyed", "keyed", "keyed", "none", "bare"])
            if kind == "keyed":
                # few keys, many sections: sharing is the common case
                owner = KeyedOwner(keys[int(rng.integers(len(keys)))])
            elif kind == "none":
                owner = KeyedOwner(None)
            else:
                owner = StubOwner()
            live.append(_register(core, oracle, owner))
        elif op == "release" and live:
            live.pop(int(rng.integers(len(live)))).release()
        elif op == "usage":
            # make usage meet the device's count (agreement) or miss it
            key = keys[int(rng.integers(len(keys)))]
            want = oracle.device_counts.get(key, 0) + int(rng.integers(0, 2))
            ledger.record(key[1], key[0], want - ledger.usage_of(*key))
        elif op == "settle":
            # accounting catches up: every limited key's usage is what the
            # device last said, so the recount's fast path may open
            for key, (used, _r, hard) in ledger.snapshot().items():
                if hard != UNLIMITED and key in oracle.device_counts:
                    ledger.record(key[1], key[0],
                                  oracle.device_counts[key] - used)
        elif op == "limit":
            key = keys[int(rng.integers(len(keys)))]
            ledger.set_hard(*key, int(rng.choice([UNLIMITED, 100])))
        elif op == "clock":
            clock[0] += float(rng.uniform(0, 25))
        elif op == "collect":
            # the wire's capacity is the submit's: usually it covers every
            # segment, a wire submitted before a registration does not
            full = max(8, 1 << int(core._next_seg).bit_length())
            cap = full if rng.random() < 0.7 else max(
                1, int(rng.integers(1, core._next_seg + 2)))
            seg_counts = rng.integers(0, 5, cap).astype(np.int32)
            oracle.publish(seg_counts)
            core._publish_fleet_counts(seg_counts)
            collects += 1
            for key in keys:
                assert ledger.device_usage_of(*key) == \
                    oracle.device_counts.get(key), (seed, collects, key)
            assert _rise(before, UPDATES) == oracle.updates
            assert _rise(before, DRIFT) == oracle.drift
            if oracle.gauge is not None:
                assert REGISTRY.snapshot()[GAUGE] == oracle.gauge
            assert set(core._segments) == set(oracle.segments)
        assert ledger.device_counts_agree(30.0) == oracle.agree(30.0)
    assert collects > 20 and oracle.updates > 20, (collects, oracle.updates)


def test_agreement_is_reached_and_lost(clock):
    """The recount controller's fast path sees what it saw: fresh,
    agreeing counts of every limited key, until usage or the clock move."""
    ledger = QuotaLedger(cap=2)
    core = FusedCore()
    core.ledger = ledger
    # three engines of one workspace report one key: their counts sum
    for _ in range(3):
        _register(core, None, KeyedOwner(("ws", "deployments.apps")))
    _register(core, None, KeyedOwner(("other", "configmaps")))
    ledger.set_hard("ws", "deployments.apps", 50)
    ledger.record("deployments.apps", "ws", 9)
    assert ledger.device_usage_of("ws", "deployments.apps") is None
    assert not ledger.device_counts_agree(60.0)
    core._publish_fleet_counts(np.array([2, 3, 4, 7, 0, 0, 0, 0], np.int32))
    assert ledger.device_usage_of("ws", "deployments.apps") == 9
    assert ledger.device_usage_of("other", "configmaps") == 7
    assert REGISTRY.snapshot()[GAUGE] == 16
    assert ledger.device_counts_agree(60.0)
    clock[0] += 61.0
    assert not ledger.device_counts_agree(60.0)
    core._publish_fleet_counts(np.array([2, 3, 4, 7, 0, 0, 0, 0], np.int32))
    assert ledger.device_counts_agree(60.0)  # every collect restamps
    ledger.record("deployments.apps", "ws", 1)
    assert not ledger.device_counts_agree(60.0)


def test_another_ledger_rebuilds_the_map():
    """``core.ledger`` may be assigned after construction: slots of one
    ledger mean nothing in another."""
    core = FusedCore()
    first, second = QuotaLedger(), QuotaLedger()
    second.record("x", "pad", 1)  # so that the two intern differently
    _register(core, None, KeyedOwner(("c1", "configmaps")))
    core.ledger = first
    core._publish_fleet_counts(np.full(8, 5, np.int32))
    before = REGISTRY.snapshot()
    core.ledger = second
    core._publish_fleet_counts(np.full(8, 6, np.int32))
    assert _rise(before, REBUILDS) == 1
    assert first.device_usage_of("c1", "configmaps") == 5
    assert second.device_usage_of("c1", "configmaps") == 6
    assert second.device_usage_of("pad", "x") is None
    core.ledger = None  # KCP_ADMISSION=0, a remote store: nothing to feed
    core._publish_fleet_counts(np.full(8, 7, np.int32))
    assert second.device_usage_of("c1", "configmaps") == 6


def test_a_limit_set_while_the_arrays_grow_is_kept():
    """``set_hard`` on a key that is interned at the arrays' edge: the
    limit lands in the grown array (it indexed the old one), and the
    device lane grows beside usage with its "never reported" state."""
    ledger = QuotaLedger(cap=1)
    ledger.set_hard("a", "configmaps", 5)
    ledger.set_hard("b", "configmaps", 7)  # interning "b" doubles the arrays
    ledger.set_hard("c", "configmaps", 9)  # and again
    assert [ledger.peek(c, "configmaps")[2] for c in "abc"] == [5, 7, 9]
    assert ledger.device_usage_of("c", "configmaps") is None
    ledger.ingest_device_counts(ledger.device_slots([("c", "configmaps")]),
                                np.array([3]))
    assert ledger.device_usage_of("c", "configmaps") == 3
    assert ledger.device_usage_of("b", "configmaps") is None


def _lines_of_a_collect(core: FusedCore, seg_counts: np.ndarray) -> int:
    """Python lines executed by one collect, every callee included."""
    lines = 0

    def tracer(_frame, event, _arg):
        nonlocal lines
        if event == "line":
            lines += 1
        return tracer

    previous = sys.gettrace()
    sys.settrace(tracer)
    try:
        core._publish_fleet_counts(seg_counts)
    finally:
        sys.settrace(previous)
    return lines


@pytest.mark.parametrize("n", [1000, 4000])
def test_collect_cost_does_not_grow_with_sections(n):
    """By counting: once the map is built a collect reads no section's
    ledger key and runs the same few lines at any size; a register or a
    release makes the NEXT collect rebuild once, reading each live
    section once."""
    core = FusedCore()
    core.ledger = ledger = QuotaLedger()
    sections = [
        _register(core, None, KeyedOwner((f"c{i // 8}", "deployments.apps")))
        for i in range(n)]
    seg_counts = np.ones(1 << n.bit_length(), np.int32)

    def collect_and_count() -> tuple[int, float]:
        KeyedOwner.calls = 0
        before = REGISTRY.snapshot()
        core._publish_fleet_counts(seg_counts)
        return KeyedOwner.calls, _rise(before, REBUILDS)

    assert collect_and_count() == (n, 1)
    for _ in range(3):
        assert collect_and_count() == (0, 0)
    assert ledger.device_usage_of("c0", "deployments.apps") == 8
    # what "constant" means here: the same lines (64, registry and lock
    # included) at 1,000 and at 4,000; the loop ran seven a section
    assert _lines_of_a_collect(core, seg_counts) < 100

    sections.append(_register(core, None, KeyedOwner(("late", "configmaps"))))
    assert collect_and_count() == (n + 1, 1)
    assert collect_and_count() == (0, 0)
    assert ledger.device_usage_of("late", "configmaps") == 1

    sections[3].release()
    sections[n].release()
    assert collect_and_count() == (n - 1, 1)
    assert collect_and_count() == (0, 0)
    assert len(core._segments) == n - 1
    # a released section's rows are gone from the device's count too (the
    # wire would say so); here the stub wire still says 1 per segment
    assert ledger.device_usage_of("c0", "deployments.apps") == 7
    assert ledger.device_usage_of("late", "configmaps") == 1  # not re-reported
