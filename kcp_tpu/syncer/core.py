"""FusedCore — the served control plane runs the flagship device program.

The reference runs one goroutine pair per (cluster, GVR)
(pkg/syncer/syncer.go:46-64 StartSyncer); round 1 of this build ran one
small device program per (cluster, GVR). This module closes the gap
between the benched program and the served one: every sync engine in the
process registers a row *section* inside a shared schema bucket, and each
reconcile tick runs ONE fused ``reconcile_step_fleet`` over every
bucket's rows — resident donated state, packed one-array-each-way wire
format, pipelined collection.

Topology (a bucket stages on the host, the fleet batch owns the device):

  FusedCore ── one per asyncio loop (the process's serving loop)
    ├── BatchController      one tick loop draining all engines' events
    ├── FusedBucket(S)       one per slot capacity (the schema bucket):
    │     │                  host [B, S] mirrors + per-row status masks
    │     │                  (engines have different slot vocabularies,
    │     │                  so masks are [B, S]), staged wire entries
    │     └── Section        one per engine: a set of rows + callbacks
    └── FleetBatch           the one device owner: every bucket's rows
                             in one resident ReconcileState, one jitted
                             step, the wire buffers, probe + bisection

Tick pipeline — three explicit stages with a PIPELINE_DEPTH-deep
in-flight window (pipeline="double", the default; "serial" runs the
stages back-to-back as the A/B reference):

  drain/pack  — drain events (the NEXT batch drains concurrently with
                this tick: BatchController overlap_drain), engines
                encode touched keys, buckets stage rows, the fleet packs
                them into one of its rotating pre-allocated wire buffers
                (WireBuffers — tick N's device_put never races tick
                N+1's packing); the ack lane is laid into that buffer's
                tail rows, so the tick's input is ONE array
  dispatch    — ONE device_put (the packed array, ack lane and all; to
                every device of a serving mesh) + fused step (donated
                resident state; it splits the lane off at its head) +
                wire.copy_to_host_async(); the host never blocks here
  fetch/apply — a wire is fetched when the device has finished it:
                on an asynchronous backend (the chip) a waiter thread
                blocks on each submitted wire with the GIL released and
                WAKES the loop (call_soon_threadsafe -> _on_wire_ready),
                nothing polls; on the synchronous CPU backend once the
                loop has been quiet for IDLE_FLUSH_S. A wire beyond the
                in-flight window (2 ticks old) is fetched by the tick
                itself — blocking ONLY on the compact patch wire, never
                the donated state. Collects run on the loop, in submit
                order: unpacked and routed to owning sections; engines'
                appliers take it from there without blocking the tick

Patch overflow: the wire carries at most ``patch_capacity`` actionable
rows. Because the loop is level-triggered (every tick re-decides every
row), overflow loses nothing — the core doubles capacity (one recompile)
and re-ticks.

Mesh serving: pass ``mesh=`` to shard the fleet state over a
(tenants, slots) device mesh — same layout as ``parallel/mesh.py`` and
``dryrun_multichip``. Stats reductions lower to cross-device collectives;
the packed wire batch is replicated (it is O(events), not O(fleet)).
"""

from __future__ import annotations

import asyncio
import logging
import os
import queue
import threading
import time
from typing import Callable, NamedTuple, Protocol, Sequence

import jax
import numpy as np

from .. import faults, obs
from ..models.reconcile_model import (
    MASK_STAMP_BIT,
    PACK_HDR,
    SEG_NONE,
    SEG_SHIFT,
    ReconcileState,
    WireBuffers,
    reconcile_step_fleet,
    unpack_patches,
    unpack_placement,
    unpack_seg_counts,
)
from ..ops.encode import pad_pow2
from ..reconciler.controller import BatchController
from ..utils.trace import DEPTH_BUCKETS, REGISTRY

log = logging.getLogger(__name__)


def _grown(a: np.ndarray, shape, dtype) -> np.ndarray:
    """Zero-padded copy of ``a`` at a larger ``shape`` (growth helper for
    the mirror and staging buffers)."""
    out = np.zeros(shape, dtype)
    out[: a.shape[0], ...] = a
    return out


#: the phases of a tick, host time each (``fused_<phase>_seconds``): the
#: 'where does tick time go' answer that /debug/profile and the
#: benchmark's layer readers report. ``encode`` is observed only on
#: ticks that touched keys,
#: ``full_upload`` replaces ``pack`` on a tick that re-uploads the whole
#: mirror, and ``compile`` replaces ``step_dispatch`` on the first
#: dispatch of a set of shapes and static arguments (XLA compiles it, or
#: reads it from the disk cache, on the loop), so the means stay
#: meaningful. Histograms fetched once.
TICK_PHASES = ("encode", "pack", "full_upload", "put", "step_dispatch",
               "compile", "collect_wait", "dispatch")
_PHASE_H = {p: REGISTRY.histogram(f"fused_{p}_seconds",
                                  "host time of one tick phase")
            for p in TICK_PHASES}
_TICK_H = REGISTRY.histogram(
    "fused_tick_seconds",
    "one fused tick, whole: tick start -> its patches dispatched to the "
    "owners (every phase, pack included, and the pipeline's wait for "
    "the wire)")
_FLEET_TICKS = REGISTRY.counter(
    "fused_fleet_ticks_total", "fleet-wide ragged batch steps dispatched")
_ENCODED_ROWS = REGISTRY.counter(
    "fused_encoded_rows_total",
    "rows (touched keys, both sides of each) re-encoded by the ticks' "
    "encode phase: fused_encode_seconds' sum over this is the host cost "
    "of one row")
# how far the encode phase shares its per-call cost: sections a tick
# gathered over the bucket-wide stagings that carried them
_ENCODED_SECTIONS = REGISTRY.counter(
    "fused_encoded_sections_total",
    "sections whose touched keys an encode phase gathered into a "
    "bucket's batch")
_STAGE_BATCHES = REGISTRY.counter(
    "fused_stage_batches_total",
    "batches an encode phase staged: one a tick for each bucket with "
    "touched rows, however many sections brought them (one a section "
    "where their vectors' widths differ)")
# growth of the fleet batch: each is a full upload or a new program for
# the jitted step, on the serving loop
_ROW_GROWTHS = REGISTRY.counter(
    "fused_fleet_row_growths_total",
    "changes of the fleet batch's row count B after its first layout "
    "(a bucket passed a power of two, or a bucket joined)")
_SEGMENT_GROWTHS = REGISTRY.counter(
    "fused_fleet_segment_growths_total",
    "changes of the fleet step's static segment capacity (registered "
    "sections passed a power of two)")
_PATCH_GROWTHS = REGISTRY.counter(
    "fused_fleet_patch_growths_total",
    "changes of the fleet step's static pooled patch capacity (a patch "
    "overflow doubled it, or B grew beneath it)")
_UPLOAD_BYTES = REGISTRY.counter(
    "fused_fleet_state_upload_bytes_total",
    "bytes of resident fleet state put on the device by full uploads")
# what a serving mesh costs the tick: the packed wire (the ack lane in its
# tail rows) goes to every device of the mesh, replicated
_MESH_SHARDS = REGISTRY.gauge(
    "fused_fleet_mesh_shards",
    "devices the fleet state's rows are sharded over (the serving "
    "mesh's row factor; 1 with no mesh)")
_PUT_BYTES = REGISTRY.counter(
    "fused_fleet_put_bytes_total",
    "bytes the ticks' put phase handed to the devices: the packed event "
    "wire with the ack lane in its tail rows, times the devices it is "
    "written to (every device of a serving mesh; 1 with no mesh)")
_PUTS = REGISTRY.counter(
    "fused_fleet_puts_total",
    "host->device transfers the ticks' submits made: every "
    "jax.device_put call — the packed wire, and the placement-leaves "
    "swap's two on a tick that has one — times the devices each is "
    "written to (a full upload's state leaves are not counted here: "
    "fused_fleet_state_upload_bytes_total)")
_PL_RETIRED = REGISTRY.counter(
    "fused_placement_rows_retired_total",
    "placement rows retired by their owner (free_pl_row found the key): "
    "each is zeroed on the device by the next tick's placement-leaves "
    "swap and comes free when that tick's wire has been dispatched")
# a row follows the LIVE object: retired when both sides of its key are
# gone, held back while a wire in flight can still name it, then reused
_ROWS_RETIRED = REGISTRY.counter(
    "fused_rows_retired_total",
    "sync rows retired because both sides of their key were gone (the "
    "section forgot the key; the row waits for its tick's wire)")
_ROWS_REUSED = REGISTRY.counter(
    "fused_rows_reused_total",
    "rows a new key took from a bucket's free list")
_ROWS_FRESH = REGISTRY.counter(
    "fused_rows_fresh_total",
    "rows a new key took past the bucket's high-water mark (the only "
    "allocations that can make B grow)")
_ROWS_HELD = REGISTRY.gauge(
    "fused_rows_held_back",
    "retired rows not yet free: waiting for a submit, or riding a wire "
    "that has not been dispatched")
_ROW_RETIRE_H = REGISTRY.histogram(
    "fused_row_retire_seconds",
    "both sides of a key seen gone -> its row free for another key "
    "(the wait for the tick's submit and for that wire's dispatch)")
# who began a collect, and what is left of the wait for a wire: stamped
# with perf_counter on the loop (submit, collect) and on the waiter
# thread (ready), observed on the loop
_COLLECT_WOKEN = REGISTRY.counter(
    "fused_collect_woken_total",
    "collects begun by the waiter thread's wake (the wire was on the "
    "host; asynchronous backends only)")
_COLLECT_DEPTH = REGISTRY.counter(
    "fused_collect_depth_total",
    "collects made by a tick's depth rule (a wire beyond the in-flight "
    "window: the fetch may block) or by the shutdown drain")
_WIRE_READY_H = REGISTRY.histogram(
    "fused_wire_ready_seconds",
    "end of a tick's submit -> the waiter thread saw its wire on the "
    "host: launch, device step and fetch, as the host sees them")
_COLLECT_LAG_H = REGISTRY.histogram(
    "fused_collect_lag_seconds",
    "the waiter thread saw a wire ready -> its collect began on the "
    "loop: the GIL hand-over and the wake's turn in the loop's queue")


class _Phases:
    """A cursor over the consecutive phases of one synchronous section
    of a tick: ``enter(name)`` closes the phase before it at the same
    clock read, so adjacent phases share their boundary. Each phase is a
    ``fused_<name>_seconds`` observation and a ``kcp.tick.<name>``
    section (``obs.annotate``: the loop ledger's self seconds, and an
    annotation on the profiler's clock while a session is open), which
    is handed the stamp read here. One perf_counter read per boundary,
    never per row; ``close()`` belongs in a ``finally`` (a section is a
    begin/end pair)."""

    __slots__ = ("_name", "_t", "_ann")

    def __init__(self) -> None:
        self._name = None

    def enter(self, name: str) -> None:
        now = time.perf_counter()
        self._end(now)
        self._name, self._t = name, now
        self._ann = obs.annotate(f"kcp.tick.{name}")
        self._ann.begin(now)

    def close(self) -> None:
        self._end(time.perf_counter())

    def discard(self) -> None:
        """End the open phase without an observation."""
        if self._name is not None:
            self._ann.end(time.perf_counter())
            self._name = None

    def _end(self, now: float) -> None:
        if self._name is not None:
            self._ann.end(now)
            _PHASE_H[self._name].observe(now - self._t)
            self._name = None

MIN_ROWS = 64
MIN_EVENTS = 64
MIN_PATCH_CAPACITY = 256
# pipelined tick window: in-flight steps before a blocking
# collect. Depth 2 is the double-buffered pipeline — while the device
# executes tick N, the host packs tick N+1 and applies tick N-1.
# WireBuffers rotates one staging slot more than the window, so a slot
# comes round again only after the step that read it was collected: its
# reuse gate (that step's output) then never waits. "serial"
# mode (depth 0) is the A/B reference: pack -> step -> fetch -> apply
# with no overlap, the sum-of-phases loop the pipeline exists to beat.
PIPELINE_DEPTH = 2
PIPELINE_MODES = ("serial", "double")
IDLE_FLUSH_S = 0.003  # collect leftovers when no new tick arrives
# poison-row quarantine: a failed device step is retried once wholesale
# (full re-upload from the host mirrors); a second consecutive failure
# bisects the submitted rows with probe steps to isolate the poison.
# Quarantined keys are requeued to their owners with bounded backoff.
QUARANTINE_BASE_BACKOFF = 0.05
QUARANTINE_MAX_BACKOFF = 5.0
BISECT_MAX_PROBES = 64
# a queue item with no section: "tick again" (placement staged outside the
# section path, a failed step's retry, a patch overflow). One constant, so
# the work queue's dedup folds any number of requests into one tick.
_RETICK = ("__retick__", False, None, None)


def _group_test_poison(probe: Callable[[Sequence[int]], bool],
                       groups: Sequence[Sequence[int]],
                       max_probes: int) -> list[int]:
    """The shared bisection loop: group-test ``groups`` of suspect rows
    against a probe oracle (~k*log2(n) probes for k poisons). Seeding
    with one group per segment makes the fleet bisection segment-scoped:
    a clean segment is cleared in ONE probe, and poison isolates within
    its own segment without probing cross-segment mixtures."""
    bad: list[int] = []
    stack: list[list[int]] = [list(g) for g in groups if g]
    probes = 0
    while stack:
        rows = stack.pop()
        if not rows:
            continue
        if probes >= max_probes:
            log.warning("fused-core: bisection probe budget exhausted; "
                        "quarantining %d unresolved rows wholesale",
                        len(rows))
            bad.extend(rows)
            continue
        probes += 1
        if probe(rows):
            continue
        if len(rows) == 1:
            bad.append(rows[0])
        else:
            mid = len(rows) // 2
            stack.append(rows[:mid])
            stack.append(rows[mid:])
    return bad


class SectionOwner(Protocol):
    """What an engine provides to its section (see BatchSyncEngine)."""

    def fused_encode(self, key) -> tuple[np.ndarray, bool, np.ndarray, bool]:
        """(up_vals[S], up_exists, down_vals[S], down_exists) for a key,
        re-read from the informer caches. May raise BucketOverflow."""
        ...

    def fused_status_mask(self) -> np.ndarray:
        """bool[S] — the engine's current status-slot mask. Asked once a
        touched section a tick: a mask that has not changed is the SAME
        array as last time (``Section.refresh_mask`` returns on
        identity), one that has is another array; the section copies
        what it keeps."""
        ...

    def fused_apply(self, patches: list[tuple[object, int, bool]]) -> None:
        """Receive (key, decision_code, upsync) patches for this engine's
        rows. Must not block the loop (hand off to an applier pool)."""
        ...

    def fused_overflow(self) -> None:
        """The engine's slot vocabulary outgrew its bucket: grow the
        encoder, re-register in a larger bucket, replay all rows."""
        ...

    # optional: ``fused_retired(keys)`` — both sides of these keys are
    # gone and the section has forgotten them (Section.retire): whatever
    # the owner keeps per key goes too


class Section:
    """One engine's row allocation inside a bucket."""

    def __init__(self, bucket: "FusedBucket", owner: SectionOwner):
        self.bucket = bucket
        self.owner = owner
        self.rows: dict[object, int] = {}  # key -> global row
        self.row_keys: dict[int, object] = {}  # global row -> key
        # seed the mask cache now: row_for stamps every new row with the
        # current mask, so refresh_mask must only fire on real changes
        self._owner_mask: np.ndarray = owner.fused_status_mask()
        self._mask: np.ndarray = self._owner_mask.copy()
        # fleet segment id (FusedCore.register assigns it): the per-row
        # identity the ragged fleet batch carries on device so the
        # per-segment counters can attribute live rows to this section
        self.seg: int | None = None
        # the registering core (FusedCore.register sets it beside seg):
        # a release tells it that the set of live sections changed
        self.core: "FusedCore | None" = None
        self.released = False

    def row_for(self, key) -> int:
        row = self.rows.get(key)
        if row is None:
            row = self.bucket.alloc_row(self)
            self.rows[key] = row
            self.row_keys[row] = key
            # stamp with the cached mask; refresh_mask restamps everything
            # if the owner's vocabulary has drifted since. A row taken
            # from the free list still holds its last occupant's mask,
            # which may be another section's
            self.bucket.status_mask[row] = False
            self.bucket.status_mask[row, : self._mask.shape[0]] = self._mask
            # the DEVICE must see this stamp too: the delta wire carries
            # values only, and without a mask stamp a row allocated after
            # the last full upload reads its status churn as spec churn
            # forever (fuzz-found) — ship it as a wire entry. A stale
            # bucket needs no stamp: the pending full upload carries the
            # host mask arrays wholesale (and bulk row preallocation
            # before the first tick would otherwise stage one per row).
            # EVERY new row stamps (even an all-False mask): the stamp
            # entry is also how the device learns the row's segment id
            # for the per-segment counters
            if not self.bucket._stale:
                self.bucket.stage_mask(row, self.bucket.status_mask[row])
        return row

    def refresh_mask(self) -> None:
        """Restamp this section's rows after the owner's vocabulary grew
        new status slots (rare; triggers a full re-upload)."""
        mask = self.owner.fused_status_mask()
        if mask is self._owner_mask:
            return
        self._owner_mask = mask
        if np.array_equal(self._mask, mask):
            return
        self._mask = mask.copy()
        for row in self.rows.values():
            self.bucket.status_mask[row] = False
            self.bucket.status_mask[row, : mask.shape[0]] = mask
        self.bucket.mark_stale()

    def retire_gone(self, keys: Sequence) -> None:
        """Of ``keys`` — the informer caches hold neither of their
        objects, and this tick's events are staged — retire those whose
        host mirrors read absent on both sides, as the device's row
        will once this tick's wire lands. A side not staged in this
        tick keeps what an earlier tick staged: its own event is still
        to come, and the key keeps its row until then."""
        up, down = self.bucket.up_exists, self.bucket.down_exists
        gone = [k for k in keys
                if not (up[row := self.rows[k]] or down[row])]
        if gone:
            self.retire(gone)

    def retire(self, keys: Sequence) -> None:
        """Forget keys whose objects are gone on BOTH sides and give
        their rows back by the ordinary delta wire — no ``mark_stale``,
        no upload, no wire entry of its own.

        The caller has staged this tick's events: the host mirrors of
        each row read ``up_exists == down_exists == False`` with zero
        values, and the tick's wire (or its full upload) makes the
        device's row read the same. The invariant, as ``free_pl_row``
        has it for placement rows: a row is in ``_free`` only when the
        device's row reads absent-and-zero AND no wire still to be
        dispatched can name it. A wire submitted before this tick's may
        still hold a patch of the old key (a level-triggered DELETE
        re-emitted while the downstream delete was in flight), and
        ``route_patches`` maps row -> key when that wire is dispatched:
        released now, the patch would be routed to the row's next
        occupant. So the row waits in the bucket's ``_retiring`` until a
        submit takes it (``_take_rows_retiring``) and enters ``_free``
        when THAT wire is dispatched (``FleetBatch.dispatch``); wires
        are dispatched in submit order, and every later wire was built
        on a device row that reads absent. Until then a patch for the
        row is dropped and counted (``route_patches``), and the key,
        written again, gets another row.

        The next occupant is a key new to its section: the tick's
        encode stages both sides for it (``_gather_section``'s side
        mask, in its bucket's batch) and ``row_for`` stamps its own
        status mask and segment id, whichever section held the row
        before."""
        bucket = self.bucket
        now = time.monotonic()
        for key in keys:
            row = self.rows.pop(key)
            del self.row_keys[row]
            del bucket.row_owner[row]
            bucket._retiring.append(row)
            bucket._held[row] = now
        _ROWS_RETIRED.inc(len(keys))
        retired = getattr(self.owner, "fused_retired", None)
        if retired is not None:
            retired(keys)

    def release(self) -> None:
        self.released = True
        if self.core is not None:
            self.core._segments_version += 1
        for row in self.rows.values():
            self.bucket.free_row(row)
        self.rows.clear()
        self.row_keys.clear()


class FusedBucket:
    """One schema bucket: host staging only. The [B, S] mirrors, row
    allocation, sections, placement rows and the staged wire-layout
    entries live here; the FleetBatch packs them and owns the device."""

    def __init__(self, slots: int, mesh=None):
        self.S = slots
        self.B = 0
        self.mesh = mesh
        # sharded state must split cleanly: row counts are padded to
        # a multiple of the row-axis product (see _grow), and the slots
        # axis must divide the (power-of-two) slot capacity up front
        self._row_factor = 1
        if mesh is not None:
            from ..parallel.mesh import row_factor, slot_factor

            self._row_factor = row_factor(mesh)
            slot_dim = slot_factor(mesh)
            if slots % slot_dim:
                raise ValueError(
                    f"bucket slot capacity {slots} is not divisible by the "
                    f"mesh slots axis ({slot_dim}); use a power-of-two "
                    f"slots axis"
                )
        self.up_vals = np.zeros((0, slots), np.uint32)
        self.down_vals = np.zeros((0, slots), np.uint32)
        self.up_exists = np.zeros(0, bool)
        self.down_exists = np.zeros(0, bool)
        self.status_mask = np.zeros((0, slots), bool)
        self.sections: list[Section] = []
        self.row_owner: dict[int, Section] = {}
        # LIFO, so the rows in use stay dense at the low end
        self._free: list[int] = []
        self._next = 0
        # rows retired since the last submit (Section.retire): the next
        # wire carries their absent-and-zero mirrors
        self._retiring: list[int] = []
        # every retired row that is not free yet (in _retiring, or riding
        # a wire's FleetMeta to its dispatch) -> when it was retired
        self._held: dict[int, float] = {}
        # placement lanes (the deployment splitter's serving section):
        # root rows with replicas + per-cluster availability, returned as
        # compacted dirty rows in the wire's placement segment
        self.placement_owner = None
        self.P = 8
        self.R = 0
        self.pl_replicas = np.zeros(0, np.int32)
        self.pl_avail = np.zeros((0, 8), bool)
        self.pl_rows: dict[object, int] = {}
        self.pl_row_keys: dict[int, object] = {}
        self._pl_free: list[int] = []
        # rows retired since the last submit: zeroed on the host, their
        # `current` on the device not yet (see free_pl_row)
        self._pl_retiring: list[int] = []
        self._pl_next = 0
        self._pl_staged = False
        self._stale = True
        self.patch_capacity = MIN_PATCH_CAPACITY
        # staged events for the next tick, accumulated directly in the
        # packed-wire layout (vals / row / flags) with last-wins dedup via
        # an O(1) (row<<1|side) -> slot map. The dict-of-arrays this
        # replaced cost ~23ms/tick at bench scale (encode staging + the
        # np.stack repack); the array form stages and packs in ~2ms.
        self._staged_slot = np.full(0, -1, np.int32)  # [2B] key -> slot
        self._staged_vals = np.zeros((0, slots), np.uint32)
        self._staged_rows = np.zeros(0, np.uint32)
        self._staged_flags = np.zeros(0, np.uint32)
        self._staged_keys = np.zeros(0, np.int64)  # slot -> key, for reset
        # converged-row ack compression (reconcile_step_packed's acks
        # lane): a down-side event equal to the resident up mirror ships
        # as a 4-byte row index instead of an (S+2)-column entry
        self._staged_ack = np.zeros(0, bool)
        self._staged_n = 0
        # mask stamps for rows allocated since the last full upload
        # (row -> bool[S]); ride the packed wire as MASK_STAMP entries
        self._staged_masks: dict[int, np.ndarray] = {}
        # acks-lane floor this bucket asks of the fleet wire (a caller
        # that knows its burst size pre-warms it): the fleet's sticky
        # high-water never drops below it, so the (packed, acks) shape
        # pair stays stable after warmup — a mid-serving growth costs a
        # recompile, seconds of p99, while padding costs ~µs
        self.ack_capacity = 1024
        self._dropped_logged: set[int] = set()
        self.stats = {"ticks": 0, "full_uploads": 0, "overflows": 0,
                      "acked": 0, "step_failures": 0, "quarantined": 0}

    # ------------------------------------------------------------- rows

    def section(self, owner: SectionOwner) -> Section:
        s = Section(self, owner)
        self.sections.append(s)
        return s

    def alloc_row(self, section: Section) -> int:
        if self._free:
            row = self._free.pop()
            _ROWS_REUSED.inc()
        else:
            if self._next >= self.B:
                self._grow(self._next + 1)
            row = self._next
            self._next += 1
            _ROWS_FRESH.inc()
        self.row_owner[row] = section
        return row

    def free_row(self, row: int) -> None:
        self.up_exists[row] = self.down_exists[row] = False
        self.up_vals[row] = self.down_vals[row] = 0
        self.row_owner.pop(row, None)
        self._free.append(row)
        self.mark_stale()

    def _grow(self, needed: int) -> None:
        new_b = pad_pow2(max(needed, MIN_ROWS))
        if new_b % self._row_factor:
            # non-power-of-two row sharding (e.g. a 5-device tenants
            # axis): round up so every row dimension shards cleanly
            new_b += self._row_factor - new_b % self._row_factor

        self.up_vals = _grown(self.up_vals, (new_b, self.S), np.uint32)
        self.down_vals = _grown(self.down_vals, (new_b, self.S), np.uint32)
        self.up_exists = _grown(self.up_exists, (new_b,), bool)
        self.down_exists = _grown(self.down_exists, (new_b,), bool)
        self.status_mask = _grown(self.status_mask, (new_b, self.S), bool)
        slot = np.full(2 * new_b, -1, np.int32)
        slot[: self._staged_slot.shape[0]] = self._staged_slot
        self._staged_slot = slot
        self.B = new_b
        self.mark_stale()

    def mark_stale(self) -> None:
        self._stale = True

    # -------------------------------------------------------- placement

    def register_placement(self, owner, p: int = 8) -> None:
        """Attach the deployment splitter as this bucket's placement
        owner: its roots ride the replicas/avail lanes of the SAME fused
        step that serves the sync sections (VERDICT r3 item 5 — the
        serving tick computes real placement, not zeros)."""
        if self.placement_owner is not None and self.placement_owner is not owner:
            raise RuntimeError("bucket already has a placement owner")
        self.placement_owner = owner
        self.P = pad_pow2(max(p, 1), floor=8)
        if self.pl_avail.shape[1] != self.P:
            old = self.pl_avail
            self.pl_avail = np.zeros((old.shape[0], self.P), bool)
            self.pl_avail[:, : old.shape[1]] = old[:, : self.P]
            self.mark_stale()

    def pl_row_for(self, key) -> int:
        row = self.pl_rows.get(key)
        if row is None:
            if self._pl_free:
                row = self._pl_free.pop()
            else:
                if self._pl_next >= self.R:
                    self._pl_grow(self._pl_next + 1)
                row = self._pl_next
                self._pl_next += 1
            self.pl_rows[key] = row
            self.pl_row_keys[row] = key
        return row

    def free_pl_row(self, key) -> None:
        """Retire a root's placement row: zero its inputs and let the
        next tick's placement-leaves swap carry them — never a rebuild
        of the resident state.

        The invariant: a row is in ``_pl_free`` only when the device's
        ``current[row]`` is zero AND no wire still to be dispatched can
        name it. The device's ``current[row]`` holds the retired root's
        last split; a later occupant whose split EQUALS it would never
        come back dirty. The step sets ``current`` to the split of the
        row's inputs on every tick, and the split of zeroed inputs is
        all zeros: the tick that carries them zeroes ``current[row]``
        by itself and emits that one row once, all zeros, which
        ``route_placement`` drops (the row has no key). So the row
        waits in ``_pl_retiring`` until a submit takes it
        (``_take_retiring``) and is released when THAT wire is
        dispatched (``FleetBatch.dispatch``) — released at the submit,
        a new occupant could meet the zero emission, fail the owner's
        sum check and force ``invalidate_placement``. Until then
        ``pl_row_for`` allocates a fresh row. A full upload zeroes
        ``current`` wholesale and takes the same road."""
        row = self.pl_rows.pop(key, None)
        if row is None:
            return
        self.pl_row_keys.pop(row, None)
        self.pl_replicas[row] = 0
        self.pl_avail[row] = False
        self._pl_retiring.append(row)
        self._pl_staged = True
        _PL_RETIRED.inc()

    def _take_rows_retiring(self) -> list[int]:
        """The sync rows whose absent-and-zero mirrors the submit just
        made carried (by its delta wire or its full upload): they ride
        that wire's FleetMeta to its dispatch (see Section.retire)."""
        rows, self._retiring = self._retiring, []
        return rows

    def _release_retired(self, rows: Sequence[int]) -> None:
        """The wire that carried these rows' last events has been
        dispatched: no wire still in flight can name them."""
        now = time.monotonic()
        for row in rows:
            _ROW_RETIRE_H.observe(now - self._held.pop(row))
        self._free.extend(rows)

    def _take_retiring(self) -> list[int]:
        """The rows whose zeroed inputs the submit just made carried (by
        its placement-leaves swap or its full upload): they ride that
        wire's FleetMeta to its dispatch."""
        rows, self._pl_retiring = self._pl_retiring, []
        return rows

    def invalidate_placement(self) -> None:
        """Force every placement row to re-emit on the next tick (rebuilds
        the resident state, zeroing `current`). Used when a host-side
        apply rejected device counts — identical re-staged inputs would
        otherwise never re-dirty."""
        self.mark_stale()

    def _pl_grow(self, needed: int) -> None:
        new_r = pad_pow2(max(needed, 8))
        if new_r % self._row_factor:
            new_r += self._row_factor - new_r % self._row_factor
        self.pl_replicas = _grown(self.pl_replicas, (new_r,), np.int32)
        self.pl_avail = _grown(self.pl_avail, (new_r, self.P), bool)
        self.R = new_r
        # shape change: the resident current[R,P] must be rebuilt too
        self.mark_stale()

    def stage_placement(self, key, replicas: int, n_clusters: int) -> None:
        """Stage one root's desired placement inputs (replicas + how many
        of the P cluster slots are available). The width grows on demand
        — P is a padding floor, never a silent cap (matching the host
        splitter's 'width follows the widest row' contract)."""
        row = self.pl_row_for(key)
        if n_clusters > self.P:
            self._pl_widen(pad_pow2(n_clusters, floor=8))
        self.pl_replicas[row] = replicas
        self.pl_avail[row] = False
        self.pl_avail[row, :n_clusters] = True
        self._pl_staged = True

    def _pl_widen(self, new_p: int) -> None:
        avail = np.zeros((self.R, new_p), bool)
        avail[:, : self.P] = self.pl_avail
        self.pl_avail = avail
        self.P = new_p
        # shape change: resident avail/current must be rebuilt
        self.mark_stale()

    # ------------------------------------------------------------ events

    def _ensure_staged_capacity(self, need: int) -> None:
        cap = self._staged_vals.shape[0]
        if need <= cap:
            return
        new_cap = pad_pow2(max(need, MIN_EVENTS))
        self._staged_vals = _grown(self._staged_vals, (new_cap, self.S), np.uint32)
        self._staged_rows = _grown(self._staged_rows, (new_cap,), np.uint32)
        self._staged_flags = _grown(self._staged_flags, (new_cap,), np.uint32)
        self._staged_keys = _grown(self._staged_keys, (new_cap,), np.int64)
        self._staged_ack = _grown(self._staged_ack, (new_cap,), bool)

    def _clear_staged(self) -> None:
        n = self._staged_n
        if n:
            self._staged_slot[self._staged_keys[:n]] = -1
            self._staged_n = 0
        self._staged_masks.clear()

    def stage_mask(self, row: int, mask: np.ndarray) -> None:
        """Stage a status-mask stamp for a newly-allocated row (ships as
        a MASK_STAMP wire entry; a full upload supersedes it)."""
        self._staged_masks[row] = mask.copy()

    def stage(self, row: int, side: bool, vals: np.ndarray, exists: bool) -> None:
        """Stage one delta event (last-wins per (row, side)) and mirror it
        into host staging (the rebuild source of truth). The 1-row form
        of :meth:`stage_many` — one copy of the slot-map logic."""
        self.stage_many(np.array([row]), side, np.asarray(vals)[None, :],
                        np.array([exists]))

    def stage_many(self, rows: np.ndarray, side: bool, vals: np.ndarray,
                   exists: np.ndarray) -> None:
        """Vectorized :meth:`stage` for one side of a unique row batch
        of ONE BUCKET, any number of sections (the tick's encode stages
        every touched section of a bucket in one call a side; rows of
        different sections never coincide, ``row_owner``): fancy-indexed
        mirror writes plus a single slot-map pass, no per-event python
        loop. Up before down for a row touched on both sides in one
        tick: the down side's ``ack_ok`` must see the up entry staged.
        The ORDER of the entries in ``_staged_*`` means nothing: the
        packed wire's entries are scattered on the device by (row,
        side), one entry each (last-wins through ``_staged_slot``), and
        ``FleetBatch._submit`` reads them as a set."""
        n, w = vals.shape
        ack_ok = False
        if side:
            # ack eligibility must be proven BEFORE any buffers change:
            # the event's value equals the host up mirror (which equals
            # the device's resident row, because no up-side entry is
            # staged for it this tick) — then the device can produce the
            # row itself from a 4-byte index
            ack_ok = (exists & self.up_exists[rows]
                      & (self._staged_slot[rows.astype(np.int64) << 1] < 0)
                      & (vals == self.up_vals[rows, :w]).all(axis=1))
            if w < self.S:
                ack_ok &= (self.up_vals[rows, w:] == 0).all(axis=1)
            self.down_vals[rows, :w] = vals
            self.down_vals[rows, w:] = 0
            self.down_exists[rows] = exists
        else:
            self.up_vals[rows, :w] = vals
            self.up_vals[rows, w:] = 0
            self.up_exists[rows] = exists
        keys = (rows.astype(np.int64) << 1) | (1 if side else 0)
        slots = self._staged_slot[keys].astype(np.int64)
        fresh = slots < 0
        n_new = int(fresh.sum())
        if n_new:
            self._ensure_staged_capacity(self._staged_n + n_new)
            new_slots = np.arange(self._staged_n, self._staged_n + n_new)
            slots[fresh] = new_slots
            self._staged_slot[keys[fresh]] = new_slots
            self._staged_keys[new_slots] = keys[fresh]
            self._staged_rows[slots] = rows
            self._staged_n += n_new
        self._staged_vals[slots, :w] = vals
        self._staged_vals[slots, w:] = 0
        self._staged_flags[slots] = (exists.astype(np.uint32)
                                     | (2 if side else 0) | 4)
        self._staged_ack[slots] = ack_ok

    @property
    def dirty(self) -> bool:
        return (bool(self._staged_n) or bool(self._staged_masks)
                or self._stale or self._pl_staged)

    # ------------------------------------------------------- quarantine

    def quarantine_row(self, row: int) -> tuple[object | None, Section | None]:
        """Evict one poisoned row: zero its host mirrors (the pending
        full re-upload then excludes it from the resident state), free
        the row, and return (key, section) so the core can requeue the
        key to its owner with bounded backoff. One bad object must never
        stall its bucket's co-tenants."""
        sec = self.row_owner.get(row)
        key = sec.row_keys.get(row) if sec is not None else None
        self.up_vals[row] = 0
        self.down_vals[row] = 0
        self.up_exists[row] = False
        self.down_exists[row] = False
        self.status_mask[row] = False
        if sec is not None:
            if key is not None:
                sec.rows.pop(key, None)
            sec.row_keys.pop(row, None)
            self.row_owner.pop(row, None)
            self._free.append(row)
        self.stats["quarantined"] += 1
        REGISTRY.counter(
            "quarantined_rows",
            "rows evicted from fused buckets by poison-row quarantine").inc()
        self.mark_stale()
        return key, sec

    # ----------------------------------------------------------- routing

    def route_patches(self, idx: np.ndarray, code: np.ndarray,
                      upsync: np.ndarray) -> None:
        """Route patch rows (bucket-local indices) to their owning
        sections (the fleet batch splits its wire's patches by row range
        first)."""
        per_section: dict[Section, list[tuple[object, int, bool]]] = {}
        dropped = 0
        for r, c, u in zip(idx.tolist(), code.tolist(), upsync.tolist()):
            s = self.row_owner.get(r)
            key = s.row_keys.get(r) if s is not None else None
            if key is None:
                # an unowned/unkeyed patch row (released section, freed or
                # quarantined row, in-flight wire racing a retirement):
                # benign by design, but it must be COUNTED, not silent.
                # A row held back by Section.retire is the expected case
                # (a wire submitted before its key's last events): it is
                # counted and not logged
                dropped += 1
                if r not in self._held and r not in self._dropped_logged:
                    self._dropped_logged.add(r)
                    log.warning(
                        "fused-core: dropping patch for row %d (%s); "
                        "counted in fused_dropped_patch_rows", r,
                        "no owning section" if s is None else "no key mapping")
                continue
            per_section.setdefault(s, []).append((key, c, u))
        if dropped:
            REGISTRY.counter(
                "fused_dropped_patch_rows",
                "patch rows dropped at dispatch because their row had no "
                "owner/key (released, freed, or quarantined)").inc(dropped)
        for s, patches in per_section.items():
            s.owner.fused_apply(patches)

    def route_placement(self, rows: np.ndarray, counts: np.ndarray) -> None:
        """Route dirty placement rows (bucket-local) to the placement
        owner."""
        if self.placement_owner is None:
            return
        applies = []
        for i, row in enumerate(rows.tolist()):
            key = self.pl_row_keys.get(row)
            if key is not None:
                # copy: a view would pin the whole wire buffer in the
                # applier queue / retry cache
                applies.append((key, counts[i].copy()))
        if applies:
            self.placement_owner.placement_apply(applies)

    def note_overflow(self) -> None:
        self.stats["overflows"] += 1
        self.patch_capacity = min(self.patch_capacity * 2, max(self.B, MIN_ROWS))


class _Gathered(NamedTuple):
    """What one tick's encode has gathered for ONE touched section,
    before anything is staged: the rows and side masks (bit 1 = up, bit
    2 = down) of its kept keys as plain lists in one order, and for
    each side (0 = up, 1 = down) the exists flags, a list, and the
    vectors as its owner brought them (a list of [S] vectors from the
    per-key loop, or an [n, S] block from ``fused_encode_many``).
    ``FusedCore._stage_batch`` makes one array of each over all the
    sections of a bucket and stages the lot."""

    section: Section
    absent: list            # its keys gone on both sides
    rows: list
    masks: list
    vals: tuple             # (ups, downs)
    exists: tuple           # (up_e, down_e)


def _joined(parts) -> np.ndarray:
    """One [n, w] array of the sections' vectors, in order. ValueError
    where their widths differ (``np.stack`` and ``np.concatenate``
    both refuse)."""
    blocks: list = []
    run: list = []
    for part in parts:
        if isinstance(part, list):
            run += part
        else:
            if run:
                blocks.append(np.stack(run))
                run = []
            blocks.append(part)
    if run:
        blocks.append(np.stack(run))
    return blocks[0] if len(blocks) == 1 else np.concatenate(blocks)


def _taken(seq, keep: list[int]):
    return seq[keep] if isinstance(seq, np.ndarray) else [seq[i] for i in keep]


class FleetMeta(NamedTuple):
    """Per-submit layout snapshot riding with an in-flight fleet wire.

    The fleet layout can change while a wire is still in flight (bucket
    growth, new buckets, placement widening all mark the fleet stale for
    the NEXT tick) — collection must unpack against the layout the wire
    was built under, never the current one."""

    k: int                      # patch capacity submitted
    p: int                      # placement width in the wire
    r_total: int                # placement rows in the wire
    members: tuple              # member buckets, layout order
    bases: tuple[int, ...]      # fleet row base per member
    ends: tuple[int, ...]       # fleet row end (base + B) per member
    pl_members: tuple           # members contributing placement rows
    pl_bases: tuple[int, ...]
    pl_ends: tuple[int, ...]
    seg_capacity: int
    # (bucket, rows) retired before this submit: the step zeroed their
    # `current`; they come free when this wire is dispatched
    pl_retired: tuple
    # (bucket, sync rows) retired before this submit (Section.retire):
    # this wire carried their last events; free at its dispatch
    rows_retired: tuple


class FleetBatch:
    """One ragged device batch for the whole bucket fleet — the core's
    one device owner (state, jit step, wire buffers, probe + bisection).

    The fleet batch packs EVERY bucket's rows into one unified
    ReconcileState (rows range-partitioned by bucket, slot columns
    zero-padded to the widest member, per-row status masks — the [B, S]
    form the kernels already take) so a reconcile tick is ONE pipelined
    ``reconcile_step_fleet`` no matter how many buckets exist, and the
    mesh shardings in parallel/mesh.py spread that single batch over all
    devices. Results scatter back to per-bucket patch streams on collect
    (row ranges -> bucket.route_patches): each owner sees its rows'
    decisions in row order, whatever else shares the batch.

    Per-row *segment ids* (the owning section) ride the batch as a
    resident int32 lane; the step returns per-segment live-row counts on
    the wire tail, which the core forwards to the admission quota ledger
    (admission accounting rides the same batch: one vector hand-over a
    collect, no host-side pass over the tenants).

    Degraded mode preserves the PR 2 semantics: a failed step retries
    once wholesale, then bisects *by segment* — the group test is seeded
    with one group per member bucket, so poison isolates within its own
    segment and only the poison rows are quarantined (via the owning
    bucket, which requeues the keys with bounded backoff).
    """

    def __init__(self, core: "FusedCore"):
        self.core = core
        self.mesh = core.mesh
        self.use_pallas = core.use_pallas
        self._members: list[FusedBucket] = []
        self._bases: list[int] = []
        self._ends: list[int] = []
        self._pl_members: list[FusedBucket] = []
        self._pl_bases: list[int] = []
        self._pl_ends: list[int] = []
        self._layout_key: tuple | None = None
        self.B = 0
        self.S = 0
        self.R = 0
        self.P = 8
        self._state: ReconcileState | None = None
        self._seg_ids = None  # device int32 [B]: row -> section segment
        self._seg_capacity = 8
        self._patch_k: int | None = None  # pooled patch capacity last dispatched
        # (shapes, static arguments) the step has been dispatched with:
        # the first dispatch of another one is the tick's `compile` phase
        self._dispatched: set[tuple] = set()
        self._stale = True
        # acks-lane wire capacity: sticky high-water doubling, so the
        # (event rows, lane capacity) pair stays stable after warmup —
        # per-tick pow2 padding here would multiply compiled-shape variants
        self.ack_capacity = 1024
        # rotating packed-wire staging (models/reconcile_model.py): tick
        # N+1 packs into another buffer while tick N's device_put may
        # still be reading this one — the allocation-free hot path that
        # makes the 2-deep pipeline window safe
        self._wire_bufs = WireBuffers(PIPELINE_DEPTH + 1)
        # where the packed wire is put: every device of a serving mesh
        # (replicated: it is O(events), not O(fleet)), else the default
        self._wire_sharding = None
        self._wire_devices = 1
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            self._wire_sharding = NamedSharding(self.mesh, PartitionSpec())
            self._wire_devices = self.mesh.size
        # the resident state is donated on every backend: steady state
        # lives in device memory and only deltas cross the link, and the
        # tests' CPU backend runs the same donated program as the chip
        self._step = jax.jit(
            reconcile_step_fleet,
            donate_argnums=(0, 1),
            static_argnames=("ack_capacity", "patch_capacity",
                             "seg_capacity", "use_pallas", "mesh"),
        )
        # degraded-mode bookkeeping (poison-row quarantine): the rows the
        # last submission covered (the bisection's suspect set), the
        # consecutive step-failure count, and the non-donating probe step
        # used by the bisection (donation would consume the resident
        # state probes must leave intact)
        self._probe_step = None
        self._last_rows: list[int] = []
        self._step_failures = 0
        self.stats = {"ticks": 0, "full_uploads": 0, "overflows": 0,
                      "acked": 0, "step_failures": 0, "quarantined": 0}

    # ----------------------------------------------------------- layout

    def _refresh_layout(self) -> None:
        members = list(self.core.buckets.values())
        key = tuple((id(b), b.B, b.S, b.R, b.P) for b in members)
        if key == self._layout_key:
            return
        self._layout_key = key
        self._members = members
        self._bases, self._ends = [], []
        base, s = 0, 0
        for b in members:
            self._bases.append(base)
            base += b.B
            self._ends.append(base)
            s = max(s, b.S)
        if self.B and base != self.B:
            _ROW_GROWTHS.inc()
        self.B = base
        self.S = s
        _MESH_SHARDS.set(members[0]._row_factor if members else 1)
        self._pl_members, self._pl_bases, self._pl_ends = [], [], []
        r, p = 0, 8
        for b in members:
            if b.R:
                self._pl_members.append(b)
                self._pl_bases.append(r)
                r += b.R
                self._pl_ends.append(r)
                p = max(p, b.P)
        self.R = r
        self.P = p
        # any layout change invalidates the resident fleet state: row
        # bases moved, so a full re-upload rebuilds it (bucket growth is
        # pow2 + rare)
        self._stale = True

    @property
    def dirty(self) -> bool:
        return self._stale or any(b.dirty
                                  for b in self.core.buckets.values())

    def mark_stale(self) -> None:
        self._stale = True

    def _locate(self, fleet_row: int) -> tuple[FusedBucket, int]:
        """(owning bucket, bucket-local row) for a fleet row index."""
        for b, base, end in zip(self._members, self._bases, self._ends):
            if base <= fleet_row < end:
                return b, fleet_row - base
        raise KeyError(f"fleet row {fleet_row} outside layout (B={self.B})")

    # ------------------------------------------------------------ state

    def _placement_leaves(self) -> tuple[np.ndarray, np.ndarray, int, int]:
        f = self._members[0]._row_factor if self._members else 1
        if self.R:
            r, p = self.R, self.P
            replicas = np.zeros(r, np.int32)
            avail = np.zeros((r, p), bool)
            for b, pb in zip(self._pl_members, self._pl_bases):
                replicas[pb:pb + b.R] = b.pl_replicas
                avail[pb:pb + b.R, :b.P] = b.pl_avail
        else:
            r = ((8 + f - 1) // f) * f
            p = 8
            replicas = np.zeros(r, np.int32)
            avail = np.zeros((r, p), bool)
        return replicas, avail, r, p

    def _device_state(self) -> tuple[ReconcileState, jax.Array]:
        """The concatenated fleet state + the row->segment lane, sharded
        by parallel/mesh.py's specs (rows over tenants/hosts, slots over
        the slots axis; the seg lane shards like the exists flags).
        Placement lanes are real when a placement owner registered (the
        splitter's roots), minimal placeholders otherwise — either way
        the program IS the flagship step, lanes and all."""
        s = self.S
        up_vals = np.zeros((self.B, s), np.uint32)
        down_vals = np.zeros((self.B, s), np.uint32)
        up_exists = np.zeros(self.B, bool)
        down_exists = np.zeros(self.B, bool)
        status_mask = np.zeros((self.B, s), bool)
        seg = np.full(self.B, SEG_NONE, np.int32)
        for b, base in zip(self._members, self._bases):
            end = base + b.B
            up_vals[base:end, :b.S] = b.up_vals
            down_vals[base:end, :b.S] = b.down_vals
            up_exists[base:end] = b.up_exists
            down_exists[base:end] = b.down_exists
            status_mask[base:end, :b.S] = b.status_mask
            for row, sec in b.row_owner.items():
                if sec.seg is not None:
                    seg[base + row] = sec.seg
        replicas, avail, r, p = self._placement_leaves()
        state = ReconcileState(
            up_vals=up_vals, up_exists=up_exists,
            down_vals=down_vals, down_exists=down_exists,
            status_mask=status_mask,
            replicas=replicas, avail=avail,
            current=np.zeros((r, p), np.int32),
            pair_hashes=np.zeros((self.B, 1), np.uint32),
            sel_hashes=np.zeros(8, np.uint32),
        )
        _UPLOAD_BYTES.inc(seg.nbytes + sum(leaf.nbytes for leaf in state))
        if self.mesh is not None:
            from ..parallel.mesh import shard_state, state_shardings

            return (shard_state(state, self.mesh),
                    jax.device_put(seg, state_shardings(self.mesh)["flags"]))
        return jax.tree.map(jax.device_put, state), jax.device_put(seg)

    # ------------------------------------------------------------- tick

    def _patch_capacity(self) -> int:
        # member patch capacities pool into the fleet wire, so one
        # bucket's overflow-doubled budget benefits the whole batch
        return min(sum(b.patch_capacity for b in self._members), self.B)

    def submit(self) -> tuple[jax.Array, FleetMeta] | None:
        """Pack every dirty bucket's staged rows into one ragged batch,
        run ONE fused step, return the wire (copy_to_host_async issued)
        plus the layout snapshot needed to unpack it at collect time."""
        if not self.dirty:
            return None
        self._refresh_layout()
        if not self._members:
            return None
        ph = _Phases()
        try:
            return self._submit(ph)
        finally:
            ph.close()

    def _submit(self, ph: _Phases) -> tuple[jax.Array, FleetMeta]:
        s = self.S
        seg_capacity = pad_pow2(max(self.core._next_seg, 1), floor=8)
        if seg_capacity != self._seg_capacity:
            self._seg_capacity = seg_capacity
            _SEGMENT_GROWTHS.inc()
        was_stale = self._stale or any(b._stale for b in self._members)
        # a stale tick re-uploads the whole mirror to the device, which
        # is not the steady-state pack — the histograms stay separable
        ph.enter("full_upload" if was_stale else "pack")
        local_rows: list[int] = []  # bucket-local ids for KCP_FAULTS
        puts = 1  # jax.device_put calls of this submit: the packed wire
        if was_stale:
            self._state, self._seg_ids = self._device_state()
            self._stale = False
            self._last_rows = []
            # a full upload re-submits every owned row — they are all
            # suspects if this step fails (quarantine bisection input)
            for b, base in zip(self._members, self._bases):
                b._stale = False
                b._clear_staged()
                b._pl_staged = False
                b.stats["full_uploads"] += 1
                owned = sorted(b.row_owner)
                local_rows.extend(owned)
                self._last_rows.extend(base + r for r in owned)
            self.stats["full_uploads"] += 1
            # full upload replaces the mirrors wholesale; still run the
            # step so decisions for the new state come back
            d, cap = MIN_EVENTS, self.ack_capacity
            buf_slot, packed, acks = self._wire_bufs.acquire(d, s + 2, cap)
        else:
            if any(b._pl_staged for b in self._members):
                # placement inputs changed (roots staged/retired): swap
                # ONLY the small replicas/avail leaves — never the [B,S]
                # mirrors (shapes are stable here; growth marks stale)
                for b in self._members:
                    b._pl_staged = False
                replicas, avail, _r, _p = self._placement_leaves()
                if self.mesh is not None:
                    from ..parallel.mesh import state_shardings

                    sh = state_shardings(self.mesh)
                    reps = jax.device_put(replicas, sh["placement_rows"])
                    av = jax.device_put(avail, sh["placement"])
                else:
                    reps = jax.device_put(replicas)
                    av = jax.device_put(avail)
                puts += 2
                self._state = self._state._replace(replicas=reps, avail=av)
            # gather the members' staged arrays (already the packed-wire
            # layout) into one fleet wire: row indices shift by the
            # member's base, ack-eligible slots pool on the one ack lane
            # (the wire's tail rows), mask stamps gain the owning
            # section's segment id
            per: list[tuple] = []
            nf_total = na_total = nm_total = 0
            for b, base in zip(self._members, self._bases):
                n = b._staged_n
                ack_sel = b._staged_ack[:n]
                na = int(ack_sel.sum())
                nm = len(b._staged_masks)
                per.append((b, base, n, ack_sel, na, nm))
                nf_total += n - na
                na_total += na
                nm_total += nm
            d = pad_pow2(nf_total + nm_total, floor=MIN_EVENTS)
            # always ship the ack lane, even all-padding: a wire without
            # it would be a SECOND jit trace variant, and the first
            # ack-bearing tick would then compile it mid-serving — a
            # seconds-long loop stall (measured) vs the ~nothing an
            # all-dropped scatter pass costs per tick. The capacity
            # honors each member's floor (bench pre-warms
            # bucket.ack_capacity to dodge mid-serving recompiles)
            cap = max(self.ack_capacity,
                      max((b.ack_capacity for b in self._members),
                          default=1024))
            while cap < na_total:
                cap *= 2
            self.ack_capacity = cap
            buf_slot, packed, acks = self._wire_bufs.acquire(d, s + 2, cap)
            pos = apos = 0
            self._last_rows = []
            for b, base, n, ack_sel, na, nm in per:
                w = b.S
                if n:
                    if na:
                        full_sel = ~ack_sel
                        nf = n - na
                        packed[pos:pos + nf, :w] = b._staged_vals[:n][full_sel]
                        packed[pos:pos + nf, s] = (
                            b._staged_rows[:n][full_sel] + np.uint32(base))
                        packed[pos:pos + nf, s + 1] = (
                            b._staged_flags[:n][full_sel])
                        acks[apos:apos + na] = (
                            b._staged_rows[:n][ack_sel].astype(np.int32)
                            + base)
                        apos += na
                        b.stats["acked"] += na
                        self.stats["acked"] += na
                        pos += nf
                    else:
                        packed[pos:pos + n, :w] = b._staged_vals[:n]
                        packed[pos:pos + n, s] = (
                            b._staged_rows[:n] + np.uint32(base))
                        packed[pos:pos + n, s + 1] = b._staged_flags[:n]
                        pos += n
                if nm:
                    mrows = np.fromiter(b._staged_masks, np.uint32, nm)
                    masks = np.stack(list(b._staged_masks.values()))
                    packed[pos:pos + nm, :masks.shape[1]] = (
                        masks.astype(np.uint32))
                    packed[pos:pos + nm, s] = mrows + np.uint32(base)
                    segs = np.fromiter(
                        (sec.seg if (sec := b.row_owner.get(r)) is not None
                         and sec.seg is not None else SEG_NONE
                         for r in b._staged_masks), np.uint32, nm)
                    packed[pos:pos + nm, s + 1] = (
                        4 | MASK_STAMP_BIT | (segs << SEG_SHIFT))
                    pos += nm
                touched = set(b._staged_rows[:n].tolist())
                touched.update(b._staged_masks)
                local_rows.extend(touched)
                self._last_rows.extend(base + r for r in sorted(touched))
                b._clear_staged()
        ph.enter("put")
        # ONE array crosses: the event rows and, in its tail, the ack lane
        packed_d = jax.device_put(packed, self._wire_sharding)
        _PUT_BYTES.inc(packed.nbytes * self._wire_devices)
        _PUTS.inc(puts * self._wire_devices)
        k = self._patch_capacity()
        if k != self._patch_k:
            if self._patch_k is not None:
                _PATCH_GROWTHS.inc()
            self._patch_k = k
        shapes = (self.B, s, self._state.replicas.shape[0],
                  self._state.avail.shape[1], d, cap, k, self._seg_capacity)
        ph.enter("step_dispatch" if shapes in self._dispatched else "compile")
        # KCP_FAULTS `device.step` injection point (raise@tick / error /
        # poison_row): fires HERE, where a real XLA dispatch failure
        # would surface — the quarantine machinery recovers either way.
        # Rows are BUCKET-LOCAL ids (the union across members), so a
        # poison_row spec names an owner's row whatever the layout
        faults.maybe_fail("device.step", rows=local_rows)
        self._state, self._seg_ids, wire = self._step(
            self._state, self._seg_ids, packed_d, ack_capacity=cap,
            patch_capacity=k, seg_capacity=self._seg_capacity,
            use_pallas=self.use_pallas, mesh=self.mesh,
        )
        # the staging buffers may be re-acquired only once this step has
        # read them (see WireBuffers)
        self._wire_bufs.commit(buf_slot, packed_d, wire)
        self._dispatched.add(shapes)
        self._step_failures = 0
        wire.copy_to_host_async()
        ph.close()
        self.stats["ticks"] += 1
        # member tick counters advance too: the fleet step covers every
        # bucket's rows, and engines/benches read their bucket's counter
        for b in self._members:
            b.stats["ticks"] += 1
        _FLEET_TICKS.inc()
        REGISTRY.gauge(
            "fused_fleet_rows", "rows in the fleet batch").set(self.B)
        REGISTRY.gauge(
            "fused_fleet_buckets",
            "schema buckets packed into the fleet batch").set(
            len(self._members))
        REGISTRY.gauge(
            "fused_fleet_segments",
            "registered sections (fleet segments)").set(
            len(self.core._segments))
        meta = FleetMeta(
            k=k, p=int(self._state.avail.shape[1]),
            r_total=int(self._state.replicas.shape[0]),
            members=tuple(self._members), bases=tuple(self._bases),
            ends=tuple(self._ends), pl_members=tuple(self._pl_members),
            pl_bases=tuple(self._pl_bases), pl_ends=tuple(self._pl_ends),
            seg_capacity=self._seg_capacity,
            # taken only now that the step accepted the inputs: a failed
            # submit leaves them for the retry's full upload
            pl_retired=tuple((b, b._take_retiring()) for b in self._members
                             if b._pl_retiring),
            rows_retired=tuple((b, b._take_rows_retiring())
                               for b in self._members if b._retiring),
        )
        return wire, meta

    # ---------------------------------------------------------- routing

    def dispatch(self, wire: np.ndarray, meta: FleetMeta) -> bool:
        """Scatter a collected fleet wire back to per-bucket patch
        streams: split patches and placement rows by the row ranges of
        the submitting layout, then route through each member's own
        section/placement routing. Returns True on patch overflow."""
        idx, code, upsync, overflow, _stats = unpack_patches(wire)
        if idx.size:
            ends = np.asarray(meta.ends, np.int64)
            mi = np.searchsorted(ends, idx, side="right")
            for j, b in enumerate(meta.members):
                sel = mi == j
                if sel.any():
                    b.route_patches(idx[sel] - meta.bases[j],
                                    code[sel], upsync[sel])
        if meta.pl_members:
            rows, counts = unpack_placement(wire, meta.k, meta.p,
                                            r=meta.r_total)
            if rows.size:
                pl_ends = np.asarray(meta.pl_ends, np.int64)
                pmi = np.searchsorted(pl_ends, rows, side="right")
                for j, b in enumerate(meta.pl_members):
                    sel = pmi == j
                    if sel.any():
                        pw = min(b.P, meta.p)
                        b.route_placement(rows[sel] - meta.pl_bases[j],
                                          counts[sel][:, :pw])
        # this wire's zero-count emissions of the rows retired before its
        # submit found no key above: the rows may be taken again
        for b, retired in meta.pl_retired:
            b._pl_free.extend(retired)
        # and no wire still in flight can name the sync rows whose last
        # events this one carried
        for b, retired in meta.rows_retired:
            b._release_retired(retired)
        if meta.rows_retired:
            _ROWS_HELD.set(sum(len(b._held) for b in meta.members))
        # per-segment live-row counts -> the admission quota ledger
        self.core._publish_fleet_counts(
            unpack_seg_counts(wire, meta.k, meta.r_total, meta.p,
                              meta.seg_capacity))
        if overflow:
            self.stats["overflows"] += 1
            for b in meta.members:
                b.note_overflow()
        return bool(overflow)

    # ------------------------------------------------------- quarantine

    def note_step_failure(self) -> None:
        self.stats["step_failures"] += 1
        self._step_failures += 1
        for b in self._members:
            b.stats["step_failures"] += 1

    def probe_rows(self, rows: Sequence[int]) -> bool:
        """The fleet bisection oracle: one non-donating trial step over a
        synthetic wire carrying only ``rows`` (fleet ids, both sides),
        rebuilt from the owning buckets' host mirrors, discarding the
        result. True iff the step completed.

        The probe jit does NOT donate: the resident state must survive
        an arbitrary number of probes. Probe wire shapes are pow2-padded,
        so a bisection compiles at most a handful of variants (this is
        the rare failure path; docs/operations.md covers the cost)."""
        if self.B == 0:
            return True
        rows = [int(r) for r in rows]
        locs = [self._locate(r) for r in rows]
        try:
            faults.maybe_fail("device.step", rows=[lr for _b, lr in locs])
            if self._probe_step is None:
                self._probe_step = jax.jit(
                    reconcile_step_fleet,
                    static_argnames=("ack_capacity", "patch_capacity",
                                     "seg_capacity", "use_pallas", "mesh"))
            if self._state is None:
                self._state, self._seg_ids = self._device_state()
                self._stale = False
            s = self.S
            d = pad_pow2(max(2 * len(rows), 1), floor=MIN_EVENTS)
            # a wire of its own, laid out like a tick's: an empty ack lane
            cap = self.ack_capacity
            _slot, packed, _acks = WireBuffers(1).acquire(d, s + 2, cap)
            for i, ((b, lr), fr) in enumerate(zip(locs, rows)):
                packed[2 * i, :b.S] = b.up_vals[lr]
                packed[2 * i, s] = fr
                packed[2 * i, s + 1] = (1 if b.up_exists[lr] else 0) | 4
                packed[2 * i + 1, :b.S] = b.down_vals[lr]
                packed[2 * i + 1, s] = fr
                packed[2 * i + 1, s + 1] = (
                    (1 if b.down_exists[lr] else 0) | 2 | 4)
            _state, _seg, wire = self._probe_step(
                self._state, self._seg_ids,
                jax.device_put(packed, self._wire_sharding),
                ack_capacity=cap, patch_capacity=self._patch_capacity(),
                seg_capacity=self._seg_capacity,
                use_pallas=self.use_pallas, mesh=self.mesh)
            np.asarray(wire)  # force execution; async backends defer errors
            return True
        except Exception:  # noqa: BLE001 — any failure means "poisoned"
            return False

    def bisect_poison(self, suspects: Sequence[int],
                      max_probes: int = BISECT_MAX_PROBES) -> list[int] | None:
        """Segment-scoped bisection over the ragged batch: the group test
        is seeded with one suspect group per member bucket, so a clean
        segment clears in one probe and poison isolates within its own
        segment (~k*log2(n) probes for k poisons). None when even the
        empty probe fails — the failure is row-independent and quarantine
        cannot help. If the probe budget runs out, the unresolved
        remainder is quarantined wholesale (innocents may be swept up;
        degraded beats dead, and their requeue brings them back)."""
        if not self.probe_rows([]):
            return None
        groups: dict[int, list[int]] = {}
        for r in suspects:
            b, _lr = self._locate(int(r))
            groups.setdefault(id(b), []).append(int(r))
        return _group_test_poison(self.probe_rows, list(groups.values()),
                                  max_probes)

    def quarantine_row(self, row: int) -> tuple[object | None, Section | None]:
        """Evict one poisoned fleet row via its owning bucket (which
        zeroes the mirrors, frees the row, marks itself stale — forcing
        the fleet re-upload — and hands back the key for requeue)."""
        b, lr = self._locate(int(row))
        self.stats["quarantined"] += 1
        return b.quarantine_row(lr)


class FusedCore:
    """The per-loop serving core: one tick loop, one fleet step a tick."""

    _instances: dict[int, "FusedCore"] = {}
    # process-default admission quota ledger (set_process_ledger): the
    # sink for the fleet batch's device-side per-segment counters
    _process_ledger = None

    def __init__(self, mesh=None, batch_window: float = 0.002,
                 use_pallas: bool | None = None,
                 pipeline: str | None = None):
        self.mesh = mesh
        # the fused Pallas decision+fanout pass (ops/pallas_kernels.py);
        # on a mesh it runs per device via shard_map (reconcile_model
        # gates on local-row divisibility and falls back to XLA lanes)
        if use_pallas is None:
            use_pallas = os.environ.get("KCP_PALLAS", "") == "1"
        self.use_pallas = use_pallas
        # every tick packs all dirty buckets into ONE pipelined device
        # program: the fleet batch is the core's one device owner
        self._fleet = FleetBatch(self)
        self._segments: dict[int, Section] = {}  # seg id -> section
        self._next_seg = 0
        # bumped by every register and every Section.release: the one
        # signal that the segment -> ledger slot map below is out of date
        self._segments_version = 0
        self.ledger = FusedCore._process_ledger
        # what the map was built for: (_segments_version, the collected
        # wire's seg_capacity, the ledger whose slots it holds)
        self._ledger_map_key: tuple | None = None
        # the map as a gather plan: accounting segment ids ordered by
        # ledger slot, the start of each slot's run in that order, and
        # the distinct slots — one take and one reduceat a collect
        self._ledger_segs = np.zeros(0, np.int64)
        self._ledger_starts = np.zeros(0, np.int64)
        self._ledger_slots = np.zeros(0, np.int64)
        # tick pipelining mode: "double" (default) keeps up to
        # PIPELINE_DEPTH steps in flight — pack N+1 and apply N-1 while
        # the device runs N; "serial" collects every wire in the tick
        # that submitted it (the reference of the equivalence fuzz,
        # tests/test_pipeline.py)
        pipeline = pipeline or "double"
        if pipeline not in PIPELINE_MODES:
            raise ValueError(f"pipeline must be one of {PIPELINE_MODES}, "
                             f"got {pipeline!r}")
        self.pipeline = pipeline
        self.fetch_depth = PIPELINE_DEPTH if pipeline == "double" else 0
        REGISTRY.gauge(
            "fused_pipeline_window",
            "configured in-flight tick window (0 = serial mode)",
        ).set(self.fetch_depth)
        self.buckets: dict[int, FusedBucket] = {}
        self.controller = BatchController(
            "fused-core", self._process_batch, batch_window=batch_window,
            overlap_drain=(pipeline == "double"),
        )
        # (wire, layout meta, start stamp of the submitting tick)
        self._inflight: list[tuple[jax.Array, FleetMeta, float]] = []
        # start stamp (time.monotonic()) of the tick whose wire is being
        # collected, while its patches are handed to the owners
        self.collecting_tick_start: float | None = None
        self._depth_h = REGISTRY.histogram(
            "fused_pipeline_depth",
            "in-flight steps at submit time",
            buckets=DEPTH_BUCKETS)
        self._overlap_ticks = REGISTRY.counter(
            "fused_pipeline_overlap_ticks_total",
            "submits issued while a previous step was still in flight "
            "(overlapped ticks)")
        # how a wire left in flight between ticks is collected turns on
        # the backend (resolved at the first such wire): woken by the
        # waiter thread where dispatch is asynchronous, by the quiet-loop
        # flush task on the synchronous CPU backend
        self._eager_collect: bool | None = None
        self._flush_task: asyncio.Task | None = None
        # (wire, its submit's end stamp) for the waiter thread; None ends it
        self._wires: queue.SimpleQueue = queue.SimpleQueue()
        self._waiter: threading.Thread | None = None
        # quarantined keys awaiting their bounded-backoff requeue
        self._quarantine_retries: dict[tuple[int, object], int] = {}
        self._refs = 0
        self._started = False
        self._stopping = False
        self._stop_done: asyncio.Event | None = None
        self._loop = None

    # ---------------------------------------------------------- lifecycle

    @classmethod
    def for_current_loop(cls, mesh=None) -> "FusedCore":
        """The process-wide core for the running asyncio loop (tests run
        many loops sequentially; each gets a fresh core).

        ``mesh=None`` falls back to the process serving mesh
        (parallel.mesh.set_serving_mesh — the server's Config.mesh /
        --mesh flag), so a configured process serves sharded without
        every engine re-plumbing the mesh."""
        if mesh is None:
            from ..parallel.mesh import get_serving_mesh

            mesh = get_serving_mesh()
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            loop = None
        core = cls._instances.get(id(loop))
        # the identity check guards against id() reuse after a dead loop
        # is garbage-collected: a stale core's tick task died with its loop
        if core is None or core._closed() or core._loop is not loop:
            core = cls(mesh=mesh)
            core._loop = loop
            cls._instances[id(loop)] = core
        elif mesh is not None and core.mesh != mesh:
            log.warning("FusedCore for this loop already exists with a "
                        "different mesh; keeping the existing core's mesh")
        return core

    @classmethod
    def set_process_ledger(cls, ledger) -> None:
        """Install the admission quota ledger the fleet batch's device-
        side per-segment counters feed (server.py wires this when the
        admission chain has a quota ledger). Applies to live cores too."""
        cls._process_ledger = ledger
        for core in cls._instances.values():
            core.ledger = ledger

    def _publish_fleet_counts(self, seg_counts: np.ndarray) -> None:
        """Forward a collected fleet wire's per-segment live-row counts
        to the quota ledger, summed per owning section's
        ``fused_ledger_key()`` (sections without one don't account).

        Runs on every collect, so it holds no per-section Python: the
        segment -> ledger slot map is rebuilt only when a section was
        registered or released, the wire's segment capacity changed
        (registrations grow it) or another ledger was attached. An
        owner's ``fused_ledger_key()`` is therefore read once per
        rebuild and must not change while its section lives."""
        ledger = self.ledger
        if ledger is None:
            return
        key = (self._segments_version, seg_counts.shape[0], ledger)
        if key != self._ledger_map_key:
            self._rebuild_ledger_map(key)
        if self._ledger_segs.size:
            ledger.ingest_device_counts(
                self._ledger_slots,
                np.add.reduceat(seg_counts[self._ledger_segs],
                                self._ledger_starts, dtype=np.int64))
            REGISTRY.counter(
                "fused_fleet_ledger_updates_total",
                "device-side per-segment count batches forwarded to the "
                "quota ledger").inc()

    def _rebuild_ledger_map(self, key: tuple) -> None:
        """Drop released sections and re-read every live section's ledger
        key. A section does not account if it has no ``fused_ledger_key``
        or a None key, or if its segment id lies beyond this wire's
        capacity (it registered after the wire was submitted)."""
        for seg in [s for s, sec in self._segments.items() if sec.released]:
            del self._segments[seg]
        _version, cap, ledger = key
        segs, keys = [], []
        for seg, section in self._segments.items():
            if seg >= cap:
                continue
            keyfn = getattr(section.owner, "fused_ledger_key", None)
            ledger_key = keyfn() if keyfn is not None else None
            if ledger_key is not None:
                segs.append(seg)
                keys.append(ledger_key)
        # segments that share a key share a slot and must sum: order the
        # accounting segments by slot, so each slot is one run
        slots = ledger.device_slots(keys)
        order = np.argsort(slots, kind="stable")
        self._ledger_segs = np.asarray(segs, np.int64)[order]
        self._ledger_slots, self._ledger_starts = np.unique(
            slots[order], return_index=True)
        self._ledger_map_key = key
        REGISTRY.counter(
            "fused_fleet_ledger_map_rebuilds_total",
            "rebuilds of the fleet segment -> quota ledger slot map (a "
            "section registered or released, the wire's segment capacity "
            "grew, another ledger attached); flat while serving").inc()

    def _closed(self) -> bool:
        return self._started and self._refs == 0

    async def start(self) -> None:
        self._refs += 1
        if not self._started:
            self._started = True
            await self.controller.start()

    async def stop(self) -> None:
        if self._refs > 0:
            self._refs -= 1
        if self._refs > 0 or not self._started:
            return
        if self._stopping:
            # double-stop (or stop concurrent with an in-flight stop):
            # an idempotent no-op — wait for the first stop's drain so
            # every caller returns to a fully-drained core
            if self._stop_done is not None:
                await self._stop_done.wait()
            return
        self._stopping = True
        self._stop_done = asyncio.Event()
        try:
            # controller first: its shutdown drain runs the FINAL ticks,
            # and those submits append in-flight wires — draining
            # _inflight before the tick loop exits would strand (and
            # silently drop) the last window's patches (proven by the
            # pipeline shutdown/drain test)
            await self.controller.stop()
            if self._flush_task is not None:
                self._flush_task.cancel()
                self._flush_task = None
            if self._waiter is not None:
                # it ends once every wire handed to it is on the host —
                # what the drain below would block on; its late wakes
                # find nothing in flight
                self._wires.put(None)
                self._waiter.join()
                self._waiter = None
            await self._drain_inflight()
            # drop the registry entry so closed cores (and their device-
            # resident fleet state) do not accumulate across loops
            for k, v in list(FusedCore._instances.items()):
                if v is self:
                    del FusedCore._instances[k]
        finally:
            self._stop_done.set()

    # ------------------------------------------------------------ plumbing

    def bucket(self, slots: int) -> FusedBucket:
        b = self.buckets.get(slots)
        if b is None:
            b = FusedBucket(slots, mesh=self.mesh)
            self.buckets[slots] = b
        return b

    def register(self, owner: SectionOwner, slots: int) -> Section:
        section = self.bucket(slots).section(owner)
        # fleet segment id: stable for the section's lifetime; retired
        # ids are not reused (the capacity is pow2-padded and tiny)
        section.seg = self._next_seg
        section.core = self
        self._segments[self._next_seg] = section
        self._next_seg += 1
        self._segments_version += 1
        return section

    def register_placement(self, owner, p: int = 8,
                           slots: int = 64) -> FusedBucket:
        """Attach a placement owner (the deployment splitter) to the
        default bucket — its roots then ride the SAME fused step that
        serves the sync sections."""
        b = self.bucket(slots)
        b.register_placement(owner, p)
        return b

    def kick(self) -> None:
        """Request a tick for a bucket dirtied outside the section path
        (placement staging)."""
        self.controller.queue.add(_RETICK)

    def enqueue(self, section: Section, side: bool, key) -> None:
        self.controller.enqueue((id(section.owner), side, key, section))

    def enqueue_many(self, section: Section, side: bool, keys) -> None:
        """Batch enqueue a churn/feedback key set (one queue crossing)."""
        oid = id(section.owner)
        self.controller.enqueue_many(
            [(oid, side, key, section) for key in keys])

    # ---------------------------------------------------------------- tick

    async def _process_batch(self, items: Sequence) -> list:
        # one tick: a synchronous section of the loop, so one kcp.tick
        # section spans it
        t_tick = time.monotonic()
        with obs.annotate("kcp.tick"):
            return self._tick(items, t_tick)

    def _tick(self, items: Sequence, t_tick: float) -> list:
        # 1. encode touched keys (engines re-read their informer caches):
        #    gathered section by section, staged once a BUCKET
        #    (_encode_sections). section=None items are retick markers —
        #    whatever they dirtied is submitted by this tick's fleet
        #    step. Items whose section was released (engine stop or
        #    vocabulary migration) are stale: touching them would
        #    resurrect rows in the old bucket — drop them, the
        #    replacement section was re-enqueued with the same keys.
        ph = _Phases()
        ph.enter("encode")
        # per key, remember WHICH side(s) this batch's events touched —
        # an informer event changes exactly one mirror side (the
        # reference's two controllers each watch one apiserver,
        # pkg/syncer/specsyncer.go:43-55 / statussyncer.go:29-39), so an
        # existing row ships only that side's wire entry; mask bit 1 = up,
        # bit 2 = down
        touched: dict[Section, dict] = {}
        for _oid, side, key, section in items:
            if section is not None and not section.released:
                km = touched.setdefault(section, {})
                km[key] = km.get(key, 0) | (2 if side else 1)
        try:
            self._encode_sections(touched)
        finally:
            if touched:
                ph.close()
                _ENCODED_ROWS.inc(sum(map(len, touched.values())))
            else:  # nothing encoded: the mean stays that of real encodes
                ph.discard()

        # 2. ONE fused step over every dirty bucket; collection is
        #    pipelined. Occupancy telemetry per submit: how deep the
        #    in-flight window already was (depth histogram) and whether
        #    this dispatch overlapped an executing step (the pipeline's
        #    whole point)
        fleet = self._fleet
        try:
            submitted = fleet.submit()
        except Exception as err:  # noqa: BLE001 — degraded-mode gate
            if not self._recover_step_failure(err):
                # surface loudly: a row-independent submit failure (bad
                # sharding, systemic device error) otherwise dies as 5
                # silent INFO-level retries
                log.exception("fused-core: fleet submit failed "
                              "(B=%d S=%d mesh=%s)", fleet.B, fleet.S,
                              fleet.mesh is not None)
                raise
            submitted = None
        if submitted is not None:
            depth = len(self._inflight)
            self._depth_h.observe(depth)
            if depth:
                self._overlap_ticks.inc()
            # the wire carries its tick's start stamp to its collect
            self._inflight.append((*submitted, t_tick))
            if self.fetch_depth and self._collects_by_wake():
                self._watch(submitted[0])

        # 3. collect the oldest in-flight wires beyond the pipeline
        #    window (blocking is fine by then — their data has had
        #    fetch_depth full ticks to land; serial mode, depth 0,
        #    collects everything including this tick's own wire).
        #    (Measured and rejected: collecting already-ready wires
        #    opportunistically — on a synchronous backend every wire is
        #    instantly "ready", which serializes dispatch into the tick
        #    and cost ~15% throughput at bench scale.)
        while len(self._inflight) > self.fetch_depth:
            _COLLECT_DEPTH.inc()
            self._collect(*self._inflight.pop(0))
        if self._inflight and not self._collects_by_wake():
            self._schedule_flush()
        return []

    # ------------------------------------------------ degraded-mode path

    def _recover_step_failure(self, err: Exception) -> bool:
        """Survive a failed device step without stalling the poison
        row's co-tenants: retry once wholesale (full re-upload rebuilds
        the resident state from the host mirrors — the source of truth),
        and on a second consecutive failure bisect the submitted rows to
        quarantine the poison (the fleet's bisection is segment-scoped
        and its quarantine routes through the owning member bucket).
        Returns False when the failure is row-independent (the caller
        then propagates it)."""
        fleet = self._fleet
        fleet.note_step_failure()
        REGISTRY.counter(
            "fused_step_failures_total",
            "fused device-step submissions that raised").inc()
        if fleet._step_failures == 1:
            log.warning("fused-core: device step failed (%s: %s); retrying "
                        "once with a full re-upload", type(err).__name__, err)
            self._retick()
            return True
        bad = fleet.bisect_poison(list(fleet._last_rows))
        if bad is None:
            # even the empty probe fails: systemic. Propagate — but keep
            # the fleet dirty: the failed submit already consumed the
            # staged events and cleared _stale, so without this the
            # controller's retried items would find nothing to submit
            # and the fleet would wedge converged-looking forever
            fleet.mark_stale()
            return False
        for row in bad:
            key, section = fleet.quarantine_row(row)
            log.warning("fused-core: quarantined row %d (key=%r) after "
                        "repeated device-step failures", row, key)
            if key is not None and section is not None:
                self._requeue_quarantined(section, key)
        fleet._step_failures = 0
        self._retick()
        return True

    def _retick(self) -> None:
        """Level-triggered re-run: rebuild the resident state from the
        host mirrors on a tick of its own."""
        self._fleet.mark_stale()
        self.controller.queue.add(_RETICK)

    def _requeue_quarantined(self, section: Section, key) -> None:
        """Hand a quarantined key back to its owner after a bounded
        exponential backoff — level-triggered recovery: if the poison was
        transient the re-staged row converges; if not, the next failing
        tick re-quarantines it at a longer (capped) delay."""
        qk = (id(section), key)
        n = self._quarantine_retries.get(qk, 0)
        self._quarantine_retries[qk] = n + 1
        delay = min(QUARANTINE_BASE_BACKOFF * (2 ** n), QUARANTINE_MAX_BACKOFF)
        REGISTRY.counter(
            "fused_quarantine_requeues_total",
            "quarantined keys scheduled for an owner requeue").inc()
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            return  # no loop (sync drivers): the next owner event recovers

        def _requeue() -> None:
            if not section.released:
                self.enqueue(section, False, key)

        loop.call_later(delay, _requeue)

    def _encode_section(self, section: Section, keymasks: dict) -> None:
        """The one-section form of :meth:`_encode_sections` (as
        ``stage`` is the one-row form of ``stage_many``)."""
        self._encode_sections({section: keymasks})

    def _encode_sections(self, touched: dict[Section, dict]) -> None:
        """The tick's encode: gather per touched section (Python: the
        owner's vectors, the side masks, the rows — every row allocated
        before anything is staged, a ``_grow`` reallocates the mirrors),
        stage once per bucket, then each section's epilogue. The fixed
        cost of a staging (about twenty small numpy calls a side) is
        paid once for all the sections of a bucket, not once for each:
        with a thousand engines that bring a key or two a tick it was
        most of the phase."""
        batches: dict[FusedBucket, list[_Gathered]] = {}
        try:
            for section, keymasks in touched.items():
                got = self._gather_section(section, keymasks)
                if got is not None:
                    batches.setdefault(section.bucket, []).append(got)
        finally:
            # what was gathered is staged even if a later owner raised:
            # its rows are allocated, and a key that came back with a
            # row and no mirrors would ship one side only
            for bucket, batch in batches.items():
                self._stage_batch(bucket, batch)
                _ENCODED_SECTIONS.inc(len(batch))
                # the epilogue reads the mirrors this tick just wrote
                for got in batch:
                    got.section.refresh_mask()
                    if got.absent:
                        got.section.retire_gone(got.absent)

    def _gather_section(self, section: Section,
                        keymasks: dict) -> _Gathered | None:
        """One section's part of the tick's batch (its rows allocated),
        or None where it brings nothing: its owner overflowed, or every
        key was a ghost."""
        from ..ops.encode import BucketOverflow

        keys = list(keymasks)
        known = section.rows
        many = getattr(section.owner, "fused_encode_many", None)
        # keys whose objects are gone on both sides: the rare case,
        # found without a pass of its own over the others
        absent: list = []
        try:
            if many is not None:
                ups, up_e, downs, down_e = many(keys)
                ups, downs = np.asarray(ups), np.asarray(downs)
                up_e, down_e = np.asarray(up_e, bool), np.asarray(down_e, bool)
                absent = [keys[i] for i in np.flatnonzero(~(up_e | down_e))]
                up_e, down_e = up_e.tolist(), down_e.tolist()
            else:
                encode = section.owner.fused_encode
                ups, up_e, downs, down_e = [], [], [], []
                for key in keys:
                    u, ue, dv, de = encode(key)
                    ups.append(u)
                    up_e.append(ue)
                    downs.append(dv)
                    down_e.append(de)
                    if not (ue or de):
                        absent.append(key)
        except BucketOverflow:
            # engine's vocabulary outgrew this bucket: the engine
            # re-registers in a larger bucket and replays its rows; the
            # section stays out of the tick's batch
            section.owner.fused_overflow()
            return None
        if absent and not all(k in known for k in absent):
            # a key the section does not know with nothing on either
            # side (a replayed DELETED, a key re-enqueued after its row
            # was retired) needs no row
            ghosts = {k for k in absent if k not in known}
            keep = [i for i, k in enumerate(keys) if k not in ghosts]
            if not keep:
                return None
            keys = [keys[i] for i in keep]
            absent = [k for k in absent if k not in ghosts]
            ups, downs = _taken(ups, keep), _taken(downs, keep)
            up_e, down_e = _taken(up_e, keep), _taken(down_e, keep)
        # a key new to the bucket must initialize BOTH device mirror
        # sides (whoever held its row before: Section.retire); an
        # existing row ships only the side(s) its events touched
        masks = [keymasks[k] | (0 if k in known else 3) for k in keys]
        rows = [section.row_for(k) for k in keys]
        return _Gathered(section, absent, rows, masks, (ups, downs),
                         (up_e, down_e))

    def _stage_batch(self, bucket: FusedBucket,
                     batch: list[_Gathered]) -> None:
        """At most one ``stage_many`` a side for everything the tick
        gathered for this bucket; up before down (``stage_many``)."""
        masks = np.asarray([m for got in batch for m in got.masks], np.uint8)
        sels = ((masks & 1) != 0, (masks & 2) != 0)
        try:
            # a side no event touched is not stacked at all
            vals = [_joined(got.vals[side] for got in batch)
                    if sels[side].any() else None for side in (0, 1)]
        except ValueError:
            # vectors of more than one width (an engine in a vocabulary
            # migration): each section is staged by itself, and one that
            # is ragged inside key by key, both sides as before
            if len(batch) > 1:
                for got in batch:
                    self._stage_batch(bucket, [got])
            else:
                got = batch[0]
                for row, u, ue, dv, de in zip(got.rows, got.vals[0],
                                              got.exists[0], got.vals[1],
                                              got.exists[1]):
                    bucket.stage(row, False, u, ue)
                    bucket.stage(row, True, dv, de)
            return
        _STAGE_BATCHES.inc()
        rows = np.asarray([r for got in batch for r in got.rows], np.int64)
        for side in (0, 1):
            if vals[side] is None:
                continue
            sel = sels[side]
            exists = np.asarray([e for got in batch for e in got.exists[side]],
                                bool)
            if sel.all():
                bucket.stage_many(rows, bool(side), vals[side], exists)
            else:
                bucket.stage_many(rows[sel], bool(side), vals[side][sel],
                                  exists[sel])

    def _collect(self, wire: jax.Array, meta: FleetMeta,
                 tick_start: float | None = None) -> None:
        """Fetch one in-flight wire and route its patches to the owners.
        ``tick_start`` is the ``time.monotonic()`` start of the tick that
        submitted it: the owners read it (``collecting_tick_start``)
        while their patches are handed over, as the start of the `tick`
        convergence phase, and ``fused_tick_seconds`` closes on it."""
        ph = _Phases()
        ph.enter("collect_wait")
        try:
            overflow = self._fetch_and_dispatch(wire, meta, tick_start, ph)
        finally:
            ph.close()
            self.collecting_tick_start = None
        if tick_start is not None:
            _TICK_H.observe(time.monotonic() - tick_start)
        if overflow:
            # level-triggered: re-run with doubled capacity
            self._retick()

    def _fetch_and_dispatch(self, wire, meta, tick_start,
                            ph: _Phases) -> bool:
        # fetch blocks ONLY on the compact wire (copy_to_host_async was
        # issued at dispatch) — never on the donated resident state. The
        # ready split is the pipeline-occupancy answer: a blocked fetch
        # means the host outran the device by the full window.
        try:
            ready = bool(wire.is_ready())
        except AttributeError:  # plain ndarray in tests
            ready = True
        REGISTRY.counter(
            "fused_collect_ready_total" if ready
            else "fused_collect_blocked_total",
            "fetches that found the wire already on host (ready) vs had "
            "to wait for the device (blocked)").inc()
        host_wire = np.asarray(wire)
        ph.enter("dispatch")
        self.collecting_tick_start = tick_start
        return self._fleet.dispatch(host_wire, meta)

    def _collects_by_wake(self) -> bool:
        """True where dispatch is asynchronous (the chip): a wire turns
        ready some time after its submit, and the waiter thread's wake
        collects it. On the synchronous CPU backend every wire is ready
        at once, so an eager collect would serialise dispatch into the
        loop (measured ~15% of serving throughput): there the quiet-loop
        flush below collects what a tick leaves in flight."""
        if self._eager_collect is None:
            self._eager_collect = jax.default_backend() != "cpu"
        return self._eager_collect

    def _watch(self, wire) -> None:
        """Hand a submitted wire to the waiter thread (started with the
        first one; ``stop()`` joins it)."""
        if self._waiter is None:
            self._waiter = threading.Thread(
                target=self._wait_for_wires,
                args=(asyncio.get_running_loop(),),
                name="fused-wire-waiter", daemon=True)
            self._waiter.start()
        self._wires.put((wire, time.perf_counter()))

    def _wait_for_wires(self, loop: asyncio.AbstractEventLoop) -> None:
        """The waiter thread: block on each wire in submit order, GIL
        released, until the host copy that ``copy_to_host_async``
        started is in (and cached for the loop's own ``np.asarray``),
        then wake the loop. It touches nothing of the core's state."""
        while (item := self._wires.get()) is not None:
            wire, t_submit = item
            try:
                np.asarray(wire)
            except Exception:  # noqa: BLE001 — the collect on the loop
                pass           # meets the same failure and reports it
            try:
                loop.call_soon_threadsafe(
                    self._on_wire_ready, wire, t_submit, time.perf_counter())
            except RuntimeError:  # the loop is closed: nobody to wake
                return

    def _on_wire_ready(self, wire, t_submit: float, t_ready: float) -> None:
        """On the loop, woken by the waiter thread: collect, in submit
        order, every head of ``_inflight`` that is ready. A head the
        depth rule took meanwhile is not in the list any more, and a
        wake that arrives after ``stop()`` finds the list empty."""
        _WIRE_READY_H.observe(t_ready - t_submit)
        lag = time.perf_counter() - t_ready
        while self._inflight:
            head = self._inflight[0][0]
            if head is not wire and not head.is_ready():
                break
            if head is wire:
                _COLLECT_LAG_H.observe(lag)
            _COLLECT_WOKEN.inc()
            self._collect_late(self._inflight.pop(0))

    def _schedule_flush(self) -> None:
        if self._flush_task is not None:
            self._flush_task.cancel()
        self._flush_task = asyncio.create_task(self._idle_flush())

    async def _idle_flush(self) -> None:
        """The synchronous CPU backend's collect off the tick path: once
        the loop has been quiet for IDLE_FLUSH_S (every tick re-arms
        it), collect what is in flight — without it the last tick's
        patches would wait for the next informer event."""
        await asyncio.sleep(IDLE_FLUSH_S)
        while self._inflight:
            self._collect_late(self._inflight.pop(0))

    def _collect_late(self, entry: tuple) -> None:
        """A collect between ticks (a wake, the quiet-loop flush, the
        shutdown drain): its own ``kcp.tick`` on the profiler's
        timeline."""
        with obs.annotate("kcp.tick"):
            self._collect(*entry)

    async def _drain_inflight(self) -> None:
        while self._inflight:
            _COLLECT_DEPTH.inc()
            self._collect_late(self._inflight.pop(0))
