"""The flagship device program: one fused reconcile step for the fleet.

This is the framework's "model": where the reference runs thousands of
goroutines each diffing one object (SURVEY.md §2.2), this program runs
the *entire control plane's* decision math as one compiled XLA step over
device-resident state:

  1. scatter the tick's informer deltas into the resident mirrors
  2. spec/status three-way diff over every row        (syncer lanes)
  3. replica placement over every root deployment      (splitter lane)
  4. label-selector fan-out over every object x cluster (informer lane)
  5. global convergence statistics (reduced across the mesh)

Everything is fixed-shape, branch-free, elementwise + masked-reduction
work: ideal VPU/HBM streaming with nothing blocking XLA fusion. The step
is donation-friendly (state in, state out) so steady-state runs entirely
in HBM; only the delta batch crosses the host<->device link each tick,
and only the decision lanes come back.

Sharding: see kcp_tpu/parallel/mesh.py — rows over the ``tenants`` axis,
slot columns optionally over ``slots``; the stats reductions become XLA
collectives. ``dryrun_multichip`` in __graft_entry__.py exercises exactly
this step over a multi-device mesh.
"""

from __future__ import annotations

import logging
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.diff import apply_deltas, compact_patches, sync_decisions
from ..ops.labelmatch import fanout_match
from ..ops.placement import placement_changed, split_replicas

log = logging.getLogger(__name__)


class ReconcileState(NamedTuple):
    """Device-resident control-plane state (one schema bucket).

    B = object rows (all tenants), S = slot columns, R = root deployments,
    P = physical clusters, L = label slots, C = cluster selectors.
    """

    up_vals: jax.Array  # uint32 [B, S]
    up_exists: jax.Array  # bool [B]
    down_vals: jax.Array  # uint32 [B, S]
    down_exists: jax.Array  # bool [B]
    status_mask: jax.Array  # bool [S] (bucket-wide) or [B, S] (per-row)
    replicas: jax.Array  # int32 [R]
    avail: jax.Array  # bool [R, P]
    current: jax.Array  # int32 [R, P] currently-applied leaf replicas
    pair_hashes: jax.Array  # uint32 [B, L]
    sel_hashes: jax.Array  # uint32 [C]


class ReconcileDeltas(NamedTuple):
    """One tick's informer deltas, padded to a fixed D.

    Single-sided: a real informer event reports a change on exactly ONE
    side — the kcp (upstream/spec) stream or the physical (downstream/
    status) stream — the reference's two controllers each watch one
    apiserver (pkg/syncer/specsyncer.go:43-55, statussyncer.go:29-39).
    One payload column per row, routed by ``side``, halves the
    host->device bytes per tick vs. a both-sides layout.
    """

    idx: jax.Array  # int32 [D] row indices
    vals: jax.Array  # uint32 [D, S] new encoding (ignored for deletes)
    exists: jax.Array  # bool [D] False = delete event
    side: jax.Array  # bool [D] False = upstream mirror, True = downstream
    valid: jax.Array  # bool [D] padding mask


class ReconcileOutputs(NamedTuple):
    # compact lanes — the only thing the host applier fetches each tick
    patch_idx: jax.Array  # int32 [K] actionable row indices (pad = B)
    patch_code: jax.Array  # uint8 [K] decision per patch row
    patch_upsync: jax.Array  # bool [K] status-upsync flag per patch row
    patch_count: jax.Array  # int32 [] valid patch rows
    patch_overflow: jax.Array  # bool [] > K rows actionable this tick
    stats: jax.Array  # int32 [8] global counters (see STATS_FIELDS)
    # full lanes — stay device-resident; fetched only on patch_overflow
    # or by tests/debugging
    decision: jax.Array  # uint8 [B] NOOP/CREATE/UPDATE/DELETE
    status_upsync: jax.Array  # bool [B]
    leaf_replicas: jax.Array  # int32 [R, P] desired placement
    placement_dirty: jax.Array  # bool [R]
    match_counts: jax.Array  # int32 [C] objects matched per cluster selector


STATS_FIELDS = (
    "rows", "creates", "updates", "deletes", "upsyncs",
    "placement_dirty", "matched", "applied_deltas",
)


def reconcile_step(state: ReconcileState, deltas: ReconcileDeltas,
                   patch_capacity: int = 8192, use_pallas: bool = False,
                   mesh=None,
                   ) -> tuple[ReconcileState, ReconcileOutputs]:
    # Every lane runs under a jax.named_scope: metadata only (same HLO,
    # same fusions), so that a profiler trace's device operations can be
    # grouped by the lane they came from instead of read as fusion.13.
    # 1. scatter deltas, routed by side (ops/diff.apply_deltas owns the
    #    padding-drop and dedup-by-key contract: delta batches must carry
    #    unique indices)
    with jax.named_scope("apply_deltas_up"):
        up_vals, up_exists = apply_deltas(
            state.up_vals, state.up_exists, deltas.idx,
            deltas.vals, deltas.exists, deltas.valid & ~deltas.side,
        )
    with jax.named_scope("apply_deltas_down"):
        down_vals, down_exists = apply_deltas(
            state.down_vals, state.down_exists, deltas.idx,
            deltas.vals, deltas.exists, deltas.valid & deltas.side,
        )

    b = up_vals.shape[0]
    local_b = b
    if use_pallas and mesh is not None:
        from ..parallel.mesh import row_factor, slot_factor

        # the kernel runs per row-shard and needs full S per row: fall
        # back to the (slot-partitioned) XLA lanes when b does not split
        # exactly into 128-multiples per shard, or when the slots axis
        # would force redundant all-gathered work on every slot shard
        if b % (128 * row_factor(mesh)) == 0 and slot_factor(mesh) == 1:
            local_b = b // row_factor(mesh)
        else:
            local_b = 1  # fails the gate below -> XLA lanes
    br = 0
    if use_pallas and local_b % 128 == 0:
        # 2+4 fused: one Pallas pass reads each row block into VMEM once
        # and emits the decision lanes + per-selector match counts
        # (ops/pallas_kernels.py; differential-tested vs the XLA lanes).
        # On a mesh the kernel runs per device on its local row block via
        # shard_map (counts psum across the row axes). block_rows must
        # DIVIDE the local rows AND fit the measured scoped-VMEM budget
        # for this slot width (max_block_rows; 128 always divides given
        # the gate, but a very wide bucket can fail the VMEM cap)
        from ..ops.pallas_kernels import (
            decide_and_match,
            decide_and_match_sharded,
            max_block_rows,
        )

        br = max_block_rows(local_b, up_vals.shape[1],
                            labels=state.pair_hashes.shape[1],
                            per_row_mask=state.status_mask.ndim == 2)
    if use_pallas and not br:
        # the gate tells: runs at trace time, so once per compiled shape
        log.warning(
            "use_pallas=True but B=%d S=%d (rows per shard %d) fails the "
            "kernel's gate (128-row multiples per shard, full slots per "
            "row, a block inside the scoped-VMEM budget): this shape "
            "serves the XLA lanes", b, up_vals.shape[1], local_b)
    if use_pallas and br:
        with jax.named_scope("decide_and_match_pallas"):
            if mesh is not None:
                decision, status_upsync, match_counts = decide_and_match_sharded(
                    mesh, up_vals, up_exists, down_vals, down_exists,
                    state.status_mask, state.pair_hashes, state.sel_hashes,
                    block_rows=br,
                )
            else:
                decision, status_upsync, match_counts = decide_and_match(
                    up_vals, up_exists, down_vals, down_exists,
                    state.status_mask, state.pair_hashes, state.sel_hashes,
                    block_rows=br,
                )
            matched_total = match_counts.sum(dtype=jnp.int32)
    else:
        # 2. syncer lanes
        with jax.named_scope("sync_decisions"):
            d = sync_decisions(up_vals, up_exists, down_vals, down_exists,
                               state.status_mask)
            decision, status_upsync = d.decision, d.status_upsync

        # 4. informer fan-out lane — only resident upstream objects fan
        #    out (pair_hashes rows of deleted objects are stale, not
        #    cleared)
        with jax.named_scope("fanout_match"):
            match = fanout_match(state.pair_hashes, state.sel_hashes) & up_exists[:, None]  # [B, C]
            match_counts = match.sum(axis=0, dtype=jnp.int32)
            matched_total = match.sum(dtype=jnp.int32)

    # 3. splitter lane
    with jax.named_scope("placement"):
        leaf = split_replicas(state.replicas, state.avail)
        p_dirty = placement_changed(state.current, leaf)

    # 5. global stats — under a sharded mesh these reductions lower to
    #    XLA collectives over the tenants/slots axes
    with jax.named_scope("stats"):
        stats = jnp.stack([
            up_exists.sum(dtype=jnp.int32),
            (decision == 1).sum(dtype=jnp.int32),
            (decision == 2).sum(dtype=jnp.int32),
            (decision == 3).sum(dtype=jnp.int32),
            status_upsync.sum(dtype=jnp.int32),
            p_dirty.sum(dtype=jnp.int32),
            matched_total,
            deltas.valid.sum(dtype=jnp.int32),
        ])

    new_state = ReconcileState(
        up_vals=up_vals, up_exists=up_exists,
        down_vals=down_vals, down_exists=down_exists,
        status_mask=state.status_mask,
        replicas=state.replicas, avail=state.avail, current=leaf,
        pair_hashes=state.pair_hashes, sel_hashes=state.sel_hashes,
    )
    with jax.named_scope("compact_patches"):
        patches = compact_patches(decision, status_upsync, patch_capacity)
    outputs = ReconcileOutputs(
        patch_idx=patches.idx, patch_code=patches.code,
        patch_upsync=patches.upsync, patch_count=patches.count,
        patch_overflow=patches.overflow,
        decision=decision, status_upsync=status_upsync,
        leaf_replicas=leaf, placement_dirty=p_dirty,
        match_counts=match_counts, stats=stats,
    )
    return new_state, outputs


reconcile_step_jit = jax.jit(
    reconcile_step, donate_argnums=(0,),
    static_argnames=("patch_capacity", "use_pallas", "mesh"),
)


# ---------------------------------------------------------------------------
# Packed wire format — one array per direction across the host<->device link.
#
# Every array that crosses the link is its own transfer (and, where the
# device sits on another host — §2.3's "gRPC link ships informer deltas
# to a JAX worker which returns patch sets" — its own RPC); packing the
# tick's deltas into ONE uint32 array and the patch set + stats into ONE
# int32 array makes a tick exactly one upload and one download regardless
# of lane count. Patch entries carry row index (20 bits), decision code (2 bits,
# bit 20-21) and the status-upsync flag (bit 23).
#
# Wire layout (int32):
#   [0]                 patch count
#   [1]                 patch overflow flag
#   [2:10]              stats
#   [10]                placement-dirty count
#   [PACK_HDR : +K]     packed patch entries (K = patch_capacity)
#   [PACK_HDR+K : +R*(1+P)]  placement entries: R rows of
#                       (root row index or R for padding, P leaf counts)
#                       — dirty roots compacted first (the splitter lane
#                       rides the same wire as the sync lanes)
# ---------------------------------------------------------------------------

PACK_HDR = 16  # int32 slots ahead of the packed patch entries
PACK_IDX_MASK = (1 << 20) - 1
PACK_CODE_SHIFT = 20
PACK_UPSYNC_BIT = 1 << 23
PACK_PLACEMENT_COUNT = 10  # hdr slot carrying the placement-dirty count


def pack_deltas(deltas: ReconcileDeltas) -> np.ndarray:
    """Host-side: pack a delta batch into one uint32 [D, S+2] array."""
    d = np.asarray(deltas.vals).shape[0]
    flags = (
        np.asarray(deltas.exists).astype(np.uint32)
        | (np.asarray(deltas.side).astype(np.uint32) << 1)
        | (np.asarray(deltas.valid).astype(np.uint32) << 2)
    )
    return np.concatenate(
        [
            np.asarray(deltas.vals),
            np.asarray(deltas.idx).astype(np.uint32).reshape(d, 1),
            flags.reshape(d, 1),
        ],
        axis=1,
    )


MASK_STAMP_BIT = 8  # flag: entry carries a status-mask row, not a delta


def unpack_deltas(packed: jax.Array) -> ReconcileDeltas:
    """Device-side (inside jit): unpack the uint32 [D, S+2] wire array.

    Mask-stamp entries (flag bit 8) are not deltas — they are excluded
    from ``valid`` here and consumed by :func:`apply_mask_stamps`."""
    s = packed.shape[1] - 2
    flags = packed[:, s + 1]
    return ReconcileDeltas(
        idx=packed[:, s].astype(jnp.int32),
        vals=packed[:, :s],
        exists=(flags & 1) != 0,
        side=(flags & 2) != 0,
        valid=((flags & 4) != 0) & ((flags & MASK_STAMP_BIT) == 0),
    )


def apply_mask_stamps(status_mask: jax.Array, packed: jax.Array) -> jax.Array:
    """Scatter mask-stamp entries into the per-row status mask.

    A row allocated AFTER its bucket's last full upload has a host-side
    mask stamp (Section.row_for) that the device never saw — the delta
    wire carries values only. Without this lane the device's mask for
    such a row stays all-False, its status churn misreads as spec churn
    (UPDATE instead of upsync), the applier correctly no-ops the
    phantom UPDATE, and the object never converges — found by the
    randomized differential fuzz. Stamps ride the same packed array:
    flag bit 8, vals columns = the bool mask row.
    """
    if status_mask.ndim != 2:
        return status_mask  # bucket-wide [S] masks have no per-row lane
    b = status_mask.shape[0]
    s = packed.shape[1] - 2
    flags = packed[:, s + 1]
    sel = ((flags & 4) != 0) & ((flags & MASK_STAMP_BIT) != 0)
    idx = packed[:, s].astype(jnp.int32)
    tgt = jnp.where(sel, idx, b)  # non-stamp entries route OOB -> drop
    return status_mask.at[tgt].set(packed[:, :s] != 0, mode="drop")


def reconcile_step_packed(state: ReconcileState, packed: jax.Array,
                          acks: jax.Array | None = None,
                          patch_capacity: int = 8192, use_pallas: bool = False,
                          mesh=None,
                          ) -> tuple[ReconcileState, jax.Array]:
    """The wire-format step: one uint32 array in, one int32 array out.

    ``acks`` is the converged-row compression lane: int32 row indices
    (negative = padding) whose downstream mirror becomes a copy of the
    resident upstream mirror. A feedback event whose encoded row equals
    the up mirror the device already holds — the applier's up->down copy
    observed back through the downstream informer — needs only these 4
    bytes on the wire instead of a full (S+2)-column entry. The host
    stager proves eligibility (values equal the host up mirror AND no
    up-side entry staged this tick, so the resident row it copies is
    exactly that value); the copy runs before the delta scatter, which
    by the eligibility rule cannot touch an acked row's up side.

    Here the lane is an argument of its own (``None``: no lane, the form
    ``ReconcileModel``-style callers and the tests use). The serving
    path never ships it as a second array: it rides in the tail rows of
    the ONE packed array a tick puts on the device, and
    :func:`reconcile_step_fleet` splits it off at its head
    (:func:`split_ack_lane`) before calling this function.

    Output layout: [0]=patch count, [1]=overflow flag, [2:10]=stats,
    [PACK_HDR:]=packed patch entries (see module comment).
    """
    if state.up_vals.shape[0] > PACK_IDX_MASK + 1:
        # row indices go up to B-1, so B == 2^20 exactly fits the field
        raise ValueError(
            f"packed patch entries hold 20-bit row indices; "
            f"B={state.up_vals.shape[0]} exceeds {PACK_IDX_MASK + 1} — "
            f"shard the bucket or use the unpacked ReconcileOutputs lanes"
        )
    if acks is not None and state.up_vals.shape[0] > 0:
        with jax.named_scope("apply_acks"):
            b = state.up_vals.shape[0]
            valid = (acks >= 0) & (acks < b)
            # padding (-1) must not scatter AT ALL: clipping it to a real
            # row would race that row's genuine ack (duplicate-index
            # scatter order is unspecified) — route padding out of bounds
            # and drop it
            idx = jnp.where(valid, acks, b)
            gather = jnp.clip(acks, 0, b - 1)
            down_vals = state.down_vals.at[idx].set(
                state.up_vals[gather], mode="drop")
            down_exists = state.down_exists.at[idx].set(
                state.up_exists[gather], mode="drop")
            state = state._replace(down_vals=down_vals,
                                   down_exists=down_exists)
    with jax.named_scope("apply_mask_stamps"):
        state = state._replace(
            status_mask=apply_mask_stamps(state.status_mask, packed))
    with jax.named_scope("reconcile_step"):
        new_state, out = reconcile_step(
            state, unpack_deltas(packed), patch_capacity,
            use_pallas=use_pallas, mesh=mesh)
    with jax.named_scope("pack_wire"):
        entries = (
            out.patch_idx
            | (out.patch_code.astype(jnp.int32) << PACK_CODE_SHIFT)
            | jnp.where(out.patch_upsync, PACK_UPSYNC_BIT, 0)
        )
        # placement segment: dirty roots compacted first, each carrying
        # its P leaf counts (the deployment splitter's serving lane)
        r = state.replicas.shape[0]
        dirty = out.placement_dirty
        (pidx,) = jnp.nonzero(dirty, size=r, fill_value=r)
        safe = jnp.minimum(pidx, r - 1)
        valid = pidx < r
        counts = jnp.where(valid[:, None], out.leaf_replicas[safe], 0)
        pl_entries = jnp.concatenate(
            [pidx.astype(jnp.int32)[:, None], counts.astype(jnp.int32)],
            axis=1,
        ).reshape(-1)
        hdr = jnp.zeros(PACK_HDR, jnp.int32)
        hdr = hdr.at[0].set(out.patch_count)
        hdr = hdr.at[1].set(out.patch_overflow.astype(jnp.int32))
        hdr = hdr.at[2:10].set(out.stats)
        hdr = hdr.at[PACK_PLACEMENT_COUNT].set(dirty.sum(dtype=jnp.int32))
        return new_state, jnp.concatenate([hdr, entries, pl_entries])


# ---------------------------------------------------------------------------
# Fleet lane — cross-bucket ragged batching (syncer/core.py FleetBatch).
#
# The fleet batch packs every schema bucket's rows into ONE ReconcileState
# (rows range-partitioned by bucket, slot columns zero-padded to the widest
# bucket) so a tick is one pipelined device program for the whole tenant
# fleet. Each row carries a *segment id* — the owning section (engine) —
# resident on device as an int32 [B] lane beside the state. Two uses:
#
# - segment stamps: a row allocated after the last full upload ships its
#   segment id inside its MASK_STAMP wire entry (flag bits 8..23), the
#   same entry that carries its status mask — no extra wire entries;
# - per-segment counters: the step ends with a segment-sum of the new
#   ``up_exists`` lane, shipped on the wire tail, so admission usage
#   accounting (admission/quota.py) rides the same batch instead of a
#   host-side recount pass.
# ---------------------------------------------------------------------------

SEG_SHIFT = 8  # mask-stamp flag bits [8..23] carry the row's segment id
SEG_FIELD_MASK = 0xFFFF
# unowned/freed rows: always >= any real segment capacity, so the
# counter scatter drops them (capacities stay far below 16 bits)
SEG_NONE = 0xFFFF


def apply_seg_stamps(seg_ids: jax.Array, packed: jax.Array) -> jax.Array:
    """Scatter segment-id stamps from MASK_STAMP entries into the
    resident row->segment lane (the fleet analog of apply_mask_stamps:
    rows allocated after the last full upload are otherwise unknown to
    the device-side per-segment counters)."""
    b = seg_ids.shape[0]
    s = packed.shape[1] - 2
    flags = packed[:, s + 1]
    sel = ((flags & 4) != 0) & ((flags & MASK_STAMP_BIT) != 0)
    idx = packed[:, s].astype(jnp.int32)
    tgt = jnp.where(sel, idx, b)  # non-stamp entries route OOB -> drop
    seg = ((flags >> SEG_SHIFT) & SEG_FIELD_MASK).astype(jnp.int32)
    return seg_ids.at[tgt].set(seg, mode="drop")


def ack_lane_rows(ack_capacity: int, width: int) -> int:
    """Rows of a ``width``-column packed array that hold an ack lane of
    ``ack_capacity`` entries (the last row padded to the width)."""
    return -(-ack_capacity // width)


def split_ack_lane(packed: jax.Array,
                   ack_capacity: int) -> tuple[jax.Array, jax.Array]:
    """Device-side (inside jit): the one array a fleet tick puts on the
    device -> (``uint32 [d, S+2]`` event entries, ``int32
    [ack_capacity]`` ack lane). The lane lies row-major in the array's
    last :func:`ack_lane_rows` rows, its int32 entries bit for bit as
    uint32 (-1 padding is 0xFFFFFFFF there and -1 again here)."""
    d = packed.shape[0] - ack_lane_rows(ack_capacity, packed.shape[1])
    acks = jax.lax.bitcast_convert_type(
        packed[d:].reshape(-1)[:ack_capacity], jnp.int32)
    return packed[:d], acks


def reconcile_step_fleet(state: ReconcileState, seg_ids: jax.Array,
                         packed: jax.Array, ack_capacity: int,
                         patch_capacity: int = 8192, seg_capacity: int = 8,
                         use_pallas: bool = False, mesh=None,
                         ) -> tuple[ReconcileState, jax.Array, jax.Array]:
    """The fleet-batch step: :func:`reconcile_step_packed` plus the
    resident segment lane and per-segment live-row counters.

    ``packed`` is the ONE array a tick sends: ``uint32 [d + ack_rows,
    S+2]``, the event entries in its first ``d`` rows and the ack lane
    of the static ``ack_capacity`` in its tail (:func:`split_ack_lane`;
    :class:`WireBuffers` lays it out on the host). Everything below the
    split sees the two arrays it would see had they crossed apart.

    ``seg_ids`` (int32 [B], device-resident like the state) maps each
    fleet row to its owning section's segment id (SEG_NONE = unowned).
    The wire grows a tail of ``seg_capacity`` int32 counts — the number
    of live upstream rows per segment after this tick's scatter — which
    the host routes to the admission quota ledger. Out-of-range segment
    ids (padding, unowned rows) drop out of the scatter-add.
    """
    with jax.named_scope("reconcile_step_fleet"):
        with jax.named_scope("split_ack_lane"):
            packed, acks = split_ack_lane(packed, ack_capacity)
        with jax.named_scope("apply_seg_stamps"):
            seg_ids = apply_seg_stamps(seg_ids, packed)
        new_state, wire = reconcile_step_packed(
            state, packed, acks, patch_capacity, use_pallas=use_pallas,
            mesh=mesh)
        with jax.named_scope("seg_counts"):
            counts = jnp.zeros(seg_capacity, jnp.int32).at[seg_ids].add(
                new_state.up_exists.astype(jnp.int32), mode="drop")
        return new_state, seg_ids, jnp.concatenate([wire, counts])


def unpack_seg_counts(wire: np.ndarray, patch_capacity: int, r: int, p: int,
                      seg_capacity: int) -> np.ndarray:
    """Host-side: the per-segment live-row counts from a fleet wire (the
    caller knows the submitted patch capacity, placement shape and
    segment capacity — FleetBatch snapshots them per submit)."""
    off = PACK_HDR + patch_capacity + r * (1 + p)
    return wire[off:off + seg_capacity]


class WireBuffers:
    """Rotating host staging for the packed-delta wire.

    One buffer a slot: ``uint32 [d + ack_rows, width]``, the tick's event
    entries in the first ``d`` rows and the ack lane in the tail rows
    (:func:`split_ack_lane` is its device-side reader), so a tick hands
    the runtime ONE array and makes one transfer a device.

    The staging/donation contract of :func:`reconcile_step_packed`: the
    resident state is donated every tick, but the packed array is NOT,
    and the host buffer behind it stays in use after ``jax.device_put``
    returns: an accelerator's transfer may still be reading it, and the
    CPU backend's put is zero-copy — the step reads the numpy memory
    itself, whenever the asynchronous dispatch gets to it, and the put's
    own readiness says nothing about that (measured under load: a reused
    buffer lost a whole tick's events with only the put gating it).
    Fresh ``np.zeros`` per tick is safe but pays an allocation +
    page-fault cost on every tick of the hot loop. Rotating buffers make
    reuse safe: ``acquire`` hands out the least-recently-used buffer,
    first blocking until the arrays committed for it are ready — the put
    AND the output of the step that consumed it, the one signal every
    backend gives that the buffer has been read. With one slot more than
    the pipeline's in-flight window that step has always been collected
    by then, so the gate does not wait.
    """

    def __init__(self, depth: int = 2):
        self.depth = depth
        self._packed: list[np.ndarray | None] = [None] * depth
        # the put that last read each slot's host buffer, and the output
        # of the step that consumed it
        self._pending: list[tuple | None] = [None] * depth
        self._i = 0
        self.reuse_waits = 0  # acquires that had to block on a transfer

    def acquire(self, d: int, width: int,
                ack_capacity: int) -> tuple[int, np.ndarray, np.ndarray]:
        """A ``uint32 [d + ack_rows, width]`` buffer, its first ``d`` rows
        zeroed, plus the -1-filled ``int32 [ack_capacity]`` VIEW of its
        tail rows (the ack lane), both safe to fill immediately. Returns
        ``(slot, packed, acks)``; pass ``slot`` to :meth:`commit` with
        the device arrays produced from the buffer."""
        i = self._i
        self._i = (i + 1) % self.depth
        pending = self._pending[i]
        if pending is not None:
            self._pending[i] = None
            for arr in pending:
                if not arr.is_ready():
                    self.reuse_waits += 1
                    arr.block_until_ready()
        shape = (d + ack_lane_rows(ack_capacity, width), width)
        packed = self._packed[i]
        if packed is None or packed.shape != shape:
            packed = self._packed[i] = np.empty(shape, np.uint32)
        packed[:d].fill(0)
        # all-ones is -1, padding, in every int32 entry of the lane
        packed[d:].fill(0xFFFFFFFF)
        acks = packed[d:].reshape(-1)[:ack_capacity].view(np.int32)
        return i, packed, acks

    def commit(self, slot: int, *device_arrays) -> None:
        """Record the device arrays whose readiness means the slot's
        buffer has been read — the put and the consuming step's output;
        the next acquire of this slot gates on them."""
        self._pending[slot] = device_arrays


def unpack_patches(wire: np.ndarray) -> tuple[np.ndarray, np.ndarray, np.ndarray, bool, np.ndarray]:
    """Host-side: (idx, code, upsync, overflow, stats) from the wire array."""
    count = int(wire[0])
    entries = wire[PACK_HDR:PACK_HDR + count]
    return (
        entries & PACK_IDX_MASK,
        (entries >> PACK_CODE_SHIFT) & 3,
        (entries & PACK_UPSYNC_BIT) != 0,
        bool(wire[1]),
        wire[2:10],
    )


def unpack_placement(wire: np.ndarray, patch_capacity: int, p: int,
                     r: int | None = None) -> tuple[np.ndarray, np.ndarray]:
    """Host-side: (dirty root row indices [N], leaf counts [N, P]) from
    the wire's placement segment (the caller knows the bucket's static
    patch_capacity and cluster width P). ``r`` bounds the segment to
    ``r`` placement rows — required for fleet wires, whose tail carries
    the per-segment counters after the placement entries."""
    n = int(wire[PACK_PLACEMENT_COUNT])
    seg = wire[PACK_HDR + patch_capacity:]
    if r is not None:
        seg = seg[:r * (1 + p)]
    seg = seg.reshape(-1, 1 + p)
    return seg[:n, 0], seg[:n, 1:]


def example_state(
    b: int = 8192, s: int = 64, r: int = 1024, p: int = 8, l: int = 8, c: int = 64,
    seed: int = 0, dirty_frac: float = 0.01,
) -> ReconcileState:
    """A synthetic populated state (host numpy; device placement is the
    caller's choice so meshes can shard it)."""
    rng = np.random.default_rng(seed)
    up = rng.integers(1, 2**32, size=(b, s), dtype=np.uint32)
    down = up.copy()
    flip = rng.random(b) < dirty_frac
    down[flip, :1] ^= 1
    status_mask = np.zeros(s, bool)
    status_mask[-max(1, s // 8):] = True
    return ReconcileState(
        up_vals=up,
        up_exists=np.ones(b, bool),
        down_vals=down,
        down_exists=np.ones(b, bool),
        status_mask=status_mask,
        replicas=rng.integers(0, 100, size=r).astype(np.int32),
        avail=rng.random((r, p)) < 0.9,
        current=np.zeros((r, p), np.int32),
        pair_hashes=rng.integers(1, 2**32, size=(b, l), dtype=np.uint32),
        sel_hashes=rng.integers(1, 2**32, size=c, dtype=np.uint32),
    )


def example_deltas(b: int = 8192, s: int = 64, d: int = 256, seed: int = 1) -> ReconcileDeltas:
    rng = np.random.default_rng(seed)
    # unique indices: the apply_deltas contract (duplicate in-batch scatter
    # order is unspecified; the host batcher deduplicates by key)
    return ReconcileDeltas(
        idx=rng.permutation(b)[:d].astype(np.int32),
        vals=rng.integers(1, 2**32, size=(d, s), dtype=np.uint32),
        exists=np.ones(d, bool),
        side=rng.random(d) < 0.5,
        valid=rng.random(d) < 0.9,
    )


class ReconcileModel:
    """Convenience wrapper holding compiled step + device state."""

    def __init__(self, state: ReconcileState, mesh=None, donate: bool = True):
        if mesh is not None:
            from ..parallel.mesh import shard_state

            state = shard_state(state, mesh)
        else:
            state = jax.tree.map(jax.device_put, state)
        self.state = state
        self._step = reconcile_step_jit if donate else jax.jit(reconcile_step)

    def step(self, deltas: ReconcileDeltas) -> ReconcileOutputs:
        self.state, out = self._step(self.state, deltas)
        return out
