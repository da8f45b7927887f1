"""BatchSyncEngine — the vectorized spec<->status sync loop.

The reference runs two controllers per (cluster, resource-set): a spec
syncer (kcp -> physical, pkg/syncer/specsyncer.go) and a status syncer
(physical -> kcp, pkg/syncer/statussyncer.go), each deep-diffing objects
one goroutine at a time. Here both directions are lanes of ONE batched
device program per (cluster, GVR):

  informer deltas (both sides)
        -> host encode (hash tensors)            ops/encode.py
        -> device scatter into resident mirrors  ops/diff.apply_deltas
        -> device 3-way diff over ALL rows       ops/diff.sync_decisions
        -> non-NOOP rows home to host
        -> host verifies + applies patches with optimistic concurrency

The mirrors are *device-resident* in the tpu backend: host numpy copies
are the staging/rebuild area, but steady-state ticks ship only the padded
delta batch to the device and scatter there (the TPU sits behind a
host<->device link — re-uploading a 100k-row mirror per tick would be
~50MB of transfer and 1000x slower than the kernel itself).

Running the diff over the full resident mirror every tick makes the loop
level-triggered: a tick converges *everything* currently out of sync, not
just the keys that woke it. Two safety nets bound hash-collision damage:
every device decision is re-verified against the real objects before a
write (the host escape hatch), and a periodic informer resync replays the
caches (reference: resyncPeriod, pkg/syncer/syncer.go:27).

One decision does not wait for the device: on the fused backend the
status that answers a write this engine carried down (the key's
convergence entry stands between its downstream write and its first
status) is handed to the applier by the event that brought it
(``_on_down_event``, as the reference compares in its informer handler,
statussyncer.go:32-36). The row still rides the next tick, which is that
write's backstop: it re-emits the upsync if the apply failed, was skipped
behind a pending apply of the key, or the status changed again. A later
status of the same object (a controller that reports progress) has only
the tick: nobody this engine knows of waits for it, and two that meet
there go up as one write. A key whose location was seen to report
progress keeps the tick for the answer to its next write too
(``_reports``): its first status is not what that write's author waits
for, and the slower trip is where it meets the second.

Decision application parity with the reference:
- CREATE/UPDATE downstream: strip volatile metadata + ownerReferences +
  status, ensure namespace, create-then-update-on-conflict
  (specsyncer.go:86-132)
- DELETE downstream on upstream deletion (specsyncer.go:79-84)
- status upsync upstream via the status subresource, stale-RV conflicts
  requeue (statussyncer.go:41-63)
"""

from __future__ import annotations

import asyncio
import logging
import time
from typing import Sequence

import numpy as np

from .. import obs
from ..apis.scheme import GVR
from ..client import Client, Informer
from ..ops.diff import (
    DECISION_CREATE,
    DECISION_DELETE,
    DECISION_NOOP,
    DECISION_UPDATE,
)
from ..ops.encode import BucketEncoder, BucketOverflow, pad_pow2
from ..reconciler.controller import BatchController
from ..store.selectors import LabelSelector, parse_selector
from ..utils import errors
from ..utils.trace import REGISTRY
from ..utils.treecopy import tree_copy

log = logging.getLogger(__name__)

# fetched once: observed on every closed convergence / every host tick
_CONVERGENCE = REGISTRY.histogram(
    "kcp_sync_convergence_seconds",
    "one key from the write that dirtied it to converged: status "
    "upsynced, or the two sides observed equal")
_HOST_TICK = REGISTRY.histogram(
    "kcp_sync_tick_seconds", "one host-backend reconcile tick")
_TICKS = REGISTRY.counter(
    "kcp_sync_ticks_total", "reconcile ticks across all sync sessions")
_EVENTS = REGISTRY.counter(
    "kcp_sync_events_total", "informer events drained into tick batches")
_STATUS_UPSYNCS = REGISTRY.counter(
    "kcp_sync_status_upsyncs_total",
    "upstream status writes made by the syncer engines")
_STATUS_REPEATS = REGISTRY.counter(
    "kcp_sync_status_upsync_repeats_total",
    "status upsyncs of a key made before the echo of its previous one "
    "came up the informer")
_STATUS_DIRECT = REGISTRY.counter(
    "kcp_sync_status_upsyncs_direct_total",
    "upstream status writes made by an apply that the downstream event "
    "itself queued, with no tick between the event and the write")
_INITIAL_ROWS = REGISTRY.counter(
    "kcp_sync_initial_rows_total",
    "rows staged by the replay of an upstream informer's initial list "
    "(objects that existed before their syncer started), as against "
    "rows staged by a live event")
_PATCHES_DEFERRED = REGISTRY.counter(
    "kcp_sync_patches_deferred_total",
    "patches (of a collected tick, or a status handed over by its "
    "downstream event) skipped because the key's apply was still pending")

# states of a key's convergence entry, in timeline order
_STAGED, _TICKED, _PATCHED, _DOWNSTAGED, _DONE = range(5)

# in the pending-apply table: a patch of another decision was skipped
_REARM = ("rearm",)


def _rv_of(obj: dict | None) -> str:
    return str(((obj or {}).get("metadata") or {}).get("resourceVersion", ""))


def _rv_le(a: str, b: str) -> bool:
    """``a`` is ``b`` or an older resourceVersion (store RVs are
    integers; anything else compares by equality)."""
    try:
        return int(a) <= int(b)
    except ValueError:
        return a == b


class _Convergence:
    """One dirty key's timeline (obs/trace.py PHASES), every stamp
    ``time.monotonic()``. The entry is made by the up event that dirtied
    the key and carries each boundary as the row passes it, so adjacent
    phases share their stamp and the phase sum telescopes to
    ``end - start``. ``ctx`` is the committing write's trace context —
    present only for a sampled write, and the only thing the ``conv.*``
    spans need beyond what every write keeps.

    ``_DONE`` is the state of a key whose (first) status is up: ``rv`` is
    the newest status write of ours whose echo is still to come up the
    informer ("" when none is), ``t_down`` the arrival of a downstream
    status event not yet carried up — a LATER status trip of the same
    write (`restatus`), as a controller that steps a status through a
    rollout makes them. Such an entry is retired by the echo of our
    newest write once nothing waits to be carried, or where the two
    sides are observed equal."""

    __slots__ = ("state", "start", "t", "t_down", "rv", "name", "ctx",
                 "counted")

    def __init__(self, start: float, t: float, rv: str, name: str, ctx):
        self.state = _STAGED
        self.start = start  # earliest stamp known: write entry, else commit, else staged
        self.t = t          # the newest boundary passed (staged, at first)
        self.t_down = None  # last downstream event since the patch applied
        self.rv = rv        # of the dirtying write; in _DONE, of our status write
        self.name = name
        self.ctx = ctx
        self.counted = False  # kcp_sync_convergence_seconds fed once

    @classmethod
    def later_trip(cls, now: float, name: str) -> "_Convergence":
        """The ``_DONE`` entry of a key whose own entry has been retired,
        opened by a downstream status event that arrived at ``now``."""
        ent = cls(now, now, "", name, None)
        ent.state, ent.counted, ent.t_down = _DONE, True, now
        return ent

CLUSTER_LABEL = "kcp.dev/cluster"
OWNED_BY_LABEL = "kcp.dev/owned-by"

DEFAULT_RESYNC_PERIOD = 600.0  # the collision/missed-event safety net

# metadata fields that must not cross the cluster boundary
# (reference: specsyncer.go:97-108 strips UID + ResourceVersion and drops
# owner references pointing at the kcp-side owner)
_STRIP_META = frozenset({
    "uid", "resourceVersion", "creationTimestamp", "generation",
    "managedFields", "clusterName", "ownerReferences", "deletionTimestamp"})


def transform_for_downstream(obj: dict) -> dict:
    """A private copy of ``obj`` as its physical cluster gets it: no
    status, no side-local metadata. What is dropped is dropped before
    the copy, so it is never copied."""
    out = {k: v for k, v in obj.items() if k != "status"}
    meta = out.get("metadata")
    if meta:
        out["metadata"] = {k: v for k, v in meta.items()
                           if k not in _STRIP_META}
    return tree_copy(out)


def _sync_view(obj: dict) -> dict:
    """The canonical comparable view of an object on either side.

    Both mirrors encode this view, so side-local fields (uid, RV, owner
    refs) can never make the lanes dirty.
    """
    view = transform_for_downstream(obj)
    if "status" in obj:
        view["status"] = tree_copy(obj["status"])
    return view


def _sync_view_ro(obj: dict) -> dict:
    """:func:`_sync_view` without the copy, for read-only
    consumers (the encoders hash it, `_spec_differs` compares it). The
    nested values stay shared with the informer caches — which the CoW
    store shares with storage — so callers must not mutate the result;
    write paths keep using the copying :func:`_sync_view` /
    :func:`transform_for_downstream`."""
    out = {k: v for k, v in obj.items() if k != "status"}
    meta = out.get("metadata") or {}
    out["metadata"] = {k: v for k, v in meta.items() if k not in _STRIP_META}
    if "status" in obj:
        out["status"] = obj["status"]
    return out


def _sharing(client, verb: str):
    """``client``'s ``verb`` for a caller that only READS what comes
    back. An in-process client offers ``<verb>_snapshot``: the same
    call, returning the stored snapshot itself instead of a private copy
    of it (never mutated here). A REST client has no snapshot to share
    and gives its plain verb, whose result is private anyway."""
    return getattr(client, verb + "_snapshot", None) or getattr(client, verb)


class BatchSyncEngine:
    """One batched sync program for one GVR between two clusters.

    ``backend="tpu"`` registers a row section in the process-wide
    :class:`~kcp_tpu.syncer.core.FusedCore`: every engine's rows live in a
    shared schema bucket and each reconcile tick runs ONE fused
    ``reconcile_step_packed`` over the whole fleet. ``backend="host"``
    computes identical decisions in pure Python per engine — the
    differential-testing reference
    (SURVEY.md §7.1).

    Applies are pipelined: the tick never waits on a store write. Patches
    go to an applier pool that verifies against the live caches, applies
    with optimistic concurrency, and retries with per-key backoff
    (5 retries then drop, RetryableError forever — reference parity with
    pkg/syncer/syncer.go:272-291).
    """

    def __init__(
        self,
        upstream: Client,
        downstream: Client,
        gvr: GVR | str,
        cluster_id: str,
        backend: str = "tpu",
        namespace_gvr: GVR | str = "namespaces",
        batch_window: float = 0.002,
        resync_period: float | None = DEFAULT_RESYNC_PERIOD,
        core=None,
        mesh=None,
        apply_workers: int = 4,
        max_apply_retries: int = 5,
    ):
        self.upstream = upstream
        self.downstream = downstream
        # the applier reads a current object for its resourceVersion (or
        # to compare) and a write's result for its resourceVersion, if
        # at all: none of that needs a private copy
        self._up_get = _sharing(upstream, "get")
        self._up_update_status = _sharing(upstream, "update_status")
        self._down_get = _sharing(downstream, "get")
        self._down_create = _sharing(downstream, "create")
        self._down_update = _sharing(downstream, "update")
        self.gvr = gvr
        self.cluster_id = cluster_id
        self.backend = backend
        self.fused = backend == "tpu"
        self.core = core
        self.mesh = mesh  # sharding for the fused core (None = serving default)
        self.namespace_gvr = namespace_gvr
        self.selector: LabelSelector = parse_selector(f"{CLUSTER_LABEL}={cluster_id}")

        self.up_informer = Informer(
            upstream, gvr, selector=self.selector, resync_period=resync_period
        )
        self.down_informer = Informer(
            downstream, gvr, selector=self.selector, resync_period=resync_period
        )

        self.enc = BucketEncoder(capacity=64)
        # encode-once memo for the _sync_view_ro encode path: the CoW
        # store (and the informer caches fed from it) never mutates a
        # snapshot in place, so the uint32 row for a snapshot is a pure
        # function of the dict — keyed by id with a strong ref (presence
        # implies identity), cleared whenever self.enc is replaced
        # (slot assignments are append-only below that, so cached rows
        # stay valid as the vocabulary grows). Periodic resyncs and
        # level-triggered re-touches of unchanged keys hit this instead
        # of re-flattening the object.
        self._enc_memo: dict[int, tuple[dict, np.ndarray]] = {}
        self._enc_memo_max = 65536
        self.rows: dict[tuple[str, str], int] = {}  # (ns, name) -> row
        self.row_keys: list[tuple[str, str]] = []
        self.capacity = 0
        # host staging mirrors (host-backend state; fused mode stages into
        # the shared bucket instead)
        self.up_vals = self.up_exists = self.down_vals = self.down_exists = None

        self.controller = None
        self._section = None
        if not self.fused:
            self.controller = BatchController(
                f"sync-{cluster_id}-{gvr}", self._process_batch,
                batch_window=batch_window,
            )
        self.up_informer.add_handler(self._on_up_event)
        self.down_informer.add_handler(self._on_down_event)

        # pipelined applier pool
        self.apply_workers = apply_workers
        self.max_apply_retries = max_apply_retries
        self._apply_q: asyncio.Queue | None = None
        # key -> the (decision, upsync) its pending apply was handed, or
        # _REARM once a newer patch of ANOTHER decision was skipped behind
        # it: that key is re-enqueued when the apply ends
        self._apply_pending: dict = {}
        self._apply_failures: dict = {}  # key -> consecutive failure count
        self._apply_tasks: list[asyncio.Task] = []
        self._retry_tasks: set[asyncio.Task] = set()

        self.stats = {"ticks": 0, "decisions_applied": 0, "rows": 0, "full_uploads": 0}
        # the ONE per-key convergence entry: made by the up event that
        # dirtied the key, stamped at every phase boundary the row
        # passes, closed when its status is upsynced (or the two sides
        # are observed equal). Every write has one — the phase
        # histograms do not depend on the sampling coin; only the
        # conv.* spans (entry.ctx) do. Bounded FIFO: an entry whose
        # downstream never answers is evicted, not kept forever.
        self._dirty: dict[tuple[str, str], _Convergence] = {}
        self._dirty_max = 8192
        # keys whose location reports progress: a status came AFTER the
        # one that answered a write (True: during the key's newest write,
        # False: during the one before it; a write with none retires the
        # key). Nobody waits for the first status of such a key, and the
        # tick's trip is where statuses that meet go up as one
        self._reports: dict[tuple[str, str], bool] = {}

    def tick_count(self) -> int:
        """Reconcile ticks that covered this engine's rows (fused mode
        reports the shared bucket's tick counter)."""
        if self.fused and self._section is not None:
            return self._section.bucket.stats["ticks"]
        return self.stats["ticks"]

    # ------------------------------------------------------------ events

    @staticmethod
    def _obj_key(obj: dict) -> tuple[str, str]:
        m = obj["metadata"]
        return (m.get("namespace", ""), m["name"])

    def _on_up_event(self, etype: str, old: dict | None, new: dict | None) -> None:
        key = self._obj_key(new or old)
        self._apply_failures.pop(key, None)  # new data resets the budget
        if old is not new:  # a resync replay is not a write
            self._stage_up(key, new or old, new is not None)
        if self.fused:
            if self._section is not None:
                self.core.enqueue(self._section, False, key)
        else:
            self.controller.enqueue(("up", key))

    def _stage_up(self, key, obj: dict, live: bool) -> None:
        """Open the key's convergence entry; `write` and `propagate` are
        complete here (their stamps rode the event). An entry still
        ahead of its downstream write keeps its timeline — the
        level-triggered tick converges the newest state either way; our
        own status write's echo retires a finished entry; a new write
        replaces an entry whose downstream has not answered."""
        now = time.monotonic()
        rv = _rv_of(obj)
        ent = self._dirty.get(key)
        if ent is not None:
            if ent.state == _DONE:
                # the echo of our own status write(s): the level-triggered
                # tick can re-emit the upsync before the first echo lands,
                # so every rv up to the newest one we wrote is ours
                if live and ent.rv and _rv_le(rv, ent.rv):
                    if rv == ent.rv:
                        if ent.t_down is None:
                            del self._dirty[key]
                        else:  # a later status waits to be carried up
                            ent.rv = ""
                    return
                del self._dirty[key]
            elif ent.state < _PATCHED:
                return
        stamps = self.up_informer.event_stamps
        tw, tm = stamps if stamps is not None else (None, None)
        ctx = obs.conv_begin(obj) if live and obs.TRACER.enabled else None
        name = key[1]
        if tm is not None:
            if tw is not None:
                obs.phase("write", ctx, tw, tm, rv=rv, obj=name)
            obs.phase("propagate", ctx, tm, now, rv=rv)
        if self._reports.pop(key, False):  # a new write: the mark ages,
            self._reports[key] = False     # then goes
        self._admit(key, _Convergence(tw or tm or now, now, rv, name, ctx))

    def _admit(self, key, ent: _Convergence) -> None:
        while len(self._dirty) >= self._dirty_max:
            del self._dirty[next(iter(self._dirty))]
        self._dirty[key] = ent

    def _on_down_event(self, etype: str, old: dict | None, new: dict | None) -> None:
        key = self._obj_key(new or old)
        self._apply_failures.pop(key, None)
        # downstream churn (our own write's echo, then the controller's
        # status write) re-stages the row: the LAST arrival before the
        # upsync is where `downstream` ends and `upstatus` begins
        ent = self._dirty.get(key)
        wrote = new is not None and old is not new  # no replay, no delete
        answers = False  # the status a write we carried down awaits
        if new is None:
            self._reports.pop(key, None)
        if ent is None:
            if wrote:
                # no write of the tenant's is under way: a status the
                # location wrote after the first one went up (and its
                # echo retired the entry) opens a status trip of its own
                self._admit(key, _Convergence.later_trip(time.monotonic(),
                                                         key[1]))
                self._reports[key] = True
        elif ent.state >= _PATCHED:
            ent.t_down = time.monotonic()
            if ent.state < _DONE:
                ent.state = _DOWNSTAGED
                answers = wrote
            elif wrote:  # _DONE: a later status trip opens
                self._reports[key] = True
        if self.fused:
            if self._section is not None:
                if answers and key not in self._reports:
                    # a write of the tenant's waits for this status. The
                    # device's rule for an upsync (both sides exist, the
                    # statuses differ) on the two objects in hand: the
                    # applier makes the same check before it writes, so
                    # the write need not wait for a tick to ask for it
                    up = self.up_informer.get(self._up_cluster(), key[1],
                                              key[0])
                    if up is not None and new.get("status") != up.get("status"):
                        self._hand_over(key, DECISION_NOOP, True, direct=True)
                # the device mirror follows the downstream side, and the
                # tick that carries the row is the backstop: it re-emits
                # the upsync if the apply above failed or was deferred
                self.core.enqueue(self._section, True, key)
        else:
            self.controller.enqueue(("down", key))

    def _ticked(self, ent: _Convergence, tick_start: float, collected: float,
                tick_n) -> None:
        """`stage` and `tick` of a staged row whose first patch just came
        home: staged -> start of the tick that carried it -> that tick's
        patches handed to the applier (for the fused core, the pipeline's
        wait for the wire included)."""
        t0 = max(ent.t, min(tick_start, collected))
        obs.phase("stage", ent.ctx, ent.t, t0, rv=ent.rv, obj=ent.name)
        obs.phase("tick", ent.ctx, t0, collected, rv=ent.rv, tick=tick_n)
        ent.t = max(t0, collected)
        ent.state = _TICKED

    def _converged(self, ent: _Convergence, end: float) -> None:
        if not ent.counted:
            ent.counted = True
            _CONVERGENCE.observe(max(0.0, end - ent.start))

    def _observed_equal(self, key, gone: bool) -> None:
        """Both sides of a touched key are equal (or both absent): the
        key's churn has landed without (further) action. A staged entry
        ends here; a deleted one ends where its downstream delete was
        confirmed; an applied create/update is counted converged once
        but keeps its entry for the status its downstream may yet write."""
        ent = self._dirty.get(key)
        if ent is None:
            return
        if ent.state == _STAGED:
            now = time.monotonic()
            obs.phase("stage", ent.ctx, ent.t, now, rv=ent.rv, obj=ent.name)
            self._converged(ent, now)
            del self._dirty[key]
        elif _PATCHED <= ent.state <= _DOWNSTAGED:
            end = ent.t_down or time.monotonic()
            self._converged(ent, end)
            if gone:
                obs.phase("downstream", ent.ctx, ent.t, end, rv=ent.rv)
                del self._dirty[key]
        elif ent.state == _DONE:
            ent.t_down = None  # nothing left to carry up
            if not ent.rv:
                del self._dirty[key]

    # ----------------------------------------------- fused-core interface

    def fused_status_mask(self) -> np.ndarray:
        return self.enc.status_mask()

    def fused_ledger_key(self) -> tuple[str, str]:
        """(cluster, resource) key for the fleet batch's device-side
        per-segment counters: the quota ledger's interning key
        (admission/quota.py ``device_slots``), so this engine's live
        synced rows are counted on-device every tick. The core reads it
        when the set of sections changes, not per tick: it is fixed for
        the engine's lifetime."""
        return (self._up_cluster(), str(self.gvr))

    def _encode_view(self, obj: dict) -> np.ndarray:
        """Encode-once ``enc.encode(_sync_view_ro(obj))``: memoized per
        snapshot identity. The returned row is shared — callers copy it
        into staging buffers, never mutate it."""
        ent = self._enc_memo.get(id(obj))
        if ent is not None and ent[0] is obj:
            return ent[1]
        vec = self.enc.encode(_sync_view_ro(obj))
        if len(self._enc_memo) >= self._enc_memo_max:
            # blunt but bounded: informer caches churn snapshots, so a
            # periodic full reset beats per-entry tracking on this path
            self._enc_memo.clear()
        self._enc_memo[id(obj)] = (obj, vec)
        return vec

    def fused_encode(self, key: tuple[str, str]):
        """Re-encode one touched key from the informer caches for the
        shared bucket's scatter. Raises BucketOverflow if the vocabulary
        outgrew the bucket (the core then calls :meth:`fused_overflow`)."""
        ns, name = key
        up_obj = self.up_informer.get(self._up_cluster(), name, ns)
        down_obj = self.down_informer.get(self._down_cluster(), name, ns)
        s = self.enc.capacity
        up_v = (self._encode_view(up_obj) if up_obj is not None
                else np.zeros(s, np.uint32))
        down_v = (self._encode_view(down_obj) if down_obj is not None
                  else np.zeros(s, np.uint32))
        # converged-by-observation: both sides present and identical means
        # this key's churn has landed — close its convergence sample here
        # (actioned keys close theirs in the applier)
        if (up_obj is None) == (down_obj is None) and bool((up_v == down_v).all()):
            self._observed_equal(key, up_obj is None)
        return up_v, up_obj is not None, down_v, down_obj is not None

    def fused_apply(self, patches: list[tuple[tuple[str, str], int, bool]]) -> None:
        """Patch rows from a collected tick: feed the applier pool
        (dedup per key; the pool re-verifies against live caches)."""
        if self._dirty and patches:
            # which fused dispatch carried each staged row: the start
            # stamp of the tick whose wire the core is collecting, and
            # now (its patches are handed over here)
            t1 = time.monotonic()
            t0 = getattr(self.core, "collecting_tick_start", None) or t1
            tick_n = (self._section.bucket.stats.get("ticks")
                      if self._section is not None else None)
            dirty = self._dirty
            for key, _code, _upsync in patches:
                ent = dirty.get(key)
                if ent is not None and ent.state == _STAGED:
                    self._ticked(ent, t0, t1, tick_n)
        for key, code, upsync in patches:
            self._hand_over(key, code, upsync)

    def _hand_over(self, key, code: int, upsync: bool,
                   direct: bool = False) -> None:
        """One decision for the applier pool, from a collected tick or
        (``direct``) from the downstream event that carried a status."""
        pending = self._apply_pending
        handed = pending.get(key)
        if handed is not None:
            # an apply reads the live caches, so a pending one of the
            # same decision does this one's work too; one of another
            # decision (a status upsync pending when the spec patch
            # arrives, or the reverse) does not, and ticks are
            # event-driven: a fleet gone quiet never re-emits it
            _PATCHES_DEFERRED.inc()
            if handed != (code, upsync):
                pending[key] = _REARM
            return
        if self._apply_failures.get(key, 0) > self.max_apply_retries:
            return  # dropped until a new event resets the budget
        pending[key] = (code, upsync)
        self._apply_q.put_nowait((key, code, upsync, direct))

    def fused_retired(self, keys) -> None:
        """Both sides of these keys are gone and their rows given back
        (``Section.retire``): what is kept per key goes with them, so
        that a tenant that keeps creating names costs the memory of its
        live objects. ``_observed_equal(gone=True)`` has closed each
        key's timeline; an apply still pending owns its own entry of
        ``_apply_pending`` and removes it when it ends."""
        for key in keys:
            self._dirty.pop(key, None)
            self._reports.pop(key, None)
            self._apply_failures.pop(key, None)

    def fused_overflow(self) -> None:
        """Vocabulary outgrew the bucket: grow the encoder (vocab is a
        prefix, so existing slot assignments stay valid), move to the
        larger bucket, and replay every cached key."""
        self.enc = self.enc.grown()
        self._enc_memo.clear()  # rows are sized to the replaced encoder
        log.info("sync-%s-%s: bucket overflow, re-registering at %d slots",
                 self.cluster_id, self.gvr, self.enc.capacity)
        old = self._section
        self._section = self.core.register(self, self.enc.capacity)
        if old is not None:
            old.release()
        self.core.enqueue_many(self._section, False, self._all_keys())

    def _all_keys(self) -> set:
        keys = {(k[1], k[2]) for k in self.up_informer.cache}
        keys |= {(k[1], k[2]) for k in self.down_informer.cache}
        return keys

    # ----------------------------------------------------- applier pool

    async def _apply_worker(self) -> None:
        while True:
            key, code, upsync, direct = await self._apply_q.get()
            try:
                applied = await self._apply_async(key, code, upsync)
            except Exception as err:  # noqa: BLE001 — reconcile errors are data
                self._apply_failed(key, code, upsync, err)
            else:
                self._apply_failures.pop(key, None)
                if applied:
                    self.stats["decisions_applied"] += 1
                    if direct:  # a NOOP decision: what applied is the status
                        _STATUS_DIRECT.inc()
            finally:
                # pending holds until the apply FINISHES: a slow apply
                # must suppress the level-triggered re-patches every tick
                # emits for its still-divergent row, or duplicates of one
                # slow key eat the whole worker pool. A skipped patch of
                # ANOTHER decision is not covered by this apply: the key
                # is re-enqueued, so a tick re-decides its row without
                # waiting for some other key's event
                if (self._apply_pending.pop(key, None) is _REARM
                        and self._section is not None):
                    self.core.enqueue(self._section, False, key)
                self._apply_q.task_done()

    async def _apply_async(self, key, code: int, upsync: bool) -> bool:
        """Apply one verified decision. Override (or monkeypatch) to make
        applies genuinely asynchronous (e.g. thread-pooled REST calls) —
        the tick loop never waits on this. ``syncer.apply`` is a
        KCP_FAULTS injection point (error -> the worker's normal
        failure/backoff path; latency -> an awaited delay, so a slow
        apply exercises the pending-dedup discipline, never the tick)."""
        from .. import faults

        delay = faults.maybe_fail("syncer.apply")
        if delay:
            await asyncio.sleep(delay)
        with obs.annotate("kcp.apply"):
            return self._apply_decision(key, code, upsync)

    def _apply_failed(self, key, code: int, upsync: bool, err: Exception) -> None:
        n = self._apply_failures.get(key, 0) + 1
        self._apply_failures[key] = n  # backoff escalates for every failure
        retryable = errors.is_retryable(err)
        if not retryable and n > self.max_apply_retries:
            log.warning("sync-%s-%s: dropping %r after %d apply retries: %s",
                        self.cluster_id, self.gvr, key, n - 1, err)
            return
        delay = min(0.005 * (2 ** min(n, 10)), 5.0)
        hint = errors.retry_after_hint(err)
        if hint is not None:
            # 429 from an overloaded frontend: honor the server's pacing
            # hint (jittered so the applier pool doesn't re-arrive in
            # lockstep, capped so a bogus hint can't stall the row)
            import random

            delay = max(delay, min(hint, 30.0) * (1.0 + 0.25 * random.random()))
        log.info("sync-%s-%s: apply %r failed (attempt %d): %s",
                 self.cluster_id, self.gvr, key, n, err)
        t = asyncio.get_event_loop().create_task(
            self._retry_apply(key, code, upsync, delay))
        self._retry_tasks.add(t)
        t.add_done_callback(self._retry_tasks.discard)

    async def _retry_apply(self, key, code: int, upsync: bool, delay: float) -> None:
        await asyncio.sleep(delay)
        if key not in self._apply_pending:
            self._apply_pending[key] = (code, upsync)
            self._apply_q.put_nowait((key, code, upsync, False))

    # ------------------------------------------------------------- rows

    def _ensure_capacity(self, needed: int) -> None:
        if self.capacity >= needed and self.up_vals is not None:
            return
        new_cap = pad_pow2(max(needed, 8))
        s = self.enc.capacity

        def grow(a, shape, dtype):
            out = np.zeros(shape, dtype=dtype)
            if a is not None:
                src = np.asarray(a)
                out[: src.shape[0], ...] = src
            return out

        self.up_vals = grow(self.up_vals, (new_cap, s), np.uint32)
        self.down_vals = grow(self.down_vals, (new_cap, s), np.uint32)
        self.up_exists = grow(self.up_exists, (new_cap,), bool)
        self.down_exists = grow(self.down_exists, (new_cap,), bool)
        self.capacity = new_cap

    def _row_for(self, key: tuple[str, str]) -> int:
        row = self.rows.get(key)
        if row is None:
            row = len(self.row_keys)
            self.rows[key] = row
            self.row_keys.append(key)
            self._ensure_capacity(row + 1)
        return row

    def _rebuild_after_overflow(self) -> None:
        """Encoder outgrew its slots: grow until everything fits, then
        re-encode both caches (the host escape hatch for odd objects)."""
        while True:
            self.enc = self.enc.grown()
            self._enc_memo.clear()  # rows are sized to the replaced encoder
            log.info("%s: bucket overflow, re-encoding at %d slots",
                     self.controller.name, self.enc.capacity)
            cap = self.capacity
            s = self.enc.capacity
            self.up_vals = np.zeros((cap, s), np.uint32)
            self.down_vals = np.zeros((cap, s), np.uint32)
            self.up_exists = np.zeros(cap, bool)
            self.down_exists = np.zeros(cap, bool)
            try:
                for (_cl, ns, name), obj in self.up_informer.cache.items():
                    r = self._row_for((ns, name))
                    self.enc.encode(_sync_view_ro(obj), out=self.up_vals[r])
                    self.up_exists[r] = True
                for (_cl, ns, name), obj in self.down_informer.cache.items():
                    r = self._row_for((ns, name))
                    self.enc.encode(_sync_view_ro(obj), out=self.down_vals[r])
                    self.down_exists[r] = True
                break
            except BucketOverflow:
                continue

    # -------------------------------------------------------------- tick

    async def _process_batch(self, items: Sequence) -> list[tuple[object, Exception]]:
        t0 = time.monotonic()
        try:
            return self._host_tick(items, t0)
        finally:
            _HOST_TICK.observe(time.monotonic() - t0)

    def _host_tick(self, items: Sequence, t_tick0: float) -> list[tuple[object, Exception]]:
        self.stats["ticks"] += 1
        _TICKS.inc()
        _EVENTS.inc(len(items))
        # 1. dedup keys touched this tick (last event wins — we re-read
        #    caches), remembering which queue items map to each key so
        #    failures are charged to the right items' retry budgets
        key_items: dict[tuple[str, str], list] = {}
        for item in items:
            key_items.setdefault(item[1], []).append(item)

        # 2. re-encode touched keys from the informer caches
        try:
            deltas = self._apply_touched(key_items.keys())
        except BucketOverflow:
            self._rebuild_after_overflow()
            deltas = None

        # 3. full-mirror diff (pure-host reference backend; the tpu
        #    backend runs through the FusedCore, not this path)
        del deltas
        n = len(self.row_keys)
        if n == 0:
            return []
        decision, upsync = self._host_decisions()
        t_decided = time.monotonic()

        # 4. apply non-NOOP rows with host verification
        failed_keys: dict[tuple[str, str], Exception] = {}
        act_rows = np.nonzero((decision != 0) | upsync)[0]
        for r in act_rows:
            if r >= n:
                continue
            key = self.row_keys[r]
            ent = self._dirty.get(key)
            if ent is not None and ent.state == _STAGED:
                # the host backend's tick: batch start -> decisions made
                self._ticked(ent, t_tick0, t_decided, self.stats["ticks"])
            try:
                applied = self._apply_decision(key, int(decision[r]), bool(upsync[r]))
                if applied:
                    self.stats["decisions_applied"] += 1
            except Exception as err:  # noqa: BLE001 — reconcile errors are data
                failed_keys[key] = err

        # touched keys that needed no action converged by observation
        act_set = {self.row_keys[r] for r in act_rows if r < n}
        for key in key_items:
            if key not in act_set:
                self._observed_equal(key, not self.up_exists[self.rows[key]])
        self.stats["rows"] = n

        # failures on rows whose items are in this batch charge those
        # items; failed rows woken by *earlier* batches already have a
        # backing-off item in the queue and will be retried by it
        failed: list[tuple[object, Exception]] = []
        for key, err in failed_keys.items():
            for item in key_items.get(key, ()):
                failed.append((item, err))
        return failed

    def _apply_touched(self, keys):
        """Refresh host mirrors for the touched keys; return the delta batch
        (idx, up_rows, up_ex, down_rows, down_ex) for the device scatter."""
        idxs, up_rows, up_ex, down_rows, down_ex = [], [], [], [], []
        for key in keys:
            r = self._row_for(key)
            ns, name = key
            up_obj = self.up_informer.get(self._up_cluster(), name, ns)
            down_obj = self.down_informer.get(self._down_cluster(), name, ns)
            idxs.append(r)
            up_rows.append(
                self._encode_view(up_obj) if up_obj is not None
                else np.zeros(self.enc.capacity, np.uint32)
            )
            up_ex.append(up_obj is not None)
            down_rows.append(
                self._encode_view(down_obj) if down_obj is not None
                else np.zeros(self.enc.capacity, np.uint32)
            )
            down_ex.append(down_obj is not None)
        if not idxs:
            return None
        for i, r in enumerate(idxs):
            self.up_vals[r] = up_rows[i]
            self.up_exists[r] = up_ex[i]
            self.down_vals[r] = down_rows[i]
            self.down_exists[r] = down_ex[i]
        return (
            np.array(idxs, np.int32),
            np.stack(up_rows),
            np.array(up_ex, bool),
            np.stack(down_rows),
            np.array(down_ex, bool),
        )

    # ---------------------------------------------------------- backends

    def _host_decisions(self) -> tuple[np.ndarray, np.ndarray]:
        """Pure-python decision oracle (Backend=host)."""
        n = self.capacity
        decision = np.zeros(n, np.uint8)
        upsync = np.zeros(n, bool)
        status_mask = self.enc.status_mask()
        for r in range(len(self.row_keys)):
            ue, de = self.up_exists[r], self.down_exists[r]
            neq = self.up_vals[r] != self.down_vals[r]
            spec_dirty = bool((neq & ~status_mask).any())
            status_dirty = bool((neq & status_mask).any())
            if ue and not de:
                decision[r] = DECISION_CREATE
            elif de and not ue:
                decision[r] = DECISION_DELETE
            elif ue and de and spec_dirty:
                decision[r] = DECISION_UPDATE
            upsync[r] = ue and de and status_dirty
        return decision, upsync

    def _up_cluster(self) -> str:
        return self.up_informer.client.cluster

    def _down_cluster(self) -> str:
        return self.down_informer.client.cluster

    # ------------------------------------------------------------- apply

    def _apply_decision(self, key: tuple[str, str], decision: int, upsync: bool) -> bool:
        ns, name = key
        up_obj = self.up_informer.get(self._up_cluster(), name, ns)
        down_obj = self.down_informer.get(self._down_cluster(), name, ns)
        applied = False
        ent = self._dirty.get(key)

        if decision == DECISION_CREATE and up_obj is not None:
            self._ensure_namespace(ns)
            desired = transform_for_downstream(up_obj)
            try:
                self._down_create(self.gvr, desired, namespace=ns)
                applied = True
            except errors.AlreadyExistsError:
                # informer lag: fall through to update semantics
                current = self._down_get(self.gvr, name, ns)
                if self._spec_differs(desired, current):
                    merged = self._merged_downstream(desired, current)
                    self._down_update(self.gvr, merged, namespace=ns)
                    applied = True
        elif decision == DECISION_UPDATE and up_obj is not None and down_obj is not None:
            desired = transform_for_downstream(up_obj)
            # host verification: never trust a hash alone before writing
            if self._spec_differs(desired, down_obj):
                current = self._down_get(self.gvr, name, ns)
                merged = self._merged_downstream(desired, current)
                self._down_update(self.gvr, merged, namespace=ns)
                applied = True
        elif decision == DECISION_DELETE and down_obj is not None and up_obj is None:
            # the up_obj re-check re-derives the action at apply time: a
            # pipelined DELETE must not fire if the object reappeared
            # upstream while the patch was in flight
            try:
                self.downstream.delete(self.gvr, name, ns)
                applied = True
            except errors.NotFoundError:
                pass

        if ent is not None and ent.state == _TICKED:
            # the downstream write (or delete) of this row just applied:
            # patches handed over -> now is `patch` (applier queue + write)
            now = time.monotonic()
            obs.phase("patch", ent.ctx, ent.t, now, rv=ent.rv,
                      applied=applied)
            ent.t = now
            ent.state = _PATCHED

        if upsync and up_obj is not None and down_obj is not None:
            new_status = down_obj.get("status")
            if new_status != up_obj.get("status"):
                # the upstream object as it stands (its resourceVersion
                # guards the write) under the downstream status: both
                # only read here, and a status write takes nothing of
                # its argument but a copy of the status
                fresh = {**self._up_get(self.gvr, name, ns),
                         "status": new_status}
                if ent is not None and ent.ctx is not None:
                    # upstream status write runs under the row's trace
                    # context: an in-process upstream records its
                    # store.commit as a child; a REST upstream carries
                    # the traceparent to the owning shard
                    with obs.use(ent.ctx):
                        written = self._up_update_status(
                            self.gvr, fresh, namespace=ns)
                else:
                    written = self._up_update_status(
                        self.gvr, fresh, namespace=ns)
                applied = True
                _STATUS_UPSYNCS.inc()
                # the status is committed upstream: a timeline ends at
                # that commit's own stamp where the upstream can tell it
                # (what `observe` of the status event starts from), else
                # at the write's return
                end = (getattr(self.upstream, "last_commit", None)
                       or time.monotonic())
                if ent is not None and _PATCHED <= ent.state <= _DOWNSTAGED:
                    t_down = ent.t_down or ent.t
                    obs.phase("downstream", ent.ctx, ent.t, t_down, rv=ent.rv)
                    obs.phase("upstatus", ent.ctx, t_down, end, rv=ent.rv,
                              obj=ent.name)
                    self._converged(ent, end)
                    # kept until the write's own echo comes up the informer
                    ent.state = _DONE
                    ent.t_down = None
                    ent.rv = _rv_of(written)
                elif ent is not None and ent.state == _DONE:
                    if ent.t_down is None:
                        _STATUS_REPEATS.inc()  # a repeat before the echo
                    else:
                        # a later status of the same write: its own trip
                        obs.phase("restatus", None, ent.t_down, end)
                        ent.t_down = None
                    ent.rv = _rv_of(written)
        return applied

    def _ensure_namespace(self, ns: str) -> None:
        if not ns:
            return
        try:
            self._down_get(self.namespace_gvr, ns)
        except errors.NotFoundError:
            try:
                self.downstream.create(
                    self.namespace_gvr,
                    {"apiVersion": "v1", "kind": "Namespace", "metadata": {"name": ns}},
                )
            except errors.AlreadyExistsError:
                pass

    @staticmethod
    def _spec_differs(desired: dict, current: dict) -> bool:
        # pure comparison: the copy-free views suffice (and with informer
        # caches sharing CoW store snapshots, skipping the deepcopy here
        # keeps host verification off the per-patch allocation budget)
        return _sync_view_ro(desired) != {
            k: v for k, v in _sync_view_ro(current).items() if k != "status"
        }

    @staticmethod
    def _merged_downstream(desired: dict, current: dict) -> dict:
        """``desired`` at ``current``'s resourceVersion. ``desired`` is
        the private copy :func:`transform_for_downstream` just made, so
        it is stamped in place."""
        desired.setdefault("metadata", {})["resourceVersion"] = current["metadata"][
            "resourceVersion"
        ]
        return desired

    # ---------------------------------------------------------- lifecycle

    async def start(self) -> None:
        self._apply_q = asyncio.Queue()
        for _ in range(self.apply_workers):
            self._apply_tasks.append(asyncio.create_task(self._apply_worker()))
        if self.fused:
            if self.core is None:
                from .core import FusedCore

                self.core = FusedCore.for_current_loop(mesh=self.mesh)
            self._section = self.core.register(self, self.enc.capacity)
            await self.core.start()
        # informers after the section exists: their initial list replays
        # the cache through the handlers, which enqueue into the core
        await self.up_informer.start()
        # what the cache holds now is what the initial list replayed
        # through _stage_up: one add a start, nothing a row
        _INITIAL_ROWS.inc(len(self.up_informer.cache))
        await self.down_informer.start()
        if self.controller is not None:
            await self.controller.start()

    async def stop(self) -> None:
        if self.controller is not None:
            await self.controller.stop()
        if self.fused and self.core is not None:
            await self.core.stop()
            if self._section is not None:
                self._section.release()
                self._section = None
        # the core's shutdown drain may have enqueued final patches —
        # let the workers finish them before cancelling
        if self._apply_q is not None:
            try:
                await asyncio.wait_for(self._apply_q.join(), timeout=5.0)
            except asyncio.TimeoutError:
                log.warning("sync-%s-%s: applier queue not drained at stop",
                            self.cluster_id, self.gvr)
        for t in [*self._apply_tasks, *self._retry_tasks]:
            t.cancel()
        for t in [*self._apply_tasks, *self._retry_tasks]:
            try:
                await t
            except asyncio.CancelledError:
                pass
        self._apply_tasks.clear()
        self._retry_tasks.clear()
        await self.up_informer.stop()
        await self.down_informer.stop()
