"""Distributed tracing: W3C-traceparent contexts + per-process span buffers.

The reference punts on cross-process attribution — its forked apiserver
serves ``/metrics`` and ``/debug/pprof`` that nothing first-party touches
(SURVEY.md §5) — and upstream later closed the gap with API-server request
tracing (KEP-647, W3C ``traceparent`` propagation). This module is that
layer for the kcp-tpu fleet, Dapper-style:

- a :class:`TraceContext` (trace id, span id, sampled flag) minted by the
  first hop (RestClient or the serving handler) and propagated as a
  ``traceparent`` request header across router → shard → replica hops;
- head-based sampling (``KCP_TRACE_SAMPLE``, default 1-in-64) decided by
  a seeded coin BEFORE any ids are minted — the unsampled fast path
  costs one RNG draw, and a fixed ``KCP_TRACE_SEED`` reproduces the
  exact decision sequence; fault-injected runs (an active ``KCP_FAULTS``
  schedule) are always sampled, and the serving layer force-records
  requests that breach the SLO (``KCP_TRACE_SLO_MS``) even when the head
  decision said no;
- finished spans land in a bounded per-process ring buffer
  (``KCP_TRACE_BUFFER`` entries) served by ``GET /debug/trace?id=`` /
  ``?slowest=N`` — the router scatter-gathers shard buffers to assemble
  cross-process trees (:mod:`.assemble`);
- reconcile causality: a sampled spec write's context rides its WAL
  record (``rec["tc"]``) and its shared watch :class:`Event` (one stamp
  for every watcher, the PR 5/PR 11 shared-Event discipline), plus an
  object-identity link (:func:`link_obj`) so an in-process informer's
  snapshot resolves back to the committing trace with one dict probe;
- the convergence decomposition: :func:`phase` records one contiguous
  segment of the spec→status timeline as a
  ``convergence_<phase>_seconds`` observation for EVERY write and as a
  ``conv.<phase>`` span for a sampled one — phases share boundary
  stamps (all ``time.monotonic()``), so their sum telescopes to the
  end-to-end time by construction
  (``tests/test_tracing.py::test_convergence_phases_sum_reconcile_in_process``);
- the two sockets: a write that came over HTTP starts its timeline
  one phase earlier, ``ingress`` (the start of the loop pass that read
  the request's first byte → the handler's entry), and for ONE OBJECT
  IN EIGHT, chosen by its name, a bounded in-process **edge log**
  (:func:`edges`) keeps where each write request and each delivered
  watch frame of that object met the transport, on the clock every
  process of the machine shares — what a load generator on the same
  host joins its own send / ack / seen stamps against;
- the same boundaries as host SECTIONS: :func:`annotate` names the
  synchronous ``kcp.*`` sections of the tick, the store, the applier,
  the HTTP path and the watch relay. On a serving loop's thread each
  section adds its self time to that loop's ledger
  (:class:`~kcp_tpu.obs.runtime.LoopLedger`, always on); while a
  profiler session is open it is also a
  ``jax.profiler.TraceAnnotation`` on the profiler's clock.

Wire neutrality is a hard contract: tracing adds a request header on
client hops and nothing else — response bytes, watch streams, and stored
objects are byte-identical with tracing on or off (``KCP_TRACE=0``
disables even the header), proven by the differential fuzz in
tests/test_tracing.py. Off-path cost when disabled is one attribute read
per hop; when enabled-but-unsampled, one contextvar read plus a
deterministic modulo per minted trace.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
import random
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass
from threading import get_ident as _get_ident
from time import perf_counter as _perf_counter
from typing import Any, Iterator

from ..analysis.sanitize import make_lock
from ..utils.trace import REGISTRY

#: the W3C propagation header (lower-cased: the httpd lower-cases keys)
TRACEPARENT = "traceparent"

#: the convergence phases, in timeline order; adjacent phases share
#: their boundary stamp. ``ingress`` (a write that came over HTTP only:
#: the start of the loop pass that read the request's first byte →
#: entry of the serving handler — the pass's earlier callbacks, the
#: ``recv``, the reader's wake-up and the parse), ``write`` (entry of
#: the serving handler — or of
#: the store, for an in-process writer — → the commit stamp on the
#: write's event), ``propagate`` (commit → the syncer engine staged the
#: key: commit window, watch fan-out, informer), ``stage`` (staged →
#: start of the tick that carried the row), ``tick`` (tick start → that
#: tick's patches handed to the applier, the pipeline's wait included),
#: ``patch`` (patches handed over → downstream write applied),
#: ``downstream`` (downstream write applied → the downstream status
#: event re-staged the row: the physical cluster's controller),
#: ``upstatus`` (re-staged → status committed upstream), ``observe``
#: (commit of an event → its frame handed to the transport of an HTTP
#: watch stream; every delivered event, spec echo and status alike),
#: ``restatus`` (outside the telescoping sum: each LATER status trip of
#: a write whose first status is already up, as a rolling controller
#: makes them — the downstream status event re-staged the row → that
#: status committed upstream; no span, a histogram only).
PHASES = ("ingress", "write", "propagate", "stage", "tick", "patch",
          "downstream", "upstatus", "observe", "restatus")

#: the phase histograms, fetched once: an observation is a dict probe,
#: a bisect and the histogram's own leaf lock — never the registry's
_PHASE_H = {
    p: REGISTRY.histogram(
        f"convergence_{p}_seconds",
        "one phase of the spec-to-status convergence timeline")
    for p in PHASES}

# phase stamps are time.monotonic(); a span's t0 is wall-clock (spans
# from several processes are merged by t0). One offset per process.
_MONO_TO_WALL = time.time() - time.monotonic()
#: a section's stamps are time.perf_counter(); added to one, this gives
#: the same instant on time.monotonic()'s clock (0.0 where the two are
#: one clock, as on Linux): how a site that closes a section hands the
#: section's own end stamp on as a phase or edge stamp
PERF_TO_MONO = time.monotonic() - _perf_counter()

_current: contextvars.ContextVar["TraceContext | None"] = \
    contextvars.ContextVar("kcp_trace_ctx", default=None)

# lazily-bound faults module: the sampling coin checks for an active
# injector on every draw, and a per-call `from .. import` statement is
# measurable on the request fast path
_faults = None


@dataclass(frozen=True)
class TraceContext:
    """One position in a trace: (trace id, span id, sampled)."""

    trace_id: str  # 32 hex chars
    span_id: str  # 16 hex chars
    sampled: bool

    def header(self) -> str:
        """The W3C ``traceparent`` header value."""
        return f"00-{self.trace_id}-{self.span_id}-" \
               f"{'01' if self.sampled else '00'}"


class _Noop:
    """Reusable no-op context manager: the unsampled-path cost of
    :func:`span` is one contextvar read and this singleton. ``begin`` /
    ``end`` are the no-op of a host section (:func:`annotate`)."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc: object) -> bool:
        return False

    def begin(self, now: float) -> None:
        return None

    def end(self, now: float) -> None:
        return None


_NOOP = _Noop()
#: the section of a site that must not be named (a handler whose store
#: verbs leave the loop's thread): same ``with`` / ``begin`` / ``end``
NOOP = _NOOP


class Tracer:
    """Per-process trace state: sampling policy + the span ring buffer."""

    def __init__(self) -> None:
        self._lock = make_lock("obs.tracer")
        self.reconfigure()

    def reconfigure(self) -> None:
        """(Re-)read the KCP_TRACE* environment — called at import and by
        tests/benches that flip modes mid-process."""
        self.enabled = os.environ.get("KCP_TRACE", "1").lower() not in (
            "0", "false", "off")
        self.sample_n = max(1, int(os.environ.get("KCP_TRACE_SAMPLE", "64")))
        self.slo_s = float(os.environ.get("KCP_TRACE_SLO_MS", "200")) / 1000.0
        seed = os.environ.get("KCP_TRACE_SEED", "")
        self._rng = random.Random(int(seed)) if seed else random.Random()
        self.proc = os.environ.get("KCP_TRACE_PROC", f"pid{os.getpid()}")
        self._buf: deque[dict] = deque(
            maxlen=max(64, int(os.environ.get("KCP_TRACE_BUFFER", "4096"))))
        # object-identity links: id(snapshot) -> (snapshot, ctx, seq).
        # Entries hold a strong snapshot ref (presence implies identity,
        # the encode-cache discipline); bounded FIFO — the deque carries
        # (id, seq) and eviction only removes a map entry whose seq still
        # matches, so a re-linked id is never evicted by its stale slot.
        self._links: deque[tuple[int, int]] = deque()
        self._link_seq = 0
        self._link_map: dict[int, tuple[dict, TraceContext, int]] = {}
        self._recorded = REGISTRY.counter(
            "trace_spans_recorded_total",
            "spans recorded into the per-process trace ring buffer")

    # --------------------------------------------------------- contexts

    def head_sampled(self) -> bool:
        """The head sampling coin — drawn from the seeded RNG BEFORE any
        ids exist, so the unsampled fast path never pays for id minting
        (one RNG draw ≈ 0.3µs vs ~5µs of hex formatting). A fixed
        ``KCP_TRACE_SEED`` reproduces the decision sequence exactly;
        fault-injected runs (an active ``KCP_FAULTS`` schedule) always
        sample — a chaos run's whole point is explaining what the
        injected failure did."""
        if self.sample_n <= 1:
            return True
        global _faults
        if _faults is None:
            from .. import faults as _faults_mod

            _faults = _faults_mod
        if _faults._ACTIVE is not None:
            return True
        # getrandbits is a single C call (GIL-atomic): no lock needed
        return self._rng.getrandbits(30) % self.sample_n == 0

    def mint(self, sampled: bool | None = None) -> TraceContext | None:
        """A fresh root context (None when tracing is disabled)."""
        if not self.enabled:
            return None
        if sampled is None:
            sampled = self.head_sampled()
        rng = self._rng
        return TraceContext(f"{rng.getrandbits(128):032x}",
                            f"{rng.getrandbits(64):016x}", sampled)

    def child(self, ctx: TraceContext) -> TraceContext:
        """Same trace, fresh span id (the caller becomes the parent)."""
        return TraceContext(ctx.trace_id,
                            f"{self._rng.getrandbits(64):016x}",
                            ctx.sampled)

    def from_headers(self, headers: dict) -> TraceContext | None:
        """Parse an incoming ``traceparent`` header (None = absent or
        malformed or tracing disabled)."""
        if not self.enabled:
            return None
        tp = headers.get(TRACEPARENT)
        if not tp:
            return None
        parts = tp.split("-")
        if len(parts) != 4 or len(parts[1]) != 32 or len(parts[2]) != 16:
            return None
        try:
            sampled = bool(int(parts[3], 16) & 1)
            int(parts[1], 16), int(parts[2], 16)
        except ValueError:
            return None
        return TraceContext(parts[1], parts[2], sampled)

    # --------------------------------------------------------- recording

    def record(self, name: str, ctx: TraceContext, parent: str | None,
               t0: float, dur: float, attrs: dict | None = None,
               force: bool = False) -> None:
        """Append one finished span (no-op unless sampled or forced)."""
        if not self.enabled or not (ctx.sampled or force):
            return
        span = {
            "trace": ctx.trace_id, "span": ctx.span_id, "parent": parent,
            "name": name, "proc": self.proc,
            "t0": round(t0, 6), "dur": round(max(0.0, dur), 6),
        }
        if attrs:
            span["attrs"] = attrs
        with self._lock:
            self._buf.append(span)
        self._recorded.inc()

    # ----------------------------------------------------- object links

    def link_obj(self, obj: dict, ctx: TraceContext,
                 limit: int = 512) -> None:
        """Associate a stored snapshot with the trace that committed it
        (in-process informers resolve causality with one dict probe)."""
        with self._lock:
            oid = id(obj)
            self._link_seq += 1
            self._link_map[oid] = (obj, ctx, self._link_seq)
            self._links.append((oid, self._link_seq))
            while len(self._links) > limit:
                old, seq = self._links.popleft()
                ent = self._link_map.get(old)
                if ent is not None and ent[2] == seq:
                    del self._link_map[old]

    def obj_link(self, obj: dict | None) -> TraceContext | None:
        """The committing trace context of a snapshot, if linked."""
        if obj is None or not self._link_map:
            return None
        ent = self._link_map.get(id(obj))
        if ent is not None and ent[0] is obj:
            return ent[1]
        return None

    # ------------------------------------------------------------ query

    def spans(self) -> list[dict]:
        with self._lock:
            return list(self._buf)

    def get(self, trace_id: str) -> list[dict]:
        """Every buffered span of one trace, oldest first."""
        with self._lock:
            return [s for s in self._buf if s["trace"] == trace_id]

    def slowest(self, n: int = 3) -> list[dict]:
        """The ``n`` slowest buffered traces: grouped by trace id, ranked
        by wall extent (max span end - min span start)."""
        by_trace: dict[str, list[dict]] = {}
        with self._lock:
            for s in self._buf:
                by_trace.setdefault(s["trace"], []).append(s)
        ranked = []
        for tid, spans in by_trace.items():
            t0 = min(s["t0"] for s in spans)
            t1 = max(s["t0"] + s["dur"] for s in spans)
            ranked.append({"id": tid, "dur": round(t1 - t0, 6),
                           "spans": spans})
        ranked.sort(key=lambda t: -t["dur"])
        return ranked[:max(1, n)]


TRACER = Tracer()


# ---------------------------------------------------------------------------
# module-level helpers — the call-site API (and what the kcp-lint span-
# table checker reads: literal names in obs.span/obs.phase/obs.record_span
# calls must appear in docs/operations.md's trace-span table)
# ---------------------------------------------------------------------------


def current() -> TraceContext | None:
    return _current.get()


def set_current(ctx: TraceContext | None) -> contextvars.Token:
    return _current.set(ctx)


def reset_current(token: contextvars.Token) -> None:
    _current.reset(token)


@contextlib.contextmanager
def use(ctx: TraceContext | None) -> Iterator[TraceContext | None]:
    """Install ``ctx`` as the current trace context for a block."""
    token = _current.set(ctx)
    try:
        yield ctx
    finally:
        _current.reset(token)


class _Span:
    __slots__ = ("name", "ctx", "attrs", "t0", "_token", "sub")

    def __init__(self, name: str, ctx: TraceContext, attrs: dict):
        self.name = name
        self.ctx = ctx
        self.attrs = attrs

    def __enter__(self) -> TraceContext:
        self.sub = TRACER.child(self.ctx)
        self._token = _current.set(self.sub)
        self.t0 = time.time()
        return self.sub

    def __exit__(self, etype, exc, tb) -> bool:
        _current.reset(self._token)
        if etype is not None:
            self.attrs["error"] = repr(exc)[:160]
        TRACER.record(self.name, self.sub, self.ctx.span_id, self.t0,
                      time.time() - self.t0, self.attrs or None)
        return False


def span(name: str, **attrs: Any):
    """Time a block as a child span of the current context; near-free
    (:data:`_NOOP`) when untraced or unsampled."""
    ctx = _current.get()
    if ctx is None or not ctx.sampled:
        return _NOOP
    return _Span(name, ctx, attrs)


def record_span(name: str, ctx: TraceContext, parent: str | None,
                t0: float, dur: float, attrs: dict | None = None,
                force: bool = False) -> None:
    """Record an explicitly-timed span (the non-context-manager twin of
    :func:`span`, for sites that measure their own boundaries)."""
    TRACER.record(name, ctx, parent, t0, dur, attrs, force=force)


def phase(name: str, ctx: TraceContext | None, t0: float, t1: float,
          **attrs: Any) -> None:
    """One convergence phase between two ``time.monotonic()`` stamps: a
    ``convergence_<phase>_seconds`` observation always (every write,
    whatever the sampling coin said), plus a ``conv.<name>`` span when
    ``ctx`` is sampled. Adjacent phases share boundary stamps, so the
    per-phase sum telescopes to the end-to-end time."""
    dur = max(0.0, t1 - t0)
    h = _PHASE_H.get(name)
    if h is None:  # a driver's own root ("e2e"): rare, off the hot path
        h = _PHASE_H[name] = REGISTRY.histogram(
            f"convergence_{name}_seconds",
            "one phase of the spec-to-status convergence timeline")
    h.observe(dur)
    if ctx is not None and ctx.sampled and TRACER.enabled:
        sub = TRACER.child(ctx)
        TRACER.record("conv." + name, sub, ctx.span_id,
                      t0 + _MONO_TO_WALL, dur, attrs or None)


# ---------------------------------------------------------------------------
# the edge log: what happened at the two sockets, for one object in eight
# ---------------------------------------------------------------------------

#: a key is kept when ``hash(name) & EDGE_MASK == 0``: chosen by the
#: object's NAME, so the request that wrote an object and the frames that
#: carried its events are kept or dropped together within a process (a
#: coin per request cannot give that). ``str`` hashes differ between
#: processes: whoever joins the log against stamps from outside asks
#: :func:`edge_kept` in THIS process.
EDGE_MASK = 7
#: ``("req", cluster, name, rx, t0, t_out)`` once a write request of a
#: kept key, where its response has been handed to the transport (``rx``
#: the start of the loop pass that read its first byte, 0.0 = not known;
#: ``t0`` the handler's entry); ``("frame", cluster, name, tm, t_handed)``
#: once a delivered watch event of a kept key (``tm`` its commit stamp).
#: Every stamp is time.monotonic(), the one CLOCK_MONOTONIC every
#: process of the machine reads. Bounded, always on; appends are GIL-
#: atomic, :func:`edges` copies.
_EDGES: deque[tuple] = deque(maxlen=32768)
edge_append = _EDGES.append


def edge_kept(name: str) -> bool:
    """Whether the edge log of THIS process keeps the object ``name``."""
    return hash(name) & EDGE_MASK == 0


def edges() -> list[tuple]:
    """A copy of the edge log, oldest record first."""
    return list(_EDGES)


_trace_annotation = None  # jax.profiler.TraceAnnotation, once jax is imported

# a stretch of a section's self time is remembered for its pass (and shown
# if the pass turns out long, ``GET /debug/loop``) from this many seconds
# on: most sections are shorter and cost the log nothing; a long one that
# young collections cut into pieces (each ``kcp.gc`` is a boundary) still
# shows, because its pieces are longer
PASS_LOG_S = 0.0001

# thread ident -> the ledger of the serving loop that runs on that thread
# (kcp_tpu/obs/runtime.py LoopLedger registers and removes itself)
_LEDGERS: dict[int, Any] = {}


class Section:
    """One named section of one loop's ledger: the slot its self seconds
    are added to, and the context manager every :func:`annotate` of
    that name on that loop's thread returns (one object per name;
    nothing is allocated per use). SELF time is the section's duration
    less what the sections opened inside it covered: the ledger keeps
    the section that is running now (``cur``) and the stamp of the last
    boundary (``mark``), and every begin and end adds the time since
    that stamp to the section that was running — one clock read, one
    float add. ``begin`` / ``end`` take a ``time.perf_counter()`` stamp
    the caller has already read; the ``with`` form reads the clock
    itself. Single writer (the loop's thread): no lock."""

    __slots__ = ("name", "seconds", "published", "counter", "_led")

    def __init__(self, name: str, ledger: Any):
        self.name = name
        self.seconds = 0.0
        # what the ledger's beat has moved into ``counter`` so far
        self.published = 0.0
        self.counter = None
        self._led = ledger

    def __enter__(self, now: float | None = None) -> None:
        led = self._led
        if now is None:
            now = _perf_counter()
        cur = led.cur
        if cur is not None:
            ran = now - led.mark
            cur.seconds += ran
            if ran >= PASS_LOG_S:  # what a long pass can be made of
                led.log.extend((cur, ran))
        led.stack.append(cur)
        led.cur = self
        led.mark = now

    def __exit__(self, et: object, ev: object, tb: object,
                 now: float | None = None) -> bool:
        led = self._led
        if led.cur is not self:
            return False  # left open across an await: the ledger swept it
        if now is None:
            now = _perf_counter()
        ran = now - led.mark
        self.seconds += ran
        if ran >= PASS_LOG_S:
            led.log.extend((self, ran))
        led.cur = led.stack.pop()
        led.mark = now
        return False

    begin = __enter__

    def end(self, now: float) -> None:
        self.__exit__(None, None, None, now)


class _Traced:
    """A section while a profiler session is open: the
    ``TraceAnnotation`` around the ledger's section (or around
    :data:`_NOOP` on a thread that has no ledger)."""

    __slots__ = ("_ann", "_sec")

    def __init__(self, ann: Any, sec: Any):
        self._ann = ann
        self._sec = sec

    def begin(self, now: float) -> None:
        self._ann.__enter__()
        self._sec.begin(now)

    def end(self, now: float) -> None:
        self._sec.end(now)
        self._ann.__exit__(None, None, None)

    def __enter__(self) -> None:
        self.begin(_perf_counter())

    def __exit__(self, et: object, ev: object, tb: object) -> bool:
        self.end(_perf_counter())
        return False


def profiler_annotation():
    """``jax.profiler.TraceAnnotation`` once jax is imported, else None
    (ask its ``is_enabled()`` whether a session is open). A process
    that never imported jax (router, load generator) never imports it
    here, and a jax that is half imported (``sys.modules`` has it
    before ``jax.profiler`` exists: a gc callback can fire there) reads
    as no profiler."""
    global _trace_annotation
    ta = _trace_annotation
    if ta is None:
        jax = sys.modules.get("jax")
        if jax is not None:
            ta = _trace_annotation = getattr(
                getattr(jax, "profiler", None), "TraceAnnotation", None)
    return ta


def annotate(name: str, **stats: Any):
    """One named SYNCHRONOUS host section (a thread-scoped begin/end:
    no ``await`` inside), two things at one pair of clock reads:

    - on a thread whose serving loop keeps a ledger
      (:class:`~kcp_tpu.obs.runtime.LoopLedger`), the section's self
      seconds are added to ``server_loop_self_seconds_<name>`` — always
      on, no lock, nothing allocated but a stack frame;
    - while a profiler session is open, a
      ``jax.profiler.TraceAnnotation`` on the profiler's clock;
      ``stats`` ride that event (``kcp.gc`` carries ``generation``).

    With neither it is the no-op singleton: "tracing off" costs one
    thread check and one flag read per section — on a ledger's thread
    the flag is the ledger's, which asks the profiler once a beat, so a
    session is seen at most 50 ms late (these sections run
    thousands of times a second on a loop whose queueing multiplies
    every microsecond). A site that has read the clock anyway hands its
    stamps over (``begin(now)`` / ``end(now)``) in place of ``with``."""
    led = _LEDGERS.get(_get_ident())
    if led is None:
        sec = _NOOP
    else:
        sec = led.sections.get(name) or led.section(name)
        if not led.profiling:  # the ledger asks the profiler once a beat
            return sec
    ta = _trace_annotation or profiler_annotation()
    if ta is None or not ta.is_enabled():
        return sec
    return _Traced(ta(name, **stats), sec)


def write_ctx() -> TraceContext | None:
    """The current context if it is worth stamping onto a commit
    (sampled), else None — the store's one-attribute fast path."""
    ctx = _current.get()
    return ctx if ctx is not None and ctx.sampled else None


def link_obj(obj: dict, ctx: TraceContext) -> None:
    TRACER.link_obj(obj, ctx)


def obj_link(obj: dict | None) -> TraceContext | None:
    if not TRACER.enabled:
        return None
    return TRACER.obj_link(obj)


def ctx_from_wal(tc: Any) -> TraceContext | None:
    """Rebuild a context from a WAL record's ``tc`` field
    (``[trace_id, span_id]``); None-safe and shape-tolerant."""
    if (not isinstance(tc, (list, tuple)) or len(tc) != 2
            or not all(isinstance(x, str) for x in tc)):
        return None
    return TraceContext(tc[0], tc[1], True)


def conv_begin(obj: dict | None) -> TraceContext | None:
    """The context a syncer engine should attribute a staged row to: the
    committing write's own context when the snapshot is identity-linked
    (in-process informers), else a fresh root ONLY under always-on
    sampling (cross-process engines correlate fragments by rv — see
    :mod:`.assemble` — and minting per event at default sampling would
    put an RNG call on the event hot path for nothing)."""
    t = TRACER
    if not t.enabled:
        return None
    ctx = t.obj_link(obj) if t._link_map else None
    if ctx is not None:
        return ctx
    if t.sample_n <= 1:
        return t.mint(sampled=True)
    return None
