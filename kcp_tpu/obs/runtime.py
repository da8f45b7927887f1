"""The serving process's own runtime, measured from inside: what the
event loop's one thread did with its time, how late it runs its
callbacks, how long the cyclic collector stops every thread, and when
that collector runs at all.

One interpreter runs httpd, store, watch fan-out, informers, appliers
and the tick loop, so "queueing on the one interpreter" is a first-order
term of every latency the server has. :class:`RuntimeProbes` is owned by
the :class:`~kcp_tpu.server.server.Server` (started and stopped with
it); nothing here runs at import.

- the pass ledger (:class:`LoopLedger`): the loop's selector is wrapped
  once. A ``select`` that may wait is IDLE time (nothing was ready);
  everything else is BUSY, counted pass by pass (a call of ``select``
  to the next: one pass's callbacks; a ``select`` with ready work
  behind it comes straight back in 2-5 us and is counted with the pass
  it starts, so a pass costs the ledger ONE clock read).
  ``server_loop_busy_seconds_total`` + ``server_loop_idle_seconds_total``
  is wall time by construction. ``server_loop_cpu_seconds_total`` is
  the thread's CPU time between waits (``time.thread_time()`` is a
  system call: read on both sides of a ``select`` that may wait, not
  once a pass), so busy − cpu is time the loop was held and did not
  run (an ``fsync``, the wait for the device, the GIL in another
  thread's hands, descheduling). Inside a pass, every
  :func:`kcp_tpu.obs.annotate` section adds its SELF seconds to
  ``server_loop_self_seconds_<section>``; busy − their sum is work
  nobody has named. A pass of :data:`LONG_PASS_S` or more is counted
  (``server_loop_long_passes_total``, ``_long_pass_seconds_total``) and
  kept in a ring of the last :data:`RING` with its three largest
  sections (``GET /debug/loop``). The ledger's slots are plain floats
  with one writer; the beat below publishes their rise into the
  registry's counters, so a reader is at most a beat stale and no pass
  or section takes a lock. All of it is work on a loop that queues: at
  91 % busy half a percent of loop work was 8 % of the median
  (``PERF.md`` §6, PR 42), which is why a pass reads one clock;
- the passes by HANDLE (:class:`HandleTable`), only while a profiler
  slice is open: the ledger registers ``sys.monitoring`` local events
  on ``asyncio.events.Handle._run``'s code object and nothing else, so
  every callback the loop runs — a transport's ``_read_ready``, the
  self-pipe's ``_read_from_self``, a step of a task, a timer — is named
  by its kind and timed: wall seconds, and the seconds of it under no
  ``kcp.*`` section (``server_loop_handle_seconds_<kind>``,
  ``server_loop_handle_unnamed_seconds_<kind>``, ``GET /debug/loop``).
  This is what tells the loop's unnamed time apart: a sampler THREAD
  (``/debug/profile``) sees the loop's thread chiefly where it lets go
  of the GIL. With no slice open nothing is registered and the tool id
  is free;
- ``server_loop_lag_seconds``: a timer that re-arms itself every
  :data:`LAG_INTERVAL_S` on the serving loop and observes how late it
  fired — the time a ready callback waits behind whatever the loop is
  doing (one observation per beat, about 20 a second);
- ``py_gc_pause_seconds``: a ``gc.callbacks`` hook, start to stop of
  every collection; each one is also a ``kcp.gc`` section (generation
  as a stat), so an idle gap of the device, or a long pass, under a
  full collection reads as that. The same hook counts, one add a
  collection: ``py_gc_collections_total_gen0`` / ``_gen1`` / ``_gen2``
  (by the oldest generation walked; ``_gen2`` is a full collection),
  ``py_gc_collected_objects_total`` (the cyclic garbage found) and
  ``py_gc_uncollectable_total``;
- the collector's policy (:data:`GC_THRESHOLDS`): while a server of
  the process lives the young generation is larger than the largest
  burst one request allocates, so transient trees die by reference
  count before a young collection can promote them towards a full
  one. Process-wide — set by the first :meth:`RuntimeProbes.start`,
  the thresholds found there restored by the last ``stop`` — with no
  flag, and nothing is disabled;
- ``jax_backend_compile_seconds``: a ``jax.monitoring`` listener for
  the backend's compile events, with their seconds: every program this
  process asked the backend for, whoever asked — compiled by XLA, or
  read from the disk cache (the event spans both; a read is tens of
  milliseconds where a compile is seconds). Absent in a process that
  never imports jax.
"""

from __future__ import annotations

import asyncio
import functools
import gc
import re
import sys
import threading
import time
from collections import deque
from threading import get_ident
from time import perf_counter as _perf_counter

from .. import obs
from ..utils.trace import REGISTRY
from . import trace as _trace
from .trace import _LEDGERS, _NOOP, Section

LAG_INTERVAL_S = 0.05
# a pass this long is a stall worth a line of its own: the threshold
# benchmarks/run.py reports the collector's pauses at
LONG_PASS_S = 0.05
# the LAST so many long passes, not the longest since start: set-up's
# compiles would fill the latter before anyone looks
RING = 64
# the collector's policy while a server of the process lives (set by the
# first RuntimeProbes.start, the thresholds found there restored by the
# last stop). CPython starts a young collection every 700 NET container
# allocations, and a full one when the objects promoted out of
# generation 1 since the last exceed a quarter of those that survived
# it; a promotion is never taken back when the object dies by reference
# count a millisecond later. One page of a paged walk, one LIST, one
# request body, one tick's rows each allocate thousands of containers
# in one stretch, so at 700 the collector ran INSIDE them, found all of
# it alive and promoted it, and garbage that was never long-lived
# bought three to twelve walks of the whole heap (0.2-0.7 s each,
# every thread waiting) per 51 s. A young generation larger than the
# largest burst of one request is freed by reference count before it
# is ever looked at. Generations 1 and 2 keep CPython's 10 and 10.
# The sweep on the chip that chose the value (PERF.md 6, PR 55; share
# of a 51 s window inside the collector in the flood | the read cell |
# steady, and its pauses of 50 ms or more):
#    20,000: 1.38 | 1.51 | 0.56 %; every generation-1 pass 50-67 ms
#            (four, two, two a window), and the read cell keeps a full
#            collection of 0.40 s (a page of the `*` walk still outgrows
#            the young generation)
#   100,000: 1.13 | 0.34 | 0.30 %; none in the read cell and steady,
#            one generation-1 pass of 0.25 s a window in the flood
#   500,000: 2.06 | 0.30 | 0.82 %; a generation-1 pass walks up to
#            five million objects: 0.39-0.42 s, and one young pass 0.15 s
# (at CPython's 700 the same cells read 7.5 | 11.1 | 2.1-2.5 % with
# six | ten | three full collections of 0.3-0.6 s). A generation-1
# pass is ten young generations wide at any value, so none of the
# three has every pause under 50 ms in the flood; 100,000 has the
# lowest share there.
GC_THRESHOLDS = (100_000, 10, 10)

_TOTALS = {
    "busy_seconds": REGISTRY.counter(
        "server_loop_busy_seconds_total",
        "the serving loop's thread between a return of select and its "
        "next call: one pass's callbacks"),
    "idle_seconds": REGISTRY.counter(
        "server_loop_idle_seconds_total",
        "the serving loop's thread inside select: nothing was ready"),
    "cpu_seconds": REGISTRY.counter(
        "server_loop_cpu_seconds_total",
        "CPU time of the serving loop's thread over its passes (busy "
        "less this: a pass that held the loop and did not run — a "
        "blocking call, the GIL elsewhere, descheduling)"),
    "passes": REGISTRY.counter(
        "server_loop_passes_total",
        "passes of the serving loop (returns of select)"),
    "long_passes": REGISTRY.counter(
        "server_loop_long_passes_total",
        "passes of the serving loop of 50 ms or more"),
    "long_pass_seconds": REGISTRY.counter(
        "server_loop_long_pass_seconds_total",
        "wall time of the serving loop's passes of 50 ms or more"),
    "section_leaks": REGISTRY.counter(
        "server_loop_section_leaks_total",
        "obs.annotate sections found open when the serving loop went "
        "back to select (a section may not span an await): swept, not "
        "timed"),
}


class LoopLedger:
    """What one event loop's thread did with its time. Installed by
    :meth:`attach` as the ``select`` of the loop's selector (an
    instance attribute over the class's method, removed again by the
    last :meth:`detach`); every number is absent, never wrong, on a
    loop without ``_selector`` (CPython's private name).

    Single writer: :meth:`select`, and the sections of
    :func:`kcp_tpu.obs.annotate`, run on the loop's thread and add plain
    floats. :meth:`publish` (the probes' beat, same thread) moves the
    rise into the registry's counters, one ``inc`` per counter that
    moved."""

    def __init__(self, selector) -> None:
        self._selector = selector
        self._select = selector.select
        self.users = 0
        self.busy_seconds = self.idle_seconds = self.cpu_seconds = 0.0
        self.long_pass_seconds = 0.0
        self.passes = self.long_passes = self.section_leaks = 0
        self._published = dict.fromkeys(_TOTALS, 0.0)
        # the section running now, the stamp of its last boundary, and
        # the sections open around it (obs/trace.py Section)
        self.cur: Section | None = None
        self.mark = 0.0
        self.stack: list = []
        self.log: list = []  # section, seconds, ... of this pass
        # waiting is not work: select's own annotation has no slot
        self.sections: dict[str, Section] = {"kcp.loop.select": _NOOP}
        self.ring: deque = deque(maxlen=RING)
        self._tid = 0
        # a profiler session is open (asked once a beat, not a section)
        self.profiling = False
        # time by handle, while a slice is open (HandleTable); else None
        self.handles: HandleTable | None = None
        #: time.monotonic() at the start of the pass that is running (the
        #: last return of ``select``): what a request is stamped with as
        #: the instant its first byte could have been read
        self.pass_start = time.monotonic()
        self._c = 0.0

    # ---------------------------------------------------------- install

    @classmethod
    def attach(cls, loop: asyncio.AbstractEventLoop) -> "LoopLedger | None":
        selector = getattr(loop, "_selector", None)
        select = getattr(selector, "select", None)
        if select is None:
            return None
        led = getattr(select, "__self__", None)
        if not isinstance(led, cls):
            led = cls(selector)
            selector.select = led.select
            # its next pass tells the ledger which thread the loop's is
            loop.call_soon_threadsafe(led._on_loop)
        led.users += 1
        return led

    def _on_loop(self) -> None:
        if self.users:
            self._tid = get_ident()
            self._c = time.thread_time()
            _LEDGERS[self._tid] = self

    @staticmethod
    def of_this_thread() -> "LoopLedger | None":
        return _LEDGERS.get(get_ident())

    def detach(self) -> None:
        self.users -= 1
        self.publish()
        if self.users == 0:
            self._close_handles()
            del self._selector.select
            if _LEDGERS.get(self._tid) is self:
                del _LEDGERS[self._tid]

    def section(self, name: str) -> Section:
        sec = self.sections[name] = Section(name, self)
        return sec

    # ------------------------------------------------------------- pass

    def select(self, timeout=None):
        now = time.monotonic()
        busy = now - self.pass_start
        self.busy_seconds += busy
        self.passes += 1
        if busy >= LONG_PASS_S:
            self._long_pass(busy)
        if self.log:
            self.log.clear()
        if self.cur is not None:
            self.section_leaks += len(self.stack)
            self.stack.clear()
            self.cur = None
        if timeout == 0 and not self.profiling:
            # ready work is behind this select, so it comes straight
            # back (2-5 us): counted as the next pass's first
            # microseconds, at one clock read a pass
            self.pass_start = now
            return self._select(0)
        # a select that may wait: idle is measured, and the thread's
        # clock (a system call) read on both sides of it, so CPU time
        # is taken between waits and not once a pass
        cpu = time.thread_time()
        if self._tid:
            self.cpu_seconds += cpu - self._c
        if self.profiling:
            with obs.annotate("kcp.loop.select"):
                events = self._select(timeout)
        else:
            events = self._select(timeout)
        self._c = time.thread_time()
        t = self.pass_start = time.monotonic()
        self.idle_seconds += t - now
        return events

    def _long_pass(self, wall: float) -> None:
        self.long_passes += 1
        self.long_pass_seconds += wall
        by_name: dict[str, float] = {}
        log = self.log
        for sec, seconds in zip(log[::2], log[1::2]):
            by_name[sec.name] = by_name.get(sec.name, 0.0) + seconds
        top = sorted(by_name.items(), key=lambda kv: -kv[1])[:3]
        self.ring.append({
            "start": self.pass_start, "wall_s": wall,
            "sections": [[n, s] for n, s in top]})

    # ---------------------------------------------------------- publish

    def named_seconds(self) -> float:
        """Σ of the sections' self seconds as they stand."""
        return sum([s.seconds for s in self.sections.values()
                    if s is not _NOOP])

    def busy_now(self) -> float:
        """Busy seconds up to this instant, the running pass included
        (for a caller ON the loop, which is inside a pass)."""
        return self.busy_seconds + (time.monotonic() - self.pass_start)

    def _open_handles(self) -> None:
        if (self.handles is None and self._tid == get_ident()
                and _HandleHook.acquire()):
            self.handles = HandleTable(self)

    def _close_handles(self) -> None:
        table = self.handles
        if table is not None:
            table.publish()
            self.handles = None
            _HandleHook.release()

    def publish(self) -> None:
        ta = _trace._trace_annotation or _trace.profiler_annotation()
        profiling = ta is not None and ta.is_enabled()
        if profiling != self.profiling:
            # a profiler slice opened or closed since the last beat: the
            # handle table lives exactly as long
            self.profiling = profiling
            if profiling:
                self._open_handles()
            else:
                self._close_handles()
        if self.handles is not None:
            self.handles.publish()
        if self._tid == get_ident():
            # CPU time up to here: a loop that never waits (a closed
            # loop at saturation) would otherwise account none of it
            cpu = time.thread_time()
            self.cpu_seconds += cpu - self._c
            self._c = cpu
        done = self._published
        for slot, counter in _TOTALS.items():
            value = getattr(self, slot)
            if value != done[slot]:
                counter.inc(value - done[slot])
                done[slot] = value
        for sec in list(self.sections.values()):
            if sec is _NOOP or sec.seconds == sec.published:
                continue
            if sec.counter is None:
                sec.counter = REGISTRY.counter(
                    f"server_loop_self_seconds_{sec.name.replace('.', '_')}",
                    "self time of one obs.annotate section on the serving "
                    "loop: its duration less the sections inside it")
            sec.counter.inc(sec.seconds - sec.published)
            sec.published = sec.seconds

    def report(self) -> dict:
        """What ``GET /debug/loop`` serves: the totals as they stand
        and the ring, stamps on ``time.monotonic()``'s clock."""
        out = {slot: getattr(self, slot) for slot in _TOTALS}
        out.update(now=time.monotonic(), long_pass_threshold_s=LONG_PASS_S,
                   self_seconds={s.name: s.seconds
                                 for s in self.sections.values()
                                 if s is not _NOOP},
                   long_passes_recent=list(self.ring))
        if self.handles is not None:
            out["handles"] = self.handles.report()
        return out


# ---------------------------------------------------------------------------
# the loop's passes by handle (while a profiler slice is open)
# ---------------------------------------------------------------------------

#: every callback an asyncio loop runs goes through this one function
_RUN_CODE = asyncio.events.Handle._run.__code__
#: the table names at most this many kinds of handle; the rest are
#: ``other`` (the counters are a family of the registry: bounded)
MAX_KINDS = 32
_TOOL_NAME = "kcp-loop-handles"
_HANDLE_BUSY = REGISTRY.counter(
    "server_loop_handle_busy_seconds_total",
    "busy seconds of the serving loop while its handle table was open "
    "(a profiler slice): what the table's wall seconds are a share of")


class _Kind:
    """One kind of handle: its seconds, and what the beat has published."""

    __slots__ = ("wall", "unnamed", "runs", "_done", "_counters")

    def __init__(self) -> None:
        self.wall = self.unnamed = 0.0
        self.runs = 0
        self._done = [0.0, 0.0]
        self._counters: tuple | None = None

    def publish(self, kind: str) -> None:
        if self._counters is None:
            name = re.sub(r"\W", "_", kind)
            self._counters = (
                REGISTRY.counter(
                    f"server_loop_handle_seconds_{name}",
                    "wall seconds of one kind of asyncio handle on the "
                    "serving loop, while a profiler slice is open"),
                REGISTRY.counter(
                    f"server_loop_handle_unnamed_seconds_{name}",
                    "the seconds of one kind of asyncio handle on the "
                    "serving loop under no obs.annotate section, while a "
                    "profiler slice is open"))
        for i, value in enumerate((self.wall, self.unnamed)):
            if value != self._done[i]:
                self._counters[i].inc(value - self._done[i])
                self._done[i] = value


class HandleTable:
    """What one loop's handles did with the loop's busy time, kind by
    kind, for as long as a profiler slice is open (the ledger's beat
    opens and closes it with ``LoopLedger.profiling``).

    A kind is a step of a task by its coroutine (``task:HttpServer.
    _serve``), a bound method by class and name (``_SelectorSocket
    Transport._read_ready``, ``BaseSelectorEventLoop._read_from_self``,
    a timer's ``RuntimeProbes._beat``), anything else by its
    ``__qualname__``. Per kind: wall seconds from ``Handle._run``'s
    start to its return, and the part of them under no ``kcp.*``
    section — wall less the rise of the sections' self seconds across
    the handle, summed only where the ledger's boundary stamp moved
    (most handles open no section). Single writer: the loop's thread."""

    def __init__(self, ledger: LoopLedger) -> None:
        self._led = ledger
        self.kinds: dict[str, _Kind] = {}
        self._names: dict = {}  # a callback's code or (type, name) -> kind
        self._cur: _Kind | None = None
        self._t0 = 0.0
        # Σ self seconds of the ledger's sections, at the boundary stamp
        # it was summed at (_named_rise)
        self._named = 0.0
        self._named_mark = -1.0
        self._busy0 = self._busy_done = ledger.busy_now()

    def _kind_of(self, cb) -> str:
        while isinstance(cb, functools.partial):
            cb = cb.func
        owner = getattr(cb, "__self__", None)
        get_coro = getattr(owner, "get_coro", None)
        if get_coro is not None:  # a task's step or wake-up
            coro = get_coro()
            key = getattr(coro, "cr_code", None) or type(coro)
            kind = self._names.get(key)
            if kind is None:
                kind = self._names[key] = "task:" + (
                    getattr(coro, "__qualname__", "") or type(coro).__name__)
            return kind
        code = getattr(getattr(cb, "__func__", cb), "__code__", None)
        name = getattr(cb, "__name__", None) or type(cb).__name__
        if owner is not None:
            key = (type(owner), code or name)
        else:
            key = code or (type(cb), name)
        kind = self._names.get(key)
        if kind is None:
            kind = self._names[key] = (
                f"{type(owner).__name__}.{name}" if owner is not None
                else getattr(cb, "__qualname__", name))
        return kind

    def _named_rise(self) -> float:
        """The rise of the sections' Σ self seconds since the last call,
        summed only where the ledger's boundary stamp has moved."""
        mark = self._led.mark
        if mark == self._named_mark:
            return 0.0
        total = self._led.named_seconds()
        rise, self._named, self._named_mark = total - self._named, total, mark
        return rise

    def start(self, handle) -> None:
        kind = self._kind_of(handle._callback)
        slot = self.kinds.get(kind)
        if slot is None:
            if len(self.kinds) >= MAX_KINDS:
                kind = "other"
            slot = self.kinds.get(kind)
            if slot is None:
                slot = self.kinds[kind] = _Kind()
        self._cur = slot
        self._named_rise()  # what ran between two handles is not this one's
        self._t0 = _perf_counter()

    def finish(self) -> None:
        now = _perf_counter()
        slot = self._cur
        if slot is None:  # the handle that opened the table
            return
        self._cur = None
        wall = now - self._t0
        slot.wall += wall
        slot.unnamed += max(0.0, wall - self._named_rise())
        slot.runs += 1

    def publish(self) -> None:
        for kind, slot in list(self.kinds.items()):
            slot.publish(kind)
        busy = self._led.busy_now()
        _HANDLE_BUSY.inc(busy - self._busy_done)
        self._busy_done = busy

    def report(self) -> dict:
        return {"busy_s": self._led.busy_now() - self._busy0,
                "kinds": {k: {"wall_s": s.wall, "unnamed_s": s.unnamed,
                              "runs": s.runs}
                          for k, s in self.kinds.items()}}


def _on_handle_start(code, offset) -> None:
    led = _LEDGERS.get(get_ident())
    if led is not None and led.handles is not None:
        led.handles.start(sys._getframe(1).f_locals["self"])


def _on_handle_return(code, offset, retval) -> None:
    led = _LEDGERS.get(get_ident())
    if led is not None and led.handles is not None:
        led.handles.finish()


class _HandleHook:
    """``sys.monitoring``'s registration for :class:`HandleTable`: LOCAL
    events (start and return) on ``Handle._run``'s code object alone,
    held while at least one ledger of the process has its table open.
    A tool id is the interpreter's, so the ledgers share one; with no
    table open no tool id is in use and the code object carries no
    event. An interpreter without ``sys.monitoring`` (before 3.12), or
    with every tool id taken, has no table."""

    tool: int | None = None
    users = 0
    _lock = threading.Lock()

    @classmethod
    def acquire(cls) -> bool:
        mon = getattr(sys, "monitoring", None)
        if mon is None:
            return False
        with cls._lock:
            if cls.users == 0:
                free = [t for t in (mon.PROFILER_ID, 3, 4, mon.OPTIMIZER_ID)
                        if mon.get_tool(t) is None]
                if not free:
                    return False
                tool = cls.tool = free[0]
                ev = mon.events
                mon.use_tool_id(tool, _TOOL_NAME)
                mon.register_callback(tool, ev.PY_START, _on_handle_start)
                mon.register_callback(tool, ev.PY_RETURN, _on_handle_return)
                mon.set_local_events(tool, _RUN_CODE,
                                     ev.PY_START | ev.PY_RETURN)
            cls.users += 1
        return True

    @classmethod
    def release(cls) -> None:
        mon = sys.monitoring
        with cls._lock:
            cls.users -= 1
            if cls.users == 0:
                tool, cls.tool = cls.tool, None
                ev = mon.events
                mon.set_local_events(tool, _RUN_CODE, 0)
                mon.register_callback(tool, ev.PY_START, None)
                mon.register_callback(tool, ev.PY_RETURN, None)
                mon.free_tool_id(tool)


def long_passes() -> list[dict]:
    """The rings of every ledger of this process, oldest first (a
    benchmark's run process holds its server: it reads them here)."""
    return sorted((p for led in list(_LEDGERS.values()) for p in led.ring),
                  key=lambda p: p["start"])


class RuntimeProbes:
    # the collector is the process's, not a server's: ServerThread tests
    # run several servers in one process, and each pause is observed once
    _gc_users = 0
    _gc_found: tuple[int, int, int] | None = None
    _gc_t0 = 0.0
    _gc_ann = None
    # jax.monitoring keeps a listener for the life of the process: one
    _compiles_heard = False

    def __init__(self, loop: asyncio.AbstractEventLoop):
        self._loop = loop
        self._handle: asyncio.TimerHandle | None = None
        self._due = 0.0
        self.ledger: LoopLedger | None = None
        self._lag = REGISTRY.histogram(
            "server_loop_lag_seconds",
            "how late a timer on the serving loop fired: the wait of a "
            "ready callback behind the loop's current work")

    def start(self) -> "RuntimeProbes":
        self.ledger = LoopLedger.attach(self._loop)
        self._arm()
        cls = RuntimeProbes
        if cls._gc_users == 0:
            gc.callbacks.append(_on_gc)
            cls._gc_found = gc.get_threshold()
            gc.set_threshold(*GC_THRESHOLDS)
        cls._gc_users += 1
        cls._hear_compiles()
        return self

    @classmethod
    def _hear_compiles(cls) -> None:
        """Register the compile listener, once, where jax is imported
        (the beat asks again: a server may import jax after it starts)."""
        monitoring = getattr(sys.modules.get("jax"), "monitoring", None)
        if monitoring is not None and not cls._compiles_heard:
            cls._compiles_heard = True
            monitoring.register_event_duration_secs_listener(_on_compile)

    def stop(self) -> None:
        if self._handle is not None:
            self._handle.cancel()
            self._handle = None
            if self.ledger is not None:
                self.ledger.detach()
                self.ledger = None
            cls = RuntimeProbes
            cls._gc_users -= 1
            if cls._gc_users == 0:
                if _on_gc in gc.callbacks:
                    gc.callbacks.remove(_on_gc)
                if cls._gc_found is not None:
                    gc.set_threshold(*cls._gc_found)
                    cls._gc_found = None

    def _arm(self) -> None:
        self._due = time.monotonic() + LAG_INTERVAL_S
        self._handle = self._loop.call_later(LAG_INTERVAL_S, self._beat)

    def _beat(self) -> None:
        self._lag.observe(max(0.0, time.monotonic() - self._due))
        if self.ledger is not None:
            self.ledger.publish()
        if not RuntimeProbes._compiles_heard:
            self._hear_compiles()
        self._arm()


_GC_PAUSE = REGISTRY.histogram(
    "py_gc_pause_seconds",
    "one run of the interpreter's cyclic collector, start to stop "
    "(every thread of the process waits for it)")
_GC_RUNS = [
    REGISTRY.counter(
        f"py_gc_collections_total_gen{g}",
        f"runs of the cyclic collector whose oldest generation was {g} "
        f"({what})")
    for g, what in enumerate((
        "the young generation: every GC_THRESHOLDS[0] net container "
        "allocations",
        "the young generation and the one it promotes into",
        "a full collection: the whole heap is walked"))]
_GC_COLLECTED = REGISTRY.counter(
    "py_gc_collected_objects_total",
    "objects the cyclic collector found unreachable and freed: the "
    "garbage reference counting could not free, which is how much the "
    "program needs a collector at all")
_GC_UNCOLLECTABLE = REGISTRY.counter(
    "py_gc_uncollectable_total",
    "objects the cyclic collector found unreachable and could not free")


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_COMPILE = REGISTRY.histogram(
    "jax_backend_compile_seconds",
    "one program asked of the backend in this process (jax.monitoring's "
    "backend_compile_duration): XLA's compile, or the disk cache's read")


def _on_compile(event: str, seconds: float, **_kw) -> None:
    if event == _COMPILE_EVENT:
        _COMPILE.observe(seconds)


def _on_gc(phase: str, info: dict) -> None:
    cls = RuntimeProbes
    if phase == "start":
        cls._gc_ann = ann = obs.annotate(
            "kcp.gc", generation=info.get("generation", -1))
        ann.__enter__()
        cls._gc_t0 = time.monotonic()
    elif cls._gc_ann is not None:
        _GC_PAUSE.observe(time.monotonic() - cls._gc_t0)
        cls._gc_ann.__exit__(None, None, None)
        cls._gc_ann = None
        _GC_RUNS[info["generation"]].inc()
        _GC_COLLECTED.inc(info.get("collected", 0))
        _GC_UNCOLLECTABLE.inc(info.get("uncollectable", 0))
