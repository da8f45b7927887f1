"""The serving process's own runtime, measured from inside: how late the
event loop runs its callbacks, and how long the cyclic collector stops
every thread.

One interpreter runs httpd, store, watch fan-out, informers, appliers
and the tick loop, so "queueing on the one interpreter" is a first-order
term of every latency the server has. :class:`RuntimeProbes` is owned by
the :class:`~kcp_tpu.server.server.Server` (started and stopped with
it); nothing here runs at import.

- ``server_loop_lag_seconds``: a timer that re-arms itself every
  :data:`LAG_INTERVAL_S` on the serving loop and observes how late it
  fired — the time a ready callback waits behind whatever the loop is
  doing (one observation per beat, about 20 a second);
- ``py_gc_pause_seconds``: a ``gc.callbacks`` hook, start to stop of
  every collection; while a profiler session is open each one is also a
  ``kcp.gc`` annotation (generation as a stat), so an idle gap of the
  device under a full collection reads as that.
"""

from __future__ import annotations

import asyncio
import gc
import time

from .. import obs
from ..utils.trace import REGISTRY

LAG_INTERVAL_S = 0.05


class RuntimeProbes:
    # the collector is the process's, not a server's: ServerThread tests
    # run several servers in one process, and each pause is observed once
    _gc_users = 0
    _gc_t0 = 0.0
    _gc_ann = None

    def __init__(self, loop: asyncio.AbstractEventLoop):
        self._loop = loop
        self._handle: asyncio.TimerHandle | None = None
        self._due = 0.0
        self._lag = REGISTRY.histogram(
            "server_loop_lag_seconds",
            "how late a timer on the serving loop fired: the wait of a "
            "ready callback behind the loop's current work")

    def start(self) -> "RuntimeProbes":
        self._arm()
        cls = RuntimeProbes
        if cls._gc_users == 0:
            gc.callbacks.append(_on_gc)
        cls._gc_users += 1
        return self

    def stop(self) -> None:
        if self._handle is not None:
            self._handle.cancel()
            self._handle = None
            cls = RuntimeProbes
            cls._gc_users -= 1
            if cls._gc_users == 0 and _on_gc in gc.callbacks:
                gc.callbacks.remove(_on_gc)

    def _arm(self) -> None:
        self._due = time.monotonic() + LAG_INTERVAL_S
        self._handle = self._loop.call_later(LAG_INTERVAL_S, self._beat)

    def _beat(self) -> None:
        self._lag.observe(max(0.0, time.monotonic() - self._due))
        self._arm()


_GC_PAUSE = REGISTRY.histogram(
    "py_gc_pause_seconds",
    "one run of the interpreter's cyclic collector, start to stop "
    "(every thread of the process waits for it)")


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
