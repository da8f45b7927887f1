"""Controls: a run in which one guarantee of the configuration is broken
underneath the timed path, to show that ``correct`` comes out false.

Never part of a benchmark run: ``run.py --control <name>`` is an argument
the driver's command cannot carry. Each control patches the program in
this process before the server starts and is armed when the traffic
starts, so that set-up runs on the sound program and the fault sits under
the timed path alone.

- ``corrupt-downstream``: the syncer writes, for one object in eight, a
  copy that differs from the acknowledged object (the shape says which
  and how).
  Every write is still acknowledged and still converges by the status's
  lights; only the comparison of values can tell.
- ``drop-downstream``: the syncer skips, for one object in eight, every
  create and update of the copy: an acknowledged write never reaches its
  physical cluster. Such a write never converges either, so it is also
  counted ``failed``.
"""

from __future__ import annotations

import zlib

NAMES = ("corrupt-downstream", "drop-downstream")
_armed = False


def arm() -> None:
    global _armed
    _armed = True


def _salt(name: str) -> int:
    return zlib.crc32(name.encode())


def install(name: str, shape) -> None:
    from kcp_tpu.syncer import engine

    if name == "corrupt-downstream":
        sound = engine.transform_for_downstream

        def corrupted(obj: dict) -> dict:
            out = sound(obj)
            if not _armed:
                return out
            bad = shape.corrupt(out)
            return out if bad is None else bad

        engine.transform_for_downstream = corrupted
    elif name == "drop-downstream":
        sound_apply = engine.BatchSyncEngine._apply_decision

        def dropping(self, key, decision, upsync):
            if (_armed and _salt(key[1]) % 8 == 0 and decision in (
                    engine.DECISION_CREATE, engine.DECISION_UPDATE)):
                decision = 0
            return sound_apply(self, key, decision, upsync)

        engine.BatchSyncEngine._apply_decision = dropping
    else:
        raise SystemExit(f"unknown control {name!r}; one of {NAMES}")
