"""The load generator: a process of its own that never imports JAX.

It talks to the server only over HTTP (``RestClient`` for writes, one
wildcard ``RestWatch`` for convergence), stamps every operation with
CLOCK_MONOTONIC (shared with the harness's process), and hands its
records back as one JSON file. The harness starts it early, waits for
``ready`` on its stdout, and writes ``go <t_start>`` to its stdin; the
generator kind then offers ``warmup_s`` of unmeasured traffic, the
window, and ``cooldown_s`` more, so that the window's last second meets
the same system as its middle.

    python benchmarks/loadgen.py --spec <file> --out <file>
"""

from __future__ import annotations

import argparse
import asyncio
import importlib
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(HERE))

from benchmarks import shapes  # noqa: E402


class Session:
    """What every generator kind shares: clients, the watch, the records."""

    def __init__(self, spec: dict):
        from kcp_tpu.server.rest import RestClient

        self.spec = spec
        self.base = spec["server"]
        self.shape = shapes.load(spec["shape"])
        self.locations = spec["locations"]
        self.tenants = shapes.tenant_names(spec["tenants"])
        self.deadline_s = float(spec["traffic"].get("deadline_s", 10.0))
        self.records: list[dict] = []
        self.lock = threading.Lock()
        self.waiting: dict[tuple[str, str], dict] = {}
        self.on_settled = None  # generator's hook: an operation converged
        self.watch_restarts = 0
        self.watch_events = 0
        self._RestClient = RestClient
        self._loop = asyncio.new_event_loop()
        self._watch_up = threading.Event()
        self._stop = False
        self._thread = threading.Thread(target=self._watch_main, daemon=True,
                                        name="loadgen-watch")

    # ------------------------------------------------------------ clients

    def client(self):
        return self._RestClient(self.base)

    # -------------------------------------------------------------- watch

    def start_watch(self, timeout: float = 60.0) -> None:
        self._thread.start()
        if not self._watch_up.wait(timeout):
            raise RuntimeError("the wildcard watch did not come up")

    def _watch_main(self) -> None:
        asyncio.set_event_loop(self._loop)
        self._loop.run_until_complete(self._watch())

    async def _watch(self) -> None:
        wild = self._RestClient(self.base, cluster="*")
        since = None
        while not self._stop:
            w = wild.watch(self.shape.RESOURCE, since_rv=since)
            try:
                w._ensure_started()
                while not w.responded and not w.closed:
                    await asyncio.sleep(0.005)
                self._watch_up.set()
                while not self._stop:
                    for ev in await w.next_batch(max_wait=0.2):
                        self._on_event(ev)
                    if w.closed and not w.pending():
                        break
            except Exception as e:  # noqa: BLE001 — reconnect from last_rv
                print(f"loadgen: watch error {type(e).__name__}: {e}",
                      file=sys.stderr, flush=True)
            finally:
                since = w.last_rv or since
                w.close()
            if not self._stop:
                self.watch_restarts += 1
                await asyncio.sleep(0.05)

    def _on_event(self, ev) -> None:
        now = time.monotonic()
        self.watch_events += 1
        if ev.type == "DELETED":
            return
        key = (ev.cluster, ev.name)
        rec = self.waiting.get(key)
        if rec is None:
            return
        if self.shape.observe(ev.object) != rec["want"]:
            return
        with self.lock:
            if self.waiting.pop(key, None) is None:
                return
        rec["seen"] = now
        rec["evidence"] = self.shape.evidence(ev.object)
        done = rec.pop("_event", None)
        if done is not None:
            done.set()
        if self.on_settled is not None:
            self.on_settled(rec)

    # ------------------------------------------------------------- writes

    def write(self, client, kind: str, tenant: str, body: dict | None,
              name: str, due: float, *, wait: bool = False,
              aux: bool = False) -> dict:
        """One operation over REST, stamped. ``create``/``update`` register
        for convergence before they are sent, so the watch cannot win the
        race; a 409 is retried once."""
        from kcp_tpu.utils import errors

        rec = {"kind": kind, "key": [tenant, name], "due": due, "sent": None,
               "acked": None, "seen": None, "error": None, "aux": aux}
        if kind != "delete":
            rec["body"] = body
            rec["want"] = self.shape.want(body)
            if wait:
                rec["_event"] = threading.Event()
            with self.lock:
                self.waiting[(tenant, name)] = rec
        with self.lock:
            self.records.append(rec)
        client.cluster = tenant
        for attempt in (0, 1):
            rec["sent"] = time.monotonic() if rec["sent"] is None else rec["sent"]
            try:
                if kind == "create":
                    client.create(self.shape.RESOURCE, body)
                elif kind == "update":
                    client.update(self.shape.RESOURCE, body)
                else:
                    client.delete(self.shape.RESOURCE, name,
                                  self.shape.NAMESPACE)
                rec["acked"] = time.monotonic()
                break
            except errors.ConflictError as e:
                rec["error"] = f"409 {e}"
                if attempt:
                    break
            except Exception as e:  # noqa: BLE001 — recorded, counted failed
                rec["error"] = f"{type(e).__name__}: {e}"
                break
        if rec["acked"] is not None:
            rec["error"] = None
        elif kind != "delete":
            with self.lock:
                self.waiting.pop((tenant, name), None)
        return rec

    def wait_seen(self, rec: dict) -> bool:
        ev = rec.get("_event")
        if rec["seen"] is not None or ev is None:
            return rec["seen"] is not None
        left = rec["due"] + self.deadline_s - time.monotonic()
        ev.wait(max(left, 0.0))
        return rec["seen"] is not None

    # ---------------------------------------------------------------- end

    def finish(self) -> None:
        """Wait until every registered operation converged or is past its
        deadline, then stop the watch."""
        while True:
            with self.lock:
                pending = [r for r in self.waiting.values()]
            now = time.monotonic()
            live = [r for r in pending
                    if now < r["due"] + self.deadline_s]
            if not live:
                break
            time.sleep(0.05)
        self._stop = True
        self._thread.join(timeout=5)

    def dump(self, path: str, extra: dict) -> None:
        for r in self.records:
            r.pop("_event", None)
        out = dict(extra, records=self.records,
                   watch_restarts=self.watch_restarts,
                   watch_events=self.watch_events,
                   unconverged=len(self.waiting),
                   jax_imported="jax" in sys.modules)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(out, f)
        os.replace(tmp, path)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    with open(args.spec) as f:
        spec = json.load(f)
    session = Session(spec)
    kind = importlib.import_module(
        f"benchmarks.generators.{spec['traffic']['kind']}")
    plan = kind.prepare(session, spec)
    session.start_watch()
    print("ready", flush=True)
    line = sys.stdin.readline().split()
    if len(line) != 2 or line[0] != "go":
        print(f"loadgen: expected 'go <t>', got {line}", file=sys.stderr)
        return 2
    t_start = float(line[1])
    extra = kind.run(session, plan, spec, t_start)
    session.finish()
    session.dump(args.out, dict(extra or {}, t_start=t_start))
    return 0


if __name__ == "__main__":
    sys.exit(main())
