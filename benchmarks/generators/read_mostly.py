#!/usr/bin/env python3
"""Read mostly: ``open_loop``'s writes, value for value, and beside them
an open loop of READS — GET, namespace LIST (selector, Table),
list-then-watch, paged wildcard LIST — on a schedule of its own.

Traffic parameters: every one of ``open_loop``'s (the writes: the same
schedule function, seed salts and senders, so a seed offers this kind
the writes it offers ``open_loop``), and ``read_rate_per_s``,
``read_mix`` (shares of the five read verbs below), ``read_senders``
(blocking clients, over all reader processes), ``read_procs`` (reader
processes; ``walkers`` says how many of them take the paged walks and
nothing else: a page is a megabyte to parse, which would make every
other read of its process late), ``watch_hold_s``, ``limit``.

**The writes are the timed operations** and come back as ``records``,
as every kind's. Two things ride on them. Every acknowledged write
carries ``rv``, the resourceVersion its acknowledgement stated (the
reference's log needs it). A sampled write (1 in ``PROBE_EVERY``, by
the CRC of its key and revision) is PROBED by its own client: a GET
right after the acknowledgement (``shape.inspect_acked``), a LIST of the
tenant's namespace once its status was seen (``shape.inspect``), both in
``rec["inspected"]``; its key stays busy until both are back, so no
other write of the generator can lie between. ``compare.judge`` hands
``inspected`` to the shape's ``evidence_mismatches``
(``converged_for_wrong_values``). One write in ``PROBE_EVERY`` is probed.

**The reads** are offered by reader PROCESSES of this kind's own (this
file run as a script: ``--reader``; they never import JAX), because one
interpreter cannot both parse what it reads and offer the rate on time.
Each computes the same schedule — a pure function of (seed, rate, mix,
length, tenants), salt 7 — and takes its share of it: the paged walks go
round the walkers, everything else round the others.

- ``get``: GET one Deployment of the tenant by name (the ``pick``-th of
  the names this reader last saw listed there; the seeded ones until
  then): scope resource.
- ``list_selector``: LIST the tenant's namespace with
  ``labelSelector=group=load``: scope namespace.
- ``list_table``: the same scope, ``Accept: application/json;as=Table;
  v=v1;g=meta.k8s.io``.
- ``relist_watch``: LIST the tenant's cluster unselected, open a WATCH
  at its resourceVersion, hold it ``watch_hold_s`` (cut at the end of
  the traffic), close it.
- ``list_all_paged``: cluster ``*``, ``limit``, follow ``continue`` to
  the end; a 410 restarts the walk once (counted in ``restarts``).

A read's record: ``verb``, ``scope``, ``tenant``, ``name``, ``due``,
``sent``, ``done`` (the whole answer read; for ``relist_watch`` the
LIST's), ``bytes``, ``items``, ``pages``, ``status``, ``restarts``,
``error``, and ``answer``: what came back, reduced to the reference's
views (``benchmarks/k8s_load_read_reference.py``: [cluster, namespace,
name, resourceVersion, digest]). Each reader writes its records to a
file of JSON lines, EVERY read of its share exactly once (a watch still
held when the reader stops is written with an error, not dropped); this
kind's extras name the files (``read_files``: a topology holds their
lines against ``planned()``) and the readers' own CPU (``read_cpu_s``,
one entry a process, over ``read_wall_s``: a reader near one core is the
harness's limit, not the server's).
**Reads do not come back as** ``records``: ``reference.final_state``
applies every acknowledged record as a write and ``run.py`` times every
main record that is not a delete. A topology that knows this kind
(``benchmarks/read_deploy.py``) reads the files when the generator's
output comes back, has the reference judge every line, and puts the
stamps under the extras' ``reads`` key (``ctx["generator"]["reads"]``)
and the verdict under ``read_verdict``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import os
import queue
import subprocess
import sys
import threading
import time
import zlib

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import k8s_load_read_reference as ref  # noqa: E402
from benchmarks import shapes  # noqa: E402
from benchmarks.generators import open_loop  # noqa: E402

VERBS = ("get", "list_selector", "list_table", "relist_watch",
         "list_all_paged")
SCOPES = {"get": "resource", "list_selector": "namespace",
          "list_table": "namespace", "relist_watch": "cluster",
          "list_all_paged": "all"}
TABLE = "application/json;as=Table;v=v1;g=meta.k8s.io"
DRAIN_S = 0.2  # a watch is read this long past its hold before it closes
PROBE_EVERY = 10  # one write in so many is probed (a GET, then a LIST)
# seconds of one interpreter that a paged walk of the whole fleet takes
# its reader (six pages of 500 Deployments to parse and digest: 0.20 s on
# the chip's host, PERF.md 4, PR 54); walkers are kept under half busy
WALK_S = 0.2


def planned(traffic: dict, seconds: float) -> int:
    """How many reads the schedule holds: rate x the traffic's length."""
    length = traffic["warmup_s"] + seconds + traffic["cooldown_s"]
    return int(round(traffic["read_rate_per_s"] * length))


def walkers(traffic: dict) -> int:
    """Of ``read_procs`` reader processes, those that take the paged
    walks and nothing else: enough that each is under half busy at the
    traffic's walk rate, at least one, and never all."""
    procs = max(1, int(traffic.get("read_procs", 1)))
    walks_per_s = (traffic["read_rate_per_s"]
                   * traffic["read_mix"].get("list_all_paged", 0.0))
    if procs < 2 or walks_per_s <= 0:
        return 0
    return min(procs - 1, max(1, math.ceil(walks_per_s * WALK_S * 2)))


def read_schedule(seed: int, rate_per_s: float, mix: dict[str, float],
                  length_s: float, n_tenants: int,
                  ) -> list[tuple[float, str, int, int]]:
    """[(due offset s, verb, tenant index, pick)], sorted by due: exactly
    ``rate * length`` reads, verbs in exact proportion, instants sorted
    uniform (a Poisson process given its count), tenants uniform."""
    unknown = set(mix) - set(VERBS)
    if unknown:
        raise ValueError(f"read_mostly: unknown read verbs {sorted(unknown)}")
    rng = shapes.seed_rng(seed, 7)
    n = int(round(rate_per_s * length_s))
    verbs: list[str] = []
    for verb in sorted(mix):
        verbs += [verb] * int(round(mix[verb] * n))
    first = sorted(mix, key=lambda v: -mix[v])[0]
    verbs = (verbs + [first] * n)[:n]
    rng.shuffle(verbs)
    dues = sorted(rng.uniform(0.0, length_s) for _ in range(n))
    return [(due, verb, rng.randrange(n_tenants), rng.getrandbits(30))
            for due, verb in zip(dues, verbs)]


def share(plan: list, index: int, procs: int, walkers: int) -> list:
    """Reader ``index``'s part of the schedule: the last ``walkers``
    processes take the walks in turn, the others everything else."""
    others = procs - walkers
    out, n_walk, n_other = [], 0, 0
    for item in plan:
        if walkers and item[1] == "list_all_paged":
            if others + n_walk % walkers == index:
                out.append(item)
            n_walk += 1
        else:
            if n_other % others == index:
                out.append(item)
            n_other += 1
    return out


def sampled(tenant: str, name: str, body: dict | None, every: int) -> bool:
    """Whether a write is probed: a pure function of what it writes."""
    if body is None or every <= 0:
        return False
    rev = (body["metadata"].get("annotations") or {}).get(
        "deployment.kubernetes.io/revision", "")
    return zlib.crc32(f"{tenant}/{name}/{rev}".encode()) % every == 0


# ------------------------------------------------------------ the writes


class Probing:
    """``loadgen.Session`` as ``open_loop.run`` uses it, with the
    acknowledged resourceVersion noted on every record and the sampled
    writes probed. ``open_loop``'s own ``on_settled`` hook (which frees
    a key for its next write) is held back for a probed write until its
    LIST is in."""

    def __init__(self, session, every: int, workers: int = 4):
        self._s = session
        self.shape, self.tenants = session.shape, session.tenants
        self.locations = session.locations
        self.every = every
        self._freed = None
        self._lock = threading.Lock()
        self._got: dict[tuple[str, str], threading.Event] = {}
        self._jobs: queue.Queue = queue.Queue()
        self._closing = False
        self.probed = 0
        session.on_settled = self._settled
        self._workers = [threading.Thread(target=self._work, daemon=True,
                                          name=f"loadgen-probe{i}")
                         for i in range(workers)]
        for t in self._workers:
            t.start()

    @property
    def on_settled(self):
        return self._freed

    @on_settled.setter
    def on_settled(self, hook) -> None:
        self._freed = hook

    def client(self):
        return self._s.client()

    def write(self, client, kind, tenant, body, name, due, **kw) -> dict:
        probe = sampled(tenant, name, body, self.every)
        if probe:
            with self._lock:
                got = self._got[(tenant, name)] = threading.Event()
        rec = self._s.write(client, kind, tenant, body, name, due, **kw)
        floor = getattr(client, "_session", None)
        if rec["acked"] is not None and floor is not None:
            # the client's session floor of this logical cluster: the
            # resourceVersion of its last acknowledged write there (a
            # delete's is the store's when it answered)
            rec["rv"] = floor.floor(tenant)
        if probe:
            if rec["acked"] is not None:
                answer = self.shape.inspect_acked(client, body)
                with self._lock:
                    insp = rec.setdefault("inspected", {})
                    insp["rv"], insp["get"] = rec.get("rv"), answer
            else:
                with self._lock:
                    self._got.pop((tenant, name), None)
            got.set()
        return rec

    def _settled(self, rec: dict) -> None:
        key = tuple(rec["key"])
        with self._lock:
            got = self._got.pop(key, None)
            take = got is not None and not self._closing
        if take:
            self._jobs.put((rec, got))
        elif self._freed is not None:
            self._freed(rec)

    def _work(self) -> None:
        client = self._s.client()
        while True:
            job = self._jobs.get()
            if job is None:
                break
            rec, got = job
            got.wait(self._s.deadline_s)
            client.cluster = rec["key"][0]
            answer = self.shape.inspect(client, rec["body"], self.locations)
            with self._lock:
                rec.setdefault("inspected", {})["list"] = answer
                self.probed += 1
            if self._freed is not None:
                self._freed(rec)
        client.close()

    def close(self) -> None:
        with self._lock:
            self._closing = True
        for _ in self._workers:
            self._jobs.put(None)
        for t in self._workers:
            t.join()


def prepare(session, spec: dict) -> dict:
    """``open_loop``'s plan for the writes, and the reader processes
    started and ready (each computes its own share of the read schedule
    from the spec)."""
    tr = spec["traffic"]
    plan = open_loop.prepare(session, spec)
    base = os.path.splitext(spec["population_file"])[0]
    spec_path = f"{base}.readers.spec.json"
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    readers = []
    for i in range(max(1, int(tr.get("read_procs", 1)))):
        out = f"{base}.reads-{i}.jsonl"
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--reader",
             "--spec", spec_path, "--index", str(i), "--out", out],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env, text=True)
        readers.append((proc, out))
    for proc, _out in readers:
        line = proc.stdout.readline().strip()
        if line != "ready":
            for p, _o in readers:
                p.kill()
            raise RuntimeError(f"a reader said {line!r}, not 'ready'")
    plan["readers"] = readers
    return plan


def run(session, plan: dict, spec: dict, t_start: float) -> dict:
    tr = spec["traffic"]
    readers = plan["readers"]
    try:
        for proc, _out in readers:
            proc.stdin.write(f"go {t_start!r}\n")
            proc.stdin.flush()
        probing = Probing(session, PROBE_EVERY)
        extra = open_loop.run(probing, plan, spec, t_start)
        probing.close()
        left = (t_start + tr["warmup_s"] + spec["seconds"] + tr["cooldown_s"]
                + tr["deadline_s"] + 60.0)
        cpu_s = []
        for proc, _out in readers:
            rc = proc.wait(timeout=max(1.0, left - time.monotonic()))
            if rc != 0:
                raise RuntimeError(f"a reader exited with {rc}")
            cpu_s.append(float(proc.stdout.readline().split()[1]))
    finally:
        for proc, _out in readers:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    return dict(extra, read_offered_per_s=tr["read_rate_per_s"],
                read_files=[out for _p, out in readers],
                read_cpu_s=cpu_s,
                read_wall_s=(tr["warmup_s"] + spec["seconds"]
                             + tr["cooldown_s"]),
                probed=probing.probed)


# ----------------------------------------------------- a reader process


class Reader:
    """One reader process: its share of the schedule offered by a pool of
    blocking clients, its watches held on one asyncio thread, its records
    written as JSON lines as they complete."""

    def __init__(self, spec: dict, index: int, out_path: str):
        from kcp_tpu.server.rest import RestClient

        tr = spec["traffic"]
        self.spec, self.tr, self.index = spec, tr, index
        self.shape = shapes.load(spec["shape"])
        self.tenants = shapes.tenant_names(spec["tenants"])
        self.length = tr["warmup_s"] + spec["seconds"] + tr["cooldown_s"]
        procs = max(1, int(tr.get("read_procs", 1)))
        self.plan = share(
            read_schedule(spec["seed"], tr["read_rate_per_s"], tr["read_mix"],
                          self.length, spec["tenants"]),
            index, procs, walkers(tr))
        self.n_senders = max(1, -(-int(tr.get("read_senders", 16)) // procs))
        self._client = lambda: RestClient(spec["server"])
        self._stamped = stamped_watch_class()
        with open(spec["population_file"]) as f:
            self.names: dict[str, list[str]] = {t: [] for t in self.tenants}
            for tenant, name, _body in json.load(f):
                self.names[tenant].append(name)
        self.hold_s = float(tr.get("watch_hold_s", 20))
        self.limit = int(tr.get("limit", 500))
        self.out = open(out_path, "w")
        self.out_lock = threading.Lock()
        self.work: queue.Queue = queue.Queue()
        self.loop = asyncio.new_event_loop()
        # list-then-watch reads whose watch is not yet written out
        self.held: dict[int, dict] = {}
        self.t_end = 0.0

    # ------------------------------------------------------------ records

    def emit(self, rec: dict) -> None:
        line = json.dumps(rec)
        with self.out_lock:
            self.out.write(line + "\n")

    # -------------------------------------------------------------- verbs

    def fetch(self, client, path: str, accept: str = "") -> tuple[int, int, dict]:
        status, _headers, data = client.request_raw(
            "GET", path, None, {"Accept": accept} if accept else None)
        return status, len(data), (json.loads(data) if data else {})

    def one(self, client, verb: str, tenant: str, pick: int, due: float) -> None:
        shape = self.shape
        res, ns = shape.RESOURCE, shape.NAMESPACE
        client.cluster = tenant
        rec = {"verb": verb, "scope": SCOPES[verb], "tenant": tenant,
               "name": None, "due": due, "sent": time.monotonic(),
               "done": None, "bytes": 0, "items": 0, "pages": 0,
               "status": 0, "restarts": 0, "error": None, "answer": None}
        try:
            if verb == "get":
                # a tenant with nothing left asks for a name no write
                # ever gives: a 404, as the reference expects
                known = self.names[tenant]
                name = rec["name"] = (known[pick % len(known)] if known
                                      else f"{shape.PREFIX}-none")
                status, n, body = self.fetch(client, client._path(res, ns, name))
                rec.update(status=status, bytes=n, done=time.monotonic(),
                           items=int(status == 200))
                if status not in (200, 404):
                    raise RuntimeError(f"GET answered {status}: "
                                       f"{body.get('message', '')[:120]}")
                rec["answer"] = {"view": ref.view(body) if status == 200
                                 else None}
            elif verb == "list_all_paged":
                self.walk(client, rec)
            else:
                table = verb == "list_table"
                query = ("labelSelector=group%3Dload"
                         if verb == "list_selector" else "")
                scope_ns = None if verb == "relist_watch" else ns
                status, n, body = self.fetch(
                    client, client._path(res, scope_ns, query=query),
                    TABLE if table else "")
                rec.update(status=status, bytes=n, pages=1,
                           done=time.monotonic())
                if status != 200:
                    raise RuntimeError(f"LIST answered {status}: "
                                       f"{body.get('message', '')[:120]}")
                rv, items = (ref.table_views(body) if table
                             else ref.list_views(body))
                rec["items"] = len(items)
                rec["answer"] = {"rv": rv, "items": items}
                if scope_ns is not None:
                    self.names[tenant] = [i[2] for i in items] or \
                        self.names[tenant]
                if verb == "relist_watch":
                    rec["answer"]["list_done"] = rec["done"]
                    with self.out_lock:
                        self.held[id(rec)] = rec
                    self.loop.call_soon_threadsafe(
                        self.loop.create_task, self.hold(rec, tenant, rv))
                    return
        except Exception as e:  # noqa: BLE001 — recorded, counted an error
            rec["error"] = f"{type(e).__name__}: {e}"[:200]
            if rec["done"] is None:
                rec["done"] = time.monotonic()
        self.emit(rec)

    def walk(self, client, rec: dict) -> None:
        """Cluster ``*`` in pages of ``limit``; a 410 restarts the walk
        once, from scratch, and is counted."""
        client.cluster = "*"
        res = self.shape.RESOURCE
        pages: list[dict] = []
        cont = ""
        while True:
            query = f"limit={self.limit}"
            if cont:
                from urllib.parse import quote

                query += "&continue=" + quote(cont, safe="")
            status, n, body = self.fetch(
                client, client._path(res, None, query=query))
            rec["bytes"] += n
            rec["status"] = status
            if status == 410 and rec["restarts"] == 0:
                rec["restarts"] = 1
                rec["sent"], rec["bytes"] = time.monotonic(), 0
                pages, cont = [], ""
                continue
            if status != 200:
                rec["done"] = time.monotonic()
                raise RuntimeError(f"page {len(pages)} answered {status}: "
                                   f"{body.get('message', '')[:120]}")
            rv, items = ref.list_views(body)
            pages.append({"rv": rv, "items": items})
            cont = (body.get("metadata") or {}).get("continue") or ""
            if not cont:
                break
        rec.update(done=time.monotonic(), pages=len(pages),
                   items=sum(len(p["items"]) for p in pages),
                   limit=self.limit, answer={"pages": pages})

    # ------------------------------------------------------------ watches

    async def hold(self, rec: dict, tenant: str, rv: int) -> None:
        client = self._client()
        client.cluster = tenant
        w = client.watch(self.shape.RESOURCE, None, since_rv=rv)
        # the instant the response head arrives, where RestWatch sets
        # its ``responded`` flag: the same object under a class that
        # stamps that assignment
        w.__class__ = self._stamped
        watch = {"sent": time.monotonic(), "head": None, "hold_end": None,
                 "closed": None, "events": [], "error": None}
        until = min(watch["sent"] + self.hold_s, self.t_end)
        try:
            w._ensure_started()
            while not w.closed:
                left = until - time.monotonic()
                if left <= 0:
                    break
                self.take(watch, await w.next_batch(max_wait=left))
            watch["hold_end"] = time.monotonic()
            if w.closed and watch["hold_end"] < until - 0.001:
                watch["error"] = "the stream ended before the hold was over"
            else:
                await asyncio.sleep(DRAIN_S)
            self.take(watch, w.drain())
        except Exception as e:  # noqa: BLE001 — recorded, counted an error
            watch["error"] = f"{type(e).__name__}: {e}"[:200]
            watch["hold_end"] = watch["hold_end"] or time.monotonic()
        finally:
            watch["head"] = w.t_head
            w.close()
            watch["closed"] = time.monotonic()
        self.release(rec, watch)

    def release(self, rec: dict, watch: dict | None) -> None:
        """Write a list-then-watch read out, once: with its watch, or
        (``None``) as an error where the reader stopped before it."""
        with self.out_lock:
            if self.held.pop(id(rec), None) is None:
                return
        if watch is None:
            rec["error"] = "watch: the reader stopped before its hold ended"
        else:
            rec["answer"]["watch"] = watch
            if watch["error"]:
                rec["error"] = "watch: " + watch["error"]
        self.emit(rec)

    @staticmethod
    def take(watch: dict, events) -> None:
        for ev in events:
            meta = ev.object.get("metadata") or {}
            watch["events"].append(
                [ev.type, ev.cluster, ev.namespace, ev.name,
                 int(meta.get("resourceVersion") or 0),
                 ref.digest(ev.object), ev.__dict__.get("_ta")])

    # ---------------------------------------------------------------- run

    def sender(self) -> None:
        client = self._client()
        while True:
            item = self.work.get()
            if item is None:
                break
            self.one(client, *item)
        client.close()

    def run(self, t_start: float) -> None:
        self.t_end = t_start + self.length
        keeper = threading.Thread(target=self._keep, daemon=True,
                                  name="reader-watches")
        keeper.start()
        threads = [threading.Thread(target=self.sender, daemon=True,
                                    name=f"reader-s{i}")
                   for i in range(self.n_senders)]
        for t in threads:
            t.start()
        for off, verb, ti, pick in self.plan:
            due = t_start + off
            delay = due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            self.work.put((verb, self.tenants[ti], pick, due))
        for _ in threads:
            self.work.put(None)
        for t in threads:
            t.join()
        stop = time.monotonic() + self.hold_s + 30.0
        while self.held and time.monotonic() < stop:
            time.sleep(0.05)
        self.loop.call_soon_threadsafe(self.loop.stop)
        keeper.join(timeout=5)
        for rec in list(self.held.values()):
            self.release(rec, None)
        with self.out_lock:
            self.out.close()

    def _keep(self) -> None:
        asyncio.set_event_loop(self.loop)
        self.loop.run_forever()


def stamped_watch_class():
    """``RestWatch`` that stamps the instant its ``responded`` flag is
    set: where the response head has arrived."""
    from kcp_tpu.server.rest import RestWatch

    class Stamped(RestWatch):
        t_head = None

        @property
        def responded(self) -> bool:
            return self.t_head is not None

        @responded.setter
        def responded(self, value: bool) -> None:
            if value and self.t_head is None:
                self.t_head = time.monotonic()

    return Stamped


def reader_main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reader", action="store_true")
    ap.add_argument("--spec", required=True)
    ap.add_argument("--index", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    with open(args.spec) as f:
        spec = json.load(f)
    reader = Reader(spec, args.index, args.out)
    print("ready", flush=True)
    line = sys.stdin.readline().split()
    if len(line) != 2 or line[0] != "go":
        print(f"reader: expected 'go <t>', got {line}", file=sys.stderr)
        return 2
    # a reader whose generator is gone (killed with its run) goes too:
    # the generator holds this pipe open until the reader has exited
    # (the descriptor, not ``sys.stdin``: a daemon thread parked inside
    # the buffered reader's lock can abort the interpreter's shutdown)
    def gone() -> None:
        while os.read(0, 4096):
            pass
        os._exit(1)

    threading.Thread(target=gone, daemon=True, name="reader-orphan").start()
    cpu0 = time.process_time()
    reader.run(float(line[1]))
    print(f"cpu {time.process_time() - cpu0!r}", flush=True)
    if "jax" in sys.modules:
        print("reader: this process imported JAX", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(reader_main(sys.argv[1:]))
