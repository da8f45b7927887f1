"""Cold sync: the deployment's residents are in the store and no location
is registered; this kind registers every location over REST at one
instant inside the window and times the residents IT NEVER WROTE.

Traffic parameters: ``register_due_s`` (the instant, in seconds into the
window, at which every ``Cluster`` create falls due), ``register_senders``
(blocking senders that send them as fast as they are acknowledged),
``live`` (``rate_per_s``, ``senders``, ``aux``: an open loop of creates of
fresh names, tenants uniform from the seed, from the traffic's start to
its end, so that the sync shares the server with tenants' writes),
``warmup_s``, ``cooldown_s``, ``deadline_s``.

One record for every resident: ``kind: "sync"``, ``due`` = the
registration instant (the moment an operator asked for the sync),
``sent`` / ``acked`` = those of its location's ``Cluster`` create,
``body`` / ``want`` from the seeded population, ``seen`` = the status for
ITS data on the generator's one wildcard watch. All of them wait in
``session.waiting`` before the first create is sent, so the watch cannot
win the race. The topology hands the ``Cluster`` bodies over in the
spec (``clusters``, benchmarks/mapper_deploy.py).

What ``run`` returns reaches the readers as ``ctx["generator"]``:
``sync_cpu_s`` / ``sync_wall_s`` are this process's CPU seconds and the
wall seconds from the registration instant to the last resident seen
(or to the end of the wait): above four fifths, this one interpreter and
not the server set the sync's pace.
"""

from __future__ import annotations

import json
import queue
import threading
import time

from benchmarks import cold_sync_reference, shapes


def live_schedule(seed: int, rate_per_s: float, length_s: float,
                  n_tenants: int) -> list[tuple[float, int, int]]:
    """[(due offset s, tenant index, pick)] sorted by due: exactly
    ``rate * length`` creates at uniform instants, a pure function of
    its arguments."""
    rng = shapes.seed_rng(seed, 6)
    n = int(round(rate_per_s * length_s))
    dues = sorted(rng.uniform(0.0, length_s) for _ in range(n))
    return [(due, rng.randrange(n_tenants), rng.getrandbits(30))
            for due in dues]


def prepare(session, spec: dict) -> dict:
    tr = spec["traffic"]
    with open(spec["population_file"]) as f:
        pop = [(t, n, body) for t, n, body in json.load(f)]
    by_pair: dict[tuple, list[dict]] = {}
    records = []
    for tenant, name, body in pop:
        rec = {"kind": "sync", "key": [tenant, name], "due": None,
               "sent": None, "acked": None, "seen": None, "error": None,
               "aux": False, "body": body, "want": session.shape.want(body)}
        records.append(rec)
        by_pair.setdefault(
            (tenant, cold_sync_reference.placed_at(body)), []).append(rec)
    length = tr["warmup_s"] + spec["seconds"] + tr["cooldown_s"]
    return {"records": records, "by_pair": by_pair,
            "live": live_schedule(spec["seed"], tr["live"]["rate_per_s"],
                                  length, spec["tenants"])}


def run(session, plan: dict, spec: dict, t_start: float) -> dict:
    tr = spec["traffic"]
    shape, tenants = session.shape, session.tenants
    t_reg = t_start + tr["warmup_s"] + tr["register_due_s"]
    t_end = t_start + tr["warmup_s"] + spec["seconds"] + tr["cooldown_s"]

    # ---- the residents wait for their status before anything is sent
    left = [len(plan["records"])]
    last_seen: list = [None, None]  # monotonic, process_time
    done = threading.Event()

    def settled(rec: dict) -> None:
        if rec["kind"] != "sync":
            return
        with session.lock:
            left[0] -= 1
            if left[0] == 0:
                last_seen[:] = [rec["seen"], time.process_time()]
                done.set()

    session.on_settled = settled
    with session.lock:
        for rec in plan["records"]:
            rec["due"] = t_reg
            session.waiting[tuple(rec["key"])] = rec
        session.records.extend(plan["records"])
    if not plan["records"]:
        done.set()

    # ---- live creates, open loop, from the traffic's start to its end
    live_q: queue.Queue = queue.Queue()
    aux = bool(tr["live"].get("aux", True))

    def live_sender() -> None:
        client = session.client()
        while True:
            item = live_q.get()
            if item is None:
                break
            tenant, name, body, due = item
            session.write(client, "create", tenant, body, name, due, aux=aux)
        client.close()

    def live_dispatch() -> None:
        rng = shapes.seed_rng(spec["seed"], 7)
        for i, (off, ti, pick) in enumerate(plan["live"]):
            delay = t_start + off - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            name = f"{shape.PREFIX}-l{i:06d}-{pick:08x}"
            live_q.put((tenants[ti], name,
                        shape.new(name, rng, session.locations),
                        t_start + off))
        for _ in live_threads:
            live_q.put(None)

    live_threads = [threading.Thread(target=live_sender, daemon=True,
                                     name=f"loadgen-l{i}")
                    for i in range(int(tr["live"]["senders"]))]
    dispatcher = threading.Thread(target=live_dispatch, daemon=True,
                                  name="loadgen-live")
    for t in (*live_threads, dispatcher):
        t.start()

    # ---- the registration: every Cluster create due at t_reg
    resource = spec["clusters"]["resource"]
    reg_q: queue.Queue = queue.Queue()
    for item in spec["clusters"]["bodies"]:
        reg_q.put(item)
    errors: list[str] = []

    def register_sender() -> None:
        client = session.client()
        while True:
            try:
                tenant, loc, body = reg_q.get_nowait()
            except queue.Empty:
                break
            client.cluster = tenant
            sent = time.monotonic()
            acked, error = None, None
            try:
                client.create(resource, body)
                acked = time.monotonic()
            except Exception as e:  # noqa: BLE001 — recorded, counted failed
                error = f"{type(e).__name__}: {e}"
                errors.append(f"{tenant}/{loc}: {error}")
            mine = plan["by_pair"].get((tenant, loc), ())
            for rec in mine:
                rec["sent"], rec["acked"], rec["error"] = sent, acked, error
            if acked is None:
                # no location, no sync: its residents are not waited for
                with session.lock:
                    for rec in mine:
                        if session.waiting.pop(tuple(rec["key"]), None):
                            left[0] -= 1
                    if left[0] == 0:
                        done.set()
        client.close()

    delay = t_reg - time.monotonic()
    if delay > 0:
        time.sleep(delay)
    cpu0 = time.process_time()
    senders = [threading.Thread(target=register_sender, daemon=True,
                                name=f"loadgen-r{i}")
               for i in range(int(tr["register_senders"]))]
    for t in senders:
        t.start()
    for t in senders:
        t.join()
    registered_s = time.monotonic() - t_reg

    # ---- wait for the sync's end (or its deadline) and the traffic's
    done.wait(max(0.0, t_reg + session.deadline_s - time.monotonic()))
    end, cpu1 = last_seen if last_seen[0] is not None else (
        time.monotonic(), time.process_time())
    dispatcher.join()
    for t in live_threads:
        t.join()
    rest = t_end - time.monotonic()
    if rest > 0:
        time.sleep(rest)
    return {"registered": len(spec["clusters"]["bodies"]) - len(errors),
            "register_errors": errors[:5], "registered_s": registered_s,
            "unsynced": left[0], "sync_cpu_s": cpu1 - cpu0,
            "sync_wall_s": end - t_reg,
            "offered_per_s": tr["live"]["rate_per_s"]}
