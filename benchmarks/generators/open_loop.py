"""Open loop: operations fall due on a schedule fixed by the seed, whether
or not the server keeps up; each is timed from when it was DUE.

Traffic parameters: ``rate_per_s``, ``mix`` (shares of update / create /
delete), ``warmup_s``, ``cooldown_s``, ``deadline_s``, ``senders``.

The schedule is a pure function of (seed, rate, mix, length): exactly
``rate * length`` arrivals at sorted uniform instants (a Poisson process
given its count), kinds in exact proportion, tenants uniform. Every seed
offers the same amount and mix of work in another order. The object of
an update or delete is settled when the operation is dispatched: the
``pick``-th resident object of the tenant that has no write in flight.
"""

from __future__ import annotations

import json
import queue
import threading
import time

from benchmarks import shapes


def schedule(seed: int, rate_per_s: float, mix: dict[str, float],
             length_s: float, n_tenants: int) -> list[tuple[float, str, int, int]]:
    """[(due offset s, kind, tenant index, pick)], sorted by due."""
    rng = shapes.seed_rng(seed, 2)
    n = int(round(rate_per_s * length_s))
    kinds: list[str] = []
    for kind in sorted(mix):
        kinds += [kind] * int(round(mix[kind] * n))
    first = sorted(mix, key=lambda k: -mix[k])[0]
    kinds = (kinds + [first] * n)[:n]
    rng.shuffle(kinds)
    dues = sorted(rng.uniform(0.0, length_s) for _ in range(n))
    return [(dues[i], kinds[i], rng.randrange(n_tenants), rng.getrandbits(30))
            for i in range(n)]


def prepare(session, spec: dict) -> dict:
    tr = spec["traffic"]
    length = tr["warmup_s"] + spec["seconds"] + tr["cooldown_s"]
    plan = schedule(spec["seed"], tr["rate_per_s"], tr["mix"], length,
                    spec["tenants"])
    with open(spec["population_file"]) as f:
        pop = {(t, n): body for t, n, body in json.load(f)}
    resident: dict[str, list[str]] = {t: [] for t in session.tenants}
    for (tenant, name) in pop:
        resident[tenant].append(name)
    return {"schedule": plan, "bodies": pop, "resident": resident}


def run(session, plan: dict, spec: dict, t_start: float) -> dict:
    tr = spec["traffic"]
    shape, tenants = session.shape, session.tenants
    bodies, resident = plan["bodies"], plan["resident"]
    busy: set[tuple[str, str]] = set()
    state_lock = threading.Lock()
    rng = shapes.seed_rng(spec["seed"], 3)
    work: queue.Queue = queue.Queue()
    skipped = 0

    def settled(rec: dict) -> None:
        key = tuple(rec["key"])
        with state_lock:
            busy.discard(key)
            if rec["kind"] == "create":
                resident[key[0]].append(key[1])

    session.on_settled = settled

    def sender() -> None:
        client = session.client()
        while True:
            item = work.get()
            if item is None:
                break
            kind, tenant, name, body, due = item
            rec = session.write(client, kind, tenant, body, name, due)
            if rec["acked"] is None or kind == "delete":
                with state_lock:
                    busy.discard((tenant, name))
        client.close()

    threads = [threading.Thread(target=sender, name=f"loadgen-s{i}",
                                daemon=True)
               for i in range(int(tr.get("senders", 16)))]
    for t in threads:
        t.start()

    n_created = 0
    for off, kind, ti, pick in plan["schedule"]:
        due = t_start + off
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        with state_lock:
            if kind == "create":
                tenant = tenants[ti]
                name = f"{shape.PREFIX}-n{n_created:06d}-{pick:08x}"
                n_created += 1
                body = shape.new(name, rng, session.locations)
            else:
                found = None
                for step in range(len(tenants)):
                    tenant = tenants[(ti + step) % len(tenants)]
                    names = resident[tenant]
                    for j in range(len(names)):
                        cand = names[(pick + j) % len(names)]
                        if (tenant, cand) not in busy:
                            found = cand
                            break
                    if found:
                        break
                if not found:
                    skipped += 1
                    continue
                name = found
                if kind == "delete":
                    resident[tenant].remove(name)
                    body = None
                else:
                    body = shape.mutate(bodies[(tenant, name)], rng)
            busy.add((tenant, name))
            if body is not None:
                bodies[(tenant, name)] = body
        work.put((kind, tenant, name, body, due))
    for _ in threads:
        work.put(None)
    for t in threads:
        t.join()
    return {"skipped": skipped, "offered_per_s": tr["rate_per_s"]}
