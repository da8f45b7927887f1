"""Open loop: operations fall due on a schedule fixed by the seed, whether
or not the server keeps up; each is timed from when it was DUE.

Traffic parameters: ``rate_per_s``, ``mix`` (shares of update / create /
delete), ``warmup_s``, ``cooldown_s``, ``deadline_s``, ``senders``, and
optionally ``burst``: ``{"every_s": E, "size": N, "spread_ms": W}``.

The schedule is a pure function of (seed, rate, mix, length, burst):
exactly ``rate * length`` arrivals, kinds in exact proportion, tenants
uniform. Without ``burst`` they fall at sorted uniform instants (a
Poisson process given its count). With it, at 0, E, 2E, ... seconds
(every burst that ends inside the length) ``N`` of them fall due at
instants uniform in ``W`` ms, each carrying its burst's index, and the
rest are the uniform background: ``rate_per_s`` stays the mean. Every
seed offers the same amount and mix of work in another order. The object
of an update or delete is settled when the operation is dispatched: the
``pick``-th resident object of the tenant that has no write in flight.
"""

from __future__ import annotations

import json
import queue
import threading
import time

from benchmarks import shapes


def burst_starts(burst: dict | None, length_s: float) -> list[float]:
    """Offsets at which a burst begins: 0, E, 2E, ... while the burst's
    whole spread still fits inside the length."""
    if not burst:
        return []
    every, spread = float(burst["every_s"]), burst["spread_ms"] / 1e3
    if every <= 0 or spread < 0 or int(burst["size"]) < 1:
        raise ValueError(f"open_loop: malformed burst block {burst}")
    return [k * every for k in range(int((length_s - spread) // every) + 1)]


def schedule(seed: int, rate_per_s: float, mix: dict[str, float],
             length_s: float, n_tenants: int, burst: dict | None = None,
             ) -> list[tuple[float, str, int, int, int | None]]:
    """[(due offset s, kind, tenant index, pick, burst index or None)],
    sorted by due. With no ``burst`` the first four are, value for value,
    what they were before bursts existed (benchmarks/tests hold digests)."""
    rng = shapes.seed_rng(seed, 2)
    n = int(round(rate_per_s * length_s))
    kinds: list[str] = []
    for kind in sorted(mix):
        kinds += [kind] * int(round(mix[kind] * n))
    first = sorted(mix, key=lambda k: -mix[k])[0]
    kinds = (kinds + [first] * n)[:n]
    rng.shuffle(kinds)
    starts = burst_starts(burst, length_s)
    size, spread = (int(burst["size"]), burst["spread_ms"] / 1e3) if burst else (0, 0.0)
    n_bg = n - len(starts) * size
    if n_bg < 0:
        raise ValueError(f"open_loop: {len(starts)} bursts of {size} are more "
                         f"than the {n} arrivals of {rate_per_s:g}/s")
    dues = [rng.uniform(0.0, length_s) for _ in range(n_bg)]
    marks: list[int | None] = [None] * n_bg
    for b, t0 in enumerate(starts):
        dues += [t0 + rng.uniform(0.0, spread) for _ in range(size)]
        marks += [b] * size
    order = sorted(range(n), key=dues.__getitem__)
    return [(dues[i], kinds[j], rng.randrange(n_tenants), rng.getrandbits(30),
             marks[i]) for j, i in enumerate(order)]


def prepare(session, spec: dict) -> dict:
    tr = spec["traffic"]
    length = tr["warmup_s"] + spec["seconds"] + tr["cooldown_s"]
    plan = schedule(spec["seed"], tr["rate_per_s"], tr["mix"], length,
                    spec["tenants"], tr.get("burst"))
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
            kind, tenant, name, body, due, burst = item
            rec = session.write(client, kind, tenant, body, name, due)
            if burst is not None:
                rec["burst"] = burst
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
    for off, kind, ti, pick, burst in plan["schedule"]:
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
        work.put((kind, tenant, name, body, due, burst))
    for _ in threads:
        work.put(None)
    for t in threads:
        t.join()
    return {"skipped": skipped, "offered_per_s": tr["rate_per_s"]}
