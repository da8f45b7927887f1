"""Closed loop: ``clients`` pipelines that each deploy and wait. A client
creates an object (a tenant uniform from the seed), waits until its own
wildcard watch shows it converged (timed, create-due to seen; due is the
instant the client was free to send), reads what a deploy-and-wait user
reads next (``shape.inspect``), tears it down (acknowledged, untimed) and
goes on. An object that did not converge by its deadline is counted
failed and left standing for the final comparison. The load follows the
server's speed: this kind reports the rate at saturation.

Traffic parameters: ``clients``, ``warmup_s``, ``cooldown_s``,
``deadline_s``, ``keep_last`` (the object a client has in flight when
the traffic ends is left standing, so that the final comparison finds
objects the measured traffic made, downstream too).
"""

from __future__ import annotations

import threading
import time

from benchmarks import shapes


def prepare(session, spec: dict) -> dict:
    return {}


def run(session, plan: dict, spec: dict, t_start: float) -> dict:
    tr = spec["traffic"]
    shape, tenants = session.shape, session.tenants
    t_stop = t_start + tr["warmup_s"] + spec["seconds"] + tr["cooldown_s"]
    errors: list[str] = []

    def client_loop(c: int) -> None:
        rng = shapes.seed_rng(spec["seed"], 4, c)
        client = session.client()
        n = 0
        wait = t_start - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        try:
            while time.monotonic() < t_stop:
                tenant = tenants[rng.randrange(len(tenants))]
                name = f"{shape.PREFIX}-c{c:02d}-{n:05d}-{rng.getrandbits(32):08x}"
                n += 1
                body = shape.new(name, rng, session.locations)
                rec = session.write(client, "create", tenant, body, name,
                                    time.monotonic(), wait=True)
                ok = rec["acked"] is not None and session.wait_seen(rec)
                if ok:
                    client.cluster = tenant
                    rec["inspected"] = shape.inspect(client, body,
                                                     session.locations)
                if ok and tr.get("keep_last") and time.monotonic() >= t_stop:
                    break
                if not ok:
                    # refused, or not converged by its deadline: left
                    # standing, so that the final comparison reads what
                    # became of it
                    continue
                due = time.monotonic()
                client.cluster = tenant
                try:
                    names = shape.teardown(client, body, session.locations)
                except Exception as e:  # noqa: BLE001 — recorded as failed
                    names = []
                    errors.append(f"teardown {tenant}/{name}: "
                                  f"{type(e).__name__}: {e}")
                acked = time.monotonic()
                with session.lock:
                    session.waiting.pop((tenant, name), None)
                    for gone in names:
                        session.records.append({
                            "kind": "delete", "key": [tenant, gone],
                            "due": due, "sent": due, "acked": acked,
                            "seen": None, "error": None, "aux": True})
        finally:
            client.close()

    threads = [threading.Thread(target=client_loop, args=(c,),
                                name=f"loadgen-c{c}", daemon=True)
               for c in range(int(tr["clients"]))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return {"teardown_errors": errors, "clients": int(tr["clients"])}
