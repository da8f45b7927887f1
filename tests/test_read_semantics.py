"""What a read guarantees, at a small size on the CPU: a served ``Server``
(6 logical clusters, real Deployments) under seeded random writes
interleaved with GET / LIST / selector / Table / ``limit``+``continue`` /
list-then-watch through ``RestClient`` over HTTP, every answer against
the plain reference (``benchmarks/k8s_load_read_reference.py``, which
imports nothing of kcp_tpu) — the comparison that decides ``correct`` in
the benchmark cell ``k8s-load-read-1k.read-mostly``.

- the reference's own teeth: a stale GET, a LIST missing an acknowledged
  object, one listing a deleted one, a page walk with a duplicate, a
  watch with a gap, a watch replaying the LIST's own resourceVersion —
  each is reported, and the sound answer beside it is not;
- a continue token older than the watch window answers a typed 410 and
  the walk restarts once;
- the read path's counters rise by exactly one a request of their verb
  and only then, its sections' self seconds rise only on their verb, and
  ``server_loop_section_leaks_total`` stays 0 (no section is open across
  an ``await``).
"""

import asyncio
import os
import random
import sys
import threading
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import k8s_load_read_reference as ref  # noqa: E402
from benchmarks.shapes import k8s_load_read as shape  # noqa: E402

from kcp_tpu.server.rest import RestClient  # noqa: E402
from kcp_tpu.server.server import Config  # noqa: E402
from kcp_tpu.server.threaded import ServerThread  # noqa: E402
from kcp_tpu.store.selectors import parse_selector  # noqa: E402
from kcp_tpu.utils import errors  # noqa: E402
from kcp_tpu.utils.trace import REGISTRY  # noqa: E402

RES, NS = shape.RESOURCE, shape.NAMESPACE
TENANTS = [f"t{i:04d}" for i in range(6)]
TABLE = {"Accept": "application/json;as=Table;v=v1;g=meta.k8s.io"}


def serve() -> ServerThread:
    return ServerThread(Config(durable=False, tls=False,
                               install_controllers=False)).start(timeout=60)


@pytest.fixture(scope="module")
def srv():
    server = serve()
    yield server
    server.stop()


@pytest.fixture(autouse=True)
def no_section_is_left_open_across_an_await():
    """Whatever a test of this file drives: the ledger swept no section
    (the counter is the process's, so its RISE over the test)."""
    leaks = REGISTRY.counter("server_loop_section_leaks_total")
    before = leaks.value
    yield
    assert leaks.value == before


class Tenants:
    """The writer: seeded creates, updates and deletes over REST, each
    logged as the load generator logs it (key, body, sent, acknowledged,
    acknowledged resourceVersion)."""

    def __init__(self, address: str, seed: int):
        self.client = RestClient(address)
        self.rng = random.Random(seed)
        self.records: list[dict] = []
        self.bodies: dict[tuple[str, str], dict] = {}
        self.n = 0

    def write(self, kind: str, tenant: str, name: str, body: dict | None):
        rec = {"kind": kind, "key": [tenant, name], "body": body,
               "sent": time.monotonic(), "acked": None}
        c = self.client
        c.cluster = tenant
        if kind == "create":
            c.create(RES, body)
        elif kind == "update":
            c.update(RES, body)
        else:
            c.delete(RES, name, NS)
        rec["acked"] = time.monotonic()
        rec["rv"] = c._session.floor(tenant)
        self.records.append(rec)
        if body is None:
            self.bodies.pop((tenant, name), None)
        else:
            self.bodies[(tenant, name)] = body

    def create(self, tenant: str) -> str:
        name = f"deployment-{self.n:03d}-{self.rng.getrandbits(32):08x}"
        self.n += 1
        self.write("create", tenant, name, shape.new(name, self.rng, ["loc0"]))
        return name

    def step(self) -> None:
        """One seeded write: 70 % update, 15 % create, 15 % delete."""
        tenant = self.rng.choice(TENANTS)
        mine = sorted(n for t, n in self.bodies if t == tenant)
        roll = self.rng.random()
        if roll < 0.15 or not mine:
            self.create(tenant)
        elif roll < 0.30:
            self.write("delete", tenant, self.rng.choice(mine), None)
        else:
            name = self.rng.choice(mine)
            self.write("update", tenant, name,
                       shape.mutate(self.bodies[(tenant, name)], self.rng))

    def log(self) -> ref.WriteLog:
        return ref.WriteLog({}, self.records)


def fetch(client, path, headers=None):
    import json

    t0 = time.monotonic()
    status, _h, data = client.request_raw("GET", path, None, headers)
    return status, (json.loads(data) if data else {}), t0, time.monotonic()


def read(client, verb: str, tenant: str, name: str | None = None,
         limit: int = 0) -> dict:
    """One read as ``read_mostly``'s readers record it."""
    client.cluster = "*" if verb == "list_all_paged" else tenant
    rec = {"verb": verb, "tenant": tenant, "name": name, "error": None,
           "limit": limit}
    if verb == "get":
        status, body, rec["sent"], rec["done"] = fetch(
            client, client._path(RES, NS, name))
        rec["status"] = status
        rec["answer"] = {"view": ref.view(body) if status == 200 else None}
    elif verb == "list_all_paged":
        pages, cont = [], ""
        rec["sent"] = time.monotonic()
        while True:
            query = f"limit={limit}" + (f"&continue={cont}" if cont else "")
            status, body, _t0, rec["done"] = fetch(
                client, client._path(RES, None, query=query))
            assert status == 200, body
            rv, items = ref.list_views(body)
            pages.append({"rv": rv, "items": items})
            cont = (body.get("metadata") or {}).get("continue") or ""
            if not cont:
                break
        rec["status"], rec["answer"] = 200, {"pages": pages}
    else:
        table = verb == "list_table"
        query = "labelSelector=group%3Dload" if verb == "list_selector" else ""
        status, body, rec["sent"], rec["done"] = fetch(
            client, client._path(RES, None if verb == "relist_watch" else NS,
                                 query=query), TABLE if table else None)
        assert status == 200, body
        rv, items = ref.table_views(body) if table else ref.list_views(body)
        rec["status"] = status
        rec["answer"] = {"rv": rv, "items": items, "list_done": rec["done"]}
    return rec


async def hold(client, tenant: str, rv: int, seconds: float) -> dict:
    client.cluster = tenant
    w = client.watch(RES, None, since_rv=rv)
    events = []
    until = time.monotonic() + seconds
    try:
        while time.monotonic() < until:
            for ev in await w.next_batch(max_wait=0.05):
                meta = ev.object["metadata"]
                events.append([ev.type, ev.cluster, ev.namespace, ev.name,
                               int(meta["resourceVersion"]),
                               ref.digest(ev.object), time.monotonic()])
        end = time.monotonic()
        await asyncio.sleep(0.2)
        for ev in w.drain():
            meta = ev.object["metadata"]
            events.append([ev.type, ev.cluster, ev.namespace, ev.name,
                           int(meta["resourceVersion"]),
                           ref.digest(ev.object), time.monotonic()])
    finally:
        w.close()
    return {"events": events, "hold_end": end}


# ------------------------------------------------ the served system


def test_served_reads_obey_the_reference_under_interleaved_writes(srv):
    """Writes on one thread, reads of every verb on another, so that
    some reads meet a write in flight; every answer is judged."""
    tenants = Tenants(srv.address, seed=20261003)
    for tenant in TENANTS:
        for _ in range(3):
            tenants.create(tenant)
    stop = threading.Event()

    def churn():
        while not stop.is_set():
            tenants.step()
            time.sleep(0.002)

    writer = threading.Thread(target=churn, daemon=True)
    writer.start()
    rng = random.Random(7)
    client = RestClient(srv.address)
    reads = []
    try:
        for i in range(240):
            tenant = rng.choice(TENANTS)
            verb = rng.choice(["get"] * 5 + ["list_selector", "list_table",
                                             "list_all_paged", "relist_watch"])
            if verb == "get":
                names = sorted(n for t, n in list(tenants.bodies) if t == tenant)
                if not names:
                    continue
                reads.append(read(client, "get", tenant, rng.choice(names)))
            elif verb == "list_all_paged":
                reads.append(read(client, verb, tenant, limit=4))
            elif verb == "relist_watch" and i % 3 == 0:
                rec = read(client, verb, tenant)
                rec["answer"]["watch"] = asyncio.run(
                    hold(RestClient(srv.address), tenant,
                         rec["answer"]["rv"], 0.3))
                reads.append(rec)
            else:
                reads.append(read(client, verb, tenant))
    finally:
        stop.set()
        writer.join()
    log = tenants.log()
    assert len(tenants.records) > 100 and len(reads) > 150
    verbs = {r["verb"] for r in reads}
    assert verbs == {"get", "list_selector", "list_table", "list_all_paged",
                     "relist_watch"}
    bad = []
    for r in reads:
        found, _undetermined = ref.judge(log, r)
        bad += [f"{r['verb']} {r['tenant']}: {m}" for m in found]
    assert bad == []
    # the streams carried something to judge
    assert sum(len(r["answer"]["watch"]["events"]) for r in reads
               if "watch" in r["answer"]) > 5


def test_a_selector_returns_exactly_the_matching_subset(srv):
    tenants = Tenants(srv.address, seed=5)
    tenant = "t0042"
    names = [tenants.create(tenant) for _ in range(8)]
    svc = tenants.bodies[(tenant, names[0])]["metadata"]["labels"]["svc"]
    want = sorted(n for n in names if tenants.bodies[(tenant, n)]
                  ["metadata"]["labels"]["svc"] == svc)
    assert 0 < len(want) < len(names)
    client = RestClient(srv.address, cluster=tenant)
    t0 = time.monotonic()
    items, rv = client.list(RES, NS, parse_selector(f"svc={svc}"), limit=0)
    done = time.monotonic()
    assert sorted(o["metadata"]["name"] for o in items) == want
    views = [ref.view(o) for o in items]
    assert ref.scope_mismatches(tenants.log(), tenant, rv, views, t0, done,
                                selector={"svc": svc}) == ([], False)
    # the same answer under ANOTHER selector's name is not the subset
    found, _ = ref.scope_mismatches(tenants.log(), tenant, rv, views, t0,
                                    done, selector={"group": "load"})
    assert sum("missing from the list" in m for m in found) == len(names) - len(want)


def test_an_expired_continue_token_is_a_typed_410_and_the_walk_restarts_once(
        monkeypatch):
    monkeypatch.setenv("KCP_WATCH_WINDOW", "8")
    server = serve()
    try:
        tenants = Tenants(server.address, seed=11)
        for tenant in TENANTS[:3]:
            for _ in range(3):
                tenants.create(tenant)
        client = RestClient(server.address, cluster="*")
        status, body, _t0, _t1 = fetch(client, client._path(RES, None,
                                                            query="limit=4"))
        token = body["metadata"]["continue"]
        assert status == 200 and token and len(body["items"]) == 4
        for _ in range(12):  # more than the window holds
            tenants.step()
        status, body, _t0, _t1 = fetch(client, client._path(
            RES, None, query=f"limit=4&continue={token}"))
        assert (status, body["reason"]) == (410, "Expired")
        with pytest.raises(errors.GoneError):
            client._request("GET", client._path(
                RES, None, query=f"limit=4&continue={token}"))
        # RestClient's chunked list: the 410 restarts it once, from
        # scratch, and the restarted walk is whole
        gone0 = REGISTRY.counter("list_continue_410_total").value
        sound = RestClient._request
        fired = []

        def writes_between_pages(self, method, path, body=None):
            if "continue=" in path and not fired:
                fired.append(path)
                for _ in range(12):
                    tenants.step()
            return sound(self, method, path, body)

        monkeypatch.setattr(RestClient, "_request", writes_between_pages)
        t0 = time.monotonic()
        items, rv = RestClient(server.address, cluster="*").list(RES, limit=4)
        done = time.monotonic()
        monkeypatch.setattr(RestClient, "_request", sound)
        assert len(fired) == 1
        assert REGISTRY.counter("list_continue_410_total").value == gone0 + 1
        assert ref.scope_mismatches(tenants.log(), None, rv,
                                    [ref.view(o) for o in items], t0, done,
                                    ) == ([], False)
        assert len(items) == len(tenants.bodies)
    finally:
        server.stop()


# ------------------------------------------------ the reference's teeth


def _log():
    """t0/a written at rv 10 then 20, t0/b created at 30 and deleted
    (acknowledged at rv <= 41), t0/c created at 50; all long before any
    read below (sent at 100 s)."""
    rng = random.Random(3)
    a1 = shape.new("a", rng, ["loc0"])
    a2 = shape.mutate(a1, rng)
    b1, c1 = shape.new("b", rng, ["loc0"]), shape.new("c", rng, ["loc0"])

    def rec(kind, name, body, t, rv):
        return {"kind": kind, "key": ["t0", name], "body": body, "sent": t,
                "acked": t + 0.01, "rv": rv}

    records = [rec("create", "a", a1, 1.0, 10), rec("update", "a", a2, 2.0, 20),
               rec("create", "b", b1, 3.0, 30), rec("delete", "b", None, 4.0, 41),
               rec("create", "c", c1, 5.0, 50)]
    return ref.WriteLog({}, records), {"a1": a1, "a2": a2, "b1": b1, "c1": c1}


def _item(name, rv, body):
    return ["t0", "default", name, rv, ref.digest(body)]


def _tooth(case: str):
    """(what the reference says of the sound answer, and of the broken one)."""
    log, b = _log()
    a, c = _item("a", 22, b["a2"]), _item("c", 50, b["c1"])
    if case == "a stale GET":
        return (ref.get_mismatches(log, ("t0", "a"), 200, a, 100.0, 100.1),
                ref.get_mismatches(log, ("t0", "a"), 200,
                                   _item("a", 10, b["a1"]), 100.0, 100.1))
    if case == "a LIST missing an acknowledged object":
        return (ref.scope_mismatches(log, "t0", 60, [a, c], 100.0, 100.1),
                ref.scope_mismatches(log, "t0", 60, [a], 100.0, 100.1))
    if case == "a LIST listing a deleted object":
        return (ref.scope_mismatches(log, "t0", 60, [a, c], 100.0, 100.1),
                ref.scope_mismatches(log, "t0", 60,
                                     [a, _item("b", 30, b["b1"]), c],
                                     100.0, 100.1))
    if case == "a page walk with a duplicate":
        sound = [{"rv": 60, "items": [a]}, {"rv": 60, "items": [c]}]
        twice = [{"rv": 60, "items": [a]}, {"rv": 60, "items": [a, c]}]
        return (ref.walk_mismatches(log, None, sound, 1, 100.0, 100.3),
                ref.walk_mismatches(log, None, twice, 2, 100.0, 100.3))
    # a watch opened at a LIST at rv 15: the update at 20, b's life and
    # c's create all follow it
    events = [["MODIFIED", "t0", "default", "a", 20, ref.digest(b["a2"]), 2.01],
              ["ADDED", "t0", "default", "b", 30, ref.digest(b["b1"]), 3.01],
              ["DELETED", "t0", "default", "b", 30, ref.digest(b["b1"]), 4.01],
              ["ADDED", "t0", "default", "c", 50, ref.digest(b["c1"]), 5.01]]
    sound = ref.watch_mismatches(log, "t0", 15, 1.5, events, 6.0), False
    if case == "a watch with a gap":
        return sound, (ref.watch_mismatches(log, "t0", 15, 1.5,
                                            events[:1] + events[2:], 6.0), False)
    if case == "a watch replaying the LIST's own resourceVersion":
        replay = [["ADDED", "t0", "default", "a", 10, ref.digest(b["a1"]), 1.9]]
        return sound, (ref.watch_mismatches(log, "t0", 15, 1.5,
                                            replay + events, 6.0), False)
    raise AssertionError(case)


@pytest.mark.parametrize("case,says", [
    ("a stale GET", "no longer admitted"),
    ("a LIST missing an acknowledged object", "missing from the list"),
    ("a LIST listing a deleted object", "its delete was acknowledged"),
    ("a page walk with a duplicate", "returned twice"),
    ("a watch with a gap", "no event at that rv (a gap)"),
    ("a watch replaying the LIST's own resourceVersion", "at or below the LIST's rv"),
])
def test_the_reference_reports(case, says):
    (sound, _u1), (broken, _u2) = _tooth(case)
    assert sound == []
    assert any(says in m for m in broken), broken


def test_an_answer_with_a_write_in_flight_is_admitted_and_counted():
    log, b = _log()
    # a GET that overlaps the update of a (sent 2.0, acknowledged 2.01)
    old, new = _item("a", 10, b["a1"]), _item("a", 20, b["a2"])
    assert ref.get_mismatches(log, ("t0", "a"), 200, old, 1.99, 2.005) == ([], True)
    assert ref.get_mismatches(log, ("t0", "a"), 200, new, 1.99, 2.005) == ([], True)
    # once the update is acknowledged only it is admitted, at its rv
    assert ref.get_mismatches(log, ("t0", "a"), 200, new, 2.02, 2.03) == ([], False)
    found, _ = ref.get_mismatches(log, ("t0", "a"), 200, _item("a", 19, b["a2"]),
                                  2.02, 2.03)
    assert any("below the acknowledged rv 20" in m for m in found)
    # a list that met the update in flight is pinned by the rv it states:
    # at 15 the body of before, at 25 the body after it, and no other
    assert ref.scope_mismatches(log, "t0", 15, [old], 1.99, 2.005) == ([], False)
    assert ref.scope_mismatches(log, "t0", 25, [new], 1.99, 2.005) == ([], False)
    for rv, item in ((25, old), (15, _item("a", 15, b["a2"]))):
        found, _ = ref.scope_mismatches(log, "t0", rv, [item], 1.99, 2.005)
        assert any("admitted" in m for m in found), (rv, found)
    # sent after the acknowledgement, a list at the rv of before is stale
    found, _ = ref.scope_mismatches(log, "t0", 15, [old], 2.5, 2.6)
    assert any("no longer admitted" in m for m in found)
    # a 404 is right only where the object may be gone
    assert ref.get_mismatches(log, ("t0", "b"), 404, None, 100.0, 100.1) == ([], False)
    found, _ = ref.get_mismatches(log, ("t0", "a"), 404, None, 100.0, 100.1)
    assert found and "404" in found[0]


def test_the_probes_of_one_write_are_judged_without_the_log():
    _log_, b = _log()
    body = b["a2"]
    sound = {"rv": 20,
             "get": {"status": 200, "view": _item("a", 21, body)},
             "list": {"status": 200, "rv": 40,
                      "items": [_item("a", 21, body), _item("c", 33, b["c1"])]}}
    assert ref.probe_mismatches(body, sound) == []
    assert shape.evidence_mismatches(
        body, shape.evidence(dict(body, status=None)), sound, ["loc0"]) != []  # no status yet
    stale = dict(sound, get={"status": 200, "view": _item("a", 10, b["a1"])})
    assert len(ref.probe_mismatches(body, stale)) == 2
    dropped = dict(sound, list={"status": 200, "rv": 40,
                                "items": [_item("c", 33, b["c1"])]})
    assert any("0 times" in m for m in ref.probe_mismatches(body, dropped))


# ------------------------------------------- counters and sections


def counters() -> dict:
    return {k: (v["count"] if isinstance(v, dict) else v)
            for k, v in REGISTRY.snapshot().items()
            if k.startswith(("read_", "list_cache_", "server_loop_self_seconds_kcp_read",
                             "server_loop_self_seconds_kcp_watch_open",
                             "server_loop_self_seconds_kcp_watch_close",
                             "server_loop_section_leaks", "store_fanout_plan_"))}


def settled() -> dict:
    """The counters once the ledger's 50 ms beat has published what the
    requests so far cost: two readings 120 ms apart that agree."""
    last = counters()
    for _ in range(40):
        time.sleep(0.12)
        now = counters()
        if now == last:
            return now
        last = now
    return last


def rise(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


SELF = "server_loop_self_seconds_kcp_"


@pytest.mark.parametrize("verb,n", [("get", 3), ("list", 2), ("page", 2),
                                    ("table", 1)])
def test_a_read_counts_once_on_its_verb_and_nowhere_else(srv, verb, n):
    tenants = Tenants(srv.address, seed=17)
    tenant = f"t-{verb}"
    names = [tenants.create(tenant) for _ in range(3)]
    client = RestClient(srv.address, cluster=tenant)
    before = settled()
    if verb == "get":
        for name in names:
            client.get(RES, name, NS)
    elif verb == "list":
        client.list(RES, NS, limit=0)
        client.list(RES, NS, parse_selector("group=load"), limit=0)
    elif verb == "page":
        assert len(client.list(RES, NS, limit=2)[0]) == 3  # two pages
    else:
        assert fetch(client, client._path(RES, NS), TABLE)[0] == 200
    got = rise(before, settled())
    assert got.pop(f"read_requests_total_{verb}") == n
    assert got.pop(f"read_request_seconds_{verb}") == n
    assert got.pop(f"read_request_seconds_{verb}_count") == n
    assert got.pop("read_response_bytes_total") > 1000 * n
    assert got.pop(SELF + f"read_{verb}") > 0
    if verb == "list":
        assert got.pop("list_cache_lookups_total") == 2
        got.pop("list_cache_hits_total", None)
    assert got == {}, got  # no other verb's counter, no other section, no leak


def test_a_watch_opens_and_closes_under_its_sections_and_ages_the_plan(srv):
    tenants = Tenants(srv.address, seed=23)
    tenant = "t-watch"
    tenants.create(tenant)
    before = settled()
    rv = int(RestClient(srv.address, cluster=tenant).list(RES, NS, limit=0)[1])
    got = asyncio.run(hold(RestClient(srv.address), tenant, rv, 0.15))
    tenants.create(tenant)  # a flush after the close: the plan is rebuilt
    after = rise(before, settled())
    assert got["events"] == []
    assert after.pop(SELF + "watch_open") > 0
    assert after.pop(SELF + "watch_close") > 0
    assert after.pop("store_fanout_plan_rebuilds_total", 0) >= 0
    after.pop("store_fanout_plan_watches_total", None)
    assert after.pop("read_requests_total_list") == 1
    assert "server_loop_section_leaks_total" not in after
    # a write while a watch is open rebuilds the plan over that watch
    b0 = settled()

    async def watched_write():
        client = RestClient(srv.address, cluster=tenant)
        w = client.watch(RES, None, since_rv=rv)
        w._ensure_started()
        await asyncio.sleep(0.1)
        await asyncio.get_running_loop().run_in_executor(
            None, tenants.create, tenant)
        evs = await w.next_batch(max_wait=1.0)
        w.close()
        return evs

    evs = asyncio.run(watched_write())
    assert evs and evs[-1].type == "ADDED"
    moved = rise(b0, settled())
    assert moved["store_fanout_plan_rebuilds_total"] >= 1
    assert moved["store_fanout_plan_watches_total"] >= 1
    assert "server_loop_section_leaks_total" not in moved
