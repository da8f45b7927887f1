"""Smart clients + zero-copy wire path (PR 13).

Covers the tentpole contracts:

- ``GET /ring`` serves the router's ring + epoch; ``POST /ring``
  republishes it and bumps the epoch (the elastic-topology handshake);
- a smart client computes HRW owners locally and goes DIRECT to the
  owning shard; responses are byte-identical to routed ones;
- a shard refuses a stale-ring direct request with a typed 410 carrying
  its epoch (``X-Kcp-Ring-Epoch``), and the smart client absorbs it
  with a ring re-fetch + one-shot router fallback — callers never see
  the move;
- a shard restarting on a NEW address (ring republished) converges:
  fallback first, direct to the new address after;
- the differential fuzz: the same seeded CRUD+watch workload through
  smart-direct clients and through router-only clients produces
  byte-identical final state and per-cluster event streams (the PR 6
  sharded-vs-monolith pattern, reused);
- the scatter wire path (``httpd.SCATTER``) is byte-identical to the
  join path on list bodies AND watch streams, toggled live.
"""

from __future__ import annotations

import asyncio
import hashlib
import http.client
import json
import random
import re
import socket
import time

import pytest

from kcp_tpu.client.smart import (
    RING_EPOCH_HEADER,
    SmartMultiClusterRestClient,
    SmartRestClient,
)
from kcp_tpu.server.rest import MultiClusterRestClient, RestClient
from kcp_tpu.server.server import Config
from kcp_tpu.server.threaded import ServerThread
from kcp_tpu.utils import errors
from kcp_tpu.utils.trace import REGISTRY

from helpers import shard_fleet

pytestmark = pytest.mark.filterwarnings("ignore::ResourceWarning")


def _counter(name: str) -> float:
    return REGISTRY.counter(name).value


def _cm(name, cluster, data, uid=None):
    obj = {"apiVersion": "v1", "kind": "ConfigMap",
           "metadata": {"name": name, "namespace": "default",
                        "clusterName": cluster},
           "data": data or {}}
    if uid:
        obj["metadata"]["uid"] = uid
    return obj


# ---------------------------------------------------------------------------
# /ring + direct routing
# ---------------------------------------------------------------------------


def test_ring_endpoint_and_epoch_bump():
    with shard_fleet(2) as (router, shards, ring):
        c = RestClient(router.address)
        doc = c._request("GET", "/ring")
        assert doc["epoch"] == 1
        assert [s["name"] for s in doc["shards"]] == ["s0", "s1"]
        assert [s["url"] for s in doc["shards"]] == \
            [t.address for t in shards]
        # republish (same spec): pools carry over, epoch bumps anyway —
        # the epoch is a change COUNTER, not a content hash
        spec = ",".join(f"s{i}={t.address}" for i, t in enumerate(shards))
        doc2 = c._request("POST", "/ring", {"shards": spec})
        assert doc2["epoch"] == 2
        assert c._request("GET", "/ring")["epoch"] == 2
        c.close()


def test_smart_client_goes_direct_with_byte_identical_responses():
    with shard_fleet(2) as (router, shards, ring):
        direct0 = _counter("smart_client_direct_total")
        sc = SmartRestClient(router.address, cluster="zz-a")
        made = sc.create("configmaps", _cm("one", "zz-a", {"k": "v"}))
        assert made["metadata"]["name"] == "one"
        got = sc.get("configmaps", "one", "default")
        assert got["data"] == {"k": "v"}
        assert _counter("smart_client_direct_total") > direct0
        # byte identity: the same GET routed vs direct (raw bodies)
        rc = RestClient(router.address, cluster="zz-a")
        path = ("/clusters/zz-a/api/v1/namespaces/default/"
                "configmaps/one")
        s_direct, _h1, b_direct = sc.request_raw("GET", path)
        s_routed, _h2, b_routed = rc.request_raw("GET", path)
        assert (s_direct, b_direct) == (s_routed, b_routed)
        # and the list body too
        lpath = "/clusters/zz-a/api/v1/namespaces/default/configmaps"
        _s1, _h3, lb_direct = sc.request_raw("GET", lpath)
        _s2, _h4, lb_routed = rc.request_raw("GET", lpath)
        assert hashlib.sha256(lb_direct).hexdigest() == \
            hashlib.sha256(lb_routed).hexdigest()
        # the direct request really skipped the router: it landed on the
        # owning shard's address, which serves it identically
        owner = shards[ring.owner_index("zz-a")]
        oc = RestClient(owner.address, cluster="zz-a")
        assert oc.get("configmaps", "one", "default") == got
        for c in (sc, rc, oc):
            c.close()


def test_stale_ring_gets_typed_410_and_smart_fallback_absorbs_it():
    with shard_fleet(2) as (router, shards, ring):
        cluster = "zz-b"
        idx = ring.owner_index(cluster)
        wrong = shards[1 - idx]
        # a stale-ring client talking straight to the WRONG shard: the
        # shard verifies HRW ownership and answers a typed 410 carrying
        # its ring epoch in the response headers — but ONLY for requests
        # that stamp the ring epoch (= direct smart-client traffic)
        raw = RestClient(wrong.address, cluster=cluster)
        path = (f"/clusters/{cluster}/api/v1/namespaces/default/"
                f"configmaps/nope")
        status, h, body = raw.request_raw(
            "GET", path, headers={RING_EPOCH_HEADER: "1"})
        assert status == 410
        doc = json.loads(body)
        assert doc["reason"] == "Expired"
        assert "ring mismatch" in doc["message"]
        assert {k.lower(): v for k, v in h.items()}.get(
            "x-kcp-ring-epoch") == "1"
        raw.close()
        # WITHOUT the stamp the same request is a plain 404 (routed
        # traffic through the router must never trip the check)
        raw2 = RestClient(wrong.address, cluster=cluster)
        with pytest.raises(errors.NotFoundError):
            raw2._request(
                "GET",
                f"/clusters/{cluster}/api/v1/namespaces/default/"
                f"configmaps/nope",
            )
        raw2.close()
        # a smart client whose ring is POISONED (owners swapped) never
        # surfaces the 410: one-shot fallback through the router + a
        # ring re-fetch, then back to direct
        sc = SmartRestClient(router.address, cluster=cluster)
        sc.create("configmaps", _cm("real", cluster, {"x": "1"}))
        ring_now, _epoch = sc._ring_snapshot()
        swapped = type(ring_now)(list(reversed(list(ring_now.shards))))
        # reversing changes indexes, not HRW ownership — poison by
        # remapping every shard name to the OTHER shard's url
        from kcp_tpu.sharding.ring import Shard

        a, b = ring_now.shards
        poisoned = type(ring_now)([Shard(a.name, b.url, a.replicas),
                                   Shard(b.name, a.url, b.replicas)])
        del swapped
        fb0 = _counter("smart_client_fallback_total")
        with sc._ring_state.lock:
            sc._ring_state.ring = poisoned
        got = sc.get("configmaps", "real", "default")
        assert got["data"] == {"x": "1"}
        assert _counter("smart_client_fallback_total") > fb0
        # the re-fetch repaired the ring: direct again, no fallback
        fb1 = _counter("smart_client_fallback_total")
        assert sc.get("configmaps", "real", "default") == got
        assert _counter("smart_client_fallback_total") == fb1
        sc.close()


def test_ring_change_shard_moves_to_new_address(tmp_path):
    from kcp_tpu.scenarios.topology import move_shard

    with shard_fleet(2, durable=True, root_dir=str(tmp_path)) as (
            router, shards, ring):
        cluster = "mv-a"
        idx = ring.owner_index(cluster)
        sc = SmartRestClient(router.address, cluster=cluster)
        sc.create("configmaps", _cm("pre", cluster, {"v": "0"}))
        old_addr = shards[idx].address
        moved = move_shard(shards, idx, router.address)
        assert moved.address != old_addr
        # the router's ring moved with it
        rc = RestClient(router.address)
        doc = rc._request("GET", "/ring")
        assert doc["epoch"] == 2
        assert doc["shards"][idx]["url"] == moved.address
        rc.close()
        # the smart client absorbs the move: first op falls back (its
        # ring still points at the dead address), then direct resumes
        # against the new one — and the WAL carried the data across
        fb0 = _counter("smart_client_fallback_total")
        assert sc.get("configmaps", "pre", "default")["data"] == {"v": "0"}
        sc.create("configmaps", _cm("post", cluster, {"v": "1"}))
        assert _counter("smart_client_fallback_total") > fb0
        ring_now, epoch = sc._ring_snapshot()
        assert epoch == 2
        assert ring_now.shards[idx].url == moved.address
        # direct to the NEW address, no further fallback
        fb1 = _counter("smart_client_fallback_total")
        assert sc.get("configmaps", "post", "default")["data"] == {"v": "1"}
        assert _counter("smart_client_fallback_total") == fb1
        sc.close()


def test_smart_client_parks_on_ringless_server():
    """Against a monolith (no /ring) a smart client IS a plain client:
    everything routes, nothing errors, no direct counter movement."""
    with ServerThread(Config(durable=False, install_controllers=False,
                             tls=False)) as srv:
        d0 = _counter("smart_client_direct_total")
        sc = SmartRestClient(srv.address, cluster="park")
        sc.create("configmaps", _cm("m", "park", {"a": "b"}))
        assert sc.get("configmaps", "m", "default")["data"] == {"a": "b"}
        assert _counter("smart_client_direct_total") == d0
        sc.close()


# ---------------------------------------------------------------------------
# differential fuzz: smart-direct vs router-only
# ---------------------------------------------------------------------------

_MASK_RV = re.compile(r'"resourceVersion": "\d+"')
_MASK_TS = re.compile(r'"creationTimestamp": "[^"]*"')


def _norm(obj: dict) -> str:
    s = json.dumps(obj)
    s = _MASK_RV.sub('"resourceVersion": "*"', s)
    return _MASK_TS.sub('"creationTimestamp": "*"', s)


def _workload(seed: int, clusters: list[str], steps: int):
    rng = random.Random(seed)
    live: dict[str, list[str]] = {}
    ops = []
    counter = 0
    for i in range(steps):
        cluster = rng.choice(clusters)
        names = live.setdefault(cluster, [])
        r = rng.random()
        if not names or r < 0.55:
            counter += 1
            name = f"obj-{counter}"
            ops.append(("create", cluster, name,
                        {"v": str(i)}, f"uid-{counter}"))
            names.append(name)
        elif r < 0.85:
            ops.append(("update", cluster, rng.choice(names),
                        {"v": f"u{i}"}, None))
        else:
            name = names.pop(rng.randrange(len(names)))
            ops.append(("delete", cluster, name, None, None))
    return ops


def _apply_ops(base, ops) -> None:
    for verb, cluster, name, data, _uid in ops:
        c = base.scoped(cluster)
        if verb == "create":
            c.create("configmaps", _cm(name, cluster, data, _uid))
        elif verb == "update":
            cur = c.get("configmaps", name, "default")
            cur["data"] = data
            c.update("configmaps", cur)
        else:
            c.delete("configmaps", name, "default")


def test_smart_vs_routed_differential_fuzz():
    """The same seeded CRUD+watch workload against two identical
    fleets — one driven smart-direct, one router-only: final states
    byte-identical (modulo per-store RV/timestamp stamps) and every
    cluster's watch event stream equal. The direct path must not be
    able to produce anything the routed path would not."""
    clusters = [f"df{i}" for i in range(8)]
    ops = _workload(29, clusters, 110)
    split = 60

    def run(router_addr, smart: bool):
        wc = (SmartMultiClusterRestClient(router_addr) if smart
              else MultiClusterRestClient(router_addr))
        _apply_ops(wc, ops[:split])
        events: dict[str, list] = {c: [] for c in clusters}

        async def phase2():
            # one PER-CLUSTER watch each (the smart client's watches go
            # direct to the owning shard; routed ones relay through the
            # router's zero-parse fast path)
            watches = {}
            for c in clusters:
                scoped = wc.scoped(c)
                _items, rv = scoped.list("configmaps", "default")
                watches[c] = scoped.watch("configmaps", "default",
                                          since_rv=rv)
            for w in watches.values():
                await w.next_batch(0.05)
            await asyncio.sleep(0.3)
            await asyncio.get_running_loop().run_in_executor(
                None, _apply_ops, wc, ops[split:])
            expected = len(ops) - split
            got = 0
            idle = 0
            while idle < 25:
                progressed = False
                for c, w in watches.items():
                    for ev in await w.next_batch(0.02):
                        events[c].append((ev.type, ev.name,
                                          _norm(ev.object)))
                        got += 1
                        progressed = True
                idle = 0 if progressed else idle + 1
                if got >= expected and not progressed:
                    idle = max(idle, 20)
            for w in watches.values():
                w.close()

        asyncio.run(phase2())
        items, _rv = wc.list("configmaps")
        state = {(o["metadata"]["clusterName"], o["metadata"]["name"]):
                 _norm(o) for o in items}
        wc.close()
        return state, events

    with shard_fleet(3) as (router, _shards, _ring):
        routed_state, routed_events = run(router.address, smart=False)
    with shard_fleet(3) as (router, _shards, _ring):
        d0 = _counter("smart_client_direct_total")
        smart_state, smart_events = run(router.address, smart=True)
        assert _counter("smart_client_direct_total") > d0

    assert smart_state == routed_state
    for c in clusters:
        assert smart_events[c] == routed_events[c], f"cluster {c} diverged"


# ---------------------------------------------------------------------------
# scatter wire path: byte identity
# ---------------------------------------------------------------------------


def _http_get_raw(address: str, path: str) -> tuple[int, bytes]:
    host, port = address.split("//", 1)[1].rsplit(":", 1)
    conn = http.client.HTTPConnection(host, int(port), timeout=30)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _read_watch_lines(address: str, path: str, n: int,
                      timeout: float = 20.0) -> list[bytes]:
    """Raw chunked-stream reader: the first ``n`` newline-terminated
    payload lines exactly as framed on the wire."""
    host, port = address.split("//", 1)[1].rsplit(":", 1)
    s = socket.create_connection((host, int(port)), timeout=timeout)
    try:
        s.sendall(f"GET {path} HTTP/1.1\r\nHost: {host}\r\n"
                  f"Connection: close\r\n\r\n".encode())
        buf = b""
        deadline = time.monotonic() + timeout
        while b"\r\n\r\n" not in buf:
            buf += s.recv(65536)
        buf = buf.split(b"\r\n\r\n", 1)[1]
        payload = b""
        while payload.count(b"\n") < n:
            if time.monotonic() > deadline:
                raise TimeoutError(f"watch lines: {payload!r}")
            # strip every complete chunk already buffered
            progressed = True
            while progressed:
                progressed = False
                if b"\r\n" in buf:
                    size_line, rest = buf.split(b"\r\n", 1)
                    size = int(size_line.strip() or b"0", 16)
                    if size == 0:
                        return payload.split(b"\n")[:n]
                    if len(rest) >= size + 2:
                        payload += rest[:size]
                        buf = rest[size + 2:]
                        progressed = True
            if payload.count(b"\n") >= n:
                break
            data = s.recv(65536)
            if not data:
                break
            buf += data
        return payload.split(b"\n")[:n]
    finally:
        s.close()


def test_wire_scatter_byte_identity(monkeypatch):
    """The scatter-write path (``httpd.SCATTER``, what a server runs) must be
    byte-identical to the join path on list bodies and watch streams —
    toggled live against ONE server so even RVs and timestamps match."""
    with ServerThread(Config(durable=False, install_controllers=False,
                             tls=False)) as srv:
        wc = MultiClusterRestClient(srv.address)
        big = "x" * 40000  # one span big enough to take the scatter arm
        for i in range(30):
            wc.create("configmaps", _cm(
                f"sc-{i}", "wire", {"v": str(i), "pad": big if i % 7 == 0
                                    else "small"}))
        _items, rv0 = wc.scoped("wire").list("configmaps", "default")
        for i in range(12):
            wc.create("configmaps", _cm(f"late-{i}", "wire", {"v": "L"}))
        lpath = "/clusters/wire/api/v1/namespaces/default/configmaps"
        wpath = (lpath + f"?watch=true&resourceVersion={rv0}")

        from kcp_tpu.server import httpd

        assert httpd.SCATTER
        st1, body_scatter = _http_get_raw(srv.address, lpath)
        lines_scatter = _read_watch_lines(srv.address, wpath, 12)
        monkeypatch.setattr(httpd, "SCATTER", False)
        st2, body_join = _http_get_raw(srv.address, lpath)
        lines_join = _read_watch_lines(srv.address, wpath, 12)

        assert st1 == st2 == 200
        assert hashlib.sha256(body_scatter).hexdigest() == \
            hashlib.sha256(body_join).hexdigest()
        assert lines_scatter == lines_join
        assert len(lines_scatter) == 12
        # and the scatter path actually exercised span writes
        assert _counter("wire_spans_written_total") > 0
        wc.close()
