"""Encode-once serving: cached vs uncached byte-identity.

The encode-once stack (``encode_cache=True``: per-snapshot byte cache,
per-bucket list spans, RV-keyed list bodies, shared watch-event lines)
must serve wires byte-identical to the per-call ``json.dumps`` path
(``encode_cache=False``). The differential fuzz drives two full
RestHandler+LogicalStore stacks side-by-side through random REST traffic
and compares every observable: response status + body bytes for lists
(repeated at the same RV, churned, selector-filtered, namespaced,
wildcard), single GETs, status-subresource reads, and watch streams
(live ADDED/MODIFIED/DELETED, selector-rewrite events, ``since_rv``
replay) — including under an active ``encode.cache`` fault schedule that
force-drops cached entries mid-serve.

Also pins the cache's safety contract (a cached body never reflects a
later write) and the RestWatch chunk reassembly satellite (multi-event
chunks decoded once and split, surviving arbitrary chunk boundaries).
"""

import asyncio
import json
import random

import pytest

from kcp_tpu import faults
from kcp_tpu.apis.scheme import default_scheme
from kcp_tpu.server.handler import RestHandler
from kcp_tpu.server.httpd import Request
from kcp_tpu.server.rest import RestWatch
from kcp_tpu.store.store import LogicalStore
from kcp_tpu.utils.trace import REGISTRY

CLUSTERS = ("c0", "c1", "c2")
NAMESPACES = ("ns0", "ns1")
NAMES = tuple(f"n{i}" for i in range(6))
LABELS = [None, {"team": "a"}, {"team": "b"},
          {"team": "a", "tier": "web"}, {"tier": "db"}]


def _req(method, path, query=None, body=None):
    payload = b"" if body is None else json.dumps(body).encode()
    return Request(method, path, query or {}, {}, payload)


def _cm(name, ns, v, labels=None, finalizers=None):
    meta = {"name": name, "namespace": ns, "uid": f"uid-{name}-{ns}"}
    if labels:
        meta["labels"] = dict(labels)
    if finalizers:
        meta["finalizers"] = list(finalizers)
    return {"apiVersion": "v1", "kind": "ConfigMap", "metadata": meta,
            "data": {"v": v}}


class _Sink:
    """The StreamResponse encode surface without a socket: json sends
    serialize exactly like httpd.StreamResponse, the raw send takes the
    relay's pre-encoded lines — so comparing accumulated bytes between
    the cached (raw) and uncached (json) stacks proves wire identity."""

    def __init__(self):
        self.data = b""

    async def send_json(self, obj):
        self.data += json.dumps(obj).encode() + b"\n"

    async def send_json_many(self, objs):
        self.data += b"".join(json.dumps(o).encode() + b"\n" for o in objs)

    async def send_raw_many(self, lines):
        self.data += b"".join(lines)


class _Stack:
    def __init__(self, encode_cache: bool):
        self.store = LogicalStore(indexed=True, encode_cache=encode_cache,
                                  clock=lambda: 1_700_000_000.0)
        self.handler = RestHandler(self.store, default_scheme(),
                                   admission=None)


class _Pair:
    """The same REST request executed against both stacks, every
    response compared byte-for-byte."""

    def __init__(self):
        self.stacks = (_Stack(True), _Stack(False))

    async def call(self, method, path, query=None, body=None):
        out = []
        for st in self.stacks:
            resp = await st.handler(_req(method, path, query, body))
            out.append((resp.status, resp.body))
        (sa, ba), (sb, bb) = out
        assert sa == sb, (method, path, query, sa, sb, ba, bb)
        assert ba == bb, (method, path, query, sa, ba, bb)
        return out[0]

    def path(self, cluster, ns=None, name=None, sub=None):
        p = f"/clusters/{cluster}/api/v1"
        if ns:
            p += f"/namespaces/{ns}"
        p += "/configmaps"
        if name:
            p += f"/{name}"
        if sub:
            p += f"/{sub}"
        return p


def _rand_op(pair, rng, counter):
    cluster = rng.choice(CLUSTERS)
    ns = rng.choice(NAMESPACES)
    name = rng.choice(NAMES)
    roll = rng.random()
    if roll < 0.4:
        counter[0] += 1
        obj = _cm(name, ns, str(counter[0]), rng.choice(LABELS),
                  ["t.dev/hold"] if rng.random() < 0.15 else None)
        obj["metadata"]["uid"] = f"uid-{counter[0]}"
        return ("POST", pair.path(cluster, ns), None, obj)
    if roll < 0.75:
        # update from the cached stack's current state (stacks agree
        # inductively); relabels force the selector-rewrite fan-out
        obj = _cm(name, ns, f"u{counter[0]}", rng.choice(LABELS))
        counter[0] += 1
        if rng.random() < 0.25:
            obj["status"] = {"phase": rng.choice(["Ready", "Pending"])}
            return ("PUT", pair.path(cluster, ns, name, "status"), None, obj)
        return ("PUT", pair.path(cluster, ns, name), None, obj)
    return ("DELETE", pair.path(cluster, ns, name), None, None)


async def _fuzz(seed, steps=220):
    rng = random.Random(seed)
    pair = _Pair()
    counter = [0]
    for _step in range(steps):
        method, path, query, body = _rand_op(pair, rng, counter)
        # PUTs need the live resourceVersion: read it through the
        # handler (GETs are compared too) and graft it onto the body
        if method == "PUT" and body is not None:
            status, raw = await pair.call("GET", path.removesuffix("/status"))
            if status != 200:
                continue
            current = json.loads(raw)
            body["metadata"]["resourceVersion"] = (
                current["metadata"]["resourceVersion"])
            body["metadata"]["uid"] = current["metadata"]["uid"]
        await pair.call(method, path, query, body)
        if rng.random() < 0.25:
            cluster = rng.choice(("*",) + CLUSTERS)
            q = {}
            if rng.random() < 0.5:
                q["labelSelector"] = [rng.choice(
                    ["team=a", "team!=a", "tier in (web,db)", "!team"])]
            ns = rng.choice((None,) + NAMESPACES)
            lp = pair.path(cluster, ns)
            # twice at the same RV: the second serve must come out of
            # the RV-keyed body cache on the cached stack, byte-equal
            await pair.call("GET", lp, q)
            await pair.call("GET", lp, q)
        if rng.random() < 0.15:
            await pair.call(
                "GET", pair.path(rng.choice(CLUSTERS), rng.choice(NAMESPACES),
                                 rng.choice(NAMES)))
    # final exhaustive sweep
    for cluster in ("*",) + CLUSTERS:
        for ns in (None,) + NAMESPACES:
            await pair.call("GET", pair.path(cluster, ns))
    for st in pair.stacks:
        st.store.close()
        st.handler.close()


@pytest.mark.parametrize("seed", [7, 23, 91])
def test_rest_serving_byte_identical_fuzz(seed):
    asyncio.run(_fuzz(seed))


def test_rest_serving_byte_identical_under_cache_faults():
    """encode.cache drops force mid-serve re-encodes; the wire must not
    change by a byte, and the drops must actually fire."""
    faults.install(faults.FaultInjector("encode.cache:drop=0.4", seed=5))
    try:
        before = REGISTRY.counter("fault_injected_encode_cache_total").value
        asyncio.run(_fuzz(13, steps=120))
        fired = (REGISTRY.counter("fault_injected_encode_cache_total").value
                 - before)
        assert fired > 0, "encode.cache fault schedule never fired"
    finally:
        faults.clear()


async def _watch_stream_bytes(seed):
    rng = random.Random(seed)
    pair = _Pair()
    specs = [
        ({}, None),                                   # everything
        ({"labelSelector": ["team=a"]}, None),        # eq fast path + rewrites
        ({"labelSelector": ["team in (a,b),tier!=db"]}, None),
        ({}, "ns0"),                                  # namespaced scope
    ]
    sinks = {0: [], 1: []}
    tasks = []
    for si, st in enumerate(pair.stacks):
        for q, ns in specs:
            query = dict(q)
            query["watch"] = ["true"]
            p = "/clusters/*/api/v1"
            if ns:
                p += f"/namespaces/{ns}"
            p += "/configmaps"
            stream = await st.handler(_req("GET", p, query))
            sink = _Sink()
            sinks[si].append(sink)
            tasks.append(asyncio.ensure_future(stream.producer(sink)))
    await asyncio.sleep(0.01)  # all producers subscribed

    counter = [0]
    for _step in range(120):
        method, path, query, body = _rand_op(pair, rng, counter)
        if method == "PUT" and body is not None:
            status, raw = await pair.call("GET", path.removesuffix("/status"))
            if status != 200:
                continue
            current = json.loads(raw)
            body["metadata"]["resourceVersion"] = (
                current["metadata"]["resourceVersion"])
            body["metadata"]["uid"] = current["metadata"]["uid"]
        await pair.call(method, path, query, body)
        if _step % 16 == 15:
            await asyncio.sleep(0)  # let the relays drain
    # drain everything, then close the stores to end the producers
    for _ in range(3):
        await asyncio.sleep(0.01)
    for st in pair.stacks:
        st.store.close()
    await asyncio.gather(*tasks, return_exceptions=True)
    for i, (cached, uncached) in enumerate(zip(sinks[0], sinks[1])):
        assert cached.data == uncached.data, f"watch stream {i} diverged"
    assert any(s.data for s in sinks[0]), "streams delivered nothing"
    for st in pair.stacks:
        st.handler.close()
    return pair


@pytest.mark.parametrize("seed", [3, 17])
def test_watch_stream_bytes_identical(seed):
    asyncio.run(_watch_stream_bytes(seed))


def test_watch_stream_bytes_identical_under_cache_faults():
    faults.install(faults.FaultInjector("encode.cache:drop=0.3", seed=9))
    try:
        asyncio.run(_watch_stream_bytes(29))
    finally:
        faults.clear()


async def _since_rv_replay_bytes():
    pair = _Pair()
    # scripted history: creates, a label flip (selector rewrite), a
    # status write, a finalizer-held delete, a real delete
    for st in pair.stacks:
        s = st.store
        s.create("configmaps", "c0", _cm("a", "ns0", "1", {"team": "a"}))
        s.create("configmaps", "c0", _cm("b", "ns0", "2", {"team": "b"}))
        obj = s.get("configmaps", "c0", "b", "ns0")
        obj["metadata"]["labels"] = {"team": "a"}
        s.update("configmaps", "c0", obj, "ns0")
        obj = s.get("configmaps", "c0", "a", "ns0")
        obj["status"] = {"phase": "Ready"}
        s.update_status("configmaps", "c0", obj, "ns0")
        s.delete("configmaps", "c0", "a", "ns0")
    for since in (0, 1, 3):
        for q in ({}, {"labelSelector": ["team=a"]}):
            outs = []
            for st in pair.stacks:
                query = dict(q)
                query["watch"] = ["true"]
                query["resourceVersion"] = [str(since)]
                query["timeoutSeconds"] = ["0.3"]
                stream = await st.handler(
                    _req("GET", "/clusters/*/api/v1/configmaps", query))
                sink = _Sink()
                await stream.producer(sink)
                outs.append(sink.data)
            assert outs[0] == outs[1], (since, q)
            assert since > 3 or outs[0], "replay produced nothing"
    for st in pair.stacks:
        st.store.close()
        st.handler.close()


def test_since_rv_replay_bytes_identical():
    asyncio.run(_since_rv_replay_bytes())


def test_cached_body_never_reflects_later_write():
    """Mutation safety: bytes handed out for a snapshot stay frozen; the
    write replaces the snapshot, so the next encode sees the new state
    and the old bytes still parse to the old state."""
    s = LogicalStore(indexed=True, encode_cache=True)
    s.create("configmaps", "t", _cm("x", "d", "old"))
    snap = s.get_snapshot("configmaps", "t", "x", "d")
    b1 = s.encode_obj(snap)
    obj = s.get("configmaps", "t", "x", "d")
    obj["data"] = {"v": "new"}
    s.update("configmaps", "t", obj, "d")
    b2 = s.encode_obj(s.get_snapshot("configmaps", "t", "x", "d"))
    assert json.loads(b1)["data"] == {"v": "old"}
    assert json.loads(b2)["data"] == {"v": "new"}
    # the retained old snapshot still serves its own (old) bytes
    assert s.encode_obj(snap) == b1
    s.close()


def test_rv_keyed_list_cache_invalidates_on_write():
    async def main():
        st = _Stack(True)
        st.store.create("configmaps", "t", _cm("x", "d", "1"))
        r1 = await st.handler(_req("GET", "/clusters/t/api/v1/configmaps"))
        r2 = await st.handler(_req("GET", "/clusters/t/api/v1/configmaps"))
        assert r1.body == r2.body  # same RV: served from the body cache
        obj = st.store.get("configmaps", "t", "x", "d")
        obj["data"] = {"v": "2"}
        st.store.update("configmaps", "t", obj, "d")
        r3 = await st.handler(_req("GET", "/clusters/t/api/v1/configmaps"))
        assert r3.body != r1.body
        assert json.loads(r3.body)["items"][0]["data"] == {"v": "2"}
        st.store.close()
        st.handler.close()

    asyncio.run(main())


def test_encode_cache_metrics_count_hits_and_misses():
    hits0 = REGISTRY.counter("encode_cache_hits_total").value
    miss0 = REGISTRY.counter("encode_cache_misses_total").value
    shared0 = REGISTRY.counter("encode_cache_bytes_shared_total").value
    s = LogicalStore(indexed=True, encode_cache=True)
    s.create("configmaps", "t", _cm("x", "d", "1"))
    snap = s.get_snapshot("configmaps", "t", "x", "d")
    b = s.encode_obj(snap)
    assert REGISTRY.counter("encode_cache_misses_total").value == miss0 + 1
    assert s.encode_obj(snap) is b
    assert REGISTRY.counter("encode_cache_hits_total").value == hits0 + 1
    assert (REGISTRY.counter("encode_cache_bytes_shared_total").value
            == shared0 + len(b))
    s.close()


def test_encode_disabled_keeps_plain_dumps():
    s = LogicalStore(indexed=True, encode_cache=False)
    assert not s.encode_cache_enabled
    s.create("configmaps", "t", _cm("x", "d", "1"))
    snap = s.get_snapshot("configmaps", "t", "x", "d")
    assert s.encode_obj(snap) == json.dumps(snap).encode()
    assert not s._enc_bytes  # nothing cached when disabled
    s.close()


# ------------------------------------------------- RestWatch reassembly


def _watch_lines(n=3):
    lines = []
    for i in range(1, n + 1):
        lines.append(json.dumps({
            "type": "ADDED",
            "object": {"metadata": {"name": f"obj-é{i}",
                                    "clusterName": "c", "namespace": "ns",
                                    "resourceVersion": str(i)}},
        }, ensure_ascii=False))
    return lines


def _drain_events(rw):
    out = []
    while not rw._events.empty():
        out.append(rw._events.get_nowait())
    return out


def test_restwatch_multi_event_chunk_single_split():
    """A relay burst (send_raw_many/send_json_many) arrives as ONE chunk
    holding many newline-terminated events: one decode, one split."""
    rw = RestWatch("127.0.0.1", 1, "/w", "configmaps")
    chunk = ("\n".join(_watch_lines(3)) + "\n").encode()
    rw._feed(chunk)
    evs = _drain_events(rw)
    assert [(e.type, e.name, e.rv) for e in evs] == [
        ("ADDED", "obj-é1", 1),
        ("ADDED", "obj-é2", 2),
        ("ADDED", "obj-é3", 3),
    ]
    assert rw._buf == ""


def test_restwatch_chunks_survive_arbitrary_boundaries():
    """Every possible chunk boundary — including ones splitting a
    multi-byte UTF-8 sequence — reassembles the same events."""
    payload = ("\n".join(_watch_lines(2)) + "\n").encode()
    for cut in range(1, len(payload)):
        rw = RestWatch("127.0.0.1", 1, "/w", "configmaps")
        rw._feed(payload[:cut])
        rw._feed(payload[cut:])
        evs = _drain_events(rw)
        assert [(e.name, e.rv) for e in evs] == [
            ("obj-é1", 1), ("obj-é2", 2)], f"boundary {cut}"


def test_restwatch_partial_line_carries_over():
    rw = RestWatch("127.0.0.1", 1, "/w", "configmaps")
    line = _watch_lines(1)[0]
    rw._feed(line[:10].encode())
    assert _drain_events(rw) == []
    rw._feed((line[10:] + "\n").encode())
    evs = _drain_events(rw)
    assert [(e.name, e.rv) for e in evs] == [("obj-é1", 1)]
