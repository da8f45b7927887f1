"""What changes hands at the store, and what does not (PR 32).

The store copies an object once per hand-over, with ``tree_copy``, and
successive snapshots of one object share the subtrees a write leaves
alone. None of that may show: every public verb still returns a
private, mutable, plain copy; nothing of a caller's argument is aliased
into the store; events, lists and informer caches keep sharing the
stored snapshot's identity; an old snapshot still reads what it read
before the write; and the WAL and the wire carry byte for byte what a
``copy.deepcopy``-built snapshot would. Each case runs with and without
the sanitizer (``KCP_SANITIZE``), under which every committed snapshot
is frozen and an in-place mutation raises at its line.
"""

import ast
import asyncio
import copy
import json

import pytest

from kcp_tpu.analysis import sanitize
from kcp_tpu.analysis.base import SourceFile, parse_waivers
from kcp_tpu.analysis.cow import CowChecker
from kcp_tpu.apis.scheme import default_scheme
from kcp_tpu.client import Client, Informer
from kcp_tpu.server.handler import RestHandler
from kcp_tpu.server.httpd import Request
from kcp_tpu.store import LogicalStore

RES, CL, NS, NAME = "deployments.apps", "t0", "default", "web"
VERBS = ("create", "update", "update_status")


@pytest.fixture(params=[False, True], ids=["plain", "sanitized"])
def sanitized(request):
    was = sanitize._ENABLED
    sanitize.enable(request.param)
    yield request.param
    sanitize._ENABLED = was


def _store(**kw) -> LogicalStore:
    store = LogicalStore(indexed=True, encode_cache=True, **kw)
    assert store._sanitize == sanitize.enabled()
    return store


def _body(replicas: int = 3) -> dict:
    return {
        "apiVersion": "apps/v1", "kind": "Deployment",
        "metadata": {"name": NAME, "namespace": NS,
                     "labels": {"app": "web", "kcp.dev/cluster": "east"},
                     "annotations": {"rev": "1"}},
        "spec": {"replicas": replicas,
                 "template": {"spec": {"containers": [
                     {"name": "c", "image": "img:1",
                      "env": [{"name": "A", "value": "1"}]}],
                     "tolerations": [{"key": "k", "operator": "Exists"}]}}},
    }


def _status(n: int) -> dict:
    return {"replicas": n, "readyReplicas": n, "observedGeneration": 1,
            "conditions": [{"type": "Available", "status": "True"}]}


def _seed(store: LogicalStore) -> None:
    """An object with a spec and a status, two writes old."""
    store.create(RES, CL, _body())
    with_status = store.get(RES, CL, NAME, NS)
    with_status["status"] = _status(3)
    store.update_status(RES, CL, with_status)


def _argument(store: LogicalStore, verb: str) -> dict:
    if verb == "create":
        return _body()
    arg = store.get(RES, CL, NAME, NS)
    if verb == "update":
        arg["spec"]["replicas"] = 5
        arg["spec"]["template"]["spec"]["containers"][0]["env"][0]["value"] = "2"
        arg["metadata"]["annotations"]["rev"] = "2"
    else:
        arg["status"] = _status(5)
    return arg


def _write(store: LogicalStore, verb: str, arg: dict) -> dict:
    return getattr(store, verb)(RES, CL, arg)


def _scribble(obj) -> None:
    """Mutate a private object in every container it holds."""
    if isinstance(obj, dict):
        for v in list(obj.values()):
            _scribble(v)
        for k in list(obj):
            if not isinstance(obj[k], (dict, list)):
                obj[k] = "scribbled"
        obj["scribbled"] = True
    elif isinstance(obj, list):
        for v in obj:
            _scribble(v)
        obj.append("scribbled")


def _plain(obj) -> dict:
    return sanitize.thaw(obj)


def _assert_plain_private(obj, snap) -> None:
    """``obj`` is plain ``dict``/``list`` all the way down and shares no
    container with the stored snapshot."""
    def containers(o, out):
        if isinstance(o, (dict, list)):
            out.append(o)
            for v in (o.values() if isinstance(o, dict) else o):
                containers(v, out)
        return out

    stored = {id(c) for c in containers(snap, [])}
    for c in containers(obj, []):
        assert type(c) in (dict, list)
        assert id(c) not in stored


async def _observers(store: LogicalStore):
    """A watch and a running informer: the two consumers that share the
    stored snapshots."""
    informer = Informer(Client(store, CL), RES)
    await informer.start()
    return store.watch(RES), informer


async def _settled(informer: Informer) -> dict:
    for _ in range(50):
        await asyncio.sleep(0)
    return informer.get(CL, NAME, NS)


# ---------------------------------------------------------------------------
# nobody outside can reach what the store holds
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("verb", VERBS)
def test_mutating_argument_or_result_leaves_every_shared_view_alone(
        verb, sanitized):
    async def main():
        store = _store()
        if verb != "create":
            _seed(store)
        watch, informer = await _observers(store)
        watch.drain()
        arg = _argument(store, verb)
        sent = copy.deepcopy(arg)
        result = _write(store, verb, arg)
        snap = store.get_snapshot(RES, CL, NAME, NS)
        held = copy.deepcopy(_plain(snap))

        # the result is a private, mutable, plain copy of the snapshot
        assert result == held and arg == sent
        _assert_plain_private(result, snap)
        _assert_plain_private(arg, snap)
        _scribble(arg)
        _scribble(result)

        ev = watch.drain()[-1]
        cached = await _settled(informer)
        # one snapshot, shared by identity, and it never noticed
        assert ev.object is snap and cached is snap
        assert store.get_snapshot(RES, CL, NAME, NS) is snap
        assert _plain(snap) == held
        assert store.list(RES, CL)[0][0] is snap
        await informer.stop()

    asyncio.run(main())


def test_mutating_gets_result_leaves_every_shared_view_alone(sanitized):
    async def main():
        store = _store()
        watch, informer = await _observers(store)
        _seed(store)
        snap = store.get_snapshot(RES, CL, NAME, NS)
        held = copy.deepcopy(_plain(snap))
        got = store.get(RES, CL, NAME, NS)
        assert got == held
        _assert_plain_private(got, snap)
        _scribble(got)
        via_client = Client(store, CL).get(RES, NAME, NS)
        _assert_plain_private(via_client, snap)
        _scribble(via_client)
        assert watch.drain()[-1].object is snap
        assert await _settled(informer) is snap
        assert _plain(snap) == held
        await informer.stop()

    asyncio.run(main())


@pytest.mark.parametrize("verb", VERBS)
def test_snapshot_verbs_return_the_stored_identity_and_alias_nothing(
        verb, sanitized):
    store = _store()
    if verb != "create":
        _seed(store)
    client = Client(store, CL)
    arg = _argument(store, verb)
    sent = copy.deepcopy(arg)
    written = getattr(client, verb + "_snapshot")(RES, arg)
    snap = store.get_snapshot(RES, CL, NAME, NS)
    assert written is snap is client.get_snapshot(RES, NAME, NS)
    assert arg == sent
    _assert_plain_private(arg, snap)
    held = copy.deepcopy(_plain(snap))
    _scribble(arg)
    assert _plain(snap) == held
    if sanitized:
        with pytest.raises(sanitize.ContractViolation):
            written["metadata"]["labels"]["x"] = "y"


# ---------------------------------------------------------------------------
# successive snapshots share what a write left alone, and only that
# ---------------------------------------------------------------------------


def test_after_a_status_update_the_old_snapshot_reads_as_before(sanitized):
    store = _store()
    _seed(store)
    watch = store.watch(RES)
    old = store.get_snapshot(RES, CL, NAME, NS)
    before = copy.deepcopy(_plain(old))
    arg = _argument(store, "update_status")
    arg["spec"]["replicas"] = 99  # a status write takes the status alone
    store.update_status(RES, CL, arg)
    new = store.get_snapshot(RES, CL, NAME, NS)
    ev = watch.drain()[-1]
    assert ev.old_object is old and ev.object is new and new is not old
    assert _plain(old) == before
    assert old["status"] == _status(3) and new["status"] == _status(5)
    assert (old["metadata"]["resourceVersion"]
            != new["metadata"]["resourceVersion"])
    assert old["metadata"]["generation"] == new["metadata"]["generation"] == 1
    assert new["spec"]["replicas"] == 3
    if not sanitized:  # freezing re-wraps; plain stores share by identity
        assert new["spec"] is old["spec"]
        assert new["metadata"] is not old["metadata"]
        assert new["metadata"]["labels"] is old["metadata"]["labels"]
    assert new["status"] is not arg["status"]


def test_after_a_spec_update_the_old_snapshot_keeps_its_spec(sanitized):
    store = _store()
    _seed(store)
    watch = store.watch(RES)
    old = store.get_snapshot(RES, CL, NAME, NS)
    before = copy.deepcopy(_plain(old))
    arg = _argument(store, "update")
    arg["status"] = {"replicas": 77}  # not writable through update
    store.update(RES, CL, arg)
    new = store.get_snapshot(RES, CL, NAME, NS)
    ev = watch.drain()[-1]
    assert ev.old_object is old and ev.object is new
    assert _plain(old) == before
    assert old["spec"]["replicas"] == 3 and new["spec"]["replicas"] == 5
    assert old["metadata"]["annotations"] == {"rev": "1"}
    assert old["metadata"]["generation"] == 1
    assert new["metadata"]["generation"] == 2
    assert new["status"] == old["status"] == _status(3)
    if not sanitized:
        assert new["status"] is old["status"]
        assert new["spec"] is not old["spec"] and new["spec"] is not arg["spec"]


def test_a_delete_waiting_on_finalizers_shares_all_but_metadata(sanitized):
    store = _store()
    body = _body()
    body["metadata"]["finalizers"] = ["example.dev/hold"]
    store.create(RES, CL, body)
    old = store.get_snapshot(RES, CL, NAME, NS)
    before = copy.deepcopy(_plain(old))
    store.delete(RES, CL, NAME, NS)
    new = store.get_snapshot(RES, CL, NAME, NS)
    assert _plain(old) == before and "deletionTimestamp" not in old["metadata"]
    assert new["metadata"]["deletionTimestamp"]
    assert list(new["metadata"]) == [*old["metadata"], "deletionTimestamp"]
    assert (new["metadata"]["resourceVersion"]
            != old["metadata"]["resourceVersion"])
    if not sanitized:
        assert new["spec"] is old["spec"]
    # releasing the finalizer through the sanctioned path completes it
    fresh = store.get(RES, CL, NAME, NS)
    fresh["metadata"]["finalizers"] = []
    store.update(RES, CL, fresh)
    assert not store.list(RES, CL)[0]


# ---------------------------------------------------------------------------
# the log and the wire carry what a deepcopy-built snapshot would hold
# ---------------------------------------------------------------------------


def _by_hand(verb: str, old: dict | None, arg: dict, new_meta: dict) -> dict:
    """The snapshot as the ``copy.deepcopy``-built store made it, key
    order included; ``new_meta`` supplies what only the store can know
    (uid, timestamps, resourceVersion)."""
    if verb == "create":
        want = copy.deepcopy(arg)
        want["metadata"].update(
            namespace=NS, clusterName=CL, uid=new_meta["uid"],
            creationTimestamp=new_meta["creationTimestamp"], generation=1,
            resourceVersion=new_meta["resourceVersion"])
        return want
    old = copy.deepcopy(old)
    if verb == "update":
        want = copy.deepcopy(arg)
        want["status"] = old["status"]
        want["metadata"].update(
            uid=old["metadata"]["uid"],
            creationTimestamp=old["metadata"]["creationTimestamp"],
            clusterName=CL, namespace=NS, name=NAME)
        want["metadata"]["generation"] = old["metadata"]["generation"] + 1
    else:
        want = old
        want["status"] = copy.deepcopy(arg["status"])
    want["metadata"]["resourceVersion"] = new_meta["resourceVersion"]
    return want


@pytest.mark.parametrize("verb", VERBS)
def test_wal_record_and_watch_frame_are_those_of_a_deepcopy_built_snapshot(
        verb, sanitized, tmp_path):
    path = str(tmp_path / "wal.jsonl")
    store = _store(wal_path=path, wal_backend="json")
    if verb != "create":
        _seed(store)
    watch = store.watch(RES)
    old = (_plain(store.get_snapshot(RES, CL, NAME, NS))
           if verb != "create" else None)
    arg = _argument(store, verb)
    result = _write(store, verb, arg)
    want = _by_hand(verb, old, arg, result["metadata"])
    dumped = json.dumps(want)  # dicts keep their order: bytes, not values

    assert json.dumps(result) == dumped
    ev = watch.drain()[-1]
    frame = json.loads(store.encode_event(ev))
    assert frame["type"] == ("ADDED" if verb == "create" else "MODIFIED")
    assert json.dumps(frame["object"]) == dumped
    assert json.dumps(json.loads(store.encode_obj(ev.object))) == dumped
    store.close()
    with open(path) as fh:
        rec = json.loads(fh.readlines()[-1])
    assert rec["op"] == "put" and rec["key"] == [RES, CL, NS, NAME]
    assert json.dumps(rec["obj"]) == dumped
    # and a restart holds the same object
    again = LogicalStore(wal_path=path, wal_backend="json")
    assert json.dumps(again.get(RES, CL, NAME, NS)) == dumped
    again.close()


# ---------------------------------------------------------------------------
# the REST write path encodes the snapshot without touching it
# ---------------------------------------------------------------------------


def test_rest_write_response_is_stamped_on_a_copy_of_the_top_level(sanitized):
    async def main():
        store = _store()
        handler = RestHandler(store, default_scheme(), admission=None)
        base = f"/clusters/{CL}/apis/apps/v1/namespaces/{NS}/deployments"
        body = _body()
        del body["kind"], body["apiVersion"]  # the handler stamps both

        async def send(method, path, obj):
            resp = await handler(Request(
                method=method, path=path, query={}, headers={},
                body=json.dumps(obj).encode()))
            assert resp.status in (200, 201), resp.body
            return json.loads(resp.body)

        created = await send("POST", base, body)
        snap = store.get_snapshot(RES, CL, NAME, NS)
        assert created["kind"] == "Deployment"
        assert created["apiVersion"] == "apps/v1"
        assert "kind" not in snap and "apiVersion" not in snap
        assert {k: v for k, v in created.items()
                if k not in ("kind", "apiVersion")} == _plain(snap)

        created["spec"]["replicas"] = 4
        updated = await send("PUT", f"{base}/{NAME}", created)
        snap2 = store.get_snapshot(RES, CL, NAME, NS)
        assert updated == _plain(snap2) and snap2["spec"]["replicas"] == 4
        assert snap["spec"]["replicas"] == 3  # the old snapshot, untouched

        updated["status"] = _status(4)
        acked = await send("PUT", f"{base}/{NAME}/status", updated)
        snap3 = store.get_snapshot(RES, CL, NAME, NS)
        assert acked == _plain(snap3) and snap3["status"] == _status(4)
        assert "status" not in snap2

    asyncio.run(main())


# ---------------------------------------------------------------------------
# the static checker knows the new names
# ---------------------------------------------------------------------------


def _cow_findings(text: str):
    waivers, bad = parse_waivers(text, "fixture.py")
    assert not bad
    return CowChecker().check(
        SourceFile("fixture.py", text, ast.parse(text), waivers))


def test_cow_checker_trusts_tree_copy_and_still_guards_snapshots():
    assert _cow_findings("""\
from kcp_tpu.utils.treecopy import tree_copy

def ok(store, informer, ev):
    mine = tree_copy(store.get_snapshot("cm", "c", "x"))
    mine["metadata"]["labels"] = {}
    other = tree_copy(informer.get("c", "x"))
    other.setdefault("status", {})
    third = tree_copy(ev.object)
    third["spec"] = {}
""") == []
    findings = _cow_findings("""\
def a(store):
    snap = store.get_snapshot("cm", "c", "x")
    snap["metadata"]["labels"] = {}

def b(client, obj):
    written = client.update_snapshot("cm", obj)
    written.setdefault("status", {})

def c(client, obj):
    made = client.create_snapshot("cm", obj)
    made["spec"] = {}

def d(client, obj):
    acked = client.update_status_snapshot("cm", obj)
    acked["status"]["x"] = 1
""")
    assert [(f.line, f.rule) for f in sorted(findings, key=lambda f: f.line)] \
        == [(3, "cow-mutation"), (7, "cow-mutation"), (11, "cow-mutation"),
            (15, "cow-mutation")], findings
