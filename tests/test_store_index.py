"""Indexed store read path + batched watch fan-out: equivalence vs naive.

The indexed store (``indexed=True``: secondary buckets, CoW shared
references, vectorized micro-batched fan-out) must be observably
byte-identical to the legacy path (linear scan, per-match/per-event
deepcopy, per-watch python matching). The fuzz drives both side-by-side
through random put/update/delete/finalizer/selector traffic and compares
every return value, error, list result, and watch event stream —
including the selector-bound ADDED/DELETED rewrite cases and oversized
selectors that fall back to exact host matching.
"""

import asyncio
import json
import random

import pytest

from kcp_tpu.store import LogicalStore, parse_selector
from kcp_tpu.store.store import ADDED, DELETED, MODIFIED, WILDCARD
from kcp_tpu.utils import errors
from kcp_tpu.utils.trace import REGISTRY

RESOURCES = ("configmaps", "secrets")
CLUSTERS = ("c0", "c1", "c2")
NAMESPACES = ("ns0", "ns1", "ns2")
NAMES = tuple(f"n{i}" for i in range(8))

# watch shapes: scope variants, every selector operator class, the
# single-equality fast path, and two oversized selectors (>8 requirements
# / >8 alternatives) that must take the exact host fallback
WATCH_SPECS = [
    ("configmaps", WILDCARD, None, ""),
    ("configmaps", "c0", None, "team=a"),
    ("configmaps", WILDCARD, "ns1", "team in (a,b),tier!=db"),
    ("configmaps", WILDCARD, None, "!tier"),
    ("configmaps", WILDCARD, None, "team notin (b),tier"),
    ("secrets", WILDCARD, None, "team=b"),
    ("configmaps", WILDCARD, None,
     "team=a,k1,k2,!k3,k4,k5,k6,k7,k8"),  # 9 requirements -> fallback
    ("configmaps", WILDCARD, None,
     "team in (a,b,c,d,e,f,g,h,i)"),  # 9 alternatives -> fallback
]

LABEL_CHOICES = [
    None,
    {"team": "a"},
    {"team": "b"},
    {"team": "c", "tier": "web"},
    {"tier": "db"},
    {"team": "a", "tier": "web", "k1": "1", "k4": "x"},
    {"k1": "1", "k2": "2", "k3": "3"},
]


def _ev_tuple(e):
    return (e.type, e.resource, e.cluster, e.namespace, e.name, e.rv,
            json.dumps(e.object, sort_keys=True),
            json.dumps(e.old_object, sort_keys=True)
            if e.old_object is not None else None)


def _items_json(items):
    return json.dumps(items, sort_keys=True)


class _Pair:
    """The same store API executed against both implementations, with
    every observable compared."""

    def __init__(self):
        clock = lambda: 1_700_000_000.0  # noqa: E731 — identical timestamps
        self.idx = LogicalStore(clock=clock, indexed=True)
        self.naive = LogicalStore(clock=clock, indexed=False)
        self.watches = [
            (self.idx.watch(r, c, ns, parse_selector(sel) if sel else None),
             self.naive.watch(r, c, ns, parse_selector(sel) if sel else None))
            for r, c, ns, sel in WATCH_SPECS
        ]

    def call(self, fn_name, *args, **kwargs):
        results = []
        for s in (self.idx, self.naive):
            try:
                results.append(("ok", getattr(s, fn_name)(*args, **kwargs)))
            except errors.ApiError as e:
                results.append(("err", type(e).__name__))
        (ka, va), (kb, vb) = results
        assert ka == kb, (fn_name, args, results)
        if ka == "ok" and va is not None:
            if isinstance(va, tuple):  # list(): (items, rv)
                assert va[1] == vb[1], (fn_name, args)
                assert _items_json(va[0]) == _items_json(vb[0]), (fn_name, args)
            else:
                assert json.dumps(va, sort_keys=True) == json.dumps(vb, sort_keys=True)
        return results[0]

    def compare_drains(self):
        for i, (wi, wn) in enumerate(self.watches):
            got = [_ev_tuple(e) for e in wi.drain()]
            want = [_ev_tuple(e) for e in wn.drain()]
            assert got == want, f"watch {i} ({WATCH_SPECS[i]}) diverged"

    def compare_lists(self, rng):
        resource = rng.choice(RESOURCES)
        cluster = rng.choice((WILDCARD,) + CLUSTERS)
        namespace = rng.choice((None,) + NAMESPACES)
        sel = parse_selector(rng.choice(
            ["", "team=a", "team!=a", "tier in (web,db)", "!team",
             "team=a,k1,k2,!k3,k4,k5,k6,k7,k8"]))
        self.call("list", resource, cluster, namespace, sel)


def _random_op(pair: _Pair, rng: random.Random, op_counter: list):
    resource = rng.choice(RESOURCES)
    cluster = rng.choice(CLUSTERS)
    namespace = rng.choice(NAMESPACES)
    name = rng.choice(NAMES)
    roll = rng.random()
    if roll < 0.35:
        op_counter[0] += 1
        obj = {"apiVersion": "v1", "kind": "ConfigMap",
               "metadata": {"name": name, "namespace": namespace,
                            "uid": f"uid-{op_counter[0]}"},
               "data": {"v": str(rng.randrange(1000))}}
        labels = rng.choice(LABEL_CHOICES)
        if labels:
            obj["metadata"]["labels"] = dict(labels)
        if rng.random() < 0.15:
            obj["metadata"]["finalizers"] = ["test.dev/hold"]
        pair.call("create", resource, cluster, obj, namespace)
    elif roll < 0.70:
        # update from the current stored state (both stores agree
        # inductively); randomly relabel to force the selector-bound
        # ADDED/DELETED rewrites
        kind, cur = pair.call("get", resource, cluster, name, namespace)
        if kind != "ok":
            return
        cur["data"] = {"v": str(rng.randrange(1000))}
        if rng.random() < 0.6:
            labels = rng.choice(LABEL_CHOICES)
            cur["metadata"].pop("labels", None)
            if labels:
                cur["metadata"]["labels"] = dict(labels)
        if rng.random() < 0.3 and cur["metadata"].get("deletionTimestamp"):
            cur["metadata"]["finalizers"] = []  # release -> completes delete
        if rng.random() < 0.2:
            cur["status"] = {"phase": rng.choice(["Ready", "Pending"])}
            pair.call("update_status", resource, cluster, cur, namespace)
        else:
            pair.call("update", resource, cluster, cur, namespace)
    else:
        pair.call("delete", resource, cluster, name, namespace)


@pytest.mark.parametrize("seed", [3, 11, 42])
def test_indexed_vs_naive_equivalence_fuzz(seed):
    rng = random.Random(seed)
    pair = _Pair()
    op_counter = [0]
    for step in range(500):
        _random_op(pair, rng, op_counter)
        if rng.random() < 0.15:
            pair.compare_drains()
        if rng.random() < 0.10:
            pair.compare_lists(rng)
        if rng.random() < 0.05 and pair.idx.resource_version > 2:
            # resume-replay equivalence at a random past RV
            since = rng.randrange(1, pair.idx.resource_version)
            spec = rng.choice(WATCH_SPECS)
            sel = parse_selector(spec[3]) if spec[3] else None
            wi = pair.idx.watch(spec[0], spec[1], spec[2], sel, since_rv=since)
            wn = pair.naive.watch(spec[0], spec[1], spec[2], sel, since_rv=since)
            assert ([_ev_tuple(e) for e in wi.drain()]
                    == [_ev_tuple(e) for e in wn.drain()]), (seed, step, since)
            wi.close()
            wn.close()
    pair.compare_drains()
    # final exhaustive list sweep
    for resource in RESOURCES:
        for cluster in (WILDCARD,) + CLUSTERS:
            for namespace in (None,) + NAMESPACES:
                pair.call("list", resource, cluster, namespace)
    assert len(pair.idx) == len(pair.naive)
    assert pair.idx.resources() == pair.naive.resources()
    assert pair.idx.clusters() == pair.naive.clusters()


def _cm(name, ns="default", labels=None, cluster_unused=None):
    obj = {"apiVersion": "v1", "kind": "ConfigMap",
           "metadata": {"name": name, "namespace": ns}}
    if labels:
        obj["metadata"]["labels"] = labels
    return obj


def test_locate_finds_owning_clusters():
    s = LogicalStore()
    s.create("configmaps", "a", _cm("x"))
    s.create("configmaps", "b", _cm("x"))
    s.create("configmaps", "c", _cm("y"))
    assert s.locate("configmaps", "x", "default") == ["a", "b"]
    assert s.locate("configmaps", "y", "default") == ["c"]
    assert s.locate("configmaps", "z", "default") == []
    assert s.locate("secrets", "x", "default") == []
    s.delete("configmaps", "a", "x", "default")
    assert s.locate("configmaps", "x", "default") == ["b"]


def test_oversized_selector_falls_back_and_counts():
    before = REGISTRY.counter("labelmatch_fallback_total").value
    s = LogicalStore(indexed=True)
    w = s.watch("configmaps", selector=parse_selector(
        "team=a,k1,k2,k3,k4,k5,k6,k7,k8"))  # 9 requirements
    assert REGISTRY.counter("labelmatch_fallback_total").value == before + 1
    s.create("configmaps", "t", _cm("hit", labels={
        "team": "a", "k1": "1", "k2": "1", "k3": "1", "k4": "1",
        "k5": "1", "k6": "1", "k7": "1", "k8": "1"}))
    s.create("configmaps", "t", _cm("miss", labels={"team": "a"}))
    evs = w.drain()
    assert [(e.type, e.name) for e in evs] == [(ADDED, "hit")]


def test_batched_fanout_delivers_to_async_consumer():
    """Deferred flush must wake async iterators without an explicit drain."""

    async def main():
        s = LogicalStore(indexed=True)
        w = s.watch("configmaps", selector=parse_selector("team=a"))
        got = []

        async def consume():
            async for ev in w:
                got.append((ev.type, ev.name))
                if len(got) == 3:
                    return

        task = asyncio.create_task(consume())
        await asyncio.sleep(0)
        s.create("configmaps", "t", _cm("a1", labels={"team": "a"}))
        s.create("configmaps", "t", _cm("b1", labels={"team": "b"}))
        obj = s.get("configmaps", "t", "b1", "default")
        obj["metadata"]["labels"] = {"team": "a"}  # rewrite -> ADDED
        s.update("configmaps", "t", obj)
        s.delete("configmaps", "t", "a1", "default")
        await asyncio.wait_for(task, timeout=2.0)
        assert got == [(ADDED, "a1"), (ADDED, "b1"), (DELETED, "a1")]
        s.close()

    asyncio.run(main())


def test_emit_batch_threshold_flushes_inline():
    s = LogicalStore(indexed=True)
    s._emit_batch = 4
    w = s.watch("configmaps")
    for i in range(5):
        s.create("configmaps", "t", _cm(f"n{i}"))
    # threshold flush happened without any consumer access
    assert len(w._events) >= 4
    assert [e.name for e in w.drain()] == [f"n{i}" for i in range(5)]


def test_list_metrics_count_scanned_and_returned():
    s = LogicalStore(indexed=True)
    for i in range(10):
        s.create("configmaps", "a" if i % 2 else "b", _cm(f"n{i}"))
    scanned0 = REGISTRY.counter("store_list_scanned_total").value
    returned0 = REGISTRY.counter("store_list_returned_total").value
    items, _ = s.list("configmaps", "a")
    assert len(items) == 5
    assert REGISTRY.counter("store_list_scanned_total").value - scanned0 == 5
    assert REGISTRY.counter("store_list_returned_total").value - returned0 == 5


def test_cow_list_shares_but_write_paths_copy():
    """The CoW contract: listed items share references with storage, and
    the store's own write path still snapshots — a later update must not
    mutate a previously returned item."""
    s = LogicalStore(indexed=True)
    s.create("configmaps", "t", _cm("x", labels={"team": "a"}))
    items, _ = s.list("configmaps")
    before = json.dumps(items[0], sort_keys=True)
    obj = s.get("configmaps", "t", "x", "default")
    obj["data"] = {"changed": "yes"}
    s.update("configmaps", "t", obj)
    # the frozen snapshot the first list returned is untouched
    assert json.dumps(items[0], sort_keys=True) == before


def test_index_survives_wal_restore(tmp_path):
    wal = str(tmp_path / "s.wal")
    s = LogicalStore(wal_path=wal, indexed=True)
    s.create("configmaps", "a", _cm("x", ns="n1"))
    s.create("configmaps", "b", _cm("y", ns="n2"))
    s.delete("configmaps", "b", "y", "n2")
    s.close()
    s2 = LogicalStore(wal_path=wal, indexed=True)
    assert s2.locate("configmaps", "x", "n1") == ["a"]
    assert s2.locate("configmaps", "y", "n2") == []
    items, _ = s2.list("configmaps", "a", "n1")
    assert [i["metadata"]["name"] for i in items] == ["x"]
    s2.close()


def test_modified_rewrites_inside_one_batch():
    """Label transitions coalesced into a single micro-batch must still
    rewrite per-event (ADDED when labels start matching, DELETED when
    they stop)."""
    s = LogicalStore(indexed=True)
    w = s.watch("configmaps", selector=parse_selector("team=a"))
    s.create("configmaps", "t", _cm("x", labels={"team": "a"}))
    for team in ("b", "a", "b"):
        obj = s.get("configmaps", "t", "x", "default")
        obj["metadata"]["labels"] = {"team": team}
        s.update("configmaps", "t", obj)
    s.delete("configmaps", "t", "x", "default")
    types = [e.type for e in w.drain()]
    assert types == [ADDED, DELETED, ADDED, DELETED]
    # MODIFIED never surfaced: every event was a boundary transition
    assert MODIFIED not in types


# ---------------------------------------------------------------------------
# the fan-out's index (cluster and label-pair buckets): every watch sees
# what Watch._transform gives event by event, whatever the mix of watches
# ---------------------------------------------------------------------------

from kcp_tpu import faults  # noqa: E402

MIX_CLUSTERS = (WILDCARD, "c0", "c1", "c2", "c3")
MIX_NAMESPACES = (None, None, "ns0", "ns1")
MIX_SELECTORS = (
    "", "",  # empty
    "team=a", "team=a", "team=b", "tier=web", "team=nobody",  # one pair id
    "team in (a,b),tier!=db", "!tier", "team notin (b),tier",  # compiled
    "team=a,k1,k2,!k3,k4,k5,k6,k7,k8",  # 9 requirements: oversized
    "team in (a,b,c,d,e,f,g,h,i)",  # 9 alternatives: oversized
)
MIX_LABELS = (
    None, None,
    {"team": "a"}, {"team": "b"}, {"team": "a", "tier": "web"},
    {"tier": "db"}, {"team": "c", "tier": "web", "owner": "x"},
    {"team": "a", "k1": "1", "k2": "1", "k4": "1", "k5": "1", "k6": "1",
     "k7": "1", "k8": "1"},
)


@pytest.fixture
def _no_faults():
    yield
    faults.clear()


def _mixed_watches(store, rng, n):
    out = []
    for _ in range(n):
        sel = rng.choice(MIX_SELECTORS)
        out.append(store.watch(
            rng.choice(("configmaps", "configmaps", "secrets")),
            rng.choice(MIX_CLUSTERS), rng.choice(MIX_NAMESPACES),
            parse_selector(sel) if sel else None))
    return out


def _mixed_event(store, rng, serial):
    """One random ADDED / MODIFIED (labels starting, stopping, changing,
    absent, unchanged) / DELETED; False where the roll met no object."""
    resource = rng.choice(("configmaps", "configmaps", "secrets"))
    cluster = rng.choice(MIX_CLUSTERS[1:])
    namespace = rng.choice(("ns0", "ns1", "ns2"))
    name = f"n{rng.randrange(6)}"
    roll = rng.random()
    try:
        if roll < 0.35:
            obj = _cm(name, ns=namespace, labels=rng.choice(MIX_LABELS))
            obj["data"] = {"v": str(serial)}
            store.create(resource, cluster, obj, namespace)
        elif roll < 0.8:
            cur = store.get(resource, cluster, name, namespace)
            cur["data"] = {"v": str(serial)}
            if rng.random() < 0.6:
                labels = rng.choice(MIX_LABELS)
                cur["metadata"].pop("labels", None)
                if labels:
                    cur["metadata"]["labels"] = dict(labels)
            store.update(resource, cluster, cur, namespace)
        else:
            store.delete(resource, cluster, name, namespace)
    except (errors.NotFoundError, errors.AlreadyExistsError):
        return False
    return True


def _drive_flushes(store, rng, flush, events):
    """``events`` mutations, fanned out in flushes of ``flush``."""
    store._emit_batch = 10 ** 9  # the test decides where a flush ends
    done = 0
    while done < events:
        done += _mixed_event(store, rng, done)
        if len(store._pending) >= flush:
            store._flush_events()
    store._flush_events()


def _expected(w, source):
    """What ``Watch._transform`` gives ``w``, event by event."""
    return [(ev, out) for ev in source
            if (out := w._transform(ev)) is not None]


def _assert_stream(w, got, source, rewritten, prefix=False):
    """``got`` is what ``_transform`` gives event by event: type, key,
    rv, the source's own object (identity), and the source EVENT itself
    wherever the type did not change. ``rewritten`` collects the others
    by (rv, type) across watches."""
    want = _expected(w, source)
    if prefix:
        assert len(got) <= len(want)
        want = want[:len(got)]
    assert [(e.type, e.key, e.rv) for e in got] \
        == [(out.type, out.key, out.rv) for _, out in want], \
        (w.resource, w.cluster, w.namespace, str(w.selector))
    for e, (ev, out) in zip(got, want):
        assert e.object is ev.object and e.old_object is ev.old_object
        if out is ev:
            assert e is ev  # the shared event: one wire line for all
        else:
            assert e is not ev and e.type != ev.type
            rewritten.setdefault((e.rv, e.type), []).append(e)


@pytest.mark.parametrize("flush", [1, 3, 300])
@pytest.mark.parametrize("seed", [5, 23, 77])
def test_fanout_index_delivers_what_transform_gives(seed, flush):
    rng = random.Random(seed * 1000 + flush)
    s = LogicalStore(indexed=True)
    watches = _mixed_watches(s, rng, 48)
    _drive_flushes(s, rng, flush, 600)
    source = list(s._history)
    assert len(source) == 600
    rewritten: dict = {}
    delivered = 0
    for w in watches:
        got = list(w._events)
        delivered += len(got)
        _assert_stream(w, got, source, rewritten)
    assert delivered > 600  # the mix does reach its watches
    assert rewritten, "no label transition was rewritten: the mix is too tame"
    assert any(len(es) > 1 for es in rewritten.values())
    # a rewritten event is ONE object across the watches it reaches
    assert all(e is es[0] for es in rewritten.values() for e in es)
    s.close()


@pytest.mark.parametrize("how", ["watch:drop@tick=40", "watch.evict:drop@tick=25",
                                 "queue-bound-1"])
@pytest.mark.parametrize("flush", [1, 300])
def test_watch_closed_inside_the_pass_loses_nothing_for_the_others(
        how, flush, _no_faults):
    rng = random.Random(len(how) * 1000 + flush)
    s = LogicalStore(indexed=True)
    if how == "queue-bound-1":
        s._watch_queue = 1
        doomed = [s.watch("configmaps"),
                  s.watch("configmaps", "c1", None, parse_selector("team=a")),
                  s.watch("configmaps", "c2", None, parse_selector("!tier"))]
        s._watch_queue = 0
    else:
        doomed = []
    watches = _mixed_watches(s, rng, 40)
    if how != "queue-bound-1":
        faults.install(faults.FaultInjector(how))
    _drive_flushes(s, rng, flush, 400)
    faults.clear()
    source = list(s._history)
    closed = [w for w in watches + doomed if w.closed]
    assert closed, "nothing was dropped: the drill did not fire"
    if how == "queue-bound-1":
        assert all(w.closed and w.evicted for w in doomed)
    else:
        assert len(closed) == 1
    rewritten: dict = {}
    for w in watches + doomed:
        # a closed watch holds a prefix of its stream, every other one
        # the whole of it
        _assert_stream(w, list(w._events), source, rewritten,
                       prefix=w.closed)
    # a closed watch is out of every later plan
    for res in ("configmaps", "secrets"):
        plan = s._fanout_plan(res)
        live = [w for b in list(plan.by_cluster.values()) + [plan.wild]
                if b is not None
                for w in b.all + b.transform
                + [x for ws in b.by_pid.values() for x in ws]] + plan.mx_ws
        assert not any(w.closed for w in live)
        assert len(live) == len(s._watches_by_res.get(res, ()))
    s.close()


def test_watch_added_or_closed_between_flushes_is_in_or_out_of_the_next_plan():
    s = LogicalStore(indexed=True)
    first = s.watch("configmaps", "c0", None, parse_selector("team=a"))
    s.create("configmaps", "c0", _cm("x0", labels={"team": "a"}))
    s._flush_events()
    late = [s.watch("configmaps", "c0", None, parse_selector("team=a")),
            s.watch("configmaps", WILDCARD, None, parse_selector("team=a")),
            s.watch("configmaps", WILDCARD, None, None),
            s.watch("configmaps", WILDCARD, None, parse_selector("team,!tier")),
            s.watch("configmaps", "c0", "default", None)]
    s.create("configmaps", "c0", _cm("x1", labels={"team": "a"}))
    s._flush_events()
    assert [e.name for e in first.drain()] == ["x0", "x1"]
    for w in late:
        assert [e.name for e in w.drain()] == ["x1"]
    for w in late[::2]:
        w.close()
    s.create("configmaps", "c0", _cm("x2", labels={"team": "a"}))
    s._flush_events()
    assert [e.name for e in first.drain()] == ["x2"]
    for i, w in enumerate(late):
        assert [e.name for e in w.drain()] == ([] if i % 2 == 0 else ["x2"])
    s.close()


@pytest.mark.parametrize("residual", [False, True])
def test_intern_tables_do_not_grow_with_labels_no_watch_selects(residual):
    s = LogicalStore(indexed=True)
    w = s.watch("configmaps", "c0", None, parse_selector("team=a"))
    wild = s.watch("configmaps", WILDCARD, None, parse_selector("team=a"))
    if residual:  # a compiled wildcard selector: the matrix path encodes
        s.watch("configmaps", WILDCARD, None, parse_selector("team in (a,b),tier"))
    pairs, keys, nss = (len(s._intern_pairs), len(s._intern_keys),
                        len(s._intern_ns))
    for i in range(200):
        s.create("configmaps", "c0", _cm(
            f"n{i}", ns=f"ns{i}",
            labels={"team": "a" if i % 2 else f"t{i}", f"key{i}": f"value{i}",
                    "tier": f"tier{i}"}))
        if i % 7 == 0:
            s._flush_events()
    s._flush_events()
    assert len(w.drain()) == 100 and len(wild.drain()) == 100
    assert (len(s._intern_pairs), len(s._intern_keys), len(s._intern_ns)) \
        == (pairs, keys, nss)
    s.close()


def _fanout_counters():
    return tuple(REGISTRY.counter(f"store_fanout_{n}_total").value for n in
                 ("events", "indexed_events", "candidates", "deliveries"))


def test_fanout_cost_does_not_grow_with_scoped_watches(monkeypatch):
    """A cost guard, not a timing: behind 2,000 scoped watches a flush
    of one event evaluates the watch of its cluster and the wildcard
    ones, and never enters the matrix path."""
    s = LogicalStore(indexed=True)
    scoped = [s.watch("configmaps", f"t{i}", None,
                      parse_selector(f"kcp.dev/cluster=loc{i}"))
              for i in range(2000)]
    wild = s.watch("configmaps")
    monkeypatch.setattr(
        LogicalStore, "_fanout_residual",
        lambda *a, **k: pytest.fail("the matrix path was entered"))
    monkeypatch.setattr(
        LogicalStore, "_encode_labels",
        lambda *a, **k: pytest.fail("labels were encoded for numpy"))
    s.create("configmaps", "t7", _cm("x", labels={"kcp.dev/cluster": "loc7"}))
    s._flush_events()  # builds the plan
    for labels in ({"kcp.dev/cluster": "loc7", "app": "web"},
                   {"kcp.dev/cluster": "loc8"},  # leaves t7's watch: DELETED
                   None):
        before = _fanout_counters()
        obj = s.get("configmaps", "t7", "x", "default")
        obj["metadata"].pop("labels", None)
        if labels:
            obj["metadata"]["labels"] = labels
        s.update("configmaps", "t7", obj)
        s._flush_events()
        events, indexed, cands, delivs = (
            b - a for a, b in zip(before, _fanout_counters()))
        assert events == indexed == 1
        assert 1 <= cands <= 4 and delivs <= cands
    assert [e.type for e in scoped[7].drain()] == [ADDED, MODIFIED, DELETED]
    assert not scoped[8].drain()  # another cluster's pair: out of scope
    assert len(wild.drain()) == 4
    assert s._labelmatch is None  # no selector was ever compiled
    s.close()


def test_fanout_residual_is_taken_and_counted():
    """Wildcard watches with general selectors keep the matrix path, over
    their own columns only; the counters say so."""
    s = LogicalStore(indexed=True)
    general = [s.watch("configmaps", WILDCARD, None,
                       parse_selector(f"team in (a,b{i}),tier!=db"))
               for i in range(10)]
    scoped = [s.watch("configmaps", f"t{i}", None, parse_selector("team=a"))
              for i in range(50)]
    before = _fanout_counters()
    for i in range(6):
        s.create("configmaps", f"t{i}", _cm("x", labels={"team": "a"}))
    s.create("secrets", "t0", _cm("s", labels={"team": "a"}))  # no watch
    s._flush_events()
    events, indexed, cands, delivs = (
        b - a for a, b in zip(before, _fanout_counters()))
    assert events == 6 and indexed == 0
    assert cands == 6 * 10 + 6 and delivs == 6 * 10 + 6
    plan = s._fanout_plan("configmaps")
    assert len(plan.mx_ws) == 10 and len(plan.by_cluster) == 50
    assert all(len(w.drain()) == 6 for w in general)
    assert [len(w.drain()) for w in scoped[:7]] == [1] * 6 + [0]
    s.close()
