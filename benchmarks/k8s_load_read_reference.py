"""The plain reference of the ``k8s-load-read`` deployment's READ
guarantees: what a GET, a LIST, a ``limit``/``continue`` walk, a Table
and a list-then-watch stream may contain, stated independently of the
program (pure Python; imports nothing of kcp_tpu) from the load
generator's own log of writes.

The log. Every key (logical cluster, name) has a sequence of VERSIONS:
the seeded resident body (or "absent", for a name the traffic creates),
then one per write the generator sent, each with the instants it was
sent and acknowledged (CLOCK_MONOTONIC), the resourceVersion the
acknowledgement carried, and a digest of the spec, labels and
annotations written, whole. The generator never has two writes of one
key in flight, so a key's versions do not overlap in time; only status
writes (the syncer's upsync) lie between them, and those change neither
the digest nor the order.

What a read of a key may show. A request sent at ``s`` whose answer was
complete at ``d`` may show any version from the last one acknowledged
before ``s`` up to the last one sent before ``d``: where that is more
than one version (a write was in flight during the request) the answer
is UNDETERMINED, every such version is admitted, and the case is
counted. An answer that states its resourceVersion narrows this again:
a list at ``L`` shows every write acknowledged with a resourceVersion
at or below ``L`` and none acknowledged above it, and an object
returned at resourceVersion ``r`` carries the body of the last write at
or below ``r``.

- ``get_after_ack``: a GET returns an admitted version's body, whole,
  at a resourceVersion no lower than that version's acknowledged one;
  404 only where an admitted version is "absent".
- ``list_snapshot``: no name twice; order (cluster, namespace, name);
  every item's resourceVersion at most the list's; every item an
  admitted version of a key of the scope; every key of the scope whose
  admitted versions are all present ones is there; under a selector,
  exactly the keys whose labels match.
- ``paged_list_snapshot``: the concatenation of the pages obeys
  ``list_snapshot`` at the FIRST page's resourceVersion over the whole
  walk's time, every page states that resourceVersion, and no page is
  longer than the limit. (A walk that met a 410 was restarted by the
  client: what is judged is the restarted walk; the restart is counted
  by the reader, not here.)
- ``table``: a Table's rows, by their object metadata, obey
  ``list_snapshot`` without the bodies.
- ``list_then_watch``: every ADDED or MODIFIED event lies above the
  LIST's resourceVersion, they arrive in rising resourceVersion order
  (so none twice), each carries the body of the last write at or below
  its resourceVersion, every write of the scope acknowledged above the
  LIST's resourceVersion while the watch was open is there at exactly
  its resourceVersion, and every delete sent after the LIST returned and
  acknowledged while the watch was open is there as one DELETED event
  (which carries the object's LAST state, resourceVersion included:
  this program's delete event does not carry the delete's own).

Every function returns mismatches as strings; none raises on a wrong
answer.
"""

from __future__ import annotations

import hashlib
import json

NEVER = float("-inf")


def digest(obj: dict) -> str:
    """Everything a tenant wrote, whole: spec, labels, annotations."""
    meta = obj.get("metadata") or {}
    blob = json.dumps([obj.get("spec"), meta.get("labels") or {},
                       meta.get("annotations") or {}],
                      sort_keys=True, separators=(",", ":"))
    return hashlib.blake2b(blob.encode(), digest_size=8).hexdigest()


def _rv(meta: dict) -> int:
    try:
        return int(meta.get("resourceVersion") or 0)
    except (TypeError, ValueError):
        return 0


def view(obj: dict) -> list:
    """An object as the judgement needs it: [cluster, namespace, name,
    resourceVersion, digest]."""
    meta = obj.get("metadata") or {}
    return [meta.get("clusterName", ""), meta.get("namespace", ""),
            meta.get("name", ""), _rv(meta), digest(obj)]


def list_views(body: dict) -> tuple[int, list[list]]:
    """(list resourceVersion, item views) of a List response."""
    return (_rv(body.get("metadata") or {}),
            [view(o) for o in body.get("items") or []])


def table_views(body: dict) -> tuple[int, list[list]]:
    """(resourceVersion, [cluster, namespace, name, resourceVersion] a
    row) of a Table response, from each row's object metadata."""
    rows = []
    for row in body.get("rows") or []:
        meta = (row.get("object") or {}).get("metadata") or {}
        rows.append([meta.get("clusterName", ""), meta.get("namespace", ""),
                     meta.get("name", ""), _rv(meta)])
    return _rv(body.get("metadata") or {}), rows


class Version:
    """One state of a key. ``kind``: ``initial`` (the seeded resident),
    ``absent`` (a created name before its create), ``create``,
    ``update``, ``delete``. ``rv`` is the acknowledged resourceVersion:
    exact for a create or update, an UPPER bound for a delete (the
    store's resourceVersion when it answered), 0 where unknown."""

    __slots__ = ("kind", "sent", "acked", "rv", "dig")

    def __init__(self, kind, sent, acked, rv, dig):
        self.kind, self.sent, self.acked = kind, sent, acked
        self.rv, self.dig = int(rv or 0), dig

    @property
    def gone(self) -> bool:
        return self.kind in ("absent", "delete")


class WriteLog:
    """The generator's log of writes, by key. ``population`` maps
    (cluster, name) to the seeded body; ``records`` are the generator's
    write records (``kind``, ``key``, ``body``, ``sent``, ``acked`` and
    ``rv``, the acknowledged resourceVersion). A key with a write that
    was sent and never acknowledged is UNCERTAIN from that instant on:
    reads of it are not judged."""

    def __init__(self, population: dict, records: list[dict]):
        self.versions: dict[tuple[str, str], list[Version]] = {}
        self.labels: dict[tuple[str, str], dict] = {}
        self.uncertain: dict[tuple[str, str], float] = {}
        for key, body in population.items():
            key = tuple(key)
            self.versions[key] = [Version("initial", NEVER, NEVER, 0,
                                          digest(body))]
            self.labels[key] = body["metadata"].get("labels") or {}
        for rec in sorted((r for r in records if r.get("sent") is not None),
                          key=lambda r: r["sent"]):
            key = tuple(rec["key"])
            if rec.get("acked") is None:
                self.uncertain.setdefault(key, rec["sent"])
                continue
            vs = self.versions.get(key)
            if vs is None:
                vs = self.versions[key] = [Version("absent", NEVER, NEVER,
                                                   0, None)]
            body = rec.get("body")
            if body is not None:
                self.labels.setdefault(
                    key, body["metadata"].get("labels") or {})
            vs.append(Version(rec["kind"], rec["sent"], rec["acked"],
                              rec.get("rv"),
                              None if body is None else digest(body)))
        self.by_cluster: dict[str, list[tuple[str, str]]] = {}
        self._all: list[tuple[str, str]] | None = None
        for key in sorted(set(self.versions) | set(self.uncertain)):
            self.by_cluster.setdefault(key[0], []).append(key)

    def keys(self, cluster: str | None) -> list[tuple[str, str]]:
        """The keys of a scope: one logical cluster, or all (None)."""
        if cluster is not None:
            return self.by_cluster.get(cluster, [])
        if self._all is None:
            self._all = [k for c in sorted(self.by_cluster)
                         for k in self.by_cluster[c]]
        return self._all

    def skip(self, key, done: float) -> bool:
        t = self.uncertain.get(key)
        return t is not None and done >= t

    def admitted(self, key, sent: float, done: float,
                 list_rv: int | None = None) -> list[Version]:
        """The versions a read of ``key`` over [sent, done] may show; at
        a stated list resourceVersion, only those the snapshot can
        hold."""
        vs = self.versions[key]
        lo = hi = 0
        for i, v in enumerate(vs):
            if v.acked < sent:
                lo = i
            if v.sent < done:
                hi = i
        if list_rv is not None:
            for i in range(lo + 1, hi + 1):
                if vs[i].rv and vs[i].rv <= list_rv:
                    lo = i
            while (hi > lo and vs[hi].kind != "delete" and vs[hi].rv
                   and vs[hi].rv > list_rv):
                hi -= 1
        return vs[lo:hi + 1]

    def at_rv(self, key, rv: int) -> Version | None:
        """The version an object at resourceVersion ``rv`` carries: the
        last create or update acknowledged at or below it (the seeded
        body below every write)."""
        found = None
        for v in self.versions[key]:
            if v.kind in ("initial", "absent"):
                found = v
            elif v.kind != "delete" and v.rv and v.rv <= rv:
                found = v
        return found


def _matches(labels: dict, selector: dict | None) -> bool:
    return not selector or all(labels.get(k) == v
                               for k, v in selector.items())


def _present(log: WriteLog, key, item: list, sent: float, done: float,
             list_rv: int | None, bodies: bool) -> tuple[list[str], bool]:
    """One returned object of a key against its admitted versions."""
    where = f"{key[0]}/{key[1]}"
    rv, dig = item[3], (item[4] if bodies else None)
    adm = log.admitted(key, sent, done, list_rv)
    live = [v for v in adm if not v.gone]
    if not live:
        return [f"{where}: returned at rv {rv}, but its delete was "
                f"acknowledged before the request was sent (or it was "
                f"never created)"], False
    out = []
    if bodies:
        hit = next((v for v in live if v.dig == dig), None)
        if hit is None:
            older = any(v.dig == dig for v in log.versions[key])
            out.append(f"{where}: body {dig} at rv {rv} is "
                       f"{'a version no longer' if older else 'no version'} "
                       f"admitted for this request "
                       f"({[v.dig for v in live]})")
        else:
            if hit.rv and rv < hit.rv:
                out.append(f"{where}: rv {rv} is below the acknowledged "
                           f"rv {hit.rv} of the body it carries")
            want = log.at_rv(key, rv)
            if want is not None and want.dig != dig and not want.gone:
                out.append(f"{where}: carries body {dig} at rv {rv}, above "
                           f"the rv {want.rv} of a later acknowledged write")
    else:
        floor = min(v.rv for v in live)
        if rv < floor:
            out.append(f"{where}: rv {rv} is below the acknowledged rv "
                       f"{floor}")
    return out, len(adm) > 1


def get_mismatches(log: WriteLog, key, status: int, item: list | None,
                   sent: float, done: float) -> tuple[list[str], bool]:
    """A GET of ``key``: (mismatches, undetermined)."""
    key = tuple(key)
    where = f"{key[0]}/{key[1]}"
    if log.skip(key, done):
        return [], True
    if key not in log.versions:
        return ([] if status == 404 else
                [f"{where}: never written, GET answered {status}"]), False
    if status == 404:
        adm = log.admitted(key, sent, done)
        if any(v.gone for v in adm):
            return [], len(adm) > 1
        return [f"{where}: GET 404, the object was acknowledged and no "
                f"delete was sent before the answer"], False
    if item is None:
        return [f"{where}: GET {status} with no object"], False
    if (item[0], item[2]) != key:
        return [f"{where}: GET returned {item[0]}/{item[2]}"], False
    return _present(log, key, item, sent, done, None, True)


def scope_mismatches(log: WriteLog, cluster: str | None, list_rv: int,
                     items: list[list], sent: float, done: float,
                     selector: dict | None = None, bodies: bool = True,
                     ) -> tuple[list[str], bool]:
    """A LIST (``bodies``) or a Table's rows over one logical cluster or
    all (``cluster`` None): (mismatches, undetermined)."""
    out: list[str] = []
    undetermined = False
    order = [(i[0], i[1], i[2]) for i in items]
    if order != sorted(order):
        out.append(f"list at rv {list_rv}: items are not in (cluster, "
                   f"namespace, name) order")
    seen: dict[tuple[str, str], list] = {}
    for item in items:
        key = (item[0], item[2])
        where = f"{key[0]}/{key[1]}"
        if key in seen:
            out.append(f"{where}: returned twice in one list")
            continue
        seen[key] = item
        if item[3] > list_rv:
            out.append(f"{where}: item rv {item[3]} is above the list's "
                       f"rv {list_rv}")
        if cluster is not None and key[0] != cluster:
            out.append(f"{where}: outside the listed cluster {cluster}")
        elif log.skip(key, done):
            undetermined = True
        elif key not in log.versions:
            out.append(f"{where}: listed, never written")
        elif not _matches(log.labels.get(key, {}), selector):
            out.append(f"{where}: listed, its labels do not match "
                       f"{selector}")
        else:
            m, u = _present(log, key, item, sent, done, list_rv, bodies)
            out += m
            undetermined |= u
    for key in log.keys(cluster):
        if key in seen or key not in log.versions:
            continue
        if log.skip(key, done):
            undetermined = True
            continue
        if not _matches(log.labels.get(key, {}), selector):
            continue
        adm = log.admitted(key, sent, done, list_rv)
        if not any(v.gone for v in adm):
            out.append(f"{key[0]}/{key[1]}: acknowledged before the list "
                       f"was sent and not deleted, missing from the list "
                       f"at rv {list_rv}")
        undetermined |= len(adm) > 1
    return out, undetermined


def walk_mismatches(log: WriteLog, cluster: str | None, pages: list[dict],
                    limit: int, sent: float, done: float,
                    selector: dict | None = None) -> tuple[list[str], bool]:
    """A ``limit``/``continue`` walk: ``pages`` are ``{"rv", "items"}``
    in the order they were answered."""
    if not pages:
        return ["a page walk with no page"], False
    first = pages[0]["rv"]
    out = []
    items: list[list] = []
    for n, page in enumerate(pages):
        if page["rv"] != first:
            out.append(f"page {n} states rv {page['rv']}, the first page "
                       f"pinned {first}")
        if limit and len(page["items"]) > limit:
            out.append(f"page {n} holds {len(page['items'])} items, the "
                       f"limit is {limit}")
        items += page["items"]
    m, u = scope_mismatches(log, cluster, first, items, sent, done, selector)
    return out + m, u


def watch_mismatches(log: WriteLog, cluster: str, list_rv: int,
                     list_done: float, events: list[list],
                     hold_end: float) -> list[str]:
    """The stream of a watch opened at a LIST's resourceVersion and read
    until ``hold_end``: events are [type, cluster, namespace, name,
    resourceVersion, digest, arrival]."""
    out: list[str] = []
    last = list_rv
    at: dict[tuple[tuple[str, str], int], str] = {}
    deleted: dict[tuple[str, str], int] = {}
    for typ, cl, _ns, name, rv, dig, arrived in events:
        key = (cl, name)
        where = f"{cl}/{name}"
        if cl != cluster:
            out.append(f"{where}: event outside the watched cluster "
                       f"{cluster}")
            continue
        if key not in log.versions and key not in log.uncertain:
            out.append(f"{where}: {typ} event of an object never written")
            continue
        if typ == "DELETED":
            deleted[key] = deleted.get(key, 0) + 1
            if deleted[key] > 1:
                out.append(f"{where}: DELETED delivered twice")
            if not log.skip(key, arrived) and not any(
                    v.kind == "delete" and v.sent < arrived
                    for v in log.versions[key]):
                out.append(f"{where}: DELETED event, no delete was sent")
            continue
        if rv <= list_rv:
            out.append(f"{where}: {typ} at rv {rv}, at or below the "
                       f"LIST's rv {list_rv} the watch was opened at")
        if rv <= last and rv > list_rv:
            out.append(f"{where}: {typ} at rv {rv} after rv {last}: out "
                       f"of order or delivered twice")
        last = max(last, rv)
        if key in deleted:
            out.append(f"{where}: {typ} at rv {rv} after its DELETED")
        at[(key, rv)] = typ
        if log.skip(key, arrived):
            continue
        want = log.at_rv(key, rv)
        if want is None or want.gone or want.dig != dig:
            out.append(f"{where}: {typ} at rv {rv} carries body {dig}, the "
                       f"last write at or below that rv wrote "
                       f"{None if want is None else want.dig}")
    for key in log.keys(cluster):
        if key not in log.versions or log.skip(key, hold_end):
            continue
        for v in log.versions[key]:
            if v.acked == NEVER or v.acked > hold_end:
                continue
            where = f"{key[0]}/{key[1]}"
            if v.kind == "delete":
                if v.sent > list_done and not deleted.get(key):
                    out.append(f"{where}: its delete was acknowledged "
                               f"while the watch was open, no DELETED "
                               f"event came")
            elif v.rv > list_rv:
                typ = at.get((key, v.rv))
                want = "ADDED" if v.kind == "create" else "MODIFIED"
                if typ is None:
                    out.append(f"{where}: {v.kind} acknowledged at rv "
                               f"{v.rv} while the watch was open, no event "
                               f"at that rv (a gap)")
                elif typ != want:
                    out.append(f"{where}: {v.kind} at rv {v.rv} delivered "
                               f"as {typ}")
    return out


def probe_mismatches(body: dict, inspected: dict) -> list[str]:
    """The probes of ONE write, judged without the log (its key is held
    busy until both are back, so nothing but status writes can lie
    between): ``inspected`` holds ``rv`` (the acknowledged one), ``get``
    (``{"status", "view"}``, read after the acknowledgement) and ``list``
    (``{"rv", "items"}``, the tenant's namespace read after the status
    was seen)."""
    out = []
    name, want = body["metadata"]["name"], digest(body)
    acked = int(inspected.get("rv") or 0)
    got = inspected.get("get")
    if got is not None:
        v = got.get("view")
        if got.get("status") != 200 or v is None:
            out.append(f"GET after the acknowledgement answered "
                       f"{got.get('status')} {got.get('error') or ''}")
        else:
            if v[2] != name or v[4] != want:
                out.append(f"GET after the acknowledgement returned "
                           f"{v[2]} with body {v[4]}, acknowledged {want}")
            if v[3] < acked:
                out.append(f"GET after the acknowledgement returned rv "
                           f"{v[3]}, below the acknowledged {acked}")
    lst = inspected.get("list")
    if lst is not None:
        if lst.get("status") != 200:
            return out + [f"LIST after the status was seen answered "
                          f"{lst.get('status')} {lst.get('error') or ''}"]
        names = [i[2] for i in lst["items"]]
        if names != sorted(names) or len(set(names)) != len(names):
            out.append("LIST after the status was seen: names repeated or "
                       "out of order")
        if lst["rv"] < acked:
            out.append(f"LIST after the status was seen states rv "
                       f"{lst['rv']}, below the acknowledged {acked}")
        mine = [i for i in lst["items"] if i[2] == name]
        if len(mine) != 1:
            out.append(f"LIST after the status was seen holds the written "
                       f"object {len(mine)} times")
        for i in lst["items"]:
            if i[3] > lst["rv"]:
                out.append(f"LIST item {i[2]} at rv {i[3]} above the "
                           f"list's {lst['rv']}")
        for i in mine:
            if i[4] != want or i[3] < acked:
                out.append(f"LIST after the status was seen returned body "
                           f"{i[4]} at rv {i[3]}, acknowledged {want} at "
                           f"{acked}")
    return out


SELECTOR = {"group": "load"}


def judge(log: WriteLog, read: dict) -> tuple[list[str], bool]:
    """One background read of the ``read_mostly`` generator (its record
    as the reader stamped it) against the guarantee of its verb:
    (mismatches, undetermined). A read that ended in an error has no
    answer to judge."""
    ans = read.get("answer")
    if ans is None or read.get("error"):
        return [], False
    verb, cluster = read["verb"], read["tenant"]
    sent, done = read["sent"], read["done"]
    if verb == "get":
        return get_mismatches(log, (cluster, read["name"]), read["status"],
                              ans.get("view"), sent, done)
    if verb == "list_selector":
        return scope_mismatches(log, cluster, ans["rv"], ans["items"], sent,
                                done, selector=SELECTOR)
    if verb == "list_table":
        return scope_mismatches(log, cluster, ans["rv"], ans["items"], sent,
                                done, bodies=False)
    if verb == "list_all_paged":
        return walk_mismatches(log, None, ans["pages"], read.get("limit", 0),
                               sent, done)
    if verb == "relist_watch":
        m, u = scope_mismatches(log, cluster, ans["rv"], ans["items"], sent,
                                ans["list_done"])
        w = ans.get("watch") or {}
        if w.get("events") is not None:
            m += watch_mismatches(log, cluster, ans["rv"], ans["list_done"],
                                  w["events"], w["hold_end"])
        return m, u
    return [f"unknown read verb {verb!r}"], False
