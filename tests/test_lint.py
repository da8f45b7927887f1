"""kcp-lint self-tests: every checker is regression-gated by a fixture
pair — a minimal snippet that MUST be flagged and a near-miss that MUST
NOT be — plus waiver-syntax mechanics and the repo-wide clean gate
(``python scripts/lint.py`` exits 0 on this tree).
"""

import ast
import os

from kcp_tpu.analysis.asyncdiscipline import AsyncDisciplineChecker
from kcp_tpu.analysis.base import SourceFile, parse_waivers
from kcp_tpu.analysis.cow import CowChecker
from kcp_tpu.analysis.faultpoints import FaultPointChecker
from kcp_tpu.analysis.frozenbytes import FrozenBytesChecker
from kcp_tpu.analysis.lockorder import LockOrderChecker
from kcp_tpu.analysis.metricsdoc import MetricsDocChecker
from kcp_tpu.analysis.runner import run_lint

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _src(path: str, text: str) -> SourceFile:
    waivers, findings = parse_waivers(text, path)
    assert not findings, findings
    return SourceFile(path, text, ast.parse(text), waivers)


def _check(checker, text: str, path: str = "fixture.py"):
    return checker.check(_src(path, text))


# ---------------------------------------------------------------------------
# cow-mutation
# ---------------------------------------------------------------------------


def test_cow_flags_mutation_of_list_results():
    findings = _check(CowChecker(), """\
def reconcile(store):
    items, rv = store.list("configmaps")
    for obj in items:
        obj["metadata"]["labels"] = {"touched": "yes"}
""")
    assert len(findings) == 1 and findings[0].rule == "cow-mutation"
    assert findings[0].line == 4


def test_cow_flags_snapshot_and_event_and_arg_mutator():
    findings = _check(CowChecker(), """\
def a(store):
    snap = store.get_snapshot("cm", "c", "x")
    snap.setdefault("status", {})

def b(ev):
    ev.object["spec"] = {}

def c(informer):
    obj = informer.get("c", "x")
    set_condition(obj, "Ready", "True")
""")
    rules = sorted((f.line, f.rule) for f in findings)
    assert [r for _, r in rules] == ["cow-mutation"] * 3, findings


def test_cow_near_misses_pass():
    findings = _check(CowChecker(), """\
import copy

def ok(store, informer):
    items, rv = store.list("configmaps")
    n = len(items)                       # reads are fine
    obj = copy.deepcopy(items[0])        # private copy
    obj["metadata"]["labels"] = {}
    fresh = store.get("cm", "c", "x")    # get() returns a copy
    fresh["spec"] = {"replicas": n}
    mine = {"metadata": {}}
    mine["metadata"]["name"] = "ok"      # untainted local
    cached = informer.get("c", "x")
    derived = copy.deepcopy(cached)
    derived.setdefault("status", {})
""")
    assert findings == [], findings


def test_cow_taints_through_informer_cache_and_rebind_kills():
    findings = _check(CowChecker(), """\
def flag(informer):
    for obj in informer.cache.values():
        obj["x"] = 1

def clean(informer, client):
    obj = informer.get("c", "x")
    obj = client.fetch_fresh()           # rebind kills the taint
    obj["x"] = 1
""")
    assert len(findings) == 1 and findings[0].line == 3


# ---------------------------------------------------------------------------
# frozen-bytes
# ---------------------------------------------------------------------------


def test_frozen_bytes_flags_bytearray_and_reencode():
    findings = _check(FrozenBytesChecker(), """\
import json

def a(store, obj):
    raw = store.encode_obj(obj)
    buf = bytearray(raw)

def b(store, evs):
    lines = store.encode_events(evs)
    return json.loads(lines[0])
""")
    assert sorted(f.line for f in findings) == [5, 9]
    assert all(f.rule == "frozen-bytes" for f in findings)


def test_frozen_bytes_flags_element_writes_and_augassign():
    findings = _check(FrozenBytesChecker(), """\
def a(store):
    spans, rv = store.list_encoded("cm")
    line = spans[0]
    line += b"corruption"
""")
    assert len(findings) == 1 and findings[0].line == 4


def test_frozen_bytes_near_misses_pass():
    findings = _check(FrozenBytesChecker(), """\
import json

def ok(store, obj, evs):
    raw = store.encode_obj(obj)
    n = len(raw)                          # reading is fine
    copy_ = bytes(raw)                    # bytes() of bytes is a no-op
    parts = [raw, raw]
    body = b", ".join(parts)             # splicing is the whole point
    fresh = json.loads(body[:0] + b"{}") # untainted bytes
    return n, copy_, body, fresh
""")
    assert findings == [], findings


# ---------------------------------------------------------------------------
# async-discipline
# ---------------------------------------------------------------------------


def test_async_flags_blocking_sleep_and_open():
    findings = _check(AsyncDisciplineChecker(), """\
import time

async def serve():
    time.sleep(0.1)

async def load(path):
    with open(path) as f:
        return f.read()
""")
    assert sorted(f.line for f in findings) == [4, 7]
    assert all(f.rule == "async-discipline" for f in findings)


def test_async_flags_await_under_threading_lock():
    findings = _check(AsyncDisciplineChecker(), """\
import asyncio
import threading

_lk = threading.Lock()

async def bad():
    with _lk:
        await asyncio.sleep(0)
""")
    assert len(findings) == 1 and "hybrid deadlock" in findings[0].message


def test_async_near_misses_pass():
    findings = _check(AsyncDisciplineChecker(), """\
import asyncio
import threading
import time

_lk = threading.Lock()

def sync_path():
    time.sleep(0.1)          # blocking is fine off the loop

async def ok():
    await asyncio.sleep(0)
    with _lk:
        x = 1                # no await while held
    def worker():
        time.sleep(1.0)      # nested thread fn runs elsewhere
    return x, worker

async def ok_async_lock(alk):
    async with alk:
        await asyncio.sleep(0)
""")
    assert findings == [], findings


# ---------------------------------------------------------------------------
# lock-order
# ---------------------------------------------------------------------------


def test_lock_order_flags_inverted_pair():
    f = _src("pkg/mod.py", """\
import threading

class S:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def m1(self):
        with self._a:
            with self._b:
                pass

    def m2(self):
        with self._b:
            with self._a:
                pass
""")
    findings = LockOrderChecker().check_repo([f], REPO_ROOT)
    assert len(findings) == 1 and "cycle" in findings[0].message


def test_lock_order_sees_one_level_call_indirection():
    f = _src("pkg/mod.py", """\
import threading

class S:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def outer(self):
        with self._a:
            self.inner()

    def inner(self):
        with self._b:
            pass

    def inverted(self):
        with self._b:
            with self._a:
                pass
""")
    findings = LockOrderChecker().check_repo([f], REPO_ROOT)
    assert len(findings) == 1, findings


def test_lock_order_consistent_order_passes():
    f = _src("pkg/mod.py", """\
import threading

class S:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def m1(self):
        with self._a:
            with self._b:
                pass

    def m2(self):
        with self._a:
            with self._b:
                pass
""")
    assert LockOrderChecker().check_repo([f], REPO_ROOT) == []


# ---------------------------------------------------------------------------
# fault-point-registry
# ---------------------------------------------------------------------------


def _fault_fixture(tmp_path, points, use_points, test_spec):
    faults = _src("pkg/faults.py", f"""\
POINTS = frozenset({{{', '.join(repr(p) for p in points)}}})
""")
    calls = "\n".join(f"    maybe_fail({p!r})" for p in use_points)
    site = _src("pkg/site.py", f"""\
from .faults import maybe_fail

def verb():
{calls}
""")
    tests = tmp_path / "tests"
    tests.mkdir(exist_ok=True)
    (tests / "test_x.py").write_text(test_spec)
    return [faults, site], str(tmp_path)


def test_fault_points_all_good(tmp_path):
    files, root = _fault_fixture(
        tmp_path, ["a.b"], ["a.b"], 'SPEC = "a.b:error=1.0"\n')
    assert FaultPointChecker().check_repo(files, root) == []


def test_fault_points_flag_undeclared_unused_untested(tmp_path):
    files, root = _fault_fixture(
        tmp_path, ["a.b", "dead.point"], ["a.b", "typo.point"],
        'SPEC = "other:drop"\n')
    msgs = [f.message for f in FaultPointChecker().check_repo(files, root)]
    assert any("'typo.point' is used here but not declared" in m
               for m in msgs)
    assert any("'dead.point' is declared but no code site" in m
               for m in msgs)
    assert any("'a.b' is never exercised by any test" in m for m in msgs)


# ---------------------------------------------------------------------------
# metrics-doc-drift
# ---------------------------------------------------------------------------


def _metrics_fixture(tmp_path, code, docs):
    (tmp_path / "docs").mkdir(exist_ok=True)
    (tmp_path / "docs" / "operations.md").write_text(docs)
    return [_src("pkg/mod.py", code)], str(tmp_path)


def test_metrics_doc_in_sync_passes(tmp_path):
    files, root = _metrics_fixture(tmp_path, """\
from .trace import REGISTRY

def f(name):
    REGISTRY.counter("good_total", "help").inc()
    REGISTRY.gauge(f"family_{name}_rows").set(1)
""", "| `good_total` | docs |\n| `family_<name>_rows` | docs |\n")
    assert MetricsDocChecker().check_repo(files, root) == []


def test_metrics_doc_flags_both_directions(tmp_path):
    files, root = _metrics_fixture(tmp_path, """\
from .trace import REGISTRY

def f():
    REGISTRY.counter("undocumented_total", "help").inc()
""", "| `stale_metric_total` | docs for a ghost |\n")
    msgs = [f.message for f in MetricsDocChecker().check_repo(files, root)]
    assert any("'undocumented_total' is registered here but absent" in m
               for m in msgs)
    assert any("'stale_metric_total' but nothing" in m for m in msgs)


def test_trace_span_table_both_directions(tmp_path):
    files, root = _metrics_fixture(tmp_path, """\
from kcp_tpu import obs

def f(ctx, t0, t1):
    with obs.span("server.request"):
        pass
    obs.phase("stage", ctx, t0, t1)
    obs.record_span("ghostless.span", ctx, None, t0, t1 - t0)
""", "intro prose\n"
         "<!-- trace-spans:begin -->\n"
         "| `server.request` | docs |\n"
         "| `conv.stage` | docs |\n"
         "| `conv.undocumented_emitter` | stale row |\n"
         "<!-- trace-spans:end -->\n"
         "outside the region `other.token` is ignored\n")
    msgs = [f.message for f in MetricsDocChecker().check_repo(files, root)]
    # code -> docs: the record_span literal is missing from the table
    assert any("'ghostless.span' is recorded here" in m for m in msgs)
    # docs -> code: the stale table row has no emitter
    assert any("'conv.undocumented_emitter' but no" in m for m in msgs)
    # documented spans and out-of-region tokens are clean
    assert not any("server.request" in m or "conv.stage" in m
                   or "other.token" in m for m in msgs)


def test_trace_span_table_in_sync_passes(tmp_path):
    files, root = _metrics_fixture(tmp_path, """\
from kcp_tpu import obs

def f(ctx, t0, t1):
    obs.phase("tick", ctx, t0, t1)
""", "<!-- trace-spans:begin -->\n"
         "| `conv.tick` | the reconcile dispatch |\n"
         "<!-- trace-spans:end -->\n")
    assert MetricsDocChecker().check_repo(files, root) == []


def test_metrics_doc_annotate_sites_count(tmp_path):
    # obs.annotate("...") names a host annotation on the profiler's
    # timeline: held to the trace-span table like obs.span, an f-string
    # name as a glob that a `<placeholder>` row documents
    files, root = _metrics_fixture(tmp_path, """\
from kcp_tpu import obs

def f(name):
    with obs.annotate("kcp.undocumented"):
        pass
    with obs.annotate(f"kcp.tick.{name}"):
        pass
    with obs.annotate("kcp.tick", tick=1):
        pass
""", "<!-- trace-spans:begin -->\n"
         "| `kcp.tick` | one tick |\n"
         "| `kcp.tick.<phase>` | its phases |\n"
         "| `kcp.ghost` | stale row |\n"
         "<!-- trace-spans:end -->\n")
    msgs = [f.message for f in MetricsDocChecker().check_repo(files, root)]
    assert any("'kcp.undocumented' is recorded here" in m for m in msgs)
    assert any("'kcp.ghost' but no" in m for m in msgs)
    assert not any("'kcp.tick" in m for m in msgs), msgs


# ---------------------------------------------------------------------------
# waivers
# ---------------------------------------------------------------------------


def test_waiver_silences_named_rule_only():
    text = ("def f(store):\n"
            "    snap = store.get_snapshot('cm', 'c', 'x')\n"
            "    snap['x'] = 1  # kcp-lint: disable=cow-mutation"
            " -- fixture: this store is private to one test\n")
    waivers, findings = parse_waivers(text, "w.py")
    assert not findings and 3 in waivers
    f = SourceFile("w.py", text, ast.parse(text), waivers)
    raw = CowChecker().check(f)
    assert len(raw) == 1
    w = waivers[3]
    assert raw[0].rule in w.rules


def test_waiver_without_justification_is_a_finding():
    text = "x = 1  # kcp-lint: disable=cow-mutation\n"
    _waivers, findings = parse_waivers(text, "w.py")
    assert len(findings) == 1 and findings[0].rule == "waiver-syntax"
    assert "justification" in findings[0].message


def test_prose_mentioning_the_tool_is_not_a_waiver():
    text = '"""docs discuss kcp-lint: disable= semantics here"""\nx = 1\n'
    waivers, findings = parse_waivers(text, "w.py")
    assert waivers == {} and findings == []


# ---------------------------------------------------------------------------
# the repo itself is clean (the CI lint gate, enforced from tier-1 too)
# ---------------------------------------------------------------------------


def test_repo_lint_is_clean():
    report = run_lint(REPO_ROOT)
    assert report.ok, "\n" + report.render()
    # every waiver in the tree is both used and justified
    assert report.unused_waivers == [], report.unused_waivers
    for fi in report.waived:
        assert fi.justification, fi
