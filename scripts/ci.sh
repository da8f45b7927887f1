#!/usr/bin/env bash
# CI pipeline — the analog of the reference's committed workflows
# (.github/workflows/ci.yaml: vet + race-checked tests; demos.yaml:
# golden demo runs). A fresh checkout runs this green; every stage is
# CPU-pinned (tests via conftest, demo via DEMO_JAX_PLATFORM, dryrun via
# its XLA_FLAGS guard). Correctness only: no stage holds a speed — the
# benchmark (benchmarks/, on the chip) and PERF_LEDGER.jsonl are the
# ratchet for that.
#
# Usage: scripts/ci.sh [--fast]   (--fast skips the demo + dryrun)
set -euo pipefail
cd "$(dirname "$0")/.."

# what the stages write (lint report, scenario scorecards) stays out of
# the checkout
out=$(mktemp -d)
trap 'rm -rf "$out"' EXIT

fast=0
[[ "${1:-}" == "--fast" ]] && fast=1

echo "== vet: syntax-compile every tracked python file"
python -m compileall -q kcp_tpu tests contrib chip_smoke.py __graft_entry__.py

if command -v ruff >/dev/null 2>&1; then
    echo "== lint: ruff (present on this host)"
    ruff check kcp_tpu tests chip_smoke.py __graft_entry__.py
else
    echo "== lint: ruff not installed here, skipped (vet stage above still gates syntax)"
fi

echo "== kcp-lint: contract checkers (CoW / frozen-bytes / async / lock-order / fault points / metrics docs)"
# zero active findings required; waivers are counted and reported so
# exemptions stay visible in every CI log (scripts/lint.py --help)
python scripts/lint.py --format json > "$out/lint.json" || {
    python scripts/lint.py; exit 1; }
python -c '
import json, sys
r = json.load(open(sys.argv[1]))
assert r["ok"], r["summary"]
for w in r["waived"]:
    print("  waived: %s:%s %s -- %s"
          % (w["path"], w["line"], w["rule"], w["justification"]))
print("kcp-lint ok: 0 findings | %d waiver(s), all justified | %d files"
      % (r["summary"]["waived"], r["files_checked"]))
' "$out/lint.json"

echo "== typecheck: mypy baseline gate for kcp_tpu/analysis + kcp_tpu/utils"
scripts/typecheck.sh

echo "== native: build libkcpnative.so + kcptok extension"
make -s -C native
make -s -C native kcptok.so

echo "== tests: full suite, race-checked (KCP_RACE=1 via conftest)"
python -m pytest tests/ -q

echo "== chaos: seeded KCP_FAULTS smoke (store 5xx + one device-step raise)"
# the spec grammar is documented in kcp_tpu/faults.py; the test asserts
# tier-1 convergence with zero lost patches under the injected schedule
KCP_FAULTS='store.put:error=0.05;device.step:raise@tick=5' \
    KCP_FAULTS_SEED=1337 \
    python -m pytest tests/test_faults.py::test_ci_chaos_smoke -q

echo "== sanitize: tier-1 differential fuzzes under KCP_SANITIZE=1 (freeze proxies + byte verify + lock tracking)"
# the store-index and encode-cache equivalence fuzzes must stay green
# with every snapshot frozen and every cache hit re-verified — plus the
# deliberate-violation drills in tests/test_sanitize.py
KCP_SANITIZE=1 python -m pytest \
    tests/test_sanitize.py tests/test_store_index.py \
    tests/test_encode_cache.py -q

echo "== trace: crud-churn scenario under always-on tracing (scorecard carries assembled traces)"
# the scenario engine attaches the slowest assembled traces per phase
# to the scorecard: assert at least one fully-assembled write trace
# (driver conv.write + server span + store commit + fan-out) rode along
KCP_TRACE=1 KCP_TRACE_SAMPLE=1 JAX_PLATFORMS=cpu python scripts/scenarios.py run \
    --scenarios crud-churn --seed 7 --scale 0.25 --out "$out/trace_smoke.json"
python -c '
import json, sys
r = json.load(open(sys.argv[1]))
s = r["scenarios"][0]
assert s["passed"], s["slos"]
traces = s.get("traces") or {}
attached = [t for ph in traces.values() for t in ph]
assert attached, "no traces attached to the scorecard"
names = set()
for t in attached:
    names.update(t.get("names", []))
for need in ("conv.write", "server.request", "store.commit", "store.fanout"):
    assert need in names, (need, sorted(names))
print("scenario trace smoke ok: %d attached traces across %d phases; %d distinct span names"
      % (len(attached), len(traces), len(names)))
' "$out/trace_smoke.json"

echo "== scenarios: seeded end-to-end chaos smoke (churn + reconnect storm + kill-the-primary drill)"
# reduced-scale subset of the scenario harness (scripts/scenarios.py):
# real topologies over real HTTP, hard SLO floors (zero lost acked
# writes, zero lost watch events, convergence bounds, failover
# re-homing) asserted by the engine itself — exit 1 on any miss. The
# full catalog (incl. rolling-restart drain-vs-kill) runs via
# `scripts/scenarios.py run --all --seed 42`.
JAX_PLATFORMS=cpu python scripts/scenarios.py run \
    --scenarios crud-churn,reconnect-storm,kill-primary,ring-change-under-load,scale-out-under-load,partition-during-promotion \
    --seed 42 --scale 0.4 --out "$out/smoke.json"
python -c '
import json, sys
r = json.load(open(sys.argv[1]))
assert r["passed"], "scenario smoke failed"
for s in r["scenarios"]:
    miss = [row["name"] for row in s["slos"] if not row["passed"]]
    assert not miss, (s["name"], miss)
print("scenario smoke ok:", {s["name"]: s["schedule"]["hash"] for s in r["scenarios"]})
' "$out/smoke.json"

if [[ "$fast" == "0" ]]; then
    echo "== demo: both golden scenarios, checked against committed output"
    python contrib/demo/run_demo.py all --check

    echo "== dryrun: full serving step jit + one tick on a virtual 8-device mesh"
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
        python -c "from __graft_entry__ import dryrun_multichip; dryrun_multichip(8)"
fi

echo "CI OK"
