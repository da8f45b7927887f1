#!/usr/bin/env bash
# Rehearse the benchmark on the CPU, tiny, before any chip call: the unit
# tests of the yardstick, then both kinds of run of every cell, then the
# controls. Costs no chip time; says nothing about speed (every line it
# prints carries "platform": "cpu").
#
#   bash benchmarks/rehearse.sh          # everything (about eight minutes)
#   bash benchmarks/rehearse.sh quick    # unit tests + one run of each cell
set -euo pipefail
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu
python3 -m pytest benchmarks/tests -q -p no:cacheprovider \
    --ignore=benchmarks/tests/test_rehearsal.py
cells=$(python3 -c "import json; print(' '.join(w['name'] for w in json.load(open('BENCHMARK.json'))['workloads']))")
for cell in $cells; do
  for trace in 0 1; do
    [ "${1:-}" = quick ] && [ "$trace" = 1 ] && continue
    echo "== $cell --trace $trace"
    python3 benchmarks/run.py --workload "$cell" --seed $((2147483648 + RANDOM)) \
        --seconds 4 --trace "$trace" --platform cpu --rehearse 2>&1 \
      | grep -v cpu_aot_loader | grep -E '^\{|FAILED|Traceback|Error' || true
  done
done
[ "${1:-}" = quick ] && exit 0
python3 -m pytest benchmarks/tests/test_rehearsal.py -q -p no:cacheprovider
