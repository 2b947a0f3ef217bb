#!/usr/bin/env bash
# Tier-1 verification (see ROADMAP.md): the full test suite on CPU.
#
#   scripts/tier1.sh [--bench-smoke] [extra pytest args...]
#
# Legs:
#   0. doc drift: scripts/check_docs.py (README + docs/ paths and flags);
#   1. the full suite on the default (single-device) topology;
#   2. static program audit + obs dispatch-audit smoke vs the committed
#      ANALYSIS.json / OBS.json baselines;
#   3. the sharded-warehouse suite re-run under a forced 8-device host
#      platform, where ShardedStore gets a real ('shard',) mesh and
#      queries/ingests execute as ONE shard_map dispatch with collective
#      merges (on one device the same tests cover the stacked fallback),
#      plus the audit and obs smoke on that topology.
#
# --bench-smoke additionally runs the fused-ingest, warehouse, sharded-
# warehouse, standing-query, and multi-stream benchmarks in their
# --tiny configurations after the tests, so none of the benchmark entry
# points can silently rot.
#
# Honors an existing XLA_FLAGS; otherwise forces a single host device so
# smoke tests see a deterministic topology (the sharding tests fork their
# own 8-device subprocesses).
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

BENCH_SMOKE=0
args=()
for a in "$@"; do
  if [[ "$a" == "--bench-smoke" ]]; then
    BENCH_SMOKE=1
  else
    args+=("$a")
  fi
done

python -m pytest -x -q "${args[@]+"${args[@]}"}"

echo "== doc drift check (README + docs/ vs the tree) =="
python scripts/check_docs.py

echo "== static program audit (jaxpr/HLO/source) vs ANALYSIS.json =="
# every registered engine must audit clean, and no engine's dispatch
# count may grow vs the committed baseline (generated at 1 device; the
# compare skips dispatch deltas automatically on other topologies)
AUDIT_OUT="$(mktemp)"
python -m repro.analysis --json "$AUDIT_OUT" --compare ANALYSIS.json
rm -f "$AUDIT_OUT"

echo "== obs dispatch-audit smoke vs OBS.json =="
# run every registry engine cold and warm (1 warm rep) and gate vs the
# committed baseline: any new executable / recompile / host transfer
# fails
OBS_OUT="$(mktemp)"
python -m repro.obs --smoke --json "$OBS_OUT" --compare OBS.json
rm -f "$OBS_OUT"

echo "== sharded warehouse suite on 8 forced host devices =="
# appended last: XLA flag parsing is last-wins, so this overrides any
# device-count already in XLA_FLAGS (e.g. CI's =1) for this leg only
XLA_FLAGS="${XLA_FLAGS:+$XLA_FLAGS }--xla_force_host_platform_device_count=8" \
  python -m pytest -x -q tests/test_sharded_warehouse.py \
    tests/test_sharded_properties.py tests/test_warehouse_agg_pallas.py \
    tests/test_standing.py tests/test_standing_properties.py \
    tests/test_analysis.py tests/test_pool_elastic.py

echo "== static program audit on 8 forced host devices (violations only) =="
# the shard_map engines compile with real collectives here; any
# violation (unbalanced collective, clip scatter, callback) still fails
AUDIT_OUT="$(mktemp)"
XLA_FLAGS="${XLA_FLAGS:+$XLA_FLAGS }--xla_force_host_platform_device_count=8" \
  python -m repro.analysis --json "$AUDIT_OUT"
rm -f "$AUDIT_OUT"

echo "== obs dispatch-audit smoke on 8 forced host devices =="
# --compare on a different topology skips per-engine gates but still
# proves the audit runs with real collectives
OBS_OUT="$(mktemp)"
XLA_FLAGS="${XLA_FLAGS:+$XLA_FLAGS }--xla_force_host_platform_device_count=8" \
  python -m repro.obs --smoke --json "$OBS_OUT" --compare OBS.json
rm -f "$OBS_OUT"

if [[ "$BENCH_SMOKE" == "1" ]]; then
  for bench in fused_ingest_bench warehouse_bench sharded_warehouse_bench \
               standing_query_bench multi_stream_bench pool_scale_bench; do
    echo "== bench smoke: ${bench} --tiny =="
    devflags="${XLA_FLAGS:-}"
    if [[ "$bench" == "sharded_warehouse_bench" ]]; then
      # runs on the devices it is given: 8 forced host devices here
      devflags="${devflags:+$devflags }--xla_force_host_platform_device_count=8"
    fi
    XLA_FLAGS="$devflags" PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}" \
      python "benchmarks/${bench}.py" --tiny
  done
  echo "== bench smoke: examples/vetl_observe.py (tiny traced run) =="
  python examples/vetl_observe.py
  echo "== bench smoke: examples/vetl_pool_scale.py (elastic pool walkthrough) =="
  python examples/vetl_pool_scale.py
fi
