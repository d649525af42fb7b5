#!/usr/bin/env bash
# A pre-commit gate on a CPU: ROADMAP.md's tier-1 command, then three
# tools/loadtest.py correctness smokes of the default-off tiers (device
# tier + compiled shapes + ingest tail; result cache; auto-RCA under
# injected faults): the only end-to-end gate on them, since no benchmark
# cell turns them on.
# It measures nothing: a CPU run says whether answers are right and what
# the program counts. Times come from benchmark/run.py on the chip.
#
# Exit code: pytest's own (nonzero on any F/E, including collection
# errors), else the first smoke's that failed. The DOTS_PASSED line
# mirrors the driver's pass count.
#
# Deeper, when touching the ingest/query/SLO planes:
#   python tools/loadtest.py --duration 120 --rate 10 --vulture
# runs the mixed workload with the continuous-verification prober beside
# it and gates on vulture correctness at drain (zero notfound/incorrect
# probes) and the freshness SLO.
set -uo pipefail
cd "$(dirname "$0")/.."

rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
  --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly \
  2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)"

# Hot-tier + compiled-tier + ingest-plane smoke (ISSUES 16/17/18): tiny
# loadtest with a repeat-query arm (device-resident tier serves repeats
# without re-shipping pages: h2d flat, resident hits climbing,
# transfer-stage << kernel-stage), a literal-rotation arm (the compiled
# tier's shape cache re-enters the traced executable across
# literal/window swaps: zero retraces, shape hits climbing, fused path
# dispatching), and a write-burst arm (device encode armed fleet-wide,
# just-cut tails resident: standing-fold + live-tail h2d flat while
# avoided bytes climb, device-encoded pages flushing, zero acked loss).
# Generous rss limit: a 6s run is all startup transient.
hot_rc=0
if [ "$rc" -eq 0 ]; then
  timeout -k 10 420 python tools/loadtest.py --duration 6 --rate 1 \
    --skip-sweep --slo-scale 8 --rss-growth-limit 3.0 --hot 6 --shapes 4 \
    --ingest-heavy \
    >/tmp/_t1_hot.json 2>/tmp/_t1_hot.log
  hot_rc=$?
  if [ "$hot_rc" -ne 0 ]; then
    echo "check_green: hot/compiled-tier smoke RED (exit $hot_rc)" >&2
    tail -5 /tmp/_t1_hot.log >&2
  else
    echo "check_green: hot/compiled-tier smoke green" >&2
  fi
fi

# Result-cache smoke (ISSUE 19): its own cluster with the cache forced
# on fleet-wide — it must NOT share the compiled-shapes cluster, because
# the cached metrics path answers before the compiled tier and would
# starve that arm's gates. The repeat arm fires one frozen search +
# query_range + provably-empty search cold, then 5 warm repeats, gated
# on bit-identical responses, hits climbing with misses flat, per-iter
# inspected bytes collapsing, and zero incorrect negative vetoes.
rcache_rc=0
if [ "$rc" -eq 0 ]; then
  timeout -k 10 420 python tools/loadtest.py --duration 5 --rate 1 \
    --skip-sweep --slo-scale 8 --rss-growth-limit 3.0 --repeat 5 \
    >/tmp/_t1_rcache.json 2>/tmp/_t1_rcache.log
  rcache_rc=$?
  if [ "$rcache_rc" -ne 0 ]; then
    echo "check_green: result-cache smoke RED (exit $rcache_rc)" >&2
    tail -5 /tmp/_t1_rcache.log >&2
  else
    echo "check_green: result-cache smoke green" >&2
  fi
fi

# Auto-RCA fault campaign (ISSUE 20): the chaos suite as the RCA
# plane's ground-truth generator. Two sequential single-binary
# clusters, each dogfooding vulture -> SLO burn -> incident engine: a
# TEMPO_TPU_FAULTS-seeded arm must open >=1 incident with EVERY
# unsuppressed cause == backend_fault (the injected truth), and a
# fault-free soak must open ZERO (the typed handoff dip never pages).
rca_rc=0
if [ "$rc" -eq 0 ]; then
  timeout -k 10 420 python tools/loadtest.py --rca \
    >/tmp/_t1_rca.json 2>/tmp/_t1_rca.log
  rca_rc=$?
  if [ "$rca_rc" -ne 0 ]; then
    echo "check_green: auto-RCA campaign RED (exit $rca_rc)" >&2
    tail -5 /tmp/_t1_rca.log >&2
  else
    echo "check_green: auto-RCA campaign green" >&2
  fi
fi

if [ "$rc" -ne 0 ]; then
  echo "check_green: RED (pytest exit $rc)" >&2
elif [ "$hot_rc" -ne 0 ]; then
  echo "check_green: RED (hot/compiled-tier smoke exit $hot_rc)" >&2
  rc=$hot_rc
elif [ "$rcache_rc" -ne 0 ]; then
  echo "check_green: RED (result-cache smoke exit $rcache_rc)" >&2
  rc=$rcache_rc
elif [ "$rca_rc" -ne 0 ]; then
  echo "check_green: RED (auto-RCA campaign exit $rca_rc)" >&2
  rc=$rca_rc
else
  echo "check_green: green" >&2
fi
exit "$rc"
