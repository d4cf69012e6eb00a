#!/usr/bin/env bash
# Tier-1 CI gate: the full tier-1 test suite (ROADMAP.md's verify line)
# PLUS the audit smoke (scripts/audit_smoke.py: one shadow-replay round
# + one injected-corruption detection, nonzero on a miss) PLUS the
# broadcast smoke (scripts/broadcast_smoke.py: encode-once fan-out,
# relay-hop audit, serve publish tee) PLUS the chip smoke's dry run
# (chip_smoke.py --cpu-tiny: the eight configs through ServeFrontend and
# every Pallas kernel at toy sizes, labelled cpu — the real run needs
# the chip) PLUS the continuity soak smoke
# (benchmarks/continuity_bench.py --smoke: seeded chaos with
# byte-identical reassembly + front-door kill -9 recovery, ~10 s)
# PLUS the auto-plan gate (benchmarks/plan_bench.py --check: the
# committed PLAN_BENCH.json must still clear every acceptance gate —
# planned>=1.15x default, chosen within 5% of exhaustive best at <=1/3
# live-profiled, warm plan step <50 ms, deterministic predictive
# replay spawning before the first refusal)
# PLUS the perf-regression sentinel (benchmarks/sentinel.py --quick).
# Exit nonzero on a test failure, an audit/broadcast/continuity miss,
# a stale plan artifact, OR a measured perf regression —
# the same bar the GitHub Actions workflow (.github/workflows/ci.yml)
# enforces on every push.
set -uo pipefail
cd "$(dirname "$0")/.."

echo "== tier-1 test suite =="
set -o pipefail
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)"
if [ "$rc" -ne 0 ]; then
    echo "ci_tier1: TEST FAILURE (pytest rc=$rc)" >&2
    exit "$rc"
fi

echo "== audit smoke (shadow replay + injected-corruption detection) =="
JAX_PLATFORMS=cpu python scripts/audit_smoke.py
arc=$?
if [ "$arc" -ne 0 ]; then
    echo "ci_tier1: AUDIT MISS (audit_smoke rc=$arc)" >&2
    exit "$arc"
fi

echo "== broadcast smoke (encode-once fan-out + relay-hop audit) =="
JAX_PLATFORMS=cpu python scripts/broadcast_smoke.py
brc=$?
if [ "$brc" -ne 0 ]; then
    echo "ci_tier1: BROADCAST MISS (broadcast_smoke rc=$brc)" >&2
    exit "$brc"
fi

echo "== chip smoke, CPU dry run (chip_smoke.py --cpu-tiny) =="
JAX_PLATFORMS=cpu python chip_smoke.py --cpu-tiny
ksrc=$?
if [ "$ksrc" -ne 0 ]; then
    echo "ci_tier1: CHIP SMOKE DRY RUN FAILED (chip_smoke rc=$ksrc)" >&2
    exit "$ksrc"
fi

echo "== continuity soak smoke (seeded chaos + front-door crash recovery) =="
JAX_PLATFORMS=cpu python benchmarks/continuity_bench.py --smoke
crc=$?
if [ "$crc" -ne 0 ]; then
    echo "ci_tier1: CONTINUITY MISS (continuity_bench rc=$crc)" >&2
    exit "$crc"
fi

echo "== auto-plan gate (committed PLAN_BENCH.json acceptance) =="
JAX_PLATFORMS=cpu python benchmarks/plan_bench.py --check
prc=$?
if [ "$prc" -ne 0 ]; then
    echo "ci_tier1: PLAN GATE MISS (plan_bench --check rc=$prc)" >&2
    exit "$prc"
fi

echo "== perf-regression sentinel =="
JAX_PLATFORMS=cpu python benchmarks/sentinel.py --quick
src=$?
if [ "$src" -ne 0 ]; then
    echo "ci_tier1: PERF REGRESSION (sentinel rc=$src)" >&2
    exit "$src"
fi

echo "ci_tier1: clean"
