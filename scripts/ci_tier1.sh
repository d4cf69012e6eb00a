#!/usr/bin/env bash
# Tier-1 CI gate: the full tier-1 test suite (ROADMAP.md's verify line)
# PLUS the audit smoke (scripts/audit_smoke.py: one shadow-replay round
# + one injected-corruption detection, nonzero on a miss) PLUS the
# broadcast smoke (scripts/broadcast_smoke.py: encode-once fan-out,
# relay-hop audit, serve publish tee) PLUS the chip smoke's dry run
# (chip_smoke.py --cpu-tiny: the eight configs through ServeFrontend and
# every Pallas kernel at toy sizes, labelled cpu — the real run needs
# the chip). Exit nonzero on a test failure or an audit/broadcast/chip
# smoke miss. No step times anything: speed is the chip's to say
# (BENCHMARK.json, PERF_LEDGER.jsonl).
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

echo "ci_tier1: clean"
