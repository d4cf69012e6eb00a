"""Report what MEASURED_DEFAULTS updates the A/B tables on disk imply.

After benchmarks/run_table.py lands new ``impl_comparisons`` rows, run
this to see — in one screen — which declarations in
``dvf_tpu/ops/registry.py`` agree, which have NEWER agreeing data (bump
``as_of``), and which have newer CONTRADICTING data (flip the winner +
bump ``as_of``). Report-only: the declarations stay hand-edited on
purpose — a human reads the fps margins before a default flips.

Usage: python benchmarks/fold_winners.py
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

TABLES = {
    "tpu": os.path.join(REPO, "benchmarks", "BENCH_TABLE.json"),
    "cpu": os.path.join(REPO, "benchmarks", "cpu", "BENCH_TABLE.json"),
}


def main() -> int:
    from dvf_tpu.ops.registry import MEASURED_DEFAULTS

    docs = {}
    for backend, path in TABLES.items():
        try:
            with open(path) as f:
                docs[backend] = json.load(f)
        except (OSError, json.JSONDecodeError):
            docs[backend] = {}

    pending = 0
    for key, entry in sorted(MEASURED_DEFAULTS.items()):
        for backend in TABLES:
            comp = (docs[backend].get("impl_comparisons", {})
                    .get(entry["comparison"]))
            if not isinstance(comp, dict) or comp.get("winner") in (None,
                                                                    "n/a"):
                continue
            if bool(comp.get("forced_cpu", False)) != (backend == "cpu"):
                continue
            if any(isinstance(v, dict) and "error" in v
                   for v in comp.values()):
                print(f"  {key}/{backend}: comparison has an errored leg — "
                      f"not foldable")
                continue
            winner = comp["winner"]
            stamp = comp.get("captured_utc", "")
            declared = entry["winners"].get(backend)
            expected = entry["label_to_impl"].get(winner)
            as_of = entry.get("as_of", {}).get(backend, "")
            fps = {k: v.get("fps") for k, v in comp.items()
                   if isinstance(v, dict) and "fps" in v}
            if declared != expected:
                state = "FOLD: flip winner + bump as_of"
            elif not as_of:
                state = "RECORD: agrees but no as_of — record provenance"
            elif stamp <= as_of:
                state = "OK"
            else:
                state = "OK (newer, agrees — bump as_of)"
            if state != "OK":
                pending += 1
            print(f"{key}/{backend}: declared={declared!r} committed-winner="
                  f"{winner!r}->{expected!r} at {stamp[:19] or '?'} "
                  f"(as_of {as_of[:19] or 'never'}) {fps}  [{state}]")
    print(f"\n{pending} declaration(s) need attention." if pending
          else "\nAll declarations current.")
    return 0


if __name__ == "__main__":
    sys.exit(main())
