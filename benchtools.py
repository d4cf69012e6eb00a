"""Helpers shared by the benchmark scripts and the tests.

Deliberately free of jax (and dvf_tpu) imports: benchmarks/run_table.py
orchestrates timeout-bounded children and must stay off the chip itself —
a chip belongs to one process at a time.
"""

from __future__ import annotations

import json
import os
import subprocess
from typing import Optional, Tuple


def run_cmd(cmd, env, timeout, cwd=None) -> Tuple[int, str, str]:
    """Run a child process; (rc, stdout, stderr). rc=-9 on timeout."""
    try:
        p = subprocess.run(
            cmd, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            timeout=timeout, text=True, cwd=cwd,
        )
        return p.returncode, p.stdout, p.stderr
    except subprocess.TimeoutExpired as e:
        def _s(x):
            if x is None:
                return ""
            return x.decode(errors="replace") if isinstance(x, bytes) else x
        return -9, _s(e.stdout), _s(e.stderr) + f"\n[killed: timeout after {timeout}s]"


def last_json_line(out: str) -> Optional[dict]:
    """Parse the last JSON-object line of a child's stdout."""
    for line in reversed(out.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return None


def tail(s: str, n: int = 12) -> str:
    lines = [ln for ln in s.strip().splitlines() if ln.strip()]
    return "\n".join(lines[-n:])


def git_rev(repo_dir: Optional[str] = None) -> str:
    """Short HEAD rev for measurement provenance (one shared copy — the
    persisted code_rev fields across bench.py / run_table / neural_layers
    must agree on their format)."""
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=repo_dir or os.path.dirname(os.path.abspath(__file__)),
            stdout=subprocess.PIPE, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def free_port() -> int:
    """An OS-assigned localhost TCP port (reference wire-protocol tests
    and the head-to-head bench both bind throwaway ZMQ pairs)."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def sentinel_record(bench: str, metrics: dict) -> dict:
    """The NORMALIZED bench record every writer emits for the
    perf-regression sentinel (benchmarks/sentinel.py).

    ``metrics`` maps metric name → spec::

        {"value": <measured>, "better": "higher"|"lower",
         "band_frac": <tolerated relative drift>,
         "hard_min"/"hard_max": <absolute gate, optional>}

    The sentinel diffs a fresh quick-mode run's record against the
    committed baseline's: a metric is a REGRESSION when it moved in the
    "worse" direction by more than ``band_frac`` relative, or crossed
    its absolute gate. Only steal-cancelled metrics belong here —
    ratios from concurrent A/B legs, speedups, overhead fractions —
    never absolute fps, which measures the hypervisor, not the code.
    """
    out = {}
    for name, spec in metrics.items():
        band = spec.get("band_frac", 0.25)
        row = {"value": spec.get("value"),
               "better": spec.get("better", "higher"),
               # band_frac None = no relative banding (absolute gates
               # only — e.g. a speedup whose magnitude varies 100×
               # between quick and full legs but must stay over target)
               "band_frac": float(band) if band is not None else None}
        if spec.get("abs_band") is not None:
            row["abs_band"] = float(spec["abs_band"])
        for gate in ("hard_min", "hard_max"):
            if spec.get(gate) is not None:
                row[gate] = float(spec[gate])
        out[name] = row
    return {"bench": bench, "metrics": out}


def ab_comparison(legs, measure, *, prior=None, keep_leg=None, meta=None,
                  on_leg=None, log=None):
    """One incremental A/B comparison — the leg machinery shared by
    benchmarks/run_table.py's impl-comparison phase and the auto-planner's
    candidate search (``dvf_tpu.control.planner``): bench rounds and
    production plan search must rank legs and seed partial priors the
    same way, or their winners are not comparable.

    - ``legs``: ``[(label, payload), ...]`` measured in order by
      ``measure(label, payload) -> dict`` (``{"fps": ...}`` on success,
      ``{"error": ...}`` on failure — an error leg is recorded, not
      raised).
    - ``prior``: an earlier partial comparison dict; legs whose prior
      entry passes ``keep_leg(entry)`` are seeded and not re-measured
      (the caller decides whether the prior's run mode/stamp qualifies
      it at all).
    - ``meta``: provenance merged into the comparison up front
      (code_rev, run mode).
    - ``on_leg(comp, label)``: called after every measured leg — the
      per-leg persist hook (a killed run keeps its finished legs).

    Returns the comparison; ``comp["winner"]`` is the label with the
    highest ``fps`` (``"n/a"`` when every leg errored)."""
    comp = dict(meta or {})
    prior = prior or {}
    for label, _ in legs:
        entry = prior.get(label)
        if keep_leg is not None and isinstance(entry, dict) \
                and keep_leg(entry):
            comp[label] = entry
            if log:
                log(f"{label}: kept from partial prior run")
    for label, payload in legs:
        if label in comp:
            continue
        comp[label] = measure(label, payload)
        if on_leg:
            on_leg(comp, label)
    fps = {k: v.get("fps", 0) for k, v in comp.items()
           if isinstance(v, dict) and "fps" in v}
    comp["winner"] = max(fps, key=fps.get) if any(fps.values()) else "n/a"
    return comp


def load_reference_module(filename: str, ref_dir: str = "/root/reference"):
    """Import one of the reference's modules from its read-only checkout
    (never copied). Returns the loaded module."""
    import importlib.util

    path = os.path.join(ref_dir, filename)
    spec = importlib.util.spec_from_file_location(
        "ref_" + filename.removesuffix(".py"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
