"""Run the BASELINE.json benchmark table — incrementally.

Produces ``BENCH_TABLE.json`` (machine) and ``BENCH_TABLE.md`` (human) in
``--out-dir``: device-resident fps (+ HBM-roofline fraction and MFU on
TPU) and rate-controlled e2e latency per config, plus the Pallas-vs-jnp
implementation comparisons, with the faster implementation marked.

- **Incremental + mergeable**: results persist to BENCH_TABLE.json after
  EVERY leg, each row stamped with ``captured_utc`` and the git revision.
  A rerun loads the file and fills only rows that are missing, errored, or
  older than ``--min-fresh``.
- Each leg runs in its own bounded subprocess (this orchestrator stays
  off jax, so each child has the chip to itself): a hang or crash records
  an error entry (with timestamp, so the next session retries it) instead
  of killing the table.

Usage: python benchmarks/run_table.py [--cpu] [--out-dir benchmarks]
       [--timeout 420] [--quick] [--min-fresh ISO] [--only a,b] [--force]
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchtools import (  # noqa: E402
    ab_comparison,
    git_rev,
    last_json_line as _last_json,
    run_cmd,
    tail,
)

# cli.BENCH_CONFIGS keys in table order, with a workload scale: heavy
# configs (flow ~1.7 s/frame, style ~6.5 s/frame on CPU) get proportionally
# fewer iters/frames so every row fits the per-config timeout instead of
# ERRing — measured fps is per-frame, so fewer iters costs variance, not
# bias. On TPU the scales just make the fast rows faster.
TABLE = [
    ("invert_640x480", 1.0),
    ("invert_1080p", 1.0),
    ("gauss3_1080p", 0.5),
    ("gauss9_1080p", 0.35),
    ("sobel_bilateral_1080p", 0.35),
    ("flow_720p", 0.15),
    ("style_720p", 0.05),
    ("sr2x_540p", 0.2),
]

# Pallas vs jnp implementation A/Bs: bilateral alone, the fused
# sobel+bilateral chain (BASELINE configs[2]), the flow warp (gather vs
# bounded-displacement kernel), and the separable-conv lowering three-way
# (shifted-FMA vs XLA depthwise vs fused Pallas). On a forced-CPU run the
# Pallas kernels execute in interpret mode — mechanics only, not a perf
# datapoint.
COMPARISONS = {
    # name → (h, w, batch, [(impl_label, filter_name, cfg_dict)])
    # impl pinned: get_filter("bilateral") with no config resolves to the
    # measured per-backend winner, which on TPU IS the pallas kernel.
    "bilateral_1080p": (1080, 1920, 8, [
        ("jnp", "bilateral", {"impl": "jnp"}),
        ("pallas", "bilateral_pallas", {}),
    ]),
    # impl pinned explicitly: get_filter("sobel_bilateral") with no config
    # now resolves to the measured per-backend winner, which on CPU IS the
    # pallas program — an unpinned A/B would compare pallas to itself.
    "sobel_bilateral_1080p": (1080, 1920, 8, [
        ("jnp_chain", "sobel_bilateral", {"impl": "chain"}),
        ("pallas_fused", "sobel_bilateral_pallas", {}),
    ]),
    "flow_warp_720p": (720, 1280, 4, [
        ("gather", "flow_warp", {"warp_impl": "gather"}),
        ("pallas_warp", "flow_warp", {"warp_impl": "pallas"}),
    ]),
    "gauss9_1080p": (1080, 1920, 8, [
        ("shift", "gaussian_blur", {"ksize": 9, "impl": "shift"}),
        ("depthwise", "gaussian_blur", {"ksize": 9, "impl": "depthwise"}),
        ("pallas_fused", "gaussian_blur_pallas", {"ksize": 9}),
    ]),
    # The small-kernel half of BASELINE configs[1]: the ksize<9 default
    # ("shift") was assumed, not measured, until this A/B.
    "gauss3_1080p": (1080, 1920, 8, [
        ("shift", "gaussian_blur", {"ksize": 3, "impl": "shift"}),
        ("pallas_fused", "gaussian_blur_pallas", {"ksize": 3}),
    ]),
    # ALGORITHM-VARIANT comparison (not a numerics-identical impl swap,
    # so the registry never auto-defaults on its winner): the window that
    # averages Farneback's structure tensors. "gauss" = our default
    # (OPTFLOW_FARNEBACK_GAUSSIAN parity, 15-tap separable FMA); "box" =
    # cv2's flags=0 default, an O(1)-per-pixel running-sum filter —
    # 15× fewer window FLOPs, different (slightly blunter) flow.
    "flow_win_720p": (720, 1280, 4, [
        ("gauss_win", "flow_warp", {"warp_impl": "pallas",
                                    "win_type": "gaussian"}),
        ("box_win", "flow_warp", {"warp_impl": "pallas",
                                  "win_type": "box"}),
    ]),
    # APPROXIMATION-variant comparison (like flow_win_720p, no registry
    # auto-default): the 9 inner-loop warps of the 5-channel poly stacks
    # through the bounded Pallas shift warp vs exact XLA gathers. The
    # final-warp A/B already measured the same kernel 2.3× faster on one
    # 3-channel full-res warp; the inner loop is where most warp work is.
    "flow_inner_720p": (720, 1280, 4, [
        ("gather_inner", "flow_warp", {"warp_impl": "pallas",
                                       "inner_warp": "gather"}),
        ("pallas_inner", "flow_warp", {"warp_impl": "pallas",
                                       "inner_warp": "pallas"}),
    ]),
    # Tile-height sweeps for the two winning kernels with the most
    # roofline headroom (bilateral 0.30, fused sobel_bilateral 0.42 of
    # the HBM ceiling on-chip): tile_h sets the rows-per-program of the
    # (batch, H-tiles) grid and hence the DMA slab size and halo-refetch
    # overhead (halo rows are re-read once per tile: small tiles pay more
    # redundant HBM traffic, large tiles pay VMEM pressure and less
    # grid-level parallelism). 24 is what the auto-picker (_pick_tile_h,
    # target 32) currently chooses at H=1080; 8/40/120 bracket it with
    # the other 8-aligned divisors of 1080. A measured winner ≠ 24 gets
    # wired as the per-backend default tile target.
    "bilateral_tile_1080p": (1080, 1920, 8, [
        ("tile8", "bilateral_pallas", {"tile_h": 8}),
        ("tile24", "bilateral_pallas", {"tile_h": 24}),
        ("tile40", "bilateral_pallas", {"tile_h": 40}),
        ("tile120", "bilateral_pallas", {"tile_h": 120}),
    ]),
    "sobel_bilateral_tile_1080p": (1080, 1920, 8, [
        ("tile8", "sobel_bilateral_pallas", {"tile_h": 8}),
        ("tile24", "sobel_bilateral_pallas", {"tile_h": 24}),
        ("tile40", "sobel_bilateral_pallas", {"tile_h": 40}),
        ("tile120", "sobel_bilateral_pallas", {"tile_h": 120}),
    ]),
    # gauss9's last A/B (2026-07-31, file removed in PR 21) had the
    # Pallas kernel at 186 fps vs shift's 1022 — a suspect capture (0.043
    # of the HBM ceiling) or a real kernel deficiency. This sweep
    # disambiguates: if some tile_h recovers the kernel to
    # shift-competitive, the 186 was geometry; if all tiles are slow,
    # shift stays the default with a measured reason.
    "gauss9_tile_1080p": (1080, 1920, 8, [
        ("tile8", "gaussian_blur_pallas", {"ksize": 9, "tile_h": 8}),
        ("tile24", "gaussian_blur_pallas", {"ksize": 9, "tile_h": 24}),
        ("tile40", "gaussian_blur_pallas", {"ksize": 9, "tile_h": 40}),
        ("tile120", "gaussian_blur_pallas", {"ksize": 9, "tile_h": 120}),
    ]),
    # Exact space-to-depth conv rewrite for ESPCN (VERDICT r4 item 5;
    # models.layers.conv2d_s2d; static model in models.analysis projects
    # 2-3x per layer). The winner wires into MEASURED_DEFAULTS["espcn_fast"].
    # (The style net's pair is gone with its flag: PERF.md §6, PR 28.)
    "sr_fast_540p": (540, 960, 8, [
        ("ref", "super_resolution", {"fast_convs": False}),
        ("fast", "super_resolution", {"fast_convs": True}),
    ]),
    # bf16-vs-f32 model compute dtype on the flagship neural config (the
    # VERDICT's bf16 ask, quantified): bf16 is the committed default; this
    # measures what it buys at these shapes. ALGORITHM-variant style
    # comparison (numerics differ) — no registry auto-default on it.
    "style_dtype_720p": (720, 1280, 8, [
        ("bf16", "style_transfer", {}),
        ("f32", "style_transfer", {"dtype": "float32"}),
    ]),
}


def _now() -> str:
    return datetime.datetime.now(datetime.timezone.utc).isoformat()


def _log(msg: str) -> None:
    print(f"[table] {msg}", file=sys.stderr, flush=True)


def _run(cmd, env, timeout):
    return run_cmd(cmd, env, timeout, cwd=REPO)


def bench_config(config: str, env, timeout: float, iters: int, frames: int,
                 e2e: bool, batch: int = 0) -> dict:
    cmd = [sys.executable, "-m", "dvf_tpu", "bench", "--config", config,
           "--iters", str(iters), "--frames", str(frames)]
    if batch:
        cmd += ["--batch", str(batch)]
    if e2e:
        cmd.append("--e2e")
    rc, out, err = _run(cmd, env, timeout)
    parsed = _last_json(out)
    if parsed is None:
        return {"error": f"rc={rc}: {tail(err, 6)}"}
    return parsed


def bench_impl(fname: str, cfg: dict, iters: int, batch: int, h: int, w: int,
               env, timeout: float) -> dict:
    kw = "".join(f", {k}={v!r}" for k, v in cfg.items())
    code = (
        "import json, sys\n"
        "from dvf_tpu.cli import _force_platform\n"
        "_force_platform()\n"
        "from dvf_tpu.benchmarks import bench_device_resident, roofline_fields\n"
        "from dvf_tpu.ops import get_filter\n"
        f"r = bench_device_resident(get_filter({fname!r}{kw}), {iters}, {batch}, {h}, {w})\n"
        "out = {'fps': round(r['fps'],1), 'ms_per_frame': round(r['ms_per_frame'],4)}\n"
        "out.update({k: r[k] for k in ('platform', 'device_kind', 'n_devices')})\n"
        "out.update(roofline_fields(r))\n"
        "print(json.dumps(out))\n"
    )
    rc, out, err = _run([sys.executable, "-c", code], env, timeout)
    parsed = _last_json(out)
    # 15 lines: JAX's traceback filtering puts the actual exception several
    # lines above its "internal frames removed" banner — 4 lines captured
    # only the banner for the round-3 flow_warp failure.
    return parsed if parsed else {
        "error": f"rc={rc}: " + "\n".join(err.strip().splitlines()[-15:])
    }


# ---------------------------------------------------------------------------
# Persistence


# The only top-level keys this script writes; anything else in a loaded
# file is legacy (pre-incremental: global timestamp/iters/frames, the
# bilateral_impl_comparison alias) and would be republished under a fresh
# updated_utc if preserved — superseded-methodology numbers stamped
# current. Dropped on load instead.
_DOC_KEYS = ("configs", "impl_comparisons", "updated_utc",
             "platform_forced_cpu", "wall_s_last_session")


def load_doc(json_path: str) -> dict:
    if os.path.exists(json_path):
        try:
            with open(json_path) as f:
                loaded = json.load(f)
            dropped = [k for k in loaded if k not in _DOC_KEYS]
            if dropped:
                _log(f"dropping legacy top-level keys from existing table: "
                     f"{dropped}")
            doc = {k: loaded[k] for k in _DOC_KEYS if k in loaded}
            doc.setdefault("configs", {})
            doc.setdefault("impl_comparisons", {})
            for entry in doc["configs"].values():
                # Legacy (unstamped at BOTH levels) rows measured p50/p99
                # on the UNTHROTTLED run — congestion, not transit. Until
                # the row is re-measured it renders alongside the rate-
                # controlled caption, so demote the percentiles to their
                # honest congestion_* names (render shows '—').
                e2e = entry.get("e2e")
                if (isinstance(e2e, dict)
                        and not entry.get("captured_utc")
                        and not e2e.get("captured_utc")):
                    for k in ("p50_ms", "p99_ms"):
                        if k in e2e:
                            e2e[f"congestion_{k}"] = e2e.pop(k)
            return doc
        except Exception as e:  # noqa: BLE001 — a corrupt file is replaced
            _log(f"could not load existing {json_path}: {e!r}; starting fresh")
    return {"configs": {}, "impl_comparisons": {}}


def persist(doc: dict, json_path: str, md_path: str, forced_cpu: bool) -> None:
    doc["updated_utc"] = _now()
    doc["platform_forced_cpu"] = forced_cpu
    tmp = json_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=2)
    os.replace(tmp, json_path)
    with open(md_path, "w") as f:
        f.write(render_md(doc, forced_cpu))


def leg_fresh(entry: dict, leg: str, min_fresh: str, quick: bool = False,
              forced_cpu: bool = False) -> bool:
    """One leg (device/e2e) is fresh if present, error-free, produced by
    the SAME kind of run (quick? forced-cpu?), and stamped after
    --min-fresh. Per-LEG granularity is what lets the phased runner land
    every config's device leg + the A/Bs before paying for any e2e leg.

    Stamps/mode live inside the leg dict; entry-level values are the
    fallback for rows written by the earlier entry-level schema.
    Unstamped legs (legacy pre-incremental files) are stale by definition
    — 'missing/errored rows always rerun'. The mode check prevents a
    --quick or --cpu session's legs from being skipped (i.e. silently
    republished) by a later full/TPU run in the same out-dir."""
    if not entry or leg not in entry:
        return False
    d = entry[leg]
    if not isinstance(d, dict) or "error" in d:
        return False
    # A leg hand-marked stale_code (captured before a code change to the
    # path it measured) is stale regardless of stamp — the next session
    # re-measures it and the replacement leg clears the mark.
    if d.get("stale_code"):
        return False
    if (d.get("quick", entry.get("quick", False)) != quick
            or d.get("forced_cpu", entry.get("forced_cpu", False)) != forced_cpu):
        return False
    # Methodology gate: an e2e leg that published percentiles without the
    # congestion verdict predates the backoff-verified latency leg (the
    # 0.8×-target run could silently congest and report queue residency as
    # transit) — stale regardless of stamp, so the next session re-measures
    # it with the congestion-checked harness. lat_delivery_fps marks the
    # v3 verdict (drops + steady-state delivery rate); legs with only the
    # v2 drops signal could false-negative on streams shorter than the
    # pipeline's buffering over a slow link and are equally stale.
    if leg == "e2e" and "p50_ms" in d and "lat_delivery_fps" not in d:
        return False
    # A congested capture is an upper bound, not transit — keep it (it
    # renders with the ‡ mark) but never let it satisfy freshness, so a
    # later run replaces it with an honest measurement.
    if leg == "e2e" and d.get("lat_congested"):
        return False
    stamp = d.get("captured_utc") or entry.get("captured_utc", "")
    if not stamp:
        return False
    return not min_fresh or stamp >= min_fresh


def is_fresh(entry: dict, min_fresh: str, quick: bool = False,
             forced_cpu: bool = False) -> bool:
    """A whole row is fresh when both its legs are (see leg_fresh)."""
    return (leg_fresh(entry, "device", min_fresh, quick, forced_cpu)
            and leg_fresh(entry, "e2e", min_fresh, quick, forced_cpu))


def comparison_fresh(comp: dict, min_fresh: str,
                     forced_cpu: bool = False) -> bool:
    """Fresh = completed (the 'winner' key is set only after the last impl
    leg) with no per-impl errors, a matching run mode, and a
    post---min-fresh timestamp. A comp killed between impl legs has
    finished legs persisted but no winner — stale, so the rerun fills the
    rest. (Quick mode needs no flag here: its comparisons rename their
    keys to *_48x64_quick.)"""
    if not comp or "winner" not in comp:
        return False
    if any(isinstance(v, dict) and "error" in v for v in comp.values()):
        return False
    if comp.get("forced_cpu", False) != forced_cpu:
        return False
    stamp = comp.get("captured_utc", "")
    if not stamp:
        return False
    return not min_fresh or stamp >= min_fresh


# ---------------------------------------------------------------------------
# Rendering


def render_md(doc: dict, forced_cpu: bool) -> str:
    lines = [
        "# Benchmark table — BASELINE.json configs",
        "",
        f"Updated {doc.get('updated_utc', '?')} · "
        + ("**CPU (forced — validation run, not the TPU numbers)**"
           if forced_cpu else "TPU")
        + " · incremental (per-row timestamps)",
        "",
        "| config | device fps | ms/frame | HBM roofline | MFU | e2e fps "
        "| p50 ms | p99 ms | captured (UTC) |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    overcounted = False

    def _fmt_roof(v):
        # XLA's bytes-accessed counts every HLO op's operands+results;
        # for deep fused programs (flow: hundreds of ops kept in
        # registers/VMEM) that overcounts real HBM traffic, the derived
        # "ceiling" is an underestimate, and the fraction exceeds 1 —
        # the model is not the binding one there (MFU is), so flag it
        # rather than publish a >1 "fraction of roofline".
        nonlocal overcounted
        if v is None:
            return "—"
        if v > 1.05:
            overcounted = True
            return f"{v} †"
        return str(v)

    stale_notes = []
    for name, _ in TABLE:
        r = doc["configs"].get(name)
        if not r:
            lines.append(f"| {name} | — | — | — | — | — | — | — | never |")
            continue
        d, e = r.get("device", {}), r.get("e2e", {})
        roof = d.get("hbm_roofline_frac")
        mfu = d.get("mfu")
        # ¶ = the device leg's number predates a code change to the very
        # path it measured (reason recorded in the leg's stale_code field;
        # a re-measure replaces the leg wholesale, clearing the mark) —
        # the device-side analog of the e2e legs' §.
        dev_mark = ""
        if d.get("stale_code"):
            dev_mark = " ¶"
            stale_notes.append(f"{name}: {d['stale_code']}")
        e2e_mark = ""
        if isinstance(e, dict) and e.get("stale_code"):
            # leg_fresh honors stale_code on ANY leg — the render must
            # too, or a hand-marked e2e leg would present a known-stale
            # number as current until its re-measure lands.
            e2e_mark = " ¶"
            stale_notes.append(f"{name} (e2e): {e['stale_code']}")
        stamp = ((d.get("captured_utc") if isinstance(d, dict) else "")
                 or r.get("captured_utc") or "")[:16].replace("T", " ")
        # ‡ = verified-congested upper bound; § = measured by a
        # pre-verification harness (no congestion verdict travels with
        # the number) — both are owed a re-measure and must not read as
        # verified transit under the caption below.
        if e and e.get("lat_congested"):
            mark = " ‡"
        elif e and "p50_ms" in e and "lat_delivery_fps" not in e:
            mark = " §"
        else:
            mark = ""
        lines.append(
            f"| {name} | {str(d.get('value', 'ERR')) + dev_mark} "
            f"| {d.get('ms_per_frame', '—')} "
            f"| {_fmt_roof(roof)} "
            f"| {mfu if mfu is not None else '—'} "
            f"| {str(e.get('value', 'ERR')) + e2e_mark if e else '—'} "
            f"| {str(e.get('p50_ms', '—')) + mark + e2e_mark if e else '—'} "
            f"| {str(e.get('p99_ms', '—')) + mark if e else '—'} | {stamp} |"
        )
    def _legacy_e2e(r):
        # Demoted legacy e2e: load_doc renamed its p50/p99 to congestion_*
        # because neither the entry nor the leg carried a stamp.
        e = r.get("e2e") if r else None
        return (isinstance(e, dict) and "p50_ms" not in e
                and "congestion_p50_ms" in e)

    if any(r and (_legacy_e2e(r)
                  or not (r.get("captured_utc")
                          or r.get("device", {}).get("captured_utc")))
           for r in (doc["configs"].get(n) for n, _ in TABLE)):
        lines.append(
            "\nRows with a blank timestamp — or e2e fps with no p50/p99 — "
            "are pre-incremental captures kept until the next run "
            "re-measures that leg; their unthrottled "
            "p50/p99 were demoted to `congestion_*` in the JSON (they never "
            "measured transit), and a device-leg re-measurement does not "
            "refresh them.")
    lines.append(
        "\np50/p99 are RATE-CONTROLLED transit latency (source throttled to "
        "0.8× the measured throughput, ingest queue ≈ one batch), VERIFIED "
        "uncongested on two signals — the bounded drop-oldest ingest queue "
        "recorded ≤1 drop AND the steady-state delivery rate (first→last "
        "delivery) held ≥0.85× the offered rate — halving the rate up to "
        "twice until both held. ‡ = still congested at the lowest "
        "tried rate — that p50 includes "
        "standing-queue wait and is an upper bound, not transit. § = "
        "captured by a pre-verification harness (no congestion verdict "
        "attached) — treated as stale and re-measured by the next run. The "
        "congestion percentiles of the unthrottled run are kept only in the "
        "JSON under `congestion_*`. 'HBM roofline' = measured device fps / "
        "(819 GB/s ÷ XLA-reported HBM bytes per frame) — the right model "
        "for the memory-bound filter families; MFU = achieved FLOP rate / "
        "197 bf16 TFLOP/s — the right model for the neural configs "
        "(style/SR). Both computed only on TPU.")
    if stale_notes:
        lines.append(
            "\n¶ = device number captured before a code change to the "
            "measured path — kept (best available) but owed a re-measure: "
            + "; ".join(stale_notes) + ".")
    for cname, comp in doc["impl_comparisons"].items():
        lines += [
            "",
            f"## Implementation comparison — {cname}",
            "",
            f"Captured {(comp.get('captured_utc') or '?')[:16]}",
            "",
            "| impl | fps | ms/frame | HBM roofline |",
            "|---|---|---|---|",
        ]
        for impl, c in comp.items():
            if impl in ("winner", "captured_utc", "code_rev", "forced_cpu"):
                continue
            lines.append(
                f"| {impl} | {c.get('fps', 'ERR')} "
                f"| {c.get('ms_per_frame', '—')} "
                f"| {_fmt_roof(c.get('hbm_roofline_frac'))} |")
        lines.append(f"\nWinner: **{comp.get('winner', 'n/a')}**")
    if overcounted:
        lines.append(
            "\n† fraction > 1: XLA's bytes-accessed overcounts HBM traffic "
            "for deep fused programs (every HLO op's operands+results are "
            "counted even when fusion keeps them on-chip), so the derived "
            "ceiling underestimates and the HBM model is not the binding "
            "one for this config — judge it by MFU / wall time instead.")
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--cpu", action="store_true",
                    help="force JAX_PLATFORMS=cpu (validation / fallback run)")
    ap.add_argument("--out-dir", default=os.path.join(REPO, "benchmarks"))
    ap.add_argument("--timeout", type=float, default=420.0)
    ap.add_argument("--iters", type=int, default=200)
    ap.add_argument("--cmp-iters", type=int, default=None,
                    help="iters for the impl comparisons (default: --iters; "
                         "set low for forced-CPU runs, where Pallas kernels "
                         "execute in interpret mode at a fraction of "
                         "compiled speed)")
    ap.add_argument("--frames", type=int, default=256)
    ap.add_argument("--quick", action="store_true",
                    help="tiny iteration counts (mechanics check)")
    ap.add_argument("--min-fresh", default="",
                    help="ISO timestamp: rerun rows captured before this "
                         "(missing/errored rows always rerun)")
    ap.add_argument("--only", default="",
                    help="comma-separated subset of config/comparison names")
    ap.add_argument("--force", action="store_true",
                    help="rerun everything regardless of freshness")
    ap.add_argument("--legs", default="device,e2e",
                    help="which config legs to (re)measure. An impl-default "
                         "change only moves the device numbers — "
                         "'--legs device' refreshes those without "
                         "re-streaming the e2e legs")
    ap.add_argument("--skip-comparisons", action="store_true",
                    help="config legs only — lets a caller sequence the "
                         "run (device rows, then e2e rows, THEN the "
                         "A/B phase) instead of this script's fixed "
                         "device→comparisons→e2e order")
    ap.add_argument("--render-only", action="store_true",
                    help="re-render BENCH_TABLE.md from the persisted JSON "
                         "without measuring anything — picks up caption/"
                         "mark changes (e.g. a methodology-gate edit) "
                         "immediately instead of at the next capture")
    args = ap.parse_args(argv)
    if args.render_only:
        # MD only — the JSON (including its updated_utc measurement stamp)
        # is untouched: a re-render adds no data. A missing/corrupt JSON
        # is always an error here (typo'd --out-dir, deleted file): with
        # no data source, proceeding would clobber the published MD with
        # an empty skeleton.
        json_path = os.path.join(args.out_dir, "BENCH_TABLE.json")
        doc = load_doc(json_path)
        if not doc.get("configs") and not doc.get("impl_comparisons"):
            ap.error(f"--render-only: no usable table data in {json_path}")
        md_path = os.path.join(args.out_dir, "BENCH_TABLE.md")
        with open(md_path, "w") as f:
            f.write(render_md(doc,
                              bool(doc.get("platform_forced_cpu", args.cpu))))
        _log(f"re-rendered {md_path} from persisted JSON (no measurements)")
        return 0
    legs = {s for s in args.legs.split(",") if s}
    if not legs or not legs <= {"device", "e2e"}:
        # An empty set would silently skip every leg and exit 0 with a
        # re-rendered-but-stale table.
        ap.error(f"--legs must name device and/or e2e; got {args.legs!r}")

    env = dict(os.environ)
    if args.cpu:
        env["JAX_PLATFORMS"] = "cpu"
        env["DVF_FORCE_PLATFORM"] = "cpu"
    iters = 5 if args.quick else args.iters
    cmp_iters = (3 if args.quick
                 else (args.cmp_iters if args.cmp_iters else args.iters))
    frames = 16 if args.quick else args.frames
    batch = 2 if args.quick else 0
    only = {s for s in args.only.split(",") if s}
    min_fresh = "9999" if args.force else args.min_fresh

    os.makedirs(args.out_dir, exist_ok=True)
    json_path = os.path.join(args.out_dir, "BENCH_TABLE.json")
    md_path = os.path.join(args.out_dir, "BENCH_TABLE.md")
    doc = load_doc(json_path)
    rev = git_rev(REPO)
    t0 = time.time()

    def save():
        persist(doc, json_path, md_path, args.cpu)

    comparisons = {} if args.skip_comparisons else {
        k: v for k, v in COMPARISONS.items() if not only or k in only}
    if args.quick:
        # Quick mode shrinks shapes — rename the keys so tiny-shape numbers
        # can never be published under full-resolution labels. Tile-sweep
        # variants whose pinned tile_h does not divide the quick H cannot
        # run at the shrunken geometry (tile_h must divide H) — drop those
        # impls rather than recording guaranteed-error legs every smoke.
        qh, qw = 48, 64
        comparisons = {
            k.rsplit("_", 1)[0] + "_48x64_quick": (qh, qw, b, [
                (label, fname, cfg) for (label, fname, cfg) in impls
                if not cfg.get("tile_h") or qh % cfg["tile_h"] == 0
            ])
            for k, (_, _, b, impls) in comparisons.items()
        }
        comparisons = {k: v for k, v in comparisons.items() if v[3]}

    ran = skipped = 0

    def measure_leg(name: str, scale: float, which: str):
        """Measure one leg of one config. Meta (stamp, run mode,
        workload) lives in the leg dict so each leg carries its own
        provenance."""
        nonlocal ran, skipped
        entry = doc["configs"].setdefault(name, {})
        if leg_fresh(entry, which, min_fresh, args.quick, args.cpu):
            skipped += 1
            return
        iters_c = max(3, int(iters * scale))
        frames_c = max(12, int(frames * scale))
        t_leg = time.time()
        _log(f"{name}: {which} (iters={iters_c}, frames={frames_c})…")
        # e2e gets 4× budget: it is up to FOUR pipeline runs in one child
        # (throughput, then the rate-controlled latency leg at 0.8× the
        # measured rate, which halves-and-retries up to twice when the
        # stream congests — each retry ≈ one original-leg wall).
        leg = bench_config(name, env,
                           args.timeout * (4 if which == "e2e" else 1),
                           iters_c, frames_c, e2e=(which == "e2e"),
                           batch=batch)
        leg.update(captured_utc=_now(), quick=args.quick,
                   forced_cpu=args.cpu, code_rev=rev, iters=iters_c,
                   frames=frames_c, wall_s=round(time.time() - t_leg, 1))
        prior = entry.get(which)
        if ("error" in leg and isinstance(prior, dict)
                and "value" in prior):
            # A failed RE-measure (child crashed or timed out) must not clobber
            # the kept best-available number and its provenance (e.g. a
            # stale_code-marked capture): keep the prior leg, record the
            # failed attempt beside it. The leg stays stale by whatever
            # made it re-run (stale_code / old stamp), so the next
            # session retries it.
            kept = dict(prior)
            kept["last_retry_error"] = {
                "error": leg["error"], "captured_utc": leg["captured_utc"],
                "code_rev": rev}
            entry[which] = kept
        else:
            entry[which] = leg
        # Migrate any entry-level (pre-leg-schema) provenance down into
        # the OTHER leg before clearing it: the untouched leg must keep
        # its stamp/mode (it may still be fresh), and the entry must not
        # carry a second, contradictory stamp/revision beside the new leg.
        other = entry.get("e2e" if which == "device" else "device")
        if isinstance(other, dict) and not other.get("captured_utc"):
            for k in ("captured_utc", "quick", "forced_cpu", "code_rev",
                      "iters", "frames"):
                if k in entry and k not in other:
                    other[k] = entry[k]
        for k in ("captured_utc", "quick", "forced_cpu", "code_rev",
                  "iters", "frames", "wall_s"):
            entry.pop(k, None)
        save()
        ran += 1
        _log(f"{name}: {which}={leg.get('value', leg.get('error'))}")

    # Phase 1 — device legs for every config (per-chip capability +
    # roofline fraction; seconds each on the chip).
    for name, scale in TABLE:
        if only and name not in only or "device" not in legs:
            continue
        measure_leg(name, scale, "device")

    # Phase 2 — implementation A/Bs (device-resident): the per-backend
    # winner evidence, ahead of any e2e leg.
    for cname, (h, w, cbatch, impls) in comparisons.items():
        if comparison_fresh(doc["impl_comparisons"].get(cname), min_fresh,
                            forced_cpu=args.cpu):
            skipped += 1
            continue
        _log(f"impl comparison {cname}…")
        # Seed with the finished legs of a partial prior run (killed
        # between impls): same run mode + fresh-enough + error-free legs
        # are kept, so the rerun fills ONLY what's missing.
        prior = doc["impl_comparisons"].get(cname) or {}
        prior_stamp = prior.get("captured_utc", "")
        if not (prior.get("forced_cpu", False) == args.cpu
                and prior_stamp  # unstamped legacy legs are never kept
                and (not min_fresh or prior_stamp >= min_fresh)):
            prior = {}

        def _measure(impl, payload, _h=h, _w=w, _cbatch=cbatch):
            fname, cfg = payload
            cfg = dict(cfg)
            if args.cpu and fname.endswith("_pallas"):
                cfg["interpret"] = True
            return bench_impl(fname, cfg, cmp_iters, batch or _cbatch,
                              _h, _w, env, args.timeout)

        def _on_leg(comp, impl, _cname=cname):
            # Per-impl persist: a killed run keeps finished legs. The
            # doc assignment here (not only after the loop) also covers
            # the fully-seeded case — a prior run that died after its
            # last leg but before the winner save must not leave its
            # winner computed on an orphan dict.
            comp["captured_utc"] = _now()
            doc["impl_comparisons"][_cname] = comp
            save()

        comp = ab_comparison(
            [(impl, (fname, cfg)) for impl, fname, cfg in impls],
            _measure,
            prior=prior,
            keep_leg=lambda leg: "fps" in leg,
            meta={"code_rev": rev, "forced_cpu": args.cpu},
            on_leg=_on_leg,
            log=lambda m: _log("  " + m),
        )
        doc["impl_comparisons"][cname] = comp
        comp.setdefault("captured_utc", _now())
        save()
        ran += 1

    # Phase 3 — e2e legs, last: the slowest legs run once the device rows
    # and the A/Bs are banked.
    for name, scale in TABLE:
        if only and name not in only or "e2e" not in legs:
            continue
        measure_leg(name, scale, "e2e")

    doc["wall_s_last_session"] = round(time.time() - t0, 1)
    save()
    print(json.dumps({"written": [json_path, md_path],
                      "ran": ran, "skipped_fresh": skipped,
                      "wall_s": doc["wall_s_last_session"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
