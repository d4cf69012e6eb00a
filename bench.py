"""Headline benchmark: 1080p color-invert through the framework, on the TPU.

It runs once, in this one process, on the chip or not at all: when jax
finds no TPU it exits non-zero, says what it found on stderr, and prints
no number. On the chip it prints ONE JSON line on stdout:

    {"metric": "1080p_invert_device_fps", "value": <device fps>,
     "unit": "fps", "vs_baseline": value/2000,
     "platform": "tpu", "device_kind": "...", "n_devices": N,
     "p50_latency_ms": ..., "p99_latency_ms": ..., "e2e_fps": ...,
     "link_roofline_fps": ..., ...}

``vs_baseline`` is value / 2000 — the north-star target from BASELINE.json
(>= 2000 fps AND p50 < 10 ms, 1080p invert on a v5e-4). ``p50_latency_ms``
comes from a rate-controlled run (source at 0.8x measured throughput,
ingest queue ~ one batch) so it measures pipeline transit, not standing
queue depth. ``link_roofline_fps`` is the measured host<->device link
ceiling for full-frame delivery and ``roofline_frac`` how close the
pipeline gets to it. Measurement design is in dvf_tpu/benchmarks.py; what
this measures is ROADMAP S1's to redefine.

Progress goes to stderr with timestamps. The persistent compile cache is
armed through the one resolver (runtime.engine.enable_compilation_cache),
so a rerun skips compiles.

Usage: python bench.py [--iters K] [--batch B] [--frames N] [--e2e]
"""

from __future__ import annotations

import argparse
import json
import sys
import time

E2E_BUDGET_S = 60.0      # target wall time of each e2e phase
COLLECT_MODE = "inline"  # one fewer thread on the GIL than "thread"

_T0 = time.perf_counter()


def _log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - _T0:.1f}s] {msg}",
          file=sys.stderr, flush=True)


def measure(args, mode: str) -> dict:
    """The measurement sequence: device-resident throughput, the stage
    decomposition, the link microbench, then e2e throughput and
    rate-controlled latency. Returns the raw result fields."""
    from dvf_tpu.benchmarks import (
        bench_device_resident,
        bench_e2e_latency,
        bench_e2e_streaming,
        bench_stage_decomposition,
        bench_transfer,
        roofline_fields,
    )
    from dvf_tpu.ops import get_filter

    filt = get_filter("invert")
    result: dict = {}

    if mode == "headline":
        _log(f"device-resident: batch={args.batch} iters={args.iters} "
             f"{args.height}x{args.width} (first run compiles)")
        r = bench_device_resident(filt, args.iters, args.batch,
                                  args.height, args.width)
        result.update(
            device_fps=round(r["fps"], 1),
            ms_per_batch=round(r["ms_per_batch"], 3),
            ms_per_frame=round(r["ms_per_frame"], 4),
            device_frames=r["frames"],
            device_wall_s=round(r["wall_s"], 2),
            batch=args.batch,
        )
        result.update(roofline_fields(r))
        _log(f"device-resident done: {result['device_fps']} fps "
             f"(hbm_roofline_frac={result.get('hbm_roofline_frac')})")

        # Per-stage latency decomposition at small batch: the measured
        # core of the p50 < 10 ms budget.
        _log("stage decomposition (batch 1/2/4)")
        decomp = bench_stage_decomposition(
            filt, sorted({1, 2, args.lat_batch}), args.height, args.width,
            reps=25)
        # Codec provenance travels beside the encode_ms leg it produced.
        result["codec"] = decomp.pop("codec", None)
        result["stage_decomp_ms"] = decomp
        lat_key = f"batch_{args.lat_batch}"
        if lat_key in decomp:
            result["compute_p50_ms"] = decomp[lat_key]["compute_ms"]
        _log(f"decomposition done: {json.dumps(decomp)}")

    # Link microbench — also sizes the e2e phases to their wall budget.
    _log("transfer microbench")
    tr = bench_transfer(args.e2e_batch, args.height, args.width)
    frame_mb = tr["batch_mb"] / args.e2e_batch
    roof = 1.0 / (
        frame_mb / tr["h2d_mbps"]
        + frame_mb / tr["d2h_mbps"]
        + tr["d2h_fixed_ms"] / 1e3 / args.e2e_batch
    )
    result.update(
        h2d_mbps=round(tr["h2d_mbps"], 1),
        d2h_mbps=round(tr["d2h_mbps"], 1),
        link_roofline_fps=round(roof, 1),
    )
    _log(f"link: h2d={result['h2d_mbps']} MB/s d2h={result['d2h_mbps']} MB/s "
         f"-> roofline ~ {result['link_roofline_fps']} fps at "
         f"{args.height}x{args.width}")

    n_frames = max(48, min(args.frames, int(roof * E2E_BUDGET_S)))
    _log(f"e2e throughput: batch={args.e2e_batch} frames={n_frames}")
    r = bench_e2e_streaming(filt, n_frames, args.e2e_batch,
                            args.height, args.width,
                            collect_mode=COLLECT_MODE)
    result.update(
        e2e_fps=round(r["fps"], 1),
        e2e_frames=r["frames"],
        e2e_wall_s=round(r["wall_s"], 2),
        e2e_batch=args.e2e_batch,
        # The result-fetch path the run actually took (streamed degrades
        # to monolithic where streaming cannot win) and the fraction of
        # blocking-D2H cost it hid.
        egress=r["egress"],
        egress_overlap_efficiency=r["egress_overlap_efficiency"],
        # Per-kind contained-fault counters from the run ({} = clean): a
        # number that silently absorbed dropped batches is no measurement.
        faults=r.get("faults", {}),
        recoveries=r.get("recoveries", 0),
        roofline_frac=round(r["fps"] / roof, 3) if roof else None,
    )
    _log(f"e2e done: {result['e2e_fps']} fps ({result['roofline_frac']} of "
         f"link roofline, ingest={r['ingest']} egress={r['egress']})")

    # Rate-controlled latency: 0.8x measured throughput, queue ~ batch —
    # p50 is transit, not queue depth.
    target = 0.8 * r["fps"]
    n_lat = max(32, min(args.frames, int(target * E2E_BUDGET_S)))
    _log(f"e2e latency: batch={args.lat_batch} target={target:.1f} fps "
         f"frames={n_lat}")
    rl = bench_e2e_latency(filt, n_lat, args.lat_batch,
                           args.height, args.width, target,
                           collect_mode=COLLECT_MODE)
    result.update(
        p50_ms=round(rl["p50_ms"], 2),
        p99_ms=round(rl["p99_ms"], 2),
        lat_frames=rl["frames"],
        lat_batch=args.lat_batch,
        lat_target_fps=round(rl["target_fps"], 1),
        # The latency verdict travels with the percentiles: without it a
        # reader cannot tell verified transit from a congested upper bound.
        lat_delivery_fps=round(rl["delivery_fps"], 2),
        lat_congested=rl["congested"],
        lat_backoffs=rl["backoffs"],
    )
    _log(f"latency done: p50={result['p50_ms']}ms p99={result['p99_ms']}ms "
         f"(target {result['lat_target_fps']} fps after "
         f"{rl['backoffs']} backoffs, congested={rl['congested']})")
    return result


def build_out(result: dict, mode: str, device: dict) -> dict:
    headline = result.get("device_fps", result.get("e2e_fps"))
    return {
        "metric": ("1080p_invert_device_fps" if mode == "headline"
                   else "1080p_invert_e2e_fps"),
        "value": headline,
        "unit": "fps",
        "vs_baseline": round(headline / 2000.0, 3) if headline else None,
        **device,
        "p50_latency_ms": result.get("p50_ms"),
        "p99_latency_ms": result.get("p99_ms"),
        **{k: result.get(k) for k in (
            "compute_p50_ms", "stage_decomp_ms", "codec", "egress",
            "egress_overlap_efficiency",
            "lat_target_fps", "lat_batch", "lat_delivery_fps",
            "lat_congested", "lat_backoffs", "e2e_fps", "ms_per_frame",
            "h2d_mbps", "d2h_mbps", "link_roofline_fps", "roofline_frac",
            "hbm_roofline_fps", "hbm_roofline_frac", "mfu", "batch",
            "e2e_batch", "faults", "recoveries")},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--iters", type=int, default=300,
                    help="device-resident chain length")
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--height", type=int, default=1080)
    ap.add_argument("--width", type=int, default=1920)
    ap.add_argument("--frames", type=int, default=512,
                    help="e2e streaming frame cap")
    ap.add_argument("--e2e-batch", type=int, default=16)
    ap.add_argument("--lat-batch", type=int, default=4,
                    help="batch for the rate-controlled latency run (small "
                         "batches bound the assemble wait)")
    ap.add_argument("--e2e", action="store_true",
                    help="e2e phases only (skip the device-resident leg)")
    args = ap.parse_args(argv)

    from dvf_tpu.runtime.engine import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "device_kind": devices[0].device_kind,
              "n_devices": len(devices)}
    if device["platform"] != "tpu":
        print(f"bench.py: jax found platform {device['platform']!r} "
              f"({device['device_kind']}), not a tpu — this benchmark runs "
              f"on the chip or not at all (no CPU number is a device "
              f"number). tests/ exercise the harness mechanics on the CPU.",
              file=sys.stderr)
        return 3
    _log(f"device: {device}; compile cache: {cache_dir}")
    mode = "e2e" if args.e2e else "headline"
    print(json.dumps(build_out(measure(args, mode), mode, device)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
