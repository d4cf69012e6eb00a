"""Head-to-head: the reference's own pipeline vs dvf_tpu, same host.

BASELINE.json configs[0] calls for a measured parity baseline —
"inverter.py color-invert, 640x480 webcam stream, single CPU worker".
This benchmark runs BOTH sides on this host:

- **Reference**: its unmodified ``Distributor`` (imported from
  /root/reference) + its unmodified ``InverterWorker`` in a separate OS
  process (benchmarks/ref_worker_launcher.py — the reference's own
  process topology), JPEG wire via a PyTurboJPEG-compatible shim over
  the same in-repo libjpeg-turbo codec. The app side is generous to the
  reference: frames are PRE-encoded once and re-offered, so the
  measurement covers its distribute → worker(decode+invert+encode) →
  collect → reorder path only. Processed throughput is counted by the
  reference's OWN accounting (``enable_trace_export`` complete events,
  distributor.py:75-88).
- **dvf_tpu**: the Pipeline e2e streaming bench at the same geometry on
  the CPU backend — once on the JPEG wire (same codec work per frame as
  the reference's worker), once on the raw/shm ring wire (the design
  point: JPEG is not needed intra-host).

Results persist to benchmarks/REFERENCE_HEADTOHEAD.json (+ .md); one
JSON summary line on stdout. This script is CPU-only by design (the
comparison target is the reference's CPU task farm); the TPU side of the
same workload (invert_640x480) is not measured until it is a ledgered
cell of bench.py.

Usage: python benchmarks/reference_headtohead.py [--seconds 12]
       [--workers 1] [--height 480] [--width 640]
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
REF = "/root/reference"
sys.path.insert(0, REPO)

from benchtools import free_port, git_rev, load_reference_module  # noqa: E402


import contextlib


@contextlib.contextmanager
def _reference_stack(height: int, width: int, n_workers: int = 1):
    """Start the reference's unmodified Distributor + InverterWorker
    subprocess(es); yields (dist, jpeg_frame). Tears down the workers,
    reports a dead worker's stderr tail, runs the reference's cleanup,
    and removes its CWD-relative trace export."""
    import tempfile

    import numpy as np

    from benchmarks.ref_worker_launcher import install_turbojpeg_shim

    install_turbojpeg_shim()
    mod = load_reference_module("distributor.py", REF)
    from dvf_tpu.transport.codec import make_codec

    rng = np.random.RandomState(0)
    jpeg = make_codec().encode(
        rng.randint(0, 255, (height, width, 3), np.uint8))
    p_dist, p_coll = free_port(), free_port()
    dist = mod.Distributor(distribute_port=p_dist, collect_port=p_coll,
                           frame_delay=5, enable_trace_export=True)
    dist.start()
    stderr_log = tempfile.TemporaryFile()
    workers = [
        subprocess.Popen(
            [sys.executable,
             os.path.join(REPO, "benchmarks", "ref_worker_launcher.py"),
             str(p_dist), str(p_coll)],
            stdout=subprocess.DEVNULL, stderr=stderr_log)
        for _ in range(n_workers)
    ]
    try:
        yield dist, jpeg
    finally:
        for w in workers:
            w.terminate()
        for w in workers:
            try:
                w.wait(timeout=5)
            except subprocess.TimeoutExpired:
                w.kill()
        dist.cleanup()
        # The reference's cleanup() exports its trace to a hardcoded
        # CWD-relative path (distributor.py:374-376) — don't leave the
        # stray artifact behind.
        try:
            os.remove("webcam_frame_timing.pftrace")
        except OSError:
            pass
        if any(w.returncode not in (0, -15) for w in workers):
            stderr_log.seek(0)
            tail = stderr_log.read()[-800:].decode(errors="replace")
            print(f"[h2h] reference worker stderr tail:\n{tail}",
                  file=sys.stderr)
        stderr_log.close()


def _warmup(dist, jpeg, seconds: float = 2.0) -> None:
    """Stream frames so the worker connects AND pays its cold path
    (first decode/encode, first READY round-trip) before measurement."""
    t_end = time.time() + seconds
    while time.time() < t_end:
        dist.add_frame_for_distribution(jpeg, time.time())
        dist.update_display_frame()
        time.sleep(0.002)


def bench_reference(height: int, width: int, seconds: float,
                    n_workers: int) -> dict:
    """Drive the reference's unmodified Distributor + InverterWorker."""
    with _reference_stack(height, width, n_workers) as (dist, jpeg):
        _warmup(dist, jpeg)
        n0 = len(dist.frame_timings)
        t0 = time.time()
        t_end = t0 + seconds
        offered = 0
        while time.time() < t_end:
            # Unthrottled offer with the reference's latest-wins slot
            # absorbing overload (distributor.py:214-217); the display
            # poll mirrors the app's draw loop (webcam_app.py:135-137).
            dist.add_frame_for_distribution(jpeg, time.time())
            offered += 1
            dist.update_display_frame()
            dist.get_frame_to_display()
            time.sleep(0.001)  # yield the GIL to the collect thread
        wall = time.time() - t0
        # The reference's own accounting: one 'X' complete event per
        # processed frame (log_frame_complete_timing, distributor.py:76-88).
        done = [t for t in dist.frame_timings[n0:]
                if t.get("event_ph") == "X"]
        durs = sorted(t["end_time"] - t["begin_time"] for t in done)
        return {
            "fps": round(len(done) / wall, 1),
            "frames": len(done),
            "offered_fps": round(offered / wall, 1),
            "wall_s": round(wall, 2),
            "n_workers": n_workers,
            "worker_p50_ms": round(durs[len(durs) // 2] * 1e3, 2) if durs
            else None,
        }


def bench_reference_latency(height: int, width: int, seconds: float,
                            target_fps: float) -> dict:
    """Capture→worker-end transit of the reference at a throttled offer
    rate (≈half its measured throughput, so its stream is uncongested).

    Matched per frame_index from its OWN trace events: the 'i'
    frame_captured timestamp at add (distributor.py:63-73,191) to the 'X'
    end_time the worker self-reports (worker.py:59). GENEROUS to the
    reference: the interval excludes collect-socket receipt and the
    frame_delay display-cursor wait, while ours below is full
    capture→DELIVERED through the reorder buffer."""
    with _reference_stack(height, width, 1) as (dist, jpeg):
        _warmup(dist, jpeg)
        n0 = len(dist.frame_timings)
        period = 1.0 / target_fps
        t_next = time.time()
        t_end = t_next + seconds
        while time.time() < t_end:
            dist.add_frame_for_distribution(jpeg, time.time())
            dist.update_display_frame()
            t_next += period
            time.sleep(max(0.0, t_next - time.time()))
        time.sleep(0.5)  # let in-flight results land
        evs = dist.frame_timings[n0:]
        captured = {e["frame_index"]: e["timestamp"] for e in evs
                    if e.get("event_ph") == "i"}
        transits = sorted(
            e["end_time"] - captured[e["frame_index"]] for e in evs
            if e.get("event_ph") == "X" and e.get("frame_index") in captured)
        if not transits:
            return {"error": "no matched frames"}
        return {
            "target_fps": target_fps,
            "frames": len(transits),
            "p50_ms": round(transits[len(transits) // 2] * 1e3, 2),
            "p99_ms": round(
                transits[min(len(transits) - 1,
                             int(len(transits) * 0.99))] * 1e3, 2),
        }


def bench_ours_latency(height: int, width: int, n_frames: int,
                       target_fps: float) -> dict:
    """Full capture→delivered transit through our pipeline at the same
    offered rate, same codec work (ring transport, JPEG wire), verified
    uncongested by the v3 discipline (congestion → automatic backoff)."""
    from dvf_tpu.benchmarks import bench_e2e_latency
    from dvf_tpu.ops import get_filter

    # batch_size=1: the latency-optimal config at sub-capacity rates (no
    # assembly wait) — and symmetric with the reference, which processes
    # one frame per worker request. Throughput rows above use batch 8.
    r = bench_e2e_latency(get_filter("invert"), n_frames, 1, height, width,
                          target_fps=target_fps, transport="ring",
                          wire="jpeg")
    return {"target_fps": r.get("target_fps"),
            "frames": r.get("frames"),
            "p50_ms": round(r["p50_ms"], 2),
            "p99_ms": round(r["p99_ms"], 2),
            "congested": r.get("congested"),
            "delivery_fps": r.get("delivery_fps")}


def bench_ours(height: int, width: int, seconds: float, wire: str,
               motion: str = "roll", trials: int = 1) -> dict:
    """Our Pipeline e2e at the same geometry, CPU backend.

    ``trials > 1``: repeat and keep the best run. This VM's effective
    speed moves by up to ~3× with hypervisor steal; for a CAPACITY
    measurement interference only ever subtracts, so best-of-N is the
    low-variance estimator (all trial fps are recorded beside it)."""
    from dvf_tpu.benchmarks import bench_e2e_streaming
    from dvf_tpu.ops import get_filter

    # Frame budget from a quick probe: run ~seconds of wall at steady
    # state (bench_e2e_streaming is frame-bounded, not time-bounded).
    probe = bench_e2e_streaming(get_filter("invert"), 64, 8, height, width,
                                transport="ring", wire=wire, motion=motion)
    frames = max(64, min(4000, int(probe["fps"] * seconds)))
    best, fps_trials = None, []
    for _ in range(max(1, trials)):
        r = bench_e2e_streaming(get_filter("invert"), frames, 8, height,
                                width, transport="ring", wire=wire,
                                motion=motion)
        fps_trials.append(round(r["fps"], 1))
        if best is None or r["fps"] > best["fps"]:
            best = r
    r = best
    out = {"fps": round(r["fps"], 1), "frames": r["frames"], "wire": wire,
           "motion": motion}
    if trials > 1:
        out["fps_trials"] = fps_trials
    if wire == "delta":
        enc = r.get("wire", {}).get("encode", {})
        out["dirty_ratio"] = enc.get("dirty_ratio")
        out["keyframes"] = enc.get("keyframes")
        out["codec"] = r.get("wire", {}).get("codec")
    return out


def bench_full_assist_roofline(height: int, width: int,
                               trials: int = 3) -> dict:
    """HOST-cost roofline of the r15 full-transform assist: the same
    low-motion block stream served once over the coefficient wire (host
    does entropy coding only — the r15 serving path) and once as full
    JPEG encodes (the reference's per-frame codec cycle), through the
    REAL codec code (DeltaCodec coefficient branch incl. framing,
    keyframes, entropy pool, batched shim entry).

    The fused device stage (probe+CSC+DCT+quant) runs OFFLINE here and
    its per-frame cost is recorded as a caveat datum, not added to
    either side: on this CPU-only host XLA executes the Pallas kernels
    in interpreted/compiled-CPU mode at ~3 orders of magnitude above
    any accelerator's cost for 8×8 DCTs, so including it would measure
    the tracing artifact, not the design. The roofline answers the
    question the device can't distort: how much host CPU does a codec-
    bound server spend per frame on each wire."""
    import numpy as np

    from dvf_tpu.io.sources import SyntheticSource
    from dvf_tpu.runtime.codec_assist import FusedDeltaTransform
    from dvf_tpu.transport.codec import DeltaCodec, NativeJpegCodec

    H, W, TILE, KF, N, BS = height, width, 32, 48, 400, 8
    src = SyntheticSource(height=H, width=W, n_frames=N, motion="block",
                          texture="noise")
    frames = [np.array(fr, copy=True) for fr, _ in src
              if fr is not None][:N]
    fused = FusedDeltaTransform(tile=TILE, quality=85)
    cfs, bms = [], []
    t0 = time.perf_counter()
    for i in range(0, N, BS):
        bm, cf = fused.process(np.stack(frames[i:i + BS]))
        bms.extend(list(bm))
        cfs.extend(cf)
    fused_ms = (time.perf_counter() - t0) * 1e3 / N

    def run_coef():
        inner = NativeJpegCodec(quality=85, threads=1)
        codec = DeltaCodec(inner=inner, tile=TILE, keyframe_interval=KF)
        codec.encode(None, bitmap=bms[0], coeffs=cfs[0])  # warm
        t0 = time.perf_counter()
        nb = 0
        for k in range(1, N):
            nb += len(codec.encode(None, bitmap=bms[k], coeffs=cfs[k]))
        dt = time.perf_counter() - t0
        out = ((N - 1) / dt,
               codec.entropy_ms / max(1, codec.frames - 1),
               codec.dirty_tiles / max(1, codec.total_tiles),
               nb // (N - 1))
        codec.close()
        return out

    def run_jpeg():
        codec = NativeJpegCodec(quality=85, threads=1)
        codec.encode(frames[0])  # warm
        t0 = time.perf_counter()
        nb = 0
        for k in range(1, N):
            nb += len(codec.encode(frames[k]))
        dt = time.perf_counter() - t0
        codec.close()
        return (N - 1) / dt, nb // (N - 1)

    coefs = [run_coef() for _ in range(max(1, trials))]
    jpegs = [run_jpeg() for _ in range(max(1, trials))]
    best_c, best_j = max(coefs), max(jpegs)
    return {
        "stream": {"height": H, "width": W, "tile": TILE,
                   "keyframe_interval": KF, "frames": N, "batch": BS,
                   "motion": "block", "texture": "noise", "quality": 85},
        "coef_wire_fps": round(best_c[0], 1),
        "coef_wire_fps_trials": [round(c[0], 1) for c in coefs],
        "entropy_ms_per_frame": round(best_c[1], 3),
        "dirty_ratio": round(best_c[2], 4),
        "coef_wire_bytes_per_frame": best_c[3],
        "jpeg_full_fps": round(best_j[0], 1),
        "jpeg_full_fps_trials": [round(j[0], 1) for j in jpegs],
        "jpeg_bytes_per_frame": best_j[1],
        "host_ratio_same_run": round(best_c[0] / best_j[0], 2),
        "fused_device_stage_ms_per_frame_cpu_backend": round(fused_ms, 1),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--workers", type=int, default=1,
                    help="reference worker processes (configs[0]: 1; this "
                         "host has 1 core, so more workers only measure "
                         "contention)")
    ap.add_argument("--height", type=int, default=480)
    ap.add_argument("--width", type=int, default=640)
    ap.add_argument("--out", default=os.path.join(REPO, "benchmarks",
                                                  "REFERENCE_HEADTOHEAD"))
    ap.add_argument("--reuse-reference", action="store_true",
                    help="re-measure OUR legs only, keeping the committed "
                         "artifact's reference rows (for hosts where "
                         "/root/reference is not checked out — the "
                         "reference side is content-insensitive full-"
                         "cycle codec work, so its committed rows stay "
                         "the right denominator; provenance is recorded)")
    args = ap.parse_args(argv)

    reused_reference = False
    prior = None
    if not os.path.exists(REF):
        if args.reuse_reference and os.path.exists(args.out + ".json"):
            with open(args.out + ".json") as f:
                prior = json.load(f)
            reused_reference = True
        else:
            print(json.dumps({"error": "reference not present"}))
            return 1
    # CPU-only by design, pinned explicitly (on a TPU host an unforced
    # init would take the chip for a CPU comparison): _force_platform
    # flips jax.config before first backend use.
    os.environ["DVF_FORCE_PLATFORM"] = "cpu"
    from dvf_tpu.cli import _force_platform

    _force_platform()

    if reused_reference:
        ref = prior["reference"]
        ref_lat = prior["latency_at_matched_rate"][
            "reference_capture_to_worker_end"]
        lat_rate = prior["latency_at_matched_rate"]["offered_fps"]
    else:
        ref = bench_reference(args.height, args.width, args.seconds,
                              args.workers)
        if not ref["frames"]:
            # A worker that died at startup (import error, bad env) must
            # not overwrite a good committed artifact with fps 0.0 and
            # exit 0.
            print(json.dumps({"error": "reference processed 0 frames -- "
                              "worker died at startup? (stderr tail above)",
                              "reference": ref}), flush=True)
            return 1
        # Latency leg at a matched offered rate: half the reference's
        # measured throughput, so BOTH streams run uncongested.
        lat_rate = max(5.0, round(ref["fps"] / 2.0))
        ref_lat = bench_reference_latency(args.height, args.width,
                                          args.seconds, lat_rate)
        if "error" in ref_lat:
            # Same guard as the throughput leg: never overwrite the good
            # committed artifact with a dead-worker run.
            print(json.dumps({"error": "reference latency leg failed",
                              "detail": ref_lat}), flush=True)
            return 1
    if reused_reference:
        # Every row that PAIRS with the frozen reference must come from
        # the same host era it was measured in — re-measuring our
        # jpeg/raw/latency legs today and dividing by a three-day-old
        # reference number would publish host-drift, not codec work
        # (this VM's effective speed moves ~3× with hypervisor steal).
        ours_jpeg = prior["dvf_tpu_cpu_jpeg_wire"]
        ours_raw = prior["dvf_tpu_cpu_raw_wire"]
        ours_lat = prior["latency_at_matched_rate"][
            "dvf_tpu_capture_to_delivered"]
        rates_matched = prior["latency_at_matched_rate"]["rates_matched"]
    else:
        ours_jpeg = bench_ours(args.height, args.width, args.seconds,
                               "jpeg")
        ours_raw = bench_ours(args.height, args.width, args.seconds, "raw")
        ours_lat = bench_ours_latency(args.height, args.width,
                                      max(16, int(lat_rate * args.seconds)),
                                      lat_rate)
        # bench_e2e_latency may BACK OFF (halve the rate) if our stream
        # congests — the comparison is only "matched rate" when it didn't.
        rates_matched = (not ours_lat.get("congested")
                         and ours_lat.get("target_fps") == lat_rate)
    # Low-motion legs (PR 7, ROADMAP item 3): the delta wire's claim is
    # for webcam-like streams — a moving subject on a static scene — so
    # both OUR wires run the same 'block' stream, in the SAME host era
    # (their ratio is what the anchored speedup transports). The
    # reference pays its full codec cycle per frame REGARDLESS of motion
    # (its protocol has no delta mode), so its throughput row stays the
    # right denominator.
    ours_delta_lm = bench_ours(args.height, args.width, args.seconds,
                               "delta", motion="block", trials=3)
    ours_jpeg_lm = bench_ours(args.height, args.width, args.seconds,
                              "jpeg", motion="block", trials=3)
    # r15 full-transform assist: host-cost roofline of the coefficient
    # wire vs the full JPEG cycle, same stream, best-of-3 (needs the
    # native shim's coefficient entries; skipped on cv2-fallback hosts).
    try:
        full_assist = bench_full_assist_roofline(args.height, args.width)
    except Exception as e:  # noqa: BLE001 — record, don't die
        full_assist = {"skipped": f"{type(e).__name__}: {e}"}

    # Codec provenance: the same defaults both sides of the JPEG legs use
    # (the reference worker shim and our RingFrameQueue both build the
    # default make_codec pool) — quality/threads/backend must travel with
    # the same-codec speedup they produced.
    from dvf_tpu.transport.codec import make_codec

    _codec = make_codec()
    codec_cfg = _codec.config()
    _codec.close()

    doc = {
        "captured_utc": datetime.datetime.now(
            datetime.timezone.utc).isoformat(),
        "code_rev": git_rev(REPO),
        "host": {"cores": os.cpu_count()},
        "workload": {"height": args.height, "width": args.width,
                     "filter": "invert"},
        "codec": codec_cfg,
        "reference": ref,
        **({"reference_reused_from":
                # A reuse-of-a-reuse must keep pointing at the run that
                # actually MEASURED the reference, not the intermediate
                # regeneration that carried it forward.
                prior.get("reference_reused_from") or {
                    "captured_utc": prior["captured_utc"],
                    "code_rev": prior["code_rev"]}}
           if reused_reference else {}),
        "dvf_tpu_cpu_jpeg_wire": ours_jpeg,
        "dvf_tpu_cpu_raw_wire": ours_raw,
        "dvf_tpu_cpu_jpeg_wire_low_motion": ours_jpeg_lm,
        "dvf_tpu_cpu_delta_wire_low_motion": ours_delta_lm,
        "latency_at_matched_rate": {
            "offered_fps": lat_rate,
            "rates_matched": rates_matched,
            "reference_capture_to_worker_end": ref_lat,
            "dvf_tpu_capture_to_delivered": ours_lat,
        },
        "speedup_same_codec": round(ours_jpeg["fps"] / ref["fps"], 2)
        if ref["fps"] else None,
        "speedup_raw_wire": round(ours_raw["fps"] / ref["fps"], 2)
        if ref["fps"] else None,
        # The PR-7 headline: same-codec-family wire on a low-motion
        # stream. The reference's denominator is motion-insensitive
        # (full JPEG cycle per frame no matter what changed).
        "speedup_same_codec_low_motion_delta": round(
            ours_delta_lm["fps"] / ref["fps"], 2) if ref["fps"] else None,
        "speedup_delta_vs_own_jpeg_low_motion": round(
            ours_delta_lm["fps"] / ours_jpeg_lm["fps"], 2)
        if ours_jpeg_lm["fps"] else None,
        "full_assist_roofline": full_assist,
    }
    if reused_reference and "reference_2_workers" in prior:
        doc["reference_2_workers"] = prior["reference_2_workers"]
    if reused_reference:
        # The reference row was measured on an EARLIER host state (this
        # VM's effective speed drifts by ~3× with hypervisor steal), so
        # the direct delta-vs-frozen-reference ratio above understates
        # whenever today's host is slower than the anchor run's. The
        # honest cross-era number ANCHORS on the one same-host pair the
        # committed artifact carries (reference vs our jpeg wire, both
        # measured together) and transports only the SAME-RUN delta/jpeg
        # wire ratio across: anchored = (delta/jpeg today) × (jpeg/ref
        # then). Both factors are same-host-state ratios.
        anchor = prior.get("same_host_anchor") or {
            "reference_fps": prior["reference"]["fps"],
            "jpeg_wire_fps": prior["dvf_tpu_cpu_jpeg_wire"]["fps"],
            "speedup_same_codec": prior["speedup_same_codec"],
            "captured_utc": prior["captured_utc"],
        }
        doc["same_host_anchor"] = anchor
        doc["speedup_same_codec_low_motion_delta_anchored"] = round(
            (ours_delta_lm["fps"] / ours_jpeg_lm["fps"])
            * anchor["speedup_same_codec"], 2) if ours_jpeg_lm["fps"] \
            else None
        doc["speedup_same_codec_low_motion_delta_note"] = (
            "direct figure divides a fresh leg by the frozen reference "
            "row (cross-era: host drift included); the anchored figure "
            "is the like-for-like one")
    # r15 full-assist anchored figure: the host-roofline ratio (coef
    # wire vs full JPEG cycle, SAME run, same stream, real codec code)
    # transported through the same-host anchor pair — valid exactly when
    # serving is codec-bound, which the measured rows support on both
    # sides (the reference's worker cycle is ~all codec work, and our
    # jpeg e2e leg runs at ~1/4 of the raw-wire leg, i.e. codec-bound).
    # The e2e delta leg above stays the honest end-to-end figure: it is
    # PIPELINE-bound (compare dvf_tpu_cpu_raw_wire), so the wire's host-
    # cost win only fully shows once the other stages stop masking it.
    anchor_factor = (doc.get("same_host_anchor", {}).get(
        "speedup_same_codec") if reused_reference
        else doc["speedup_same_codec"])
    if "host_ratio_same_run" in full_assist and anchor_factor:
        doc["speedup_same_codec_full_assist_anchored"] = round(
            full_assist["host_ratio_same_run"] * anchor_factor, 2)
        doc["speedup_same_codec_full_assist_derivation"] = (
            f"host-roofline ratio {full_assist['host_ratio_same_run']} "
            "(coefficient wire "
            f"{full_assist['coef_wire_fps']} fps vs full JPEG "
            f"{full_assist['jpeg_full_fps']} fps, same run, best-of-3, "
            "real DeltaCodec/NativeJpegCodec code on the same low-"
            "motion stream) x same-host anchor speedup_same_codec "
            f"{anchor_factor} (our jpeg e2e vs reference, measured "
            "together). Assumes codec-bound serving on both sides; "
            "host-cost evidence only — the fused device stage ran "
            "offline and cost "
            f"{full_assist['fused_device_stage_ms_per_frame_cpu_backend']}"
            " ms/frame on this CPU-only backend (an XLA-CPU tracing "
            "artifact ~3 orders above accelerator cost for 8x8 DCTs, "
            "so e2e CPU runs of the fused path measure tracing, not "
            "the design; see ARCHITECTURE.md r15).")
    with open(args.out + ".json", "w") as f:
        json.dump(doc, f, indent=2)
    md = (
        "# Head-to-head vs the reference — same host, same workload\n\n"
        f"Captured {doc['captured_utc'][:16]} · rev {doc['code_rev']} · "
        f"{doc['host']['cores']}-core host · {args.width}x{args.height} "
        "color-invert (BASELINE configs[0])\n\n"
        "| pipeline | fps | notes |\n|---|---|---|\n"
        f"| reference (unmodified Distributor + InverterWorker, "
        f"{ref['n_workers']} worker proc, JPEG wire) | {ref['fps']} | "
        f"offered {ref['offered_fps']} fps; worker p50 "
        f"{ref['worker_p50_ms']} ms; its own trace accounting |\n"
        f"| dvf_tpu (CPU backend, JPEG wire — same codec work/frame) | "
        f"{ours_jpeg['fps']} | **{doc['speedup_same_codec']}x** |\n"
        f"| dvf_tpu (CPU backend, raw/shm ring wire — the design point) | "
        f"{ours_raw['fps']} | **{doc['speedup_raw_wire']}x** |\n"
        f"| dvf_tpu (CPU, JPEG wire, low-motion stream) | "
        f"{ours_jpeg_lm['fps']} | same-stream A/B partner for the delta "
        f"row |\n"
        f"| dvf_tpu (CPU, temporal-DELTA wire, low-motion stream — PR 7) "
        f"| {ours_delta_lm['fps']} | "
        f"**{doc['speedup_same_codec_low_motion_delta']}x** vs reference "
        f"(whose codec cost is motion-insensitive); "
        f"{doc['speedup_delta_vs_own_jpeg_low_motion']}x vs our jpeg wire "
        f"on the same stream; dirty ratio "
        f"{ours_delta_lm.get('dirty_ratio')} |\n"
        + (f"| dvf_tpu (coefficient wire HOST roofline, low-motion — "
           f"r15 full-transform assist) | "
           f"{full_assist.get('coef_wire_fps')} | "
           f"{full_assist.get('host_ratio_same_run')}x the full-JPEG "
           f"host cycle ({full_assist.get('jpeg_full_fps')} fps) same "
           f"run; entropy {full_assist.get('entropy_ms_per_frame')} "
           f"ms/frame; anchored "
           f"**{doc.get('speedup_same_codec_full_assist_anchored')}x** "
           f"vs reference |\n\n"
           if "host_ratio_same_run" in full_assist else
           f"| dvf_tpu (coefficient wire host roofline) | skipped | "
           f"{full_assist.get('skipped')} |\n\n")
        + ("Reference rows reused from the committed artifact "
           f"(captured {doc['reference_reused_from']['captured_utc'][:16]}"
           f", rev {doc['reference_reused_from']['code_rev']}) — "
           "/root/reference is not checked out on this host. This VM's "
           "effective speed drifts with hypervisor steal, so the direct "
           "ratio against the frozen reference row is host-era-skewed; "
           "the anchored ratio "
           f"(**{doc.get('speedup_same_codec_low_motion_delta_anchored')}"
           "x**) transports only same-run ratios: (delta wire / jpeg "
           "wire, this run, same stream) x (jpeg wire / reference, the "
           "committed same-host pair at "
           f"{doc['same_host_anchor']['captured_utc'][:16]}: "
           f"{doc['same_host_anchor']['jpeg_wire_fps']} / "
           f"{doc['same_host_anchor']['reference_fps']} fps = "
           f"{doc['same_host_anchor']['speedup_same_codec']}x).\n\n"
           if reused_reference else "")
        + (("The r15 full-assist row is a HOST-cost roofline, not an "
            "e2e leg: the same pre-transformed coefficient stream is "
            "served through the real DeltaCodec coefficient branch "
            "(framing, keyframes every "
            f"{full_assist['stream']['keyframe_interval']} frames, "
            "batched entropy shim) against full JPEG encodes of the "
            "same frames, best-of-3 each. Derivation: "
            f"{doc.get('speedup_same_codec_full_assist_derivation')} "
            "The e2e delta row above is pipeline-bound (see the raw-"
            "wire row), so it UNDERSTATES the wire's host-cost win; "
            "the roofline is the codec-bound bound.\n\n")
           if "host_ratio_same_run" in full_assist else "")
        + (f"Latency at a matched {lat_rate:.0f} fps offered rate (both "
           "uncongested): " if rates_matched else
           f"Latency (NOT rate-matched — ours backed off to "
           f"{ours_lat.get('target_fps')} fps or congested; reference at "
           f"{lat_rate:.0f} fps): ")
        + "reference capture→worker-end p50 "
        f"{ref_lat.get('p50_ms')} ms / p99 {ref_lat.get('p99_ms')} ms "
        "(generous: excludes collect receipt and its frame_delay display "
        "wait); dvf_tpu full capture→DELIVERED through the reorder "
        f"buffer p50 {ours_lat.get('p50_ms')} ms / p99 "
        f"{ours_lat.get('p99_ms')} ms (congested="
        f"{ours_lat.get('congested')}).\n\n"
        "The reference runs its own code end to end (imported from "
        "/root/reference, never copied): ROUTER fan-out, latest-wins "
        "slot, PULL collect, reorder buffer, with PyTurboJPEG provided "
        "by an API shim over the same in-repo libjpeg-turbo codec both "
        "sides use. Its app side is pre-encoded (generous: no capture/"
        "encode cost counted). dvf_tpu numbers are the full Pipeline e2e "
        "(ingest -> assembler -> jitted engine -> reorder -> sink) on "
        "the CPU backend; the TPU side of this workload is not "
        "measured.\n"
    )
    with open(args.out + ".md", "w") as f:
        f.write(md)
    print(json.dumps({"reference_fps": ref["fps"],
                      "ours_jpeg_fps": ours_jpeg["fps"],
                      "ours_raw_fps": ours_raw["fps"],
                      "ours_delta_low_motion_fps": ours_delta_lm["fps"],
                      "speedup_same_codec": doc["speedup_same_codec"],
                      "speedup_raw_wire": doc["speedup_raw_wire"],
                      "speedup_same_codec_low_motion_delta":
                          doc["speedup_same_codec_low_motion_delta"],
                      "speedup_anchored": doc.get(
                          "speedup_same_codec_low_motion_delta_anchored"),
                      "full_assist_host_ratio": full_assist.get(
                          "host_ratio_same_run"),
                      "speedup_full_assist_anchored": doc.get(
                          "speedup_same_codec_full_assist_anchored"),
                      "reference_reused": reused_reference,
                      "written": args.out + ".{json,md}"}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
