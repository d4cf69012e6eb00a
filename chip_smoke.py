#!/usr/bin/env python3
"""chip_smoke.py — does the serve path still start on the chip?

One process (it holds the chip and starts no child that needs it) drives
the production path once, end to end, and checks what comes out:

1. **serve** — each of the nine ``cli.BENCH_CONFIGS`` through
   ``ServeFrontend.open_stream/submit/poll`` at its own geometry, width and
   batch: delivery complete and in order, no fault, one compile, numerics
   against a reference (``Smoke.reference``), and which ingest/egress mode
   the bucket really took.
2. **mixed** — one frontend serving three signatures at once (buckets,
   program pool, cross-session batcher).  **cli** — ``python -m dvf_tpu
   serve --sessions 4`` in-process.
3. **kernels** — every ``pl.pallas_call`` site of ``ops/pallas_kernels.py``
   compiled through Mosaic at the geometry its caller uses and executed
   against its jnp golden, and a ``tpu_custom_call`` in the served
   programs whose TPU default is a Pallas kernel.
4. **multichip** (when >= 4 devices; ``--require-devices 4`` makes fewer
   an error) — batch sharding over four chips, four one-chip fleet
   replicas, and the ``ppermute`` halo exchange.

It chooses no platform: without a TPU it exits non-zero and prints no
result.  ``--cpu-tiny`` is the one way to run it elsewhere (small frames,
Pallas interpreted, every line labelled ``cpu``) — for tier-1 and for the
dry run before a chip call.  Wall times it prints are set-up information
labelled with the device, not metrics.  The last stdout line of a passing
run is ``{"ok": true, "device": {...}}``; any failed check exits non-zero
without it.

Usage: python chip_smoke.py [--cpu-tiny] [--require-devices N]
                            [--only SUBSTR[,SUBSTR]]
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import time
import traceback

# Riskiest first, so a short chip budget is spent where trouble is likeliest.
SERVE_ORDER = ("invert_1080p", "clahe_1080p", "sobel_bilateral_1080p",
               "style_720p", "flow_720p", "invert_640x480", "gauss3_1080p",
               "gauss9_1080p", "sr2x_540p", "fastdvd_540p")

BATCHES_PER_CONFIG = 3   # frames submitted = this many device batches
N_UNIQUE = 8             # distinct seeded frames per config (cycled)
SERVE_SESSIONS = 2       # sessions sharing each served config's batches

# Bounds on |served - reference| in uint8 steps, with the reason for each.
TOL_EXACT = 0   # invert is integer arithmetic: 255 - x, bit for bit
TOL_STEP = 1    # same math in another lowering (Pallas vs jnp, XLA vs cv2):
#   float results agree to ~1e-5 and may round to neighbouring uint8 values
# Learned filters: the served program computes in bfloat16 (8 mantissa
# bits), the reference in float32 at matmul precision "highest". Across
# ~16 convolutions and instance norms the rounding compounds to a few
# uint8 steps on average and, where a sigmoid/tanh output crosses its steep
# region, tens of steps at single pixels (measured on the v5e: see
# CHANGES.md PR 21). A wrong program — other weights, a transposed kernel,
# a missing layer — lands near the ~85-step mean of two unrelated images,
# so these bounds catch that and nothing finer; ROADMAP R1 tightens them.
TOL_NEURAL_MEAN = 4.0
TOL_NEURAL_MAX = 64


class SmokeFailure(AssertionError):
    pass


class Smoke:
    """Run state: the device, the size switch, the log, the failures."""

    def __init__(self, tiny: bool, only=()):
        import jax

        self.tiny = tiny
        self.only = tuple(only)   # --only: run-name substrings (debugging)
        self.devices = jax.devices()
        d0 = self.devices[0]
        self.platform = d0.platform
        self.device = {"platform": d0.platform, "kind": d0.device_kind,
                       "count": len(self.devices)}
        self.failed: list = []
        self.summary: dict = {"device": self.device, "configs": {}}
        self._refs: dict = {}

    def log(self, msg: str) -> None:
        print(f"[smoke {self.platform}] {msg}", flush=True)

    def check(self, cond, what: str) -> None:
        if not cond:
            raise SmokeFailure(what)

    def run(self, name: str, fn) -> None:
        """One leg or config. A failure is printed in full and fails the
        run; the remaining legs still execute, because one chip call that
        shows every failure is cheaper than one call per failure."""
        if self.only and not any(sub in name for sub in self.only):
            return
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:  # noqa: BLE001 — recorded, re-surfaced by exit code
            self.failed.append(name)
            self.log(f"FAILED {name}:")
            traceback.print_exc(file=sys.stdout)
            sys.stdout.flush()
        else:
            self.log(f"ok {name} ({time.perf_counter() - t0:.1f}s wall, "
                     f"set-up information)")

    # -- sizes -----------------------------------------------------------

    def geometry(self, name: str):
        """(h, w, batch, filter kwargs) of a BENCH_CONFIGS entry — its own
        at full size; cut to a toy under --cpu-tiny (depth too: one
        residual block, the widths stay)."""
        from dvf_tpu.cli import BENCH_CONFIGS

        spec = BENCH_CONFIGS[name]
        fname, kwargs = spec["filter"]
        kwargs = dict(kwargs)
        if not self.tiny:
            return spec["h"], spec["w"], spec["batch"], fname, kwargs
        if fname == "style_transfer":
            kwargs["n_residual"] = 1
        # flow's 3-level pyramid under a 15-tap window needs the room
        h, w = (64, 96) if fname == "flow_warp" else (32, 48)
        return h, w, min(spec["batch"], 4), fname, kwargs

    # -- inputs and references -------------------------------------------

    def frames(self, name: str, n: int):
        """``n`` seeded uint8 frames for a config. Noise for the
        stateless filters (N_UNIQUE distinct frames, cycled); for flow, a
        smooth texture sliding one pixel a frame, so the estimated flow
        stays well inside the Pallas warp's +-4 px displacement bound and
        the two warps are comparable."""
        import cv2
        import numpy as np

        h, w, _, fname, _ = self.geometry(name)
        rng = np.random.default_rng(sum(map(ord, name)))
        if fname != "flow_warp":
            base = rng.integers(0, 256, (N_UNIQUE, h, w, 3), dtype=np.uint8)
            return [base[i % N_UNIQUE] for i in range(n)]
        coarse = rng.random((h // 16 + 2, (w + n) // 16 + 2, 3),
                            dtype=np.float32)
        tex = cv2.resize(coarse, (w + n, h), interpolation=cv2.INTER_CUBIC)
        tex = (np.clip(tex, 0.0, 1.0) * 255.0).astype(np.uint8)
        return [np.ascontiguousarray(tex[:, i:i + w]) for i in range(n)]

    def reference(self, name: str):
        """``(expected uint8 outputs for self.frames(name, K), check)`` —
        memoized; ``check(got, want, where)`` applies the config's bound.
        K is N_UNIQUE for the stateless configs (frame i's expectation is
        entry i % N_UNIQUE) and the whole sequence for flow."""
        if name not in self._refs:
            self._refs[name] = self._build_reference(name)
        return self._refs[name]

    def _single_device_engine(self, filt):
        from dvf_tpu.parallel.mesh import MeshConfig, make_mesh
        from dvf_tpu.runtime.engine import Engine

        return Engine(filt, mesh=make_mesh(MeshConfig(),
                                           devices=self.devices[:1]))

    def _through_engine(self, filt, frames, chunk: int):
        """Reference run on the same device: the frames, in order, through
        a one-device Engine in chunks (the last one padded by repetition,
        as the runtime pads)."""
        import numpy as np

        eng = self._single_device_engine(filt)
        out = []
        for i in range(0, len(frames), chunk):
            part = frames[i:i + chunk]
            pad = part + [part[-1]] * (chunk - len(part))
            out.extend(np.asarray(eng.submit(np.stack(pad)))[:len(part)])
        eng.free()
        return out

    def _build_reference(self, name: str):
        import cv2
        import jax
        import numpy as np

        from dvf_tpu.ops import get_filter

        h, w, batch, fname, kwargs = self.geometry(name)

        def within(max_steps, mean_steps=float("inf")):
            def check(got, want, where):
                diff = np.abs(got.astype(np.int16) - want.astype(np.int16))
                mx, mean = int(diff.max()), float(diff.mean())
                self.check(mx <= max_steps and mean <= mean_steps,
                           f"{where}: |served - reference| max {mx} mean "
                           f"{mean:.3f} uint8 steps exceeds {max_steps} / "
                           f"{mean_steps}")
                return mx, mean
            return check

        if fname == "invert":
            base = self.frames(name, N_UNIQUE)
            return [255 - f for f in base], within(TOL_EXACT)
        if fname == "gaussian_blur":
            # The cv2 golden of tests/test_ops_golden.py, in uint8.
            k = kwargs["ksize"]
            want = []
            for f in self.frames(name, N_UNIQUE):
                ref = cv2.GaussianBlur(f.astype(np.float32) / 255.0, (k, k),
                                       0.0, borderType=cv2.BORDER_REFLECT_101)
                want.append(np.round(np.clip(ref, 0, 1) * 255.0)
                            .astype(np.uint8))
            return want, within(TOL_STEP)
        if fname == "sobel_bilateral":
            # TPU default = the fused Pallas kernel; reference = the same
            # op's two-stage jnp chain, on the same device.
            ref = get_filter(fname, impl="chain", **kwargs)
            return (self._through_engine(ref, self.frames(name, N_UNIQUE), 2),
                    within(TOL_STEP))
        if fname == "clahe":
            # TPU default = the counted histograms and lane-gather lookups
            # (ops/histogram.py, impl "pallas"); reference = the same op's
            # sort + gather form on the same device: integers, bit for bit.
            ref = get_filter(fname, impl="sort", **kwargs)
            return (self._through_engine(ref, self.frames(name, N_UNIQUE), 2),
                    within(TOL_EXACT))
        if fname == "flow_warp":
            # TPU default = the Pallas bounded warp; reference = the XLA
            # gather warp, each served session's frames (k, k+n, ...: two
            # pixels of slide a frame) alone through a one-stream engine:
            # what a session gets depends on that session's frames only.
            ref = get_filter(fname, warp_impl="gather", **kwargs)
            n = BATCHES_PER_CONFIG * batch
            frames = self.frames(name, n)
            want = [None] * n
            for k in range(SERVE_SESSIONS):
                mine = range(k, n, SERVE_SESSIONS)
                outs = self._through_engine(
                    ref, [frames[j] for j in mine], batch)
                for j, out in zip(mine, outs):
                    want[j] = out
            return want, within(TOL_STEP)
        if fname == "video_denoise":
            # The served form keeps two stage-1 results a session and runs
            # two DenBlocks a frame; reference = the published window at
            # once, four blocks, uncached, float32 at precision "highest"
            # on the same device and the same seeded weights, each served
            # session's frames (k, k+n, ...) alone: delivery i is the
            # window of frames i-4 .. i (before the start: frame 0), whose
            # centre is frame max(i - 2, 0). Held to the benchmark's own
            # limits (chipbench/configs/fastdvd_540p.json).
            import json

            import jax.numpy as jnp

            from dvf_tpu.models import fastdvdnet as net

            params = net.init_fastdvdnet(jax.random.PRNGKey(0))
            config = net.FastDvdConfig(compute_dtype=jnp.float32)
            uncached = jax.jit(lambda win: jnp.round(jnp.clip(
                net.apply_fastdvdnet(params, win.astype(jnp.float32) / 255.0, config),
                0.0, 1.0) * 255.0).astype(jnp.uint8))
            n = BATCHES_PER_CONFIG * batch
            frames = self.frames(name, n)
            want = [None] * n
            with jax.default_matmul_precision("highest"):
                for k in range(SERVE_SESSIONS):
                    mine = list(range(k, n, SERVE_SESSIONS))
                    for i, j in enumerate(mine):
                        win = np.stack([frames[mine[max(i - 4 + t, 0)]] for t in range(5)])
                        want[j] = np.asarray(uncached(win[None]))[0]
            here = os.path.dirname(os.path.abspath(__file__))
            with open(os.path.join(here, "chipbench", "configs", "fastdvd_540p.json")) as f:
                limits = json.load(f)["limits"]
            return want, within(limits["max_abs_steps"], limits["mean_abs_steps"])
        # style_transfer / super_resolution: the same weights (same seed),
        # float32 compute, matmul precision "highest".
        ref = get_filter(fname, dtype="float32", **kwargs)
        with jax.default_matmul_precision("highest"):
            want = self._through_engine(ref, self.frames(name, N_UNIQUE), 2)

        return want, within(TOL_NEURAL_MAX, TOL_NEURAL_MEAN)


# ---------------------------------------------------------------------------
# Leg 0: the device
# ---------------------------------------------------------------------------


def report_device(s: Smoke, cache_dir: str) -> None:
    import jax
    import jaxlib

    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "not installed"
    s.log(f"platform: {s.platform}  device_kind: {s.device['kind']}  "
          f"devices: {s.device['count']}")
    s.log(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  "
          f"libtpu {libtpu_version}")
    s.log(f"compile cache: {cache_dir} "
          f"({len(os.listdir(cache_dir))} entries at start)")
    from dvf_tpu.cli import native_shim_status

    transport = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "dvf_tpu", "transport")
    cached = {key: os.path.exists(os.path.join(transport, lib))
              for key, lib in (("ring_shim", "_ring.so"),
                               ("jpeg_shim", "_jpeg_shim.so"))}
    for key, status in native_shim_status().items():
        how = "loaded from cache" if cached[key] else "built from source"
        s.log(f"native {key}: {how if status == 'ok' else status}")


# ---------------------------------------------------------------------------
# Leg 1 + 2: serving
# ---------------------------------------------------------------------------


STALL_S = 240.0   # no delivery for this long is a hang (the longest cold
#   compile, style_720p, is under a minute on the v5e)


def _poll_until_complete(s: Smoke, want_counts: dict, poll_once, idle) -> None:
    """Call ``poll_once() -> frames moved`` until every stream has
    ``want_counts[sid]`` deliveries (``poll_once`` keeps the tally in its
    own closure and reports it through ``idle``). Fails fast instead of
    waiting out a budget: as soon as the server is idle with frames still
    missing (they were lost, not late), or when nothing has moved for
    STALL_S."""
    last_move = time.time()
    while True:
        moved, have = poll_once()
        if all(have[sid] >= n for sid, n in want_counts.items()):
            return
        if moved:
            last_move = time.time()
            continue
        quiet = time.time() - last_move
        if quiet > 2.0:
            lost = idle()
            s.check(lost is None,
                    f"server idle with deliveries missing: have {have}, "
                    f"want {want_counts}; {lost}")
        s.check(quiet < STALL_S,
                f"no delivery for {STALL_S:.0f}s: have {have}, want "
                f"{want_counts}")
        time.sleep(0.005)


def _drive(s: Smoke, fe, streams: dict) -> dict:
    """Submit every stream's frames (round-robin, so batches mix sessions),
    poll everything out, and return the worst ``(max, mean)`` difference
    per stream. Each frame is checked as it is polled and dropped, so a
    1080p run holds one batch of outputs, not all of them."""
    import numpy as np

    got = {sid: [] for sid in streams}
    longest = max(len(st["frames"]) for st in streams.values())
    for i in range(longest):
        for sid, st in streams.items():
            if i < len(st["frames"]):
                fe.submit(sid, st["frames"][i])
    worst = {sid: (0, 0.0) for sid in streams}

    def poll_once():
        health = fe.health()
        s.check(health["ok"], f"frontend failed: {health['error']}")
        moved = 0
        for sid, st in streams.items():
            for d in fe.poll(sid):
                moved += 1
                want = st["want"][d.index % len(st["want"])]
                s.check(d.frame.shape == want.shape
                        and d.frame.dtype == np.uint8,
                        f"{sid}[{d.index}]: delivered {d.frame.shape}/"
                        f"{d.frame.dtype}, expected {want.shape}/uint8")
                mx, mean = st["check"](d.frame, want, f"{sid}[{d.index}]")
                worst[sid] = (max(worst[sid][0], mx),
                              max(worst[sid][1], mean))
                got[sid].append(d.index)
        return moved, {sid: len(v) for sid, v in got.items()}

    def idle():
        st = fe.stats()
        if st["queue_depth"] or st["inflight_batches"]:
            return None
        return {sid: {k: row[k] for k in (
            "submitted", "delivered", "shed", "failed",
            "dropped_at_ingress", "dropped_unpolled")}
            for sid, row in st["sessions"].items()}

    _poll_until_complete(s, {sid: len(st["frames"])
                             for sid, st in streams.items()},
                         poll_once, idle)
    for sid, st in streams.items():
        s.check(got[sid] == list(range(len(st["frames"]))),
                f"{sid}: delivered indices {got[sid][:8]}... are not "
                f"0..{len(st['frames']) - 1} in order")
    return worst


def _check_clean(s: Smoke, stats: dict, submitted: int) -> None:
    delivered = sum(row["delivered"] for row in stats["sessions"].values())
    s.check(delivered == submitted,
            f"delivered {delivered} != submitted {submitted}")
    s.check(stats["errors"] == 0, f"errors = {stats['errors']}")
    s.check(stats["faults"]["by_kind"] == {},
            f"faults = {stats['faults']['by_kind']}")
    s.check(stats["recoveries"] == 0, f"recoveries = {stats['recoveries']}")
    s.check(stats["shed_total"] == 0, f"shed_total = {stats['shed_total']}")
    if "supervisor" in stats:
        s.check(stats["supervisor"]["stalls"] == 0,
                f"watchdog stalls = {stats['supervisor']['stalls']}")
    for label, row in stats["buckets"].items():
        s.check(row["engine_compile_count"] == 1,
                f"bucket {label}: {row['engine_compile_count']} compiles "
                f"for one signature")


def _bucket_modes(row: dict) -> dict:
    return {side: {"mode": row[side]["mode"],
                   "fallback_reason": row[side]["fallback_reason"]}
            for side in ("ingest", "egress") if side in row}


def _has_mosaic_call(engine) -> bool:
    """Does the engine's served program contain a Mosaic kernel? (A Pallas
    kernel that quietly gave way to jnp, or to interpret mode, does not.)"""
    text = engine.compiled_step().as_text()
    return "tpu_custom_call" in text


def serve_config(s: Smoke, name: str) -> None:
    from dvf_tpu.ops import get_filter
    from dvf_tpu.serve import ServeConfig, ServeFrontend

    h, w, batch, fname, kwargs = s.geometry(name)
    filt = get_filter(fname, **kwargs)
    # Sessions sharing batches, flow included: its temporal state is per
    # session (the engine's session table).
    n_sessions = SERVE_SESSIONS
    total = BATCHES_PER_CONFIG * batch
    per = total // n_sessions
    want, check = s.reference(name)
    frames = s.frames(name, total)
    config = ServeConfig(
        batch_size=batch, resilient=False, slo_ms=3_600_000.0,
        queue_size=per, out_queue_size=per)
    fe = ServeFrontend(filt, config)
    with fe:
        sids = [fe.open_stream(frame_shape=(h, w, 3))
                for _ in range(n_sessions)]
        # Session k takes frames k, k+n, ...: its expectation for index i
        # is reference entry (k + i*n) — a frame delivered to the wrong
        # session, or out of order, compares against the wrong content.
        streams = {}
        for k, sid in enumerate(sids):
            mine = list(range(k, total, n_sessions))
            streams[sid] = {
                "frames": [frames[j] for j in mine],
                "want": [want[j % len(want)] for j in mine],
                "check": check}
        worst = _drive(s, fe, streams)
        stats = fe.stats()
        scrape = fe.registry.to_prometheus()  # what /metrics serves: runs
        #   the memory providers (device.memory_stats() is None on CPU)
        engine = fe.engine
        row = next(iter(stats["buckets"].values()))
        modes = _bucket_modes(row)
        mosaic = _has_mosaic_call(engine)
        exec_name = engine._exec_filter.name
        compile_ms = engine.last_compile_ms
        calib = {k: getattr(engine, k) for k in
                 ("h2d_block_ms", "d2h_block_ms", "step_block_ms")}
    _check_clean(s, stats, total)
    s.check("dvf_" in scrape, "metrics scrape rendered nothing")
    if name in ("sobel_bilateral_1080p", "flow_720p", "clahe_1080p") and not s.tiny:
        s.check(mosaic, f"{name}: the served program has no "
                        f"tpu_custom_call — the Pallas kernel is not in it")
    mx = max(v[0] for v in worst.values())
    mean = max(v[1] for v in worst.values())
    s.log(f"{name}: {fname} {h}x{w} batch {batch}, {n_sessions} session(s), "
          f"{total} frames delivered in order; |diff| vs reference max {mx} "
          f"mean {mean:.3f} uint8 steps; cold compile {compile_ms:.0f} ms "
          f"(set-up information); calibrations {calib}; "
          f"tpu_custom_call={mosaic}; modes {modes}; exec filter "
          f"{exec_name!r}, replicated_batches "
          f"{stats['replicated_batches']}")
    s.summary["configs"][name] = {
        "frames": total, "sessions": n_sessions, "max_diff": mx,
        "mean_diff": round(mean, 4), "compile_ms": round(compile_ms, 1),
        "tpu_custom_call": mosaic, **modes}


def serve_mixed(s: Smoke) -> None:
    """One frontend, three signatures at once: buckets, the program pool
    and the cross-session batcher — the shape production runs."""
    from dvf_tpu.ops import get_filter
    from dvf_tpu.runtime.signature import canonical_op_chain
    from dvf_tpu.serve import ServeConfig, ServeFrontend

    batch = 4 if s.tiny else 8
    per = 2 * batch
    tenants = ("invert_1080p", "sobel_bilateral_1080p", "style_720p")
    config = ServeConfig(batch_size=batch, resilient=False,
                         slo_ms=3_600_000.0, queue_size=per,
                         out_queue_size=per)
    fe = ServeFrontend(get_filter("invert"), config)
    with fe:
        streams = {}
        for name in tenants:
            h, w, _, fname, kwargs = s.geometry(name)
            want, check = s.reference(name)
            sid = fe.open_stream(
                op_chain=canonical_op_chain([(fname, kwargs)]),
                frame_shape=(h, w, 3))
            streams[sid] = {"frames": s.frames(name, per), "want": want,
                            "check": check}
        _drive(s, fe, streams)
        stats = fe.stats()
    _check_clean(s, stats, per * len(tenants))
    s.check(stats["open_buckets"] == len(tenants),
            f"{stats['open_buckets']} buckets for {len(tenants)} signatures")
    s.log(f"mixed: {sorted(stats['buckets'])} served together, "
          f"{per * len(tenants)} frames, pool {stats['pool']}")


def serve_cli(s: Smoke) -> None:
    """The CLI itself, in-process (no child: this process holds the chip)."""
    from dvf_tpu.cli import main as cli_main

    h, w, frames = (48, 64, 12) if s.tiny else (1080, 1920, 48)
    argv = ["serve", "--sessions", "4", "--height", str(h), "--width",
            str(w), "--frames", str(frames), "--quiet",
            # lossless on purpose: with the default 10-frame ingress bound
            # and 1 s SLO the first-batch compile sheds frames by design,
            # and "every stream complete" would not be checkable
            "--queue-size", str(frames), "--slo-ms", "600000"]
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli_main(argv)
    out = json.loads(buf.getvalue().strip().splitlines()[-1])
    s.check(rc == 0, f"`dvf_tpu {' '.join(argv)}` returned {rc}: {out}")
    for sid, row in out["sessions"].items():
        s.check(row["submitted"] == frames and row["delivered"] == frames,
                f"cli stream {sid} incomplete: {row}")
    s.check(out["errors"] == 0 and out["faults"] == {},
            f"cli run recorded errors/faults: {out}")
    s.log(f"cli: `python -m dvf_tpu {' '.join(argv)}` -> rc 0, "
          f"{len(out['sessions'])} streams x {frames} frames complete")


# ---------------------------------------------------------------------------
# Leg 3: every pallas_call site, compiled through Mosaic, against its golden
# ---------------------------------------------------------------------------


def kernel_cases(s: Smoke):
    """``[(name, check_fn)]`` — one entry per kernel case, each run (and
    failed) on its own so one chip call shows every kernel's verdict."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dvf_tpu.ops import get_filter
    from dvf_tpu.ops.bilateral import bilateral_nhwc
    from dvf_tpu.ops.conv import gaussian_kernel_1d, sep_conv2d
    from dvf_tpu.ops.flow import warp_by_flow
    from dvf_tpu.ops.pallas_kernels import (
        bilateral_nhwc_pallas,
        dct8x8_quant_pallas,
        dct8x8_quant_ref,
        jpeg_quant_table,
        sep_blur_nhwc_pallas,
        sobel_bilateral_nhwc_pallas,
        tile_maxdiff,
        warp_bounded_pallas,
    )
    from dvf_tpu.runtime.codec_assist import FusedDeltaTransform

    interp = s.platform != "tpu"   # Mosaic exists on the TPU only
    b = 2
    if s.tiny:
        hw1080, hw720, hw360 = (32, 48), (32, 48), (16, 24)
        tiles = ()
    else:
        hw1080, hw720, hw360 = (1080, 1920), (720, 1280), (360, 640)
        tiles = (8, 40, 120)   # pinned tiles (the auto pick is 24)
    # Float kernels are compared in float, to half a uint8 step: a
    # disagreement can then move a delivered pixel by at most one step.
    half_step = 0.5 / 255.0

    def rng():
        return np.random.default_rng(21)

    def mosaic_compiled(fn, *args):
        """jit, lower, confirm the Mosaic call is in the program (on the
        TPU), compile, execute once."""
        lowered = jax.jit(fn).lower(*args)
        if not interp:
            s.check("tpu_custom_call" in lowered.as_text(),
                    "lowered program has no tpu_custom_call")
        t0 = time.perf_counter()
        out = jax.block_until_ready(lowered.compile()(*args))
        return out, time.perf_counter() - t0

    def close(name, got, want, atol):
        got, want = np.asarray(got), np.asarray(want)
        s.check(got.shape == want.shape,
                f"{name}: shape {got.shape} != golden {want.shape}")
        err = float(np.abs(got.astype(np.float64)
                           - want.astype(np.float64)).max())
        s.check(np.isfinite(got).all() and err <= atol,
                f"{name}: max |kernel - golden| = {err:.3g} > {atol:.3g}")
        return err

    def stencil(name, kernel_fn, golden_fn):
        def run():
            frame = jnp.asarray(rng().random((b, *hw1080, 3),
                                             dtype=np.float32))
            got, secs = mosaic_compiled(kernel_fn, frame)
            err = close(name, got, jax.jit(golden_fn)(frame), half_step)
            s.log(f"kernel {name}: compiled, matches its jnp golden (max "
                  f"|diff| {err:.2e}; compile+run {secs:.1f}s)")
        return name, run

    cases = []
    chain = get_filter("sobel_bilateral", impl="chain")
    for th in (None, *tiles):
        tag = "" if th is None else f"_tile{th}"
        cases.append(stencil(
            f"bilateral_1080p{tag}",
            lambda x, th=th: bilateral_nhwc_pallas(x, tile_h=th,
                                                   interpret=interp),
            bilateral_nhwc))
        cases.append(stencil(
            f"sobel_bilateral_1080p{tag}",
            lambda x, th=th: sobel_bilateral_nhwc_pallas(x, tile_h=th,
                                                         interpret=interp),
            lambda x: chain.fn(x, None)[0]))
    for k in (3, 9):
        kern = gaussian_kernel_1d(k, 0.0)
        cases.append(stencil(
            f"gauss{k}_1080p",
            lambda x, kern=kern: sep_blur_nhwc_pallas(x, kern, kern,
                                                      interpret=interp),
            lambda x, kern=kern: sep_conv2d(x, kern, kern)))

    # The flow warp: the 3-channel final warp at 720p, and the 5-channel
    # polynomial-stack warp at the flow-estimation geometry (720p / 2).
    def warp(name, hw, c):
        def run():
            r = rng()
            img = jnp.asarray(r.random((b, *hw, c), dtype=np.float32))
            flow = jnp.asarray((r.random((b, *hw, 2), dtype=np.float32)
                                - 0.5) * 7.0)
            got, secs = mosaic_compiled(
                lambda i, f: warp_bounded_pallas(i, f, interpret=interp),
                img, flow)
            want = jax.jit(lambda i, f: warp_by_flow(
                i, jnp.clip(f, -4, 4)))(img, flow)
            err = close(name, got, want, half_step)
            s.log(f"kernel {name}: compiled, matches the gather warp (max "
                  f"|diff| {err:.2e}; compile+run {secs:.1f}s)")
        return name, run

    cases.append(warp("flow_warp_720p", hw720, 3))
    cases.append(warp("flow_inner_warp_5ch", hw360, 5))

    # tile_maxdiff: integer, so exact. On the TPU the dispatcher takes the
    # jnp golden by a named constant (the kernel does not lower through
    # Mosaic — ops/pallas_kernels.py says why); the smoke pins that the
    # route is the declared one and that what it computes is right, at
    # geometries the Pallas route would otherwise claim. Elsewhere the
    # kernel itself runs, interpreted.
    from dvf_tpu.ops.pallas_kernels import TILE_MAXDIFF_PALLAS_ON_TPU

    def maxdiff(h, w, tile):
        name = f"tile_maxdiff_{h}x{w}_t{tile}"

        def run():
            r = rng()
            a, c = (jnp.asarray(r.integers(0, 256, (b, h, w, 3),
                                           dtype=np.uint8))
                    for _ in range(2))
            fn = jax.jit(lambda x, y: tile_maxdiff(x, y, tile))
            pallas = "pallas_call" in str(jax.make_jaxpr(fn)(a, c))
            s.check(pallas == (interp or TILE_MAXDIFF_PALLAS_ON_TPU),
                    f"{name}: dispatcher took "
                    f"{'the kernel' if pallas else 'the golden'}, "
                    f"TILE_MAXDIFF_PALLAS_ON_TPU={TILE_MAXDIFF_PALLAS_ON_TPU}")
            want = np.abs(np.asarray(a).astype(np.int16)
                          - np.asarray(c).astype(np.int16)).reshape(
                b, h // tile, tile, w // tile, tile, 3).max(axis=(2, 4, 5))
            close(name, fn(a, c), want, 0)
            s.log(f"kernel {name}: the dispatcher takes "
                  f"{'the Pallas kernel' if pallas else 'the jnp golden'} "
                  f"(TILE_MAXDIFF_PALLAS_ON_TPU="
                  f"{TILE_MAXDIFF_PALLAS_ON_TPU}); exact against numpy")
        return name, run

    for h, w, tile in (((64, 64, 32),) if s.tiny
                       else ((512, 512, 32), (1080, 1920, 8))):
        cases.append(maxdiff(h, w, tile))

    def maxdiff_routes():
        # The kernel's existence is not its use, on any platform:
        # DeltaCodec's default tile is 32 (transport/codec.py), which
        # divides neither 1080 nor 720.
        for h, w in ((1080, 1920), (720, 1280)):
            x = jax.ShapeDtypeStruct((1, h, w, 3), jnp.uint8)
            jaxpr = str(jax.make_jaxpr(
                lambda p, q: tile_maxdiff(p, q, 32))(x, x))
            route = ("the Pallas kernel" if "pallas_call" in jaxpr else
                     "the jnp golden")
            s.log(f"tile_maxdiff at {h}x{w}, tile 32 (the delta wire's "
                  f"default) takes {route} ({h} % 32 = {h % 32})")

    cases.append(("tile_maxdiff_routes", maxdiff_routes))

    # DCT + quantisation. Its caller (FusedDeltaTransform) needs H and W
    # to be tile multiples with tile % 16 == 0: 720p at tile 16 gives the
    # luma 720x1280 / chroma 360x640 planes; 1080 is no multiple of 16, so
    # no caller ever sends 1080p chroma (540 rows are not 8-aligned). The
    # luma 1080x1920 plane is kept from the old compile check's list.
    q_luma, q_chroma = jpeg_quant_table(90), jpeg_quant_table(90, chroma=True)

    def dct(name, hw, q):
        def run():
            plane = jnp.asarray(rng().uniform(0, 255, (b, *hw))
                                .astype(np.float32))
            got, secs = mosaic_compiled(
                lambda p: dct8x8_quant_pallas(p, q, interpret=interp), plane)
            want = np.asarray(dct8x8_quant_ref(plane, q))
            got = np.asarray(got)
            s.check(got.shape == want.shape,
                    f"dct {name}: shape {got.shape} != {want.shape}")
            diff = np.abs(got.astype(np.int32) - want.astype(np.int32))
            n_bad = int((diff != 0).sum())
            # Bit for bit: quantised coefficients ride the wire as they
            # are and the audit plane replays them against the golden, so
            # one step of disagreement is wire-visible. It holds on the
            # v5e (PR 21: 0 of 4.1 M coefficients differ) — neither
            # compiler contracts the multiply-adds.
            s.check(n_bad == 0,
                    f"dct {name}: {n_bad} of {diff.size} coefficients "
                    f"differ from the XLA golden (max {int(diff.max())})")
            s.log(f"kernel dct8x8_quant {name}: compiled, bit-exact against "
                  f"the XLA golden over {diff.size} coefficients "
                  f"(compile+run {secs:.1f}s)")
        return f"dct8x8_quant_{name}", run

    for name, hw, q in ((("luma_32x48", (32, 48), q_luma),) if s.tiny else
                        (("luma_1080x1920", (1080, 1920), q_luma),
                         ("luma_720x1280", (720, 1280), q_luma),
                         ("chroma_360x640", (360, 640), q_chroma))):
        cases.append(dct(name, hw, q))

    def fused_transform():
        # ...and the caller itself: probe + colour convert + three DCTs in
        # one jitted program, at a geometry it supports.
        tile, (h, w) = 16, ((32, 48) if s.tiny else (720, 1280))
        s.check(FusedDeltaTransform.supports((b, h, w, 3), tile),
                f"FusedDeltaTransform refuses {h}x{w} at tile {tile}")
        fused = FusedDeltaTransform(tile=tile, quality=90)
        batch = rng().integers(0, 256, (b, h, w, 3), dtype=np.uint8)
        bitmaps, coeff_frames = fused.process(jnp.asarray(batch))
        s.check(bitmaps.shape == (b, h // tile, w // tile)
                and len(coeff_frames) == b and fused.calls == 1,
                f"fused transform returned bitmaps {bitmaps.shape}, "
                f"{len(coeff_frames)} frames, {fused.calls} dispatches")
        s.log(f"FusedDeltaTransform {h}x{w} tile {tile}: one dispatch, "
              f"bitmaps {bitmaps.shape}")

    cases.append(("fused_delta_transform", fused_transform))

    def histogram(name, **kwargs):
        # The histogram family's two kernels (tile_hist_pallas,
        # lut_apply_pallas) as the filter calls them, against the sort +
        # gather form of the same filter: integers, so bit for bit.
        def run():
            h, w = hw1080
            x = jnp.asarray(rng().integers(0, 256, (b, h, w, 3), dtype=np.uint8))
            got, dt = mosaic_compiled(
                lambda v: get_filter(name, impl="pallas", **kwargs).fn(v, None)[0], x)
            want = get_filter(name, impl="sort", **kwargs).fn(x, None)[0]
            close(name, got, want, 0)
            s.log(f"{name} {h}x{w} {kwargs}: equals the sort form, {dt:.1f}s "
                  f"compile + first run")
        return f"{name}_forms", run

    cases.append(histogram("clahe", clip_limit=2.0, grid=8))
    cases.append(histogram("equalize"))
    return cases


# ---------------------------------------------------------------------------
# Leg 4: four chips
# ---------------------------------------------------------------------------


def multichip_cases(s: Smoke):
    """``[(name, check_fn)]`` for the four-chip legs, each run on its own."""
    import numpy as np

    from dvf_tpu.fleet import FleetConfig, FleetFrontend
    from dvf_tpu.ops import get_filter
    from dvf_tpu.parallel.mesh import MeshConfig, make_mesh
    from dvf_tpu.runtime.engine import Engine
    from dvf_tpu.serve import ServeConfig, ServeFrontend

    def default_mesh(name):
        # (a) one frontend on the default mesh (all devices on the data
        # axis) gives the one-chip output — judged by the same reference
        # and bound the one-chip serve leg meets (255 - x for invert, the
        # float32 forward pass for style) — with each device holding its
        # own rows of every batch.
        def run():
            h, w, batch, fname, kwargs = s.geometry(name)
            batch = max(batch, 4)
            total = 2 * batch
            want, check = s.reference(name)
            fe = ServeFrontend(get_filter(fname, **kwargs), ServeConfig(
                batch_size=batch, resilient=False, slo_ms=3_600_000.0,
                queue_size=total, out_queue_size=total))
            with fe:
                sid = fe.open_stream(frame_shape=(h, w, 3))
                worst = _drive(s, fe, {sid: {
                    "frames": s.frames(name, total), "want": want,
                    "check": check}})
                stats = fe.stats()
                eng = fe.engine
                mesh_shape = dict(zip(eng.mesh.axis_names,
                                      eng.mesh.devices.shape))
                spans = {}
                for side, sharding, shape in (
                        ("input", eng.input_sharding, eng.signature[0]),
                        ("output", eng.output_sharding, eng.out_shape)):
                    idx = sharding.devices_indices_map(tuple(shape))
                    spans[side] = {d.id: (i[0].start, i[0].stop)
                                   for d, i in idx.items()}
                peak = {d.id: (d.memory_stats() or {}).get(
                            "peak_bytes_in_use")
                        for d in eng.mesh.devices.flatten()}
                modes = _bucket_modes(next(iter(stats["buckets"].values())))
            _check_clean(s, stats, total)
            s.check(mesh_shape["data"] == len(s.devices),
                    f"default mesh is {mesh_shape}, not "
                    f"data={len(s.devices)}")
            s.check(stats["replicated_batches"] == 0,
                    f"{stats['replicated_batches']} batches were "
                    f"replicated: every chip computed the whole batch")
            for side, span in spans.items():
                s.check(len(span) == len(s.devices)
                        and len(set(span.values())) == len(s.devices),
                        f"{name} {side} sharding does not give each device "
                        f"its own batch rows: {span}")
            if s.platform == "tpu":   # memory_stats() is None on the CPU
                s.check(all(peak.values()),
                        f"not every chip has held bytes: {peak}")
            mx, mean = list(worst.values())[0]
            s.log(f"multichip (a) {name}: mesh {mesh_shape}, batch {batch}, "
                  f"input rows {spans['input']}, output rows "
                  f"{spans['output']}, peak_bytes_in_use {peak}, modes "
                  f"{modes}; |diff| vs the one-chip leg's reference max "
                  f"{mx} mean {mean:.3f} uint8 steps")
        return f"multichip:a:{name}", run

    def fleet_replicas():
        # (b) four one-chip replicas in one process, eight sessions.
        h, w, batch, fname, kwargs = s.geometry("invert_1080p")
        batch = min(batch, 16)
        want, check = s.reference("invert_1080p")
        per = 2 * batch
        fleet = FleetFrontend(get_filter(fname, **kwargs), FleetConfig(
            mode="local", replicas=4, devices_per_replica=1,
            serve=ServeConfig(batch_size=batch, resilient=False,
                              slo_ms=3_600_000.0, queue_size=per,
                              out_queue_size=per)))
        frames = s.frames("invert_1080p", per)
        with fleet:
            sids = [fleet.open_stream(frame_shape=(h, w, 3))
                    for _ in range(8)]
            for i in range(per):
                for sid in sids:
                    fleet.submit(sid, frames[i])
            got = {sid: [] for sid in sids}

            def poll_once():
                moved = 0
                for sid in sids:
                    for d in fleet.poll(sid):
                        moved += 1
                        check(d.frame, want[d.index % len(want)],
                              f"fleet {sid}[{d.index}]")
                        got[sid].append(d.index)
                return moved, {sid: len(v) for sid, v in got.items()}

            _poll_until_complete(s, {sid: per for sid in sids}, poll_once,
                                 idle=lambda: None)
            stats = fleet.stats()
            placed = {rid: [d.id for d in
                            r.frontend.engine.mesh.devices.flatten()]
                      for rid, r in fleet._replicas.items()}
        for sid in sids:
            s.check(got[sid] == list(range(per)),
                    f"fleet {sid} out of order")
        s.check(len({tuple(v) for v in placed.values()}) == 4
                and all(len(v) == 1 for v in placed.values()),
                f"replicas do not each hold their own chip: {placed}")
        s.check(stats["stacked_replicas"] == 0
                and stats["order_violations"] == 0
                and stats["replica_losses"] == 0
                and stats["faults"]["by_kind"] == {},
                f"fleet not clean: stacked {stats['stacked_replicas']}, "
                f"order violations {stats['order_violations']}, losses "
                f"{stats['replica_losses']}, faults "
                f"{stats['faults']['by_kind']}")
        batches = {r: row.get("engine_batches")
                   for r, row in stats["replicas"].items()}
        s.check(all(batches.values()),
                f"replicas that served no batch: {batches}")
        s.log(f"multichip (b) fleet: replicas on devices {placed}, batches "
              f"{batches}, 8 sessions x {per} frames exact and in order")

    def halo_exchange():
        # (c) the ppermute halo exchange over real ICI: sobel_bilateral on
        # a data=2, space=2 mesh equals the single-device result.
        h, w, _, fname, kwargs = s.geometry("sobel_bilateral_1080p")
        if s.tiny:
            h = 64   # each of the two row shards must out-size the halo
        x = np.random.default_rng(4).integers(0, 256, (4, h, w, 3),
                                              dtype=np.uint8)
        sharded = Engine(get_filter(fname, **kwargs),
                         mesh=make_mesh(MeshConfig(data=2, space=2),
                                        devices=s.devices[:4]))
        sharded.compile(x.shape, np.uint8)
        s.check(sharded._exec_filter.name.startswith("spatial("),
                f"engine did not route through the halo exchange: "
                f"{sharded._exec_filter.name!r}")
        single = s._single_device_engine(get_filter(fname, **kwargs))
        got = np.asarray(sharded.submit(x))
        ref = np.asarray(single.submit(x))
        diff = int(np.abs(got.astype(np.int16) - ref.astype(np.int16)).max())
        s.check(diff <= TOL_STEP,
                f"halo-sharded differs from single-device by {diff} steps")
        s.log(f"multichip (c) halo: sobel_bilateral {x.shape} on "
              f"data=2,space=2 ({sharded._exec_filter.name}) equals "
              f"single-device within {diff} uint8 step(s)")
        sharded.free()
        single.free()

    return [default_mesh("invert_1080p"), default_mesh("style_720p"),
            ("multichip:b:fleet", fleet_replicas),
            ("multichip:c:halo", halo_exchange)]


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--cpu-tiny", action="store_true",
                    help="run on the CPU at toy sizes with Pallas "
                         "interpreted (tier-1, and the dry run before a "
                         "chip call); never a default")
    ap.add_argument("--require-devices", type=int, default=0, metavar="N",
                    help="fail unless N devices are attached (4 makes the "
                         "multichip leg mandatory)")
    ap.add_argument("--only", default="", metavar="SUBSTR[,SUBSTR]",
                    help="debugging: run only the checks whose name contains "
                         "one of these (names: serve:<config>, mixed, cli, "
                         "kernel:<case>, multichip:<a|b|c>:...); the default is all")
    args = ap.parse_args(argv)
    only = [x for x in args.only.split(",") if x]

    t_start = time.perf_counter()
    import jax

    if args.cpu_tiny:
        # The explicit, labelled exception: the CPU platform, with as many
        # virtual devices as --require-devices asks for.
        jax.config.update("jax_platforms", "cpu")
        # XLA:CPU compile time is most of this run; the dry run checks
        # control flow and numerics at tolerance, not generated code.
        jax.config.update("jax_disable_most_optimizations", True)
        if args.require_devices > 1:
            jax.config.update("jax_num_cpu_devices", args.require_devices)
    from dvf_tpu.runtime.engine import enable_compilation_cache

    cache_dir = enable_compilation_cache(persist_small=True)
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.cpu_tiny:
        print(f"chip_smoke: jax found platform {platform!r} "
              f"({devices[0].device_kind}), not a tpu. This smoke runs on "
              f"the chip or not at all (--cpu-tiny is the labelled "
              f"dry run).", file=sys.stderr)
        return 3
    if args.require_devices and len(devices) < args.require_devices:
        print(f"chip_smoke: --require-devices {args.require_devices} but "
              f"jax reports {len(devices)} {platform} device(s)",
              file=sys.stderr)
        return 3

    s = Smoke(tiny=args.cpu_tiny, only=only)
    entries_before = len(os.listdir(cache_dir))
    report_device(s, cache_dir)
    if only:
        s.log(f"PARTIAL RUN: --only {only}")
    for name in SERVE_ORDER:
        s.run(f"serve:{name}", lambda name=name: serve_config(s, name))
    s.run("mixed", lambda: serve_mixed(s))
    s.run("cli", lambda: serve_cli(s))
    for name, fn in kernel_cases(s):
        s.run(f"kernel:{name}", fn)
    if len(devices) >= 4:
        for name, fn in multichip_cases(s):
            s.run(name, fn)
    else:
        s.log(f"multichip: not run ({len(devices)} device)")

    entries_after = len(os.listdir(cache_dir))
    wall = time.perf_counter() - t_start
    s.log(f"compile cache {cache_dir}: {entries_before} entries before, "
          f"{entries_after} after, {entries_after - entries_before} added")
    s.log(f"wall {wall:.0f}s (set-up information on "
          f"{s.device['kind']} x{s.device['count']}, not a metric)")
    s.summary.update(only=only, failed=s.failed, wall_s=round(wall, 1),
                     cache_entries_added=entries_after - entries_before)
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_smoke_summary.json"), "w") as f:
        json.dump(s.summary, f, indent=1, default=str)
    if s.failed:
        s.log(f"FAILED legs: {s.failed}")
        return 1
    print(json.dumps({"ok": True, "device": s.device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
