"""Benchmark harnesses behind the CLI's ``bench`` command.

Measurement modes:

- **device-resident** — a dependent chain of batches through the Engine
  (uint8 in/out, donated buffers, state threading) ending in an on-device
  checksum whose host fetch forces completion. This is the framework's
  sustained filter throughput, immune to async-dispatch timing lies and to
  host↔device transfer costs.
- **transfer** — host↔device link microbench (MB/s each direction + fixed
  per-transfer cost). Measuring the link separately lets the bench report
  how close the pipeline gets to the link roofline instead of presenting
  a transfer-bound fps as a framework property.
- **e2e streaming (throughput)** — the full pipeline (synthetic source →
  batch assembler → device → ordered sink), source unthrottled: delivered
  fps, the metric the reference prints ad hoc (webcam_app.py:88-95,152-163).
- **e2e latency (rate-controlled)** — same pipeline with the source
  throttled below measured throughput and an ingest queue ≈ one batch, so
  p50/p99 measure pipeline *transit* (capture→deliver on an un-congested
  stream) rather than standing queue depth — the number BASELINE.md's
  <10 ms target is about. An unthrottled source + deep queue makes p50 a
  function of queue length, not of the pipeline.
"""

from __future__ import annotations

import time
from typing import Optional

from dvf_tpu.api.filter import Filter

# Per-chip peaks for the roofline/MFU columns, keyed by the
# ``device_kind`` jax reports. A device that is not in the table is an
# error, not a default (roofline_fields); the host CPU has no entry and
# carries no roofline claim.
DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 on the MXU,
    # 16 GB of HBM at 819 GB/s, per chip.
    "TPU v5 lite": {"hbm_gbps": 819.0, "bf16_tflops": 197.0},
}


def bench_device_resident(
    filt: Filter,
    iters: int,
    batch_size: int,
    height: int,
    width: int,
    dtype=None,
    mesh=None,
) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dvf_tpu.runtime.engine import Engine

    dtype = dtype or np.uint8
    shape = (batch_size, height, width, 3)
    engine = Engine(filt, mesh=mesh)
    engine.compile(shape, dtype)

    checksum = jax.jit(lambda a: jnp.sum(a.astype(jnp.float32)))
    rng = np.random.default_rng(0)
    host_batch = rng.integers(0, 255, size=shape, dtype=np.uint8).astype(dtype)

    t0 = time.perf_counter()
    batch = jax.device_put(host_batch)
    batch.block_until_ready()
    h2d_s = time.perf_counter() - t0
    h2d_mbps = host_batch.nbytes / 1e6 / h2d_s if h2d_s > 0 else float("inf")

    out = engine.run_device_resident(batch)
    _ = np.asarray(checksum(out))
    geometry_preserving = out.shape == batch.shape
    if geometry_preserving:
        # The engine DONATED the input — `batch` was consumed by the
        # warmup above; continue the chain from `out`. (Geometry-changing
        # filters don't donate the batch, so theirs stays live.)
        batch = out
        # Dependent chain: each output IS the next input, so async dispatch
        # can't overlap away real work.
        t0 = time.perf_counter()
        for _ in range(iters):
            batch = engine.run_device_resident(batch)
        _ = np.asarray(checksum(batch))
        wall = time.perf_counter() - t0
    else:
        # Geometry-changing filter (super_resolution): feeding the output
        # back would recompile with doubled H/W every iteration. Keep the
        # cross-iteration data dependency instead by folding a scalar of
        # the previous output into the fixed-shape input — same
        # no-overlap guarantee, stable signature.
        fold = jax.jit(
            lambda x, y: x + (jnp.sum(y.astype(jnp.float32)) * 0).astype(x.dtype)
        )
        t0 = time.perf_counter()
        for _ in range(iters):
            out = engine.run_device_resident(fold(batch, out))
        _ = np.asarray(checksum(out))
        wall = time.perf_counter() - t0

    frames = iters * batch_size
    mesh_devices = engine.mesh.devices.flatten()
    result = {
        # The device the number was taken on (on-chip guide §2): platform,
        # kind, and how many chips the engine's mesh spans — what
        # roofline_fields scales the per-chip peaks by.
        "platform": mesh_devices[0].platform,
        "device_kind": mesh_devices[0].device_kind,
        "n_devices": int(mesh_devices.size),
        "fps": frames / wall if wall > 0 else 0.0,
        "frames": frames,
        "wall_s": wall,
        "ms_per_batch": wall / iters * 1e3,
        "ms_per_frame": wall / frames * 1e3,
        "h2d_mbps": h2d_mbps,
    }
    ca = engine.cost_analysis()
    if ca is not None:
        result["flops_per_frame"] = ca["flops_per_batch"] / batch_size
        result["bytes_accessed_per_frame"] = (
            ca["bytes_accessed_per_batch"] / batch_size)
    return result


def roofline_fields(r: dict) -> dict:
    """Roofline fraction + MFU for a :func:`bench_device_resident` result.

    Memory model for the fraction (right for the stencil/pointwise filter
    families, which are HBM-bound): achievable fps ceiling = HBM bandwidth
    / XLA-reported bytes accessed per frame. MFU (right for the neural
    configs style/SR, which are MXU-bound) = achieved FLOP rate / bf16
    peak. Both are reported so each config is judged against the model
    that binds it. The peaks are the result's own ``device_kind``'s
    (:data:`DEVICE_PEAKS`) times the ``n_devices`` its mesh spanned; a CPU
    result returns {} and an accelerator kind with no table entry raises.
    """
    if r["platform"] == "cpu" or "bytes_accessed_per_frame" not in r:
        return {}
    kind = r["device_kind"]
    if kind not in DEVICE_PEAKS:
        raise ValueError(
            f"no published peaks for device_kind {kind!r} (known: "
            f"{sorted(DEVICE_PEAKS)}); add its HBM bandwidth and bf16 peak "
            f"to dvf_tpu.benchmarks.DEVICE_PEAKS with their source")
    peaks = DEVICE_PEAKS[kind]
    n = r["n_devices"]
    bytes_f = r["bytes_accessed_per_frame"]
    flops_f = r.get("flops_per_frame", 0.0)
    fps = r.get("fps", 0.0)
    out = {}
    if bytes_f > 0:
        ceil = n * peaks["hbm_gbps"] * 1e9 / bytes_f
        # "hbm_" prefix: the e2e phase already reports a LINK-based
        # `roofline_frac` (fraction of the host↔device ceiling); this one
        # is the fraction of the HBM-bandwidth ceiling for device-resident
        # throughput — different ceiling, different name.
        out["hbm_roofline_fps"] = round(ceil, 1)
        out["hbm_roofline_frac"] = round(fps / ceil, 3) if ceil else None
        out["hbm_gb_per_frame"] = round(bytes_f / 1e9, 6)
    if flops_f > 0:
        out["mfu"] = round(
            fps * flops_f / (n * peaks["bf16_tflops"] * 1e12), 5)
        out["gflops_per_frame"] = round(flops_f / 1e9, 3)
    return out


def bench_stage_decomposition(
    filt: Filter,
    batch_sizes=(1, 2, 4),
    height: int = 1080,
    width: int = 1920,
    reps: int = 50,
    transfer_reps: int = 3,
    measure_encode: bool = True,
) -> dict:
    """Per-stage latency decomposition at small batch.

    For each batch size, p50 over ``reps`` of the four legs a frame
    actually crosses in the pipeline: host staging copy (assembler
    stacking frames into the dispatch array), H2D ``device_put``, compute
    (one engine step, block_until_ready — includes dispatch overhead, as
    the pipeline experiences it), D2H (``np.asarray`` of the result).
    The decomposition exists so the compute leg can be combined with
    separately-measured link figures into an explicit latency model. The
    D2H leg is timed only ``transfer_reps`` times (matching
    bench_transfer's reps). H2D must run every rep regardless (the
    donated compute step consumes its input), so it is timed every rep.

    ``measure_encode`` adds the fifth leg a wire-delivery frame crosses:
    a single-threaded JPEG encode of the fetched batch (host work).
    It is reported per batch as ``encode_ms`` but kept
    OUT of ``total_ms``: these legs time the serialized monolithic path
    the latency model decomposes, and since the asynchronous codec plane
    (runtime/egress.py) the encode leg is overlapped with the next
    batch's compute rather than additive — the bench's egress stats
    (``encode_wait_ms`` vs ``encode_ms``) say how completely. The codec
    actually measured (backend/quality/threads) is recorded under the
    ``codec`` key.
    """
    import jax
    import numpy as np

    from dvf_tpu.runtime.engine import Engine

    rng = np.random.default_rng(0)
    out: dict = {}
    codec = None
    if measure_encode:
        from dvf_tpu.transport.codec import make_codec

        # threads=1: this is the per-frame serialized CYCLE cost the
        # latency model wants — the same quantity measure_codec_fps's
        # explicit mode="cycle" reports (pool throughput is its other,
        # now separately-named, mode).
        codec = make_codec(threads=1)
        out["codec"] = codec.config()
    for b in batch_sizes:
        shape = (b, height, width, 3)
        engine = Engine(filt)
        engine.compile(shape, np.uint8)
        frames = [rng.integers(0, 255, size=(height, width, 3), dtype=np.uint8)
                  for _ in range(b)]
        staging = np.empty(shape, np.uint8)
        d2h_dst = None  # sized from the result (geometry-changing filters)
        legs = {"staging_ms": [], "h2d_ms": [], "compute_ms": [], "d2h_ms": []}
        for rep in range(reps):
            t0 = time.perf_counter()
            for i, f in enumerate(frames):
                staging[i] = f
            t1 = time.perf_counter()
            x = jax.device_put(staging)
            x.block_until_ready()
            t2 = time.perf_counter()
            y = engine.run_device_resident(x)
            y.block_until_ready()
            t3 = time.perf_counter()
            legs["staging_ms"].append((t1 - t0) * 1e3)
            legs["h2d_ms"].append((t2 - t1) * 1e3)
            legs["compute_ms"].append((t3 - t2) * 1e3)
            if rep < transfer_reps:
                # Materialized bytes, not a possibly-zero-copy view —
                # same rationale as bench_transfer's D2H timer.
                if d2h_dst is None:
                    d2h_dst = np.empty(y.shape, y.dtype)
                    t3 = time.perf_counter()  # exclude the one-time alloc
                np.copyto(d2h_dst, np.asarray(y))
                t4 = time.perf_counter()
                legs["d2h_ms"].append((t4 - t3) * 1e3)
                if (codec is not None and d2h_dst.dtype == np.uint8
                        and d2h_dst.ndim == 4 and d2h_dst.shape[-1] == 3):
                    codec.encode_batch(list(d2h_dst))
                    legs.setdefault("encode_ms", []).append(
                        (time.perf_counter() - t4) * 1e3)
        enc = legs.pop("encode_ms", None)
        p50 = {k: round(float(np.percentile(v, 50)), 4) for k, v in legs.items()}
        # encode_ms deliberately excluded from total_ms: the legacy four
        # legs are the serialized transfer model; encode is reported
        # beside them (see docstring).
        p50["total_ms"] = round(sum(p50.values()), 4)
        if enc:
            p50["encode_ms"] = round(float(np.percentile(enc, 50)), 4)
        p50["per_frame_compute_ms"] = round(p50["compute_ms"] / b, 4)
        # Self-describing keys (BENCH rounds ≤ 5 published opaque "1"/
        # "2"/"4"), with the measured transfer mode recorded in-band:
        # these legs time the serialized whole-batch path by construction
        # (that is what the latency model decomposes); the streamed
        # per-shard path's hiding shows up in overlap_efficiency instead.
        p50["transfer_mode"] = "whole_batch"
        out[f"batch_{b}"] = p50
    if codec is not None:
        codec.close()
    return out


def bench_transfer(batch_size: int, height: int, width: int, reps: int = 3) -> dict:
    """Host↔device link microbench for one uint8 NHWC batch.

    Returns MB/s both directions plus the fixed per-transfer cost
    (estimated from a tiny D2H), so callers can compute the link roofline
    for any frame geometry: fps_ceiling = 1 / (bytes·(1/h2d + 1/d2h) + c).

    D2H measures MATERIALIZED bytes: the device result is copied into a
    preallocated host destination after ``block_until_ready``, because
    ``np.asarray`` alone can be a zero-copy view of the backend's buffer
    (CPU backend; any runtime that caches the host value) — a timer
    around it can clock a view construction, not a transfer (an earlier
    round published a 1,929,603 MB/s "link" that way). The destination memcpy is
    part of the timed cost by design — it is exactly what the pipeline's
    collect path pays to hand frames to a sink.
    """
    import jax
    import numpy as np

    shape = (batch_size, height, width, 3)
    host = np.random.default_rng(0).integers(0, 255, size=shape, dtype=np.uint8)
    dst = np.empty(shape, np.uint8)       # materialization target
    dev = jax.device_put(host)
    dev.block_until_ready()
    bump = jax.jit(lambda a: a + 1)
    tiny_dst = np.empty((1, 8, width, 3), np.uint8)

    h2d, d2h = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.device_put(host).block_until_ready()
        h2d.append(time.perf_counter() - t0)
        y = bump(dev)  # fresh result each rep — no cached host copy
        y.block_until_ready()
        t0 = time.perf_counter()
        np.copyto(dst, np.asarray(y))
        d2h.append(time.perf_counter() - t0)
    fixed = []
    for _ in range(reps):
        tiny = bump(jax.device_put(host[:1, :8]))
        tiny.block_until_ready()
        t0 = time.perf_counter()
        np.copyto(tiny_dst, np.asarray(tiny))
        fixed.append(time.perf_counter() - t0)
    # min over reps, and never let the correction exceed 90% of the bulk
    # time: one hiccup on a flaky link must not produce an absurd d2h_mbps
    # (and with it a roofline that misattributes link-bound e2e fps to
    # framework overhead).
    fixed_s = min(min(fixed), 0.9 * min(d2h))
    mb = host.nbytes / 1e6
    return {
        "h2d_mbps": mb / min(h2d),
        "d2h_mbps": mb / (min(d2h) - fixed_s),
        "d2h_fixed_ms": fixed_s * 1e3,
        "batch_mb": mb,
        "d2h_measures": "materialized_copy",  # provenance of the number
    }


def _run_pipeline(filt, source, batch_size, height, width, max_inflight,
                  queue_size, collect_mode="thread", transport="python",
                  wire="raw", mesh=None, ingest="streamed",
                  ingest_depth=4, egress="streamed") -> dict:
    import numpy as np

    from dvf_tpu.io.sinks import NullSink
    from dvf_tpu.runtime.engine import Engine
    from dvf_tpu.runtime.pipeline import Pipeline, PipelineConfig

    engine = Engine(filt, mesh=mesh)
    engine.compile((batch_size, height, width, 3), np.uint8)
    sink = NullSink()
    queue = None
    if transport == "ring":
        from dvf_tpu.transport.ring_queue import RingFrameQueue

        queue = RingFrameQueue((height, width, 3),
                               capacity_frames=queue_size,
                               wire=wire)
    pipe = Pipeline(
        source,
        filt,
        sink,
        config=PipelineConfig(
            batch_size=batch_size,
            queue_size=queue_size,
            frame_delay=0,
            max_inflight=max_inflight,
            collect_mode=collect_mode,
            ingest=ingest,
            ingest_depth=ingest_depth,
            egress=egress,
        ),
        engine=engine,
        queue=queue,
    )
    t0 = time.perf_counter()
    try:
        stats = pipe.run()
    finally:
        # run() closes the queue on the happy path only; an erroring run
        # must not leak the native ring / codec thread pool.
        if queue is not None:
            queue.close()
    wall = time.perf_counter() - t0
    pct = sink.latency_percentiles()
    ingest_stats = stats.get("ingest", {})
    egress_stats = stats.get("egress", {})
    return {
        "fps": sink.count / wall if wall > 0 else 0.0,
        # Steady-state delivery rate, first→last delivery (LatencyStats
        # .fps()): excludes compile/startup before the first frame and
        # drain after the last, so it is comparable to an offered rate
        # where the whole-wall fps above is not.
        "delivery_fps": sink.fps(),
        "frames": sink.count,
        "wall_s": wall,
        "p50_ms": pct.get("p50", float("nan")),
        "p99_ms": pct.get("p99", float("nan")),
        "dropped": stats.get("dropped_at_ingest", 0),
        # The transfer path actually taken ("streamed" may degrade to
        # "monolithic" on replicated layouts) + how much of the per-batch
        # H2D cost it hid under decode/compute (obs.metrics.IngestStats).
        "ingest": ingest_stats.get("mode", ingest),
        "ingest_depth": ingest_depth,
        "overlap_efficiency": ingest_stats.get("overlap_efficiency"),
        "ingest_stats": ingest_stats,
        # The delivery-side mirror: the fetch path actually taken
        # ("streamed" auto-degrades where streaming cannot win — e.g. the
        # CPU backend's zero-copy np.asarray) + how much of the per-batch
        # blocking-D2H cost it hid (obs.metrics.EgressStats).
        "egress": egress_stats.get("mode", egress),
        "egress_overlap_efficiency": egress_stats.get("overlap_efficiency"),
        "egress_stats": egress_stats,
        # Per-kind fault counters (resilience.faults) — a clean bench run
        # asserts an empty dict; any entry here means the measured number
        # absorbed contained faults and is suspect.
        "faults": stats.get("faults", {}).get("by_kind", {}),
        "recoveries": stats.get("recoveries", 0),
        # Wire provenance + delta accounting (dirty ratio, keyframes,
        # resyncs) when the ring transport carried a codec wire — the
        # bench JSON must say WHICH wire produced the fps beside it.
        **({"wire": queue.wire_stats()} if queue is not None else {}),
    }


def bench_e2e_streaming(
    filt: Filter,
    n_frames: int,
    batch_size: int,
    height: int,
    width: int,
    max_inflight: int = 4,
    queue_size: Optional[int] = None,
    rate: float = 0.0,
    collect_mode: str = "thread",
    transport: str = "python",
    wire: str = "raw",
    mesh=None,
    ingest: str = "streamed",
    ingest_depth: int = 4,
    egress: str = "streamed",
    motion: str = "roll",
) -> dict:
    """Throughput mode: unthrottled source (rate=0), deep queue.

    ``transport="ring"`` routes ingest through the native C++ ring
    (``wire="jpeg"`` additionally JPEG-encodes at capture and decodes into
    the dispatch staging buffer — the measured cost of the reference's
    use_jpeg path, SURVEY §7 hard part 3; ``wire="delta"`` rides the
    temporal-delta codec, whose cost scales with the stream's dirty
    ratio — pick ``motion`` accordingly: ``"roll"`` is the full-motion
    worst case, ``"block"`` the webcam-like low-motion regime the delta
    win is claimed for). The p50/p99 this returns are congestion numbers
    (queue depth), kept for backward compatibility — use
    :func:`bench_e2e_latency` for the latency claim.
    """
    from dvf_tpu.io.sources import SyntheticSource

    return _run_pipeline(
        filt,
        SyntheticSource(height=height, width=width, n_frames=n_frames,
                        rate=rate, motion=motion),
        batch_size, height, width, max_inflight,
        queue_size if queue_size is not None else max(64, 4 * batch_size),
        collect_mode=collect_mode, transport=transport, wire=wire, mesh=mesh,
        ingest=ingest, ingest_depth=ingest_depth, egress=egress,
    )


def stream_congested(delivery_fps: float, target_fps: float, dropped: int,
                     frames: int) -> bool:
    """Was a rate-controlled run congested (offered rate > capacity)?

    Two signals, each covering the other's blind spot:

    1. **Ingest drops.** With the latency config's bounded drop-oldest
       queue (one batch) a paced source that outruns service fills the
       queue within one batch period and drops from then on. Exactly one
       drop is forgiven (startup race while the ingest thread warms) — no
       percentage allowance: a steady trickle means the queue sat full
       for a stretch and queue residency leaked into the percentiles.
       Blind spot: a stream SHORTER than the pipeline's total buffering
       (queue + assembling batch + in-flight batches) never overflows, so
       a crawling link can serialize every batch without one drop.

    2. **Steady-state delivery rate** (first→last delivery, so compile/
       startup/drain overhead is excluded — whole-wall fps is NOT
       comparable to an offered rate on short legs and flagged healthy
       runs): if frames leave slower than 0.85× the offered rate, they
       are accumulating somewhere, drops or not.

    The remaining corner — all deliveries landing in one burst, where the
    first→last rate is vacuously huge — is not a blind spot: one burst
    means ONE dispatched batch, and with a single batch no frame ever
    waited behind an earlier batch, so the only waits in its p50 are the
    10 ms assembly deadline plus one irreducible batch service time —
    which IS uncongested transit, not queue residency. Congestion
    requires cross-batch queueing, which spreads deliveries into ≥2
    groups, which the rate signal then sees."""
    if target_fps <= 0:
        return True
    if frames <= 0 or delivery_fps <= 0:
        return True
    if dropped > 1:
        return True
    return delivery_fps < 0.85 * target_fps


def bench_e2e_latency(
    filt: Filter,
    n_frames: int,
    batch_size: int,
    height: int,
    width: int,
    target_fps: float,
    max_inflight: int = 2,
    collect_mode: str = "thread",
    transport: str = "python",
    wire: str = "raw",
    mesh=None,
    ingest: str = "streamed",
    ingest_depth: int = 4,
    egress: str = "streamed",
    motion: str = "roll",
    max_backoffs: int = 2,
    max_retry_stream_s: float = 400.0,
) -> dict:
    """Latency mode: source throttled to ``target_fps`` (pick ~0.8× the
    measured throughput), ingest queue bounded to one batch, shallow
    in-flight depth — p50/p99 then measure capture→deliver transit of an
    un-congested stream, the half of the north star the throughput run
    can't speak to. ``transport``/``wire`` select the same ingest path as
    the throughput mode — a ring/jpeg run's published transit MUST include
    the ring hop and codec cost it is labeled with.

    Capacity is a measurement with variance, so 0.8× the measured
    throughput can still exceed the TRUE capacity of the latency leg — the
    stream then congests and the percentiles silently become
    queue-residency numbers. This is detected (:func:`stream_congested`) and the
    leg automatically backs off — halving ``target_fps`` up to
    ``max_backoffs`` times — until the pipeline provably kept up. The
    returned dict carries the verdict: ``congested`` (final run),
    ``target_fps`` (the rate actually measured) and ``backoffs``."""
    from dvf_tpu.io.sources import SyntheticSource

    # The retry floor is a small absolute minimum capped at the ORIGINAL
    # count — a floor that could raise the count (batch-derived, or 16 on
    # a 12-frame leg) multiplies wall time on exactly the slow configs
    # that back off (the deadline assembler dispatches partial batches,
    # so percentiles from fewer-than-a-batch frames still measure
    # transit).
    n_floor = min(16, n_frames)
    attempts = 0
    while True:
        r = _run_pipeline(
            filt,
            SyntheticSource(height=height, width=width, n_frames=n_frames,
                            rate=target_fps, motion=motion),
            batch_size, height, width, max_inflight,
            queue_size=batch_size,
            collect_mode=collect_mode, transport=transport, wire=wire,
            mesh=mesh, ingest=ingest, ingest_depth=ingest_depth,
            egress=egress,
        )
        congested = stream_congested(r["delivery_fps"], target_fps,
                                     r["dropped"], r["frames"])
        retry_target = target_fps / 2.0
        retry_frames = max(n_floor, n_frames // 2)
        # A retry whose offered stream alone would outlast the wall budget
        # (ultra-slow configs: style on a 1-core CPU runs ~0.1 fps, so a
        # halved-rate retry projects to 5-10 min) is skipped — returning
        # the honest congested verdict beats burning the harness child's
        # entire timeout to confirm it.
        can_retry = (attempts < max_backoffs
                     and retry_target > 0  # target 0 = no rate to verify:
                     # fall through to the congested verdict, don't divide
                     and retry_frames / retry_target <= max_retry_stream_s)
        if not congested or not can_retry:
            r["target_fps"] = target_fps
            r["congested"] = congested
            r["backoffs"] = attempts
            return r
        attempts += 1
        target_fps = retry_target
        n_frames = retry_frames


# The fleet scaling workload: compute-dominated on purpose (a fused
# 3-deep blur chain runs ~7 ms/frame on one CPU core at 256², an order
# of magnitude over the ~0.5 ms/frame the front door spends shipping a
# frame), so the measured ratio is replica scaling, not RPC overhead.
FLEET_BENCH_FILTER = (
    "chain", {"specs": ["gaussian_blur", "gaussian_blur", "gaussian_blur"]})


def measure_parallel_capacity(n: int = 2, seconds: float = 1.5) -> float:
    """How much CPU-bound throughput ``n`` concurrent processes actually
    get vs one — the machine's REAL parallel capacity, which is what an
    N-replica CPU fleet scales into. On a dedicated host this is ~n; on
    an oversubscribed VM it can be barely 1.x even when ``nproc`` says n
    (observed on the CI container: nproc=2, capacity ≈ 1.3 — no quota,
    just steal). The fleet scaling test is GUARDED on this number: a
    ≥1.8× 2-replica claim is only falsifiable where the hardware can
    express 2-way parallelism at all, exactly like a multi-device test
    is guarded on device count. The bench records it beside the scaling
    ratio so a capacity-bound artifact is self-describing."""
    import subprocess
    import sys

    script = ("import time\nn=0\nt0=time.perf_counter()\n"
              f"while time.perf_counter()-t0<{seconds}: n+=1\nprint(n)")

    def run(k: int) -> int:
        procs = [subprocess.Popen([sys.executable, "-c", script],
                                  stdout=subprocess.PIPE, text=True)
                 for _ in range(k)]
        return sum(int(p.communicate()[0]) for p in procs)

    one = run(1)
    many = run(n)
    return round(many / max(1, one), 3)


def bench_fleet_scaling(
    filter_spec=FLEET_BENCH_FILTER,
    sessions: int = 2,
    frames_per_session: int = 100,
    height: int = 256,
    width: int = 256,
    batch: int = 4,
    replica_counts=(1, 2),
    mode: str = "process",
    pin_replicas: bool = True,
    deadline_s: float = 180.0,
) -> dict:
    """Fleet scaling round: aggregate multi-session throughput at each
    replica count, same workload, same per-replica resources.

    Per round: open ``sessions`` streams through a FleetFrontend with N
    replicas, warm each replica (one delivered frame per session — the
    engine compile must not sit inside the timed window), then blast
    ``frames_per_session`` frames per session from one thread each and
    time until every frame is delivered. Delivery polling runs
    ``meta_only`` so the front door counts frames instead of copying N
    replicas' pixels through one Python loop. ``scaling[n] =
    fps[n] / fps[min]`` is the headline (the acceptance bar for a
    2-replica CPU fleet is ≥ 1.8×); per-round ``faults``/``recoveries``
    ride along replica-attributed so a dirty round is self-evident.

    ``pin_replicas`` (process mode) pins replica i to CPU core i — the
    CPU stand-in for "each replica owns its chips". Without it the
    1-replica baseline's XLA pool spreads over every core and the fleet
    has nothing left to scale into; with it both rounds hold per-replica
    resources fixed, which is the claim being measured.
    """
    import threading

    import numpy as np

    from dvf_tpu.fleet import FleetConfig, FleetFrontend
    from dvf_tpu.serve import ServeConfig
    frame = np.random.default_rng(7).integers(
        0, 255, size=(height, width, 3), dtype=np.uint8)
    rounds = {}
    for n in replica_counts:
        cfg = FleetConfig(
            replicas=n, mode=mode, filter_spec=tuple(filter_spec),
            serve=ServeConfig(
                batch_size=batch,
                max_sessions=max(16, sessions),
                queue_size=frames_per_session + 8,  # throughput round:
                #   no drop-oldest losses, the wall clock is the bound
                out_queue_size=frames_per_session + 8,  # ditto on the
                #   poll side: N fast replicas can outrun one poll loop
                #   transiently; delivered frames must wait, not drop
                slo_ms=600_000.0,
            ),
            pin_replicas_to_cores=(pin_replicas and mode == "process"),
        )
        fleet = FleetFrontend(config=cfg)
        with fleet:
            sids = [fleet.open_stream() for _ in range(sessions)]
            # Warm every replica: one frame per session, delivered.
            for sid in sids:
                fleet.submit(sid, frame)
            deadline = time.perf_counter() + deadline_s
            warm = {sid: 0 for sid in sids}
            while (any(c < 1 for c in warm.values())
                   and time.perf_counter() < deadline):
                for sid in sids:
                    warm[sid] += len(fleet.poll(sid, meta_only=True))
                time.sleep(0.002)

            def blast(sid: str) -> None:
                for _ in range(frames_per_session):
                    fleet.submit(sid, frame)

            threads = [threading.Thread(target=blast, args=(sid,))
                       for sid in sids]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            got = {sid: 0 for sid in sids}
            target = frames_per_session
            while (any(c < target for c in got.values())
                   and time.perf_counter() < deadline):
                for sid in sids:
                    got[sid] += len(fleet.poll(sid, meta_only=True))
                # Throttle: a hot poll loop would burn a core of the
                # parent's own and hammer every (pinned) worker with
                # poll RPCs — the out queues are sized to hold the whole
                # round, so coarse sweeps lose nothing but measurement
                # granularity (~ms on a multi-second round).
                time.sleep(0.004)
            wall = time.perf_counter() - t0
            for t in threads:
                t.join()
            stats = fleet.stats()
        delivered = sum(got.values())
        rounds[n] = {
            "replicas": n,
            "fps": round(delivered / wall, 2) if wall > 0 else 0.0,
            "delivered": delivered,
            "expected": sessions * frames_per_session,
            "wall_s": round(wall, 3),
            "sessions": sessions,
            "faults": stats["faults"]["by_kind"],
            "faults_by_replica": stats["faults"].get("by_replica", {}),
            "recoveries": stats["recoveries"],
            "spillovers": stats["spillovers"],
            "per_replica_frames": {
                rid: row.get("engine_frames")
                for rid, row in stats["replicas"].items()},
        }
    base = min(replica_counts)
    base_fps = rounds[base]["fps"] or 1e-9
    return {
        "parallel_capacity": measure_parallel_capacity(max(replica_counts)),
        "mode": mode,
        "filter": [filter_spec[0], filter_spec[1]],
        "frame": [height, width, 3],
        "batch": batch,
        "pinned_replicas": bool(pin_replicas and mode == "process"),
        "rounds": {str(n): r for n, r in rounds.items()},
        "scaling": {str(n): round(rounds[n]["fps"] / base_fps, 3)
                    for n in replica_counts},
    }
