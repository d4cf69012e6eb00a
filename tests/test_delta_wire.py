"""Temporal-delta wire + on-device codec assist (PR 7).

Layers, mirroring the module split:

- codec unit layer (``transport.codec.DeltaCodec``): frame format,
  equivalence guarantees, keyframe cadence, resync protocol, wire-fault
  detection, ordered async encode;
- device layer (``ops.pallas_kernels.tile_maxdiff``,
  ``runtime.codec_assist``): kernel vs golden vs host reduction, YCbCr
  4:2:0 stages, the native shim's entropy-path encode;
- delivery paths: the ``delta_threshold=0`` static-stream BIT-IDENTITY
  to the full-frame JPEG wire on all three paths (pipeline ring, ZMQ
  worker, serve bridge), resync containment, chaos-injected truncated
  tile payloads under the ``transport`` kind with budget-bounded
  degradation back to full-frame JPEG, and the steady-state
  allocation-regression check mirroring test_egress_stream.py's.

Everything is seeded, CPU, and tier-1 (marker ``delta``).

The moving-stream equivalence claim is deliberately TILE-WISE, not
frame-wise: a delta delivery equals the full-frame JPEG wire exactly
where nothing changed since the keyframe and equals the SOURCE exactly
where something did (lossless tiles are strictly closer to the truth
than a fresh JPEG would be). Frame-wise bit-identity with the JPEG wire
under motion is impossible for ANY codec that doesn't re-run the full
JPEG cycle per frame — which is the cost this wire exists to remove.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from dvf_tpu.transport.codec import (
    DeltaCodec,
    DeltaResyncError,
    DeltaWireError,
    RawCodec,
    host_tile_changed,
    host_tile_maxdiff,
    jpeg_wire_budget,
    make_codec,
    make_wire_codec,
    measure_codec_fps,
    tile_grid,
)

pytestmark = pytest.mark.delta

H, W, TILE = 48, 64, 16


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def _stream(rng, n=10, h=H, w=W, moving=True):
    """Seeded frames: static noise base, optionally a re-randomized
    region each frame (dirty tiles known by construction)."""
    base = rng.integers(0, 255, (h, w, 3), np.uint8)
    out = [base.copy()]
    for k in range(1, n):
        f = out[-1].copy()
        if moving:
            f[16:32, 16:48] = rng.integers(0, 255, (16, 32, 3), np.uint8)
        out.append(f)
    return out


# ---------------------------------------------------------------------------
# Codec unit layer
# ---------------------------------------------------------------------------


class TestDeltaCodecUnit:

    def test_raw_inner_bit_exact_under_arbitrary_motion(self, rng):
        """threshold=0 over a raw inner wire: bit-identical to the
        full-frame raw wire for ANY motion, at a fraction of the bytes
        for low motion."""
        enc = DeltaCodec(RawCodec(H, W), tile=TILE, keyframe_interval=4)
        dec = DeltaCodec(RawCodec(H, W), tile=TILE)
        try:
            frames = _stream(rng, 12)
            blobs = [enc.encode(f) for f in frames]
            for f, b in zip(frames, blobs):
                np.testing.assert_array_equal(dec.decode(b), f)
            assert sum(len(b) for b in blobs) < 12 * H * W * 3
            s = enc.stats()
            assert s["keyframes"] >= 3 and 0 < s["dirty_ratio"] < 0.5
        finally:
            enc.close()
            dec.close()

    def test_static_stream_bit_identical_to_jpeg_wire(self, rng):
        enc = DeltaCodec(make_codec(threads=1), tile=TILE,
                         keyframe_interval=4)
        dec = DeltaCodec(make_codec(threads=1), tile=TILE)
        plain = make_codec(threads=1)
        try:
            frame = rng.integers(0, 255, (H, W, 3), np.uint8)
            jpeg_wire = plain.decode(plain.encode(frame))
            for _ in range(9):  # crosses two keyframes
                np.testing.assert_array_equal(
                    dec.decode(enc.encode(frame)), jpeg_wire)
            assert enc.stats()["dirty_ratio"] == 0.0
        finally:
            enc.close()
            dec.close()
            plain.close()

    def test_moving_stream_tilewise_equivalence(self, rng):
        """threshold=0 over JPEG: every delivered tile is either the
        keyframe's full-frame-JPEG delivery (unchanged since it) or the
        SOURCE pixels (re-sent losslessly)."""
        enc = DeltaCodec(make_codec(threads=1), tile=TILE,
                         keyframe_interval=100)
        dec = DeltaCodec(make_codec(threads=1), tile=TILE)
        plain = make_codec(threads=1)
        try:
            frames = _stream(rng, 6)
            keyframe_delivery = plain.decode(plain.encode(frames[0]))
            outs = [dec.decode(enc.encode(f)) for f in frames]
            np.testing.assert_array_equal(outs[0], keyframe_delivery)
            last = outs[-1]
            src = frames[-1]
            # changed-since-keyframe region: bit-identical to the source
            np.testing.assert_array_equal(last[16:32, 16:48],
                                          src[16:32, 16:48])
            # untouched region: bit-identical to the keyframe delivery
            np.testing.assert_array_equal(last[:16], keyframe_delivery[:16])
            np.testing.assert_array_equal(last[32:], keyframe_delivery[32:])
        finally:
            enc.close()
            dec.close()
            plain.close()

    def test_keyframe_cadence_and_scene_cut(self, rng):
        enc = DeltaCodec(RawCodec(H, W), tile=TILE, keyframe_interval=4,
                         scene_cut_ratio=0.5)
        try:
            frames = _stream(rng, 11)
            for f in frames:
                enc.encode(f)
            # frame 0 + every 5th frame (4 delta frames between keys)
            assert enc.stats()["keyframes"] == 3
            cut = 255 - frames[-1]  # every tile changes
            enc.encode(cut)
            s = enc.stats()
            assert s["scene_cuts"] == 1 and s["keyframes"] == 4
        finally:
            enc.close()

    def test_resync_raises_then_forced_keyframe_recovers(self, rng):
        enc = DeltaCodec(RawCodec(H, W), tile=TILE, keyframe_interval=100)
        dec = DeltaCodec(RawCodec(H, W), tile=TILE, on_gap="raise")
        try:
            frames = _stream(rng, 6)
            blobs = [enc.encode(f) for f in frames]
            dec.decode(blobs[0])
            dec.decode(blobs[1])
            with pytest.raises(DeltaResyncError):
                dec.decode(blobs[3])  # dropped blob 2 → gap
            # the decoder's resync request is a keyframe
            enc.force_keyframe()
            kf = enc.encode(frames[5])
            np.testing.assert_array_equal(dec.decode(kf), frames[5])
        finally:
            enc.close()
            dec.close()

    def test_tolerant_gap_composites_and_counts(self, rng):
        enc = DeltaCodec(RawCodec(H, W), tile=TILE, keyframe_interval=100)
        dec = DeltaCodec(RawCodec(H, W), tile=TILE, on_gap="composite")
        try:
            frames = _stream(rng, 6)
            blobs = [enc.encode(f) for f in frames]
            dec.decode(blobs[0])
            out = dec.decode(blobs[3])  # gap: composite on stale ref
            assert dec.stats()["resyncs"] == 1
            # the re-sent (dirty) region is absolute → still exact
            np.testing.assert_array_equal(out[16:32, 16:48],
                                          frames[3][16:32, 16:48])
        finally:
            enc.close()
            dec.close()

    def test_truncated_tile_payload_raises_wire_error(self, rng):
        enc = DeltaCodec(RawCodec(H, W), tile=TILE, keyframe_interval=100)
        dec = DeltaCodec(RawCodec(H, W), tile=TILE)
        try:
            frames = _stream(rng, 3)
            blobs = [enc.encode(f) for f in frames]
            dec.decode(blobs[0])
            dec.decode(blobs[1])
            cut = blobs[2][: len(blobs[2]) // 2]  # truncated tile bytes
            with pytest.raises(DeltaWireError):
                dec.decode(cut)
            with pytest.raises(DeltaWireError):
                dec.decode(blobs[2] + b"\x00\x01")  # trailing garbage
        finally:
            enc.close()
            dec.close()

    def test_wire_flag_governs_tile_format_not_decoder_config(self, rng):
        """The LOSSLESS header bit is authoritative: an encoder with
        lossy (inner-coded) tiles pairs with a default-config decoder
        and vice versa — the wire is self-describing."""
        lossy_enc = DeltaCodec(make_codec(threads=1), tile=TILE,
                               delta_threshold=5, keyframe_interval=100)
        default_dec = DeltaCodec(make_codec(threads=1), tile=TILE)
        lossless_enc = DeltaCodec(RawCodec(H, W), tile=TILE,
                                  keyframe_interval=100)
        lossy_cfg_dec = DeltaCodec(RawCodec(H, W), tile=TILE,
                                   delta_threshold=5,
                                   lossless_tiles=False)
        try:
            assert lossy_enc.lossless is False
            frames = _stream(rng, 4)
            for f in frames:  # lossy tiles → lossless-config decoder
                out = default_dec.decode(lossy_enc.encode(f))
                assert out.shape == f.shape
            for f in frames:  # lossless tiles → lossy-config decoder
                np.testing.assert_array_equal(
                    lossy_cfg_dec.decode(lossless_enc.encode(f)), f)
        finally:
            for c in (lossy_enc, default_dec, lossless_enc, lossy_cfg_dec):
                c.close()

    def test_unframed_jpeg_falls_through_to_inner(self, rng):
        """A peer that degraded to plain full-frame JPEG (or never spoke
        delta) stays decodable — and its full frame re-seeds the cache."""
        dec = DeltaCodec(make_codec(threads=1), tile=TILE)
        plain = make_codec(threads=1)
        try:
            frame = rng.integers(0, 255, (H, W, 3), np.uint8)
            out = dec.decode(plain.encode(frame))
            np.testing.assert_array_equal(out,
                                          plain.decode(plain.encode(frame)))
        finally:
            dec.close()
            plain.close()

    def test_full_frames_degradation_target(self, rng):
        """full_frames=True (the budget ladder's degradation) turns every
        frame into a keyframe: full-frame JPEG cost, same framed wire,
        same decoder."""
        enc = DeltaCodec(make_codec(threads=1), tile=TILE)
        dec = DeltaCodec(make_codec(threads=1), tile=TILE)
        plain = make_codec(threads=1)
        try:
            enc.full_frames = True
            frames = _stream(rng, 4)
            for f in frames:
                np.testing.assert_array_equal(
                    dec.decode(enc.encode(f)),
                    plain.decode(plain.encode(f)))
            s = enc.stats()
            assert s["keyframes"] == 4
            assert enc.config()["wire"] == "delta(full-frame)"
        finally:
            enc.close()
            dec.close()
            plain.close()

    def test_encode_batch_async_preserves_order(self, rng):
        """Two batches submitted back-to-back must encode in submission
        order (delta state is sequential) and decode correctly."""
        enc = DeltaCodec(RawCodec(H, W), tile=TILE, keyframe_interval=100)
        dec = DeltaCodec(RawCodec(H, W), tile=TILE)
        try:
            frames = _stream(rng, 8)
            futs = enc.encode_batch_async(frames[:4])
            futs += enc.encode_batch_async(frames[4:])
            blobs = [f.result(timeout=30) for f in futs]
            for f, b in zip(frames, blobs):
                np.testing.assert_array_equal(dec.decode(b), f)
        finally:
            enc.close()
            dec.close()

    def test_geometry_change_forces_keyframe(self, rng):
        enc = DeltaCodec(RawCodec(H, W), tile=TILE)
        try:
            enc.encode(rng.integers(0, 255, (H, W, 3), np.uint8))
            enc.encode(rng.integers(0, 255, (H * 2, W, 3), np.uint8))
            assert enc.stats()["keyframes"] == 2
        finally:
            enc.close()

    def test_seek_keyframe(self, rng):
        enc = DeltaCodec(make_codec(threads=1), tile=TILE,
                         keyframe_interval=3)
        plain = make_codec(threads=1)
        try:
            blobs = [enc.encode(f) for f in _stream(rng, 6)]
            assert DeltaCodec.seek_keyframe(blobs) == 0
            assert DeltaCodec.seek_keyframe(blobs[1:]) == 3  # key at 4
            assert DeltaCodec.seek_keyframe(blobs[1:4]) is None
            frame = rng.integers(0, 255, (H, W, 3), np.uint8)
            assert DeltaCodec.seek_keyframe(
                [blobs[1], plain.encode(frame)]) == 1
        finally:
            enc.close()
            plain.close()


# ---------------------------------------------------------------------------
# Device layer: tile_maxdiff kernel, probe, YCbCr assist
# ---------------------------------------------------------------------------


class TestDeviceLayer:

    def test_tile_maxdiff_pallas_matches_golden(self, rng):
        import jax.numpy as jnp

        from dvf_tpu.ops.pallas_kernels import (
            tile_maxdiff_pallas,
            tile_maxdiff_ref,
        )

        a = rng.integers(0, 255, (2, 64, 96, 3), np.uint8)
        b = rng.integers(0, 255, (2, 64, 96, 3), np.uint8)
        ref = np.asarray(tile_maxdiff_ref(jnp.asarray(a), jnp.asarray(b), 16))
        pal = np.asarray(tile_maxdiff_pallas(jnp.asarray(a), jnp.asarray(b),
                                             16, interpret=True))
        np.testing.assert_array_equal(ref, pal)

    def test_tile_reductions_agree_host_device_unaligned(self, rng):
        import jax.numpy as jnp

        from dvf_tpu.ops.pallas_kernels import tile_maxdiff

        a = rng.integers(0, 255, (70, 90, 3), np.uint8)  # edge tiles
        b = rng.integers(0, 255, (70, 90, 3), np.uint8)
        dev = np.asarray(tile_maxdiff(jnp.asarray(a), jnp.asarray(b), 16))
        host = host_tile_maxdiff(a, b, 16)
        np.testing.assert_array_equal(dev, host)
        np.testing.assert_array_equal(host_tile_changed(a, b, 16), host > 0)

    def test_host_tile_changed_word_path_exact(self, rng):
        """The uint64 equality fast path (aligned geometry) must agree
        with the magnitude reduction down to single-byte changes in the
        last byte of a tile."""
        a = rng.integers(0, 255, (64, 64, 3), np.uint8)
        b = a.copy()
        b[31, 31, 2] ^= 1  # last byte of tile (1, 1) at tile=16
        changed = host_tile_changed(a, b, 16)
        assert changed[1, 1] and changed.sum() == 1

    def test_device_delta_probe_matches_host_detection(self, rng):
        import jax.numpy as jnp

        from dvf_tpu.runtime.codec_assist import DeviceDeltaProbe

        probe = DeviceDeltaProbe(tile=16)
        frames = _stream(rng, 9, h=32, w=64)
        batches = [np.stack(frames[i:i + 3]) for i in (0, 3, 6)]
        first = probe.bitmaps(jnp.asarray(batches[0]))
        assert (first[0] == 255).all()  # row 0 has no predecessor
        for i in (1, 2):  # rows 1.. diff against in-batch predecessors
            np.testing.assert_array_equal(
                first[i] > 0,
                host_tile_changed(batches[0][i], batches[0][i - 1], 16))
        prev_tail = batches[0][-1]
        for batch in batches[1:]:
            bm = probe.bitmaps(jnp.asarray(batch))
            chain = np.concatenate([prev_tail[None], batch[:-1]])
            for i in range(batch.shape[0]):
                np.testing.assert_array_equal(
                    bm[i] > 0,
                    host_tile_changed(batch[i], chain[i], 16))
            prev_tail = batch[-1]

    def test_probe_bitmaps_drive_encoder(self, rng):
        """Device-computed bitmaps fed to ``encode(bitmap=)`` produce a
        stream the decoder reconstructs exactly (raw inner, threshold 0,
        sequential frames — the ZMQ worker's configuration)."""
        import jax.numpy as jnp

        from dvf_tpu.runtime.codec_assist import DeviceDeltaProbe

        probe = DeviceDeltaProbe(tile=16)
        enc = DeltaCodec(RawCodec(32, 64), tile=16, keyframe_interval=100)
        dec = DeltaCodec(RawCodec(32, 64), tile=16)
        try:
            frames = _stream(rng, 6, h=32, w=64)
            bms = probe.bitmaps(jnp.asarray(np.stack(frames)))
            for f, bm in zip(frames, bms):
                np.testing.assert_array_equal(
                    dec.decode(enc.encode(f, bitmap=bm)), f)
        finally:
            enc.close()
            dec.close()

    def test_ycbcr420_roundtrip(self):
        import jax.numpy as jnp

        from dvf_tpu.runtime.codec_assist import (
            DeviceCodecAssist,
            ycbcr420_to_rgb_host,
        )

        y, x = np.mgrid[0:32, 0:64].astype(np.float32)
        frame = np.stack([(x * 2) % 256, (y * 3) % 256, (x + y) % 256],
                         -1).astype(np.uint8)
        assist = DeviceCodecAssist()
        yp, cb, cr = assist.planes(jnp.asarray(frame[None]))
        assert yp.shape == (1, 32, 64) and cb.shape == (1, 16, 32)
        rgb = ycbcr420_to_rgb_host(yp[0], cb[0], cr[0])
        err = np.abs(rgb.astype(int) - frame.astype(int))
        # chroma subsample is lossy by design; smooth content bounds it
        assert err.max() <= 8 and err.mean() < 2.0

    def test_native_assist_entropy_encode(self):
        """The shim's jpeg_write_raw_data entry: encode from device-
        converted planes decodes within a small tolerance of the full
        host RGB path (float vs fixed-point convert + mean vs h2v2
        downsample), at comparable bytes."""
        import jax.numpy as jnp

        from dvf_tpu.runtime.codec_assist import DeviceCodecAssist
        from dvf_tpu.transport.codec import NativeJpegCodec

        try:
            codec = NativeJpegCodec(quality=90)
        except (RuntimeError, OSError) as e:
            pytest.skip(f"native jpeg shim unavailable: {e}")
        try:
            if not hasattr(codec._lib, "dvf_jpeg_encode_ycbcr420"):
                pytest.skip("shim predates ycbcr420 assist")
            y, x = np.mgrid[0:48, 0:64].astype(np.float32)
            frame = np.stack([(x * 3) % 256, (y * 2) % 256, (x * y) % 256],
                             -1).astype(np.uint8)
            assist = DeviceCodecAssist()
            yp, cb, cr = assist.planes(jnp.asarray(frame[None]))
            blob = codec.encode_ycbcr420(yp[0], cb[0], cr[0])
            dec = codec.decode(blob)
            ref = codec.decode(codec.encode(frame))
            err = np.abs(dec.astype(int) - ref.astype(int))
            # float convert + mean subsample vs libjpeg's fixed-point +
            # h2v2: a few counts of divergence at sharp chroma edges
            assert err.max() <= 24 and err.mean() < 1.5
            assert 0.5 < len(blob) / len(codec.encode(frame)) < 2.0
        finally:
            codec.close()


# ---------------------------------------------------------------------------
# Budget / measurement satellites
# ---------------------------------------------------------------------------


class TestBudgetSatellites:

    def test_measure_codec_fps_modes(self):
        enc_c, dec_c = measure_codec_fps(32, 32, samples=2, mode="cycle")
        enc_p, dec_p = measure_codec_fps(32, 32, samples=2, mode="pool",
                                         threads=2)
        assert enc_c > 0 and dec_c > 0 and enc_p > 0 and dec_p > 0
        with pytest.raises(ValueError):
            measure_codec_fps(32, 32, mode="batch")

    def test_jpeg_wire_budget_extended_fields(self):
        b = jpeg_wire_budget(32, 32, threads=2, overlap_depth=2,
                             expected_dirty_ratio=0.05,
                             keyframe_interval=32)
        for key in ("per_core_encode_fps", "capacity_fps",
                    "overlapped_capacity_fps", "delta_capacity_fps",
                    "expected_dirty_ratio", "wire_mode", "overlap_depth"):
            assert key in b, key
        # at 5% dirty the delta ceiling dominates clearly
        assert b["delta_capacity_fps"] > b["capacity_fps"]
        assert b["wire_mode"] == "delta"
        assert jpeg_wire_budget(32, 32, threads=2)["wire_mode"] == "jpeg"

    def test_codec_config_wire_provenance(self):
        plain = make_codec(threads=1)
        delta = make_wire_codec("delta", threads=1, tile=TILE)
        raw = make_wire_codec("raw", raw_shape=(H, W))
        try:
            assert plain.config()["wire"] == "jpeg"
            cfg = delta.config()
            assert cfg["wire"] == "delta"
            assert cfg["tile"] == TILE and "keyframe_interval" in cfg
            assert cfg["lossless_tiles"] is True  # threshold 0 default
            assert raw.config()["wire"] == "raw"
        finally:
            plain.close()
            delta.close()
            raw.close()


# ---------------------------------------------------------------------------
# Delivery paths
# ---------------------------------------------------------------------------


from dvf_tpu.io.sinks import NullSink  # noqa: E402
from dvf_tpu.io.sources import SyntheticSource  # noqa: E402
from dvf_tpu.ops import get_filter  # noqa: E402
from dvf_tpu.parallel.mesh import MeshConfig, make_mesh  # noqa: E402
from dvf_tpu.runtime.engine import Engine  # noqa: E402
from dvf_tpu.runtime.pipeline import Pipeline, PipelineConfig  # noqa: E402


def _run_ring_pipeline(wire, motion, n_frames=24, h=32, w=64, batch=4,
                       capacity=1000, ingest="streamed"):
    from dvf_tpu.transport.ring_queue import RingFrameQueue

    delivered = {}

    class CapturingSink(NullSink):
        def emit(self, index, frame, ts):
            super().emit(index, frame, ts)
            delivered[index] = frame.copy()

    queue = RingFrameQueue((h, w, 3), capacity_frames=capacity, wire=wire,
                           delta_tile=16, delta_keyframe_interval=8)
    engine = Engine(get_filter("invert"), mesh=make_mesh(MeshConfig(data=1)))
    pipe = Pipeline(
        SyntheticSource(height=h, width=w, n_frames=n_frames, motion=motion),
        get_filter("invert"), CapturingSink(),
        PipelineConfig(batch_size=batch, queue_size=capacity, frame_delay=0,
                       ingest=ingest),
        engine=engine, queue=queue)
    stats = pipe.run()
    wire_stats = queue.wire_stats()
    return delivered, stats, wire_stats


class TestPipelineRingDelta:

    def test_static_stream_bit_identical_to_jpeg_wire(self):
        """Acceptance: delta_threshold=0 delta wire ≡ full-frame JPEG
        wire, path 1 of 3 (pipeline collect over the ring transport)."""
        d_jpeg, s_jpeg, _ = _run_ring_pipeline("jpeg", "none")
        d_delta, s_delta, ws = _run_ring_pipeline("delta", "none")
        assert s_jpeg["errors"] == 0 and s_delta["errors"] == 0
        assert sorted(d_delta) == sorted(d_jpeg)
        for idx in d_jpeg:
            np.testing.assert_array_equal(d_delta[idx], d_jpeg[idx])
        assert ws["encode"]["dirty_ratio"] == 0.0
        assert ws["decode"]["resyncs"] == 0

    def test_low_motion_stream_healthy_and_cheap(self):
        d, stats, ws = _run_ring_pipeline("delta", "block", n_frames=32)
        assert len(d) == 32 and stats["errors"] == 0
        enc = ws["encode"]
        assert 0 < enc["dirty_ratio"] < 0.6
        assert enc["keyframes"] >= 1 and ws["codec"]["wire"] == "delta"

    def test_eviction_forces_keyframe_and_resync_recovers(self, rng):
        """Drop-oldest evictions under a tiny ring lose delta frames the
        decoder never saw: the producer forces a keyframe, the tolerant
        decoder counts resyncs, the stream keeps flowing."""
        from dvf_tpu.transport.ring_queue import RingFrameQueue

        q = RingFrameQueue((H, W, 3), capacity_frames=1, wire="delta",
                           delta_tile=16, delta_keyframe_interval=100)
        try:
            frames = _stream(rng, 16)
            staging = np.empty((1, H, W, 3), np.uint8)
            for i, f in enumerate(frames):
                q.put((i, f, 0.0))
                if i % 3 == 2:  # consumer lags: 1 pop per 3 puts
                    items = q.pop_up_to(1)
                    if items:
                        q.decode_into(items, staging)
            items = q.pop_up_to(16)
            st = np.empty((len(items), H, W, 3), np.uint8)
            q.decode_into(items, st)
            ws = q.wire_stats()
            assert q.dropped > 0
            assert ws["encode"]["forced_keyframes"] >= 1
            assert ws["decode"]["resyncs"] >= 1
        finally:
            q.close()

    def test_steady_state_allocation_regression(self, monkeypatch):
        """Mirror of test_egress_stream's delivery-path check for the
        delta wire: tripling the stream must not change the number of
        big host allocations — the codec's references, scratch, and the
        ring slabs are built once; the per-frame path allocates only
        payload-sized (small) buffers."""
        _BIG = 300_000

        class Counter:
            def __init__(self):
                self.real = np.empty
                self.big = 0

            def __call__(self, shape, dtype=float, **kw):
                arr = self.real(shape, dtype, **kw)
                if arr.nbytes >= _BIG:
                    self.big += 1
                return arr

        def count(n_frames):
            counter = Counter()
            monkeypatch.setattr(np, "empty", counter)
            try:
                # ingest pinned monolithic, like test_egress_stream's
                # check: partial-batch staging in the streamed assembler
                # reallocates with timing-dependent batch sizes, and this
                # test isolates the WIRE's allocations.
                d, stats, _ = _run_ring_pipeline(
                    "delta", "block", n_frames=n_frames, h=128, w=256,
                    batch=4, ingest="monolithic")
            finally:
                monkeypatch.setattr(np, "empty", counter.real)
            assert len(d) == n_frames and stats["errors"] == 0
            return counter.big

        count(8)  # uncounted warmup compile at this signature
        short = count(16)
        long = count(48)
        assert long == short, (short, long)


def _mini_app(frames_blobs):
    import zmq

    class MiniApp:
        def __init__(self, blobs):
            self.ctx = zmq.Context()
            self.router = self.ctx.socket(zmq.ROUTER)
            self.dist_port = self.router.bind_to_random_port(
                "tcp://127.0.0.1")
            self.pull = self.ctx.socket(zmq.PULL)
            self.coll_port = self.pull.bind_to_random_port("tcp://127.0.0.1")
            self.blobs = list(enumerate(blobs))
            self.results = {}

        def serve(self, n_expect, timeout_s=60.0, quiet_s=None):
            """Pump until ``n_expect`` results — or, with ``quiet_s``,
            until the blobs are exhausted and no result has arrived for
            that long (fault tests where the exact served set depends on
            timing-sensitive batch boundaries)."""
            deadline = time.time() + timeout_s
            last_progress = time.time()
            last_n = -1
            while len(self.results) < n_expect and time.time() < deadline:
                if self.router.poll(5):
                    client, _ = self.router.recv_multipart()[:2]
                    if self.blobs:
                        idx, blob = self.blobs.pop(0)
                        self.router.send_multipart(
                            [client, str(idx).encode(), blob])
                if self.pull.poll(5):
                    idx_b, *_mid, payload = self.pull.recv_multipart()
                    self.results[int(idx_b.decode())] = payload
                if quiet_s is not None:
                    if len(self.results) != last_n:
                        last_n = len(self.results)
                        last_progress = time.time()
                    elif (not self.blobs
                          and time.time() - last_progress > quiet_s):
                        break

        def close(self):
            self.router.close(0)
            self.pull.close(0)
            self.ctx.term()

    return MiniApp(frames_blobs)


def _decode_in_wire_order(results: dict, codec) -> dict:
    """Delta results must decode in WIRE sequence order (the worker
    encodes in arrival order); returns {app_index: frame}."""
    from dvf_tpu.transport.codec import _DELTA_HEADER

    by_seq = sorted(results.items(),
                    key=lambda kv: _DELTA_HEADER.unpack_from(kv[1])[3])
    return {i: codec.decode(b) for i, b in by_seq}


class TestZmqWorkerDelta:

    def _run_worker(self, blobs, n, wire, quiet_s=None, **kw):
        from dvf_tpu.transport.zmq_ingress import TpuZmqWorker

        zmq = pytest.importorskip("zmq")  # noqa: F841
        app = _mini_app(blobs)
        worker = TpuZmqWorker(
            get_filter("invert"), host="127.0.0.1",
            distribute_port=app.dist_port, collect_port=app.coll_port,
            batch_size=4, wire=wire, delta_tile=16,
            delta_keyframe_interval=8, **kw)
        t = threading.Thread(target=worker.run,
                             kwargs={"max_frames": n}, daemon=True)
        t.start()
        app.serve(n_expect=n, timeout_s=30.0, quiet_s=quiet_s)
        worker.stop()
        t.join(timeout=20)
        stats = worker.stats()
        worker.close()
        results = dict(app.results)
        app.close()
        return results, stats

    def test_static_stream_bit_identical_to_jpeg_wire(self, rng):
        """Acceptance path 2 of 3: the ZMQ worker. Same static frames in
        through both wires; the delta results decode bit-identical to
        the jpeg-wire results."""
        n = 8
        frame = rng.integers(0, 255, (32, 32, 3), np.uint8)
        plain = make_codec(threads=1)
        app_enc = DeltaCodec(make_codec(threads=1), tile=16,
                             keyframe_interval=8)
        app_dec = DeltaCodec(make_codec(threads=1), tile=16)
        try:
            jpeg_results, s1 = self._run_worker(
                [plain.encode(frame)] * n, n, "jpeg")
            delta_blobs = [app_enc.encode(frame) for _ in range(n)]
            delta_results, s2 = self._run_worker(delta_blobs, n, "delta")
            assert s1["errors"] == 0 and s2["errors"] == 0
            assert s2["wire"] == "delta"
            assert s2["delta"]["dirty_ratio"] == 0.0
            jpeg_frames = {i: plain.decode(b)
                           for i, b in jpeg_results.items()}
            delta_frames = _decode_in_wire_order(delta_results, app_dec)
            assert sorted(delta_frames) == sorted(jpeg_frames)
            for i in jpeg_frames:
                np.testing.assert_array_equal(delta_frames[i],
                                              jpeg_frames[i])
        finally:
            plain.close()
            app_enc.close()
            app_dec.close()

    def test_device_probe_path_matches_host_path(self, rng):
        """delta_device=True (DeviceDeltaProbe bitmaps) must deliver the
        same results as the host change-detection path."""
        n = 8
        frames = _stream(rng, n, h=32, w=64)
        app_enc1 = DeltaCodec(make_codec(threads=1), tile=16,
                              keyframe_interval=8)
        app_enc2 = DeltaCodec(make_codec(threads=1), tile=16,
                              keyframe_interval=8)
        app_dec1 = DeltaCodec(make_codec(threads=1), tile=16)
        app_dec2 = DeltaCodec(make_codec(threads=1), tile=16)
        try:
            r_host, s_host = self._run_worker(
                [app_enc1.encode(f) for f in frames], n, "delta")
            r_dev, s_dev = self._run_worker(
                [app_enc2.encode(f) for f in frames], n, "delta",
                delta_device=True)
            assert s_host["errors"] == 0 and s_dev["errors"] == 0
            assert s_dev["delta"]["device_probe"] is True
            f_host = _decode_in_wire_order(r_host, app_dec1)
            f_dev = _decode_in_wire_order(r_dev, app_dec2)
            assert sorted(f_host) == sorted(f_dev)
            for i in f_host:
                np.testing.assert_array_equal(f_dev[i], f_host[i])
        finally:
            for c in (app_enc1, app_enc2, app_dec1, app_dec2):
                c.close()

    def test_dropped_delta_frame_contained_and_recovers(self, rng):
        """Acceptance: decoder resync after a dropped delta frame. The
        app drops one encoded delta frame; the worker contains the gap
        under ``transport``, drops up to the next keyframe, and serves
        everything from it onward."""
        n = 12
        frames = _stream(rng, n, h=32, w=64)
        app_enc = DeltaCodec(make_codec(threads=1), tile=16,
                             keyframe_interval=4)
        app_dec = DeltaCodec(make_codec(threads=1), tile=16)
        try:
            blobs = [app_enc.encode(f) for f in frames]
            served = [(i, b) for i, b in enumerate(blobs) if i != 2]
            app = _mini_app([b for _, b in served])
            # re-key MiniApp indices to the ORIGINAL frame indices
            app.blobs = list(served)
            from dvf_tpu.transport.zmq_ingress import TpuZmqWorker

            worker = TpuZmqWorker(
                get_filter("invert"), host="127.0.0.1",
                distribute_port=app.dist_port, collect_port=app.coll_port,
                batch_size=4, wire="delta", delta_tile=16,
                delta_keyframe_interval=4)
            t = threading.Thread(target=worker.run,
                                 kwargs={"max_frames": n - 1}, daemon=True)
            t.start()
            # serve until quiet: batch boundaries are timing-sensitive,
            # so the exact set of pre-keyframe casualties varies — only
            # the post-keyframe recovery is deterministic
            app.serve(n_expect=n - 1, timeout_s=30.0, quiet_s=1.5)
            worker.stop()
            t.join(timeout=20)
            stats = worker.stats()
            worker.close()
            results = dict(app.results)
            app.close()
            assert stats["faults"]["by_kind"].get("transport", 0) >= 1
            # keyframes land at 0, 5, 10 (interval 4 → every 5th frame);
            # everything from the first post-gap keyframe must be served
            assert {10, 11} <= set(results)
            decoded = _decode_in_wire_order(results, app_dec)
            # Frame 10 entered the worker as an ingest KEYFRAME (jpeg),
            # so its RESULT is exactly 255 − decode(jpeg(frame10)). How
            # it leaves depends on the egress encoder's own cadence
            # (timing-sensitive): as an egress keyframe the delivery is
            # the double jpeg roundtrip bit-exactly; as a delta frame
            # the moving region's tiles (changed vs the previous result,
            # hence shipped raw) are the result's bit-exactly.
            from dvf_tpu.transport.codec import (
                _DELTA_FLAG_KEY,
                _DELTA_HEADER,
            )

            plain = make_codec(threads=1)
            try:
                result10 = 255 - plain.decode(plain.encode(frames[10]))
                if (_DELTA_HEADER.unpack_from(results[10])[2]
                        & _DELTA_FLAG_KEY):
                    np.testing.assert_array_equal(
                        decoded[10],
                        plain.decode(plain.encode(result10)))
                else:
                    np.testing.assert_array_equal(
                        decoded[10][16:32, 16:48],
                        result10[16:32, 16:48])
            finally:
                plain.close()
        finally:
            app_enc.close()
            app_dec.close()

    def test_chaos_truncated_tile_degrades_to_full_frame(self, rng):
        """Acceptance: chaos-injected truncated tile payloads are
        contained under ``transport`` and the budget ladder degrades the
        delta path back to full-frame JPEG — no session loss (the worker
        keeps serving; later results remain decodable)."""
        from dvf_tpu.resilience import FaultPlan

        n = 16
        frames = _stream(rng, n, h=32, w=64)
        app_enc = DeltaCodec(make_codec(threads=1), tile=16,
                             keyframe_interval=4)
        app_dec = DeltaCodec(make_codec(threads=1), tile=16)
        try:
            blobs = [app_enc.encode(f) for f in frames]
            # two truncated delta payloads in the first two batches: the
            # 3rd transport fault (the second one's resync shadow) is
            # the budget-2 overflow that triggers the degradation; the
            # post-degradation resyncs fit the fresh window, so the
            # worker keeps serving instead of failing hard
            chaos = FaultPlan(seed=7).add("decode", at=(1, 6))
            results, stats = self._run_worker(
                blobs, n, "delta", chaos=chaos, fault_budget=2,
                fault_window_s=60.0, quiet_s=1.5)
            faults = stats["faults"]["by_kind"]
            assert faults.get("transport", 0) >= 3
            assert stats["delta"]["full_frames"] is True
            assert stats["delta"]["fallback_reason"] == "delta_fault_budget"
            # session survived: the stream keeps serving past the second
            # corruption (batch boundaries are timing-sensitive, so only
            # the tail's presence is deterministic, not its exact set)
            assert len(results) >= 4 and max(results) >= 13
            assert {13, 14} <= set(results) or {14, 15} <= set(results)
            decoded = _decode_in_wire_order(results, app_dec)
            # Post-degradation results are egress KEYFRAMES: a delivered
            # frame whose ingest was also a keyframe (15, interval 4) is
            # the double jpeg roundtrip of the inversion, bit-exactly.
            if 15 in decoded:
                plain = make_codec(threads=1)
                try:
                    np.testing.assert_array_equal(
                        decoded[15],
                        plain.decode(plain.encode(
                            255 - plain.decode(plain.encode(frames[15])))))
                finally:
                    plain.close()
        finally:
            app_enc.close()
            app_dec.close()


class TestServeBridgeDelta:

    def test_static_stream_bit_identical_to_jpeg_wire(self, rng):
        """Acceptance path 3 of 3: the serve bridge (cross-session
        batcher under one session) — static stream through wire=jpeg and
        wire=delta delivers bit-identical results."""
        zmq = pytest.importorskip("zmq")
        import sys as _sys

        _sys.path.insert(0, ".")
        from _util import free_port
        from dvf_tpu.serve import ZmqStreamBridge
        from dvf_tpu.serve.server import ServeConfig, ServeFrontend

        n, size = 6, 32
        frame = rng.integers(0, 255, (size, size, 3), np.uint8)
        plain = make_codec(threads=1)
        app_enc = DeltaCodec(make_codec(threads=1), tile=16,
                             keyframe_interval=4)
        app_dec = DeltaCodec(make_codec(threads=1), tile=16)

        def run(wire, blobs):
            p_dist, p_coll = free_port(), free_port()
            ctx = zmq.Context()
            router = ctx.socket(zmq.ROUTER)
            router.bind(f"tcp://127.0.0.1:{p_dist}")
            pull = ctx.socket(zmq.PULL)
            pull.bind(f"tcp://127.0.0.1:{p_coll}")
            fe = ServeFrontend(
                get_filter("invert"),
                ServeConfig(batch_size=2, queue_size=100, slo_ms=60_000.0))
            results = []
            try:
                with fe:
                    bridge = ZmqStreamBridge(
                        fe, host="127.0.0.1", distribute_port=p_dist,
                        collect_port=p_coll, wire=wire, delta_tile=16,
                        delta_keyframe_interval=4)
                    bt = threading.Thread(target=bridge.run,
                                          kwargs={"max_frames": n},
                                          daemon=True)
                    bt.start()
                    pending = list(enumerate(blobs))
                    deadline = time.time() + 30.0
                    while len(results) < n and time.time() < deadline:
                        if router.poll(10):
                            ident, payload = router.recv_multipart()
                            assert payload == b"READY"
                            if pending:
                                idx, blob = pending.pop(0)
                                router.send_multipart(
                                    [ident, str(idx).encode(), blob])
                        while pull.poll(0):
                            idx_b, *_mid, res = pull.recv_multipart()
                            results.append((int(idx_b.decode()), res))
                    bridge.stop()
                    bt.join(timeout=10.0)
                    assert bridge.errors == 0
                    bridge.close()
            finally:
                router.close(0)
                pull.close(0)
                ctx.term()
            return dict(results)

        try:
            jpeg_res = run("jpeg", [plain.encode(frame)] * n)
            delta_res = run("delta", [app_enc.encode(frame)
                                      for _ in range(n)])
            assert len(jpeg_res) == n and len(delta_res) == n
            jpeg_frames = {i: plain.decode(b) for i, b in jpeg_res.items()}
            delta_frames = _decode_in_wire_order(delta_res, app_dec)
            for i in jpeg_frames:
                np.testing.assert_array_equal(delta_frames[i],
                                              jpeg_frames[i])
        finally:
            plain.close()
            app_enc.close()
            app_dec.close()


# ---------------------------------------------------------------------------
# Coefficient wire (full-transform assist): device DCT+quant, host
# entropy coding only
# ---------------------------------------------------------------------------


def _smooth_stream(n, h=H, w=W, moving=True):
    """Smooth gradient frames with a moving smooth patch — JPEG-friendly
    content, so decode tolerances measure the PATH divergence (float vs
    fixed-point convert, mean vs h2v2 subsample), not content entropy."""
    y, x = np.mgrid[0:h, 0:w].astype(np.float32)
    base = np.stack([(x * 3) % 256, (y * 2) % 256, (x + y) % 256],
                    -1).astype(np.uint8)
    out = [base.copy()]
    for k in range(1, n):
        f = out[-1].copy()
        if moving:
            f[16:32, 16:48] = np.stack(
                [((x + 5 * k) % 256)[16:32, 16:48],
                 ((y + 3 * k) % 256)[16:32, 16:48],
                 ((x * 2 + k) % 256)[16:32, 16:48]], -1).astype(np.uint8)
        out.append(f)
    return out


def _native_coef_codec():
    from dvf_tpu.transport.codec import NativeJpegCodec

    try:
        codec = NativeJpegCodec(quality=90, threads=1)
    except (RuntimeError, OSError) as e:
        pytest.skip(f"native jpeg shim unavailable: {e}")
    if not hasattr(codec._lib, "dvf_jpeg_encode_coefficients"):
        codec.close()
        pytest.skip("shim predates coefficient assist")
    return codec


class TestCoefficientWire:

    def test_dct_quant_golden_vs_pallas_bit_exact(self, rng):
        """Rung 1 of the equivalence ladder: the Pallas DCT+quant kernel
        is BIT-identical to the jnp golden path — quantized coefficients
        ride the wire as-is, so ±1 here is wire-visible corruption."""
        import jax.numpy as jnp

        from dvf_tpu.ops.pallas_kernels import (
            dct8x8_quant,
            dct8x8_quant_pallas,
            dct8x8_quant_ref,
            jpeg_quant_table,
        )

        for quality in (50, 90, 95):
            q = jpeg_quant_table(quality)
            for shape in ((2, 64, 128), (1, 8, 8), (3, 48, 64)):
                plane = rng.uniform(0, 255, shape).astype(np.float32)
                golden = np.asarray(dct8x8_quant_ref(jnp.asarray(plane), q))
                pal = np.asarray(dct8x8_quant_pallas(
                    jnp.asarray(plane), q, interpret=True))
                np.testing.assert_array_equal(golden, pal)
        # Edge geometry routes through the golden path with edge-padded
        # partial blocks — the dispatcher must cover it transparently.
        q = jpeg_quant_table(90)
        plane = rng.uniform(0, 255, (2, 52, 100)).astype(np.float32)
        out = np.asarray(dct8x8_quant(jnp.asarray(plane), q))
        assert out.shape == (2, 7, 13, 8, 8) and out.dtype == np.int16

    def test_equivalence_ladder_coefficients_to_host_jpeg(self):
        """Rungs 2–3: device-quantized blocks entropy-coded by the shim
        decode (a) near-exactly against the host path fed the SAME
        planes (quantization rung in isolation) and (b) within the
        pinned convert-divergence tolerance of the full host RGB
        libjpeg path."""
        import jax.numpy as jnp

        from dvf_tpu.ops.pallas_kernels import (dct8x8_quant_ref,
                                                jpeg_quant_table)
        from dvf_tpu.runtime.codec_assist import rgb_to_ycbcr420

        codec = _native_coef_codec()
        try:
            frame = _smooth_stream(1)[0]
            y, cb, cr = rgb_to_ycbcr420(jnp.asarray(frame[None]))
            ql = jpeg_quant_table(90)
            qc = jpeg_quant_table(90, chroma=True)
            yq = np.asarray(dct8x8_quant_ref(y, ql))[0]
            cbq = np.asarray(dct8x8_quant_ref(cb, qc))[0]
            crq = np.asarray(dct8x8_quant_ref(cr, qc))[0]
            blob = codec.encode_coefficients(yq, cbq, crq, H, W)
            dec = codec.decode(blob)
            if hasattr(codec._lib, "dvf_jpeg_encode_ycbcr420"):
                # same planes through the shim's own DCT+quant: only the
                # transform differs, and it must agree almost exactly
                same_planes = codec.decode(codec.encode_ycbcr420(
                    np.asarray(y[0]), np.asarray(cb[0]), np.asarray(cr[0])))
                err = np.abs(dec.astype(int) - same_planes.astype(int))
                assert err.max() <= 8 and err.mean() < 0.5
            ref = codec.decode(codec.encode(frame))
            err = np.abs(dec.astype(int) - ref.astype(int))
            # float convert + mean subsample vs libjpeg fixed-point +
            # h2v2 — the same divergence bound the ycbcr assist pins
            assert err.max() <= 24 and err.mean() < 1.5
        finally:
            codec.close()

    def test_fused_selection_bit_identical_and_one_dispatch(self, rng):
        """Acceptance: the fused probe+transform pass is ONE device
        dispatch per batch (dispatch-count assertion) and its dirty-tile
        selection is bit-identical to ``host_tile_maxdiff``."""
        import jax.numpy as jnp

        from dvf_tpu.runtime.codec_assist import FusedDeltaTransform

        fused = FusedDeltaTransform(tile=TILE, quality=90)
        frames = _stream(rng, 9)
        batches = [np.stack(frames[i:i + 3]) for i in (0, 3, 6)]
        prev_tail = None
        for bi, batch in enumerate(batches):
            bms, cfs = fused.process(jnp.asarray(batch))
            assert fused.calls == bi + 1  # ONE dispatch per batch
            assert len(cfs) == batch.shape[0]
            chain = (np.concatenate([batch[:1], batch[:-1]])
                     if prev_tail is None
                     else np.concatenate([prev_tail[None], batch[:-1]]))
            for i in range(batch.shape[0]):
                if bi == 0 and i == 0:
                    assert (bms[0] == 255).all()  # no predecessor
                    continue
                np.testing.assert_array_equal(
                    bms[i], host_tile_maxdiff(batch[i], chain[i], TILE))
            prev_tail = batch[-1]

    def test_fused_coefficient_wire_roundtrip(self, rng):
        """The fused pass's CoefficientFrames drive DeltaCodec.encode;
        an UNCHANGED delta peer decodes the stream (keyframe + delta
        framing intact, coefficient tiles lossy-JPEG, never flagged
        LOSSLESS), and provenance/stage stats land in stats()."""
        import jax.numpy as jnp

        from dvf_tpu.runtime.codec_assist import FusedDeltaTransform
        from dvf_tpu.transport.codec import (_DELTA_FLAG_KEY,
                                             _DELTA_FLAG_LOSSLESS,
                                             _DELTA_HEADER)

        codec = _native_coef_codec()
        codec.close()  # availability gate only; DeltaCodec builds its own
        from dvf_tpu.transport.codec import NativeJpegCodec

        fused = FusedDeltaTransform(tile=TILE, quality=90)
        enc = DeltaCodec(NativeJpegCodec(quality=90, threads=1), tile=TILE,
                         keyframe_interval=32)
        dec = DeltaCodec(NativeJpegCodec(quality=90, threads=1), tile=TILE)
        try:
            frames = _smooth_stream(6)
            bms, cfs = fused.process(jnp.asarray(np.stack(frames)))
            out = np.empty((H, W, 3), np.uint8)
            for k, f in enumerate(frames):
                blob = enc.encode(None, bitmap=bms[k], coeffs=cfs[k])
                _m, _v, flags, _s, _h, _w, _t = _DELTA_HEADER.unpack_from(
                    blob)
                if k == 0:
                    assert flags & _DELTA_FLAG_KEY
                else:
                    assert not flags & _DELTA_FLAG_KEY
                    assert not flags & _DELTA_FLAG_LOSSLESS
                dec.decode_into(blob, out)
                err = np.abs(out.astype(int) - f.astype(int))
                # one 4:2:0 q90 JPEG generation on smooth content
                assert err.max() <= 32 and err.mean() < 2.0
            s = enc.stats()
            assert s["assist"] == "full-transform"
            assert s["coef_frames"] == 6 and s["keyframes"] == 1
            assert s["entropy_ms"] > 0 and s["d2h_coef_bytes"] > 0
            # dirty-tile gathers cross a fraction of the full-frame bytes
            assert s["d2h_coef_bytes"] < 6 * H * W * 3
            assert "entropy_workers" in enc.config()
        finally:
            enc.close()
            dec.close()

    def test_worker_full_assist_end_to_end_with_corrupt_wire(self, rng):
        """Acceptance, end-to-end: the worker on --codec-assist full
        serves the coefficient wire under the audit envelope; a
        chaos-injected post-encode bit flip (``corrupt_wire``) is
        DETECTED by the peer's verify, and every clean payload verifies
        and decodes. Dispatch count is pinned batch-for-batch."""
        zmq = pytest.importorskip("zmq")  # noqa: F841
        from dvf_tpu.obs.audit import (WireIntegrityError, stamp_wire,
                                       verify_wire)
        from dvf_tpu.resilience import FaultPlan
        from dvf_tpu.transport.zmq_ingress import TpuZmqWorker

        _native_coef_codec().close()  # skip when the shim can't serve it
        n = 8
        frames = _smooth_stream(n, h=32, w=64)
        app_enc = DeltaCodec(make_codec(threads=1), tile=16,
                             keyframe_interval=8)
        app_dec = DeltaCodec(make_codec(threads=1), tile=16,
                             on_gap="composite")
        app = _mini_app([stamp_wire(app_enc.encode(f)) for f in frames])
        worker = TpuZmqWorker(
            get_filter("invert"), host="127.0.0.1",
            distribute_port=app.dist_port, collect_port=app.coll_port,
            batch_size=4, wire="delta", delta_tile=16,
            delta_keyframe_interval=8, codec_assist="full",
            audit_wire=True,
            chaos=FaultPlan(seed=3).add("corrupt_wire", at=(2,)))
        try:
            assert worker._fused is not None
            t = threading.Thread(target=worker.run,
                                 kwargs={"max_frames": n}, daemon=True)
            t.start()
            app.serve(n_expect=n, timeout_s=30.0)
            worker.stop()
            t.join(timeout=20)
            stats = worker.stats()
            d = stats["delta"]
            assert d["assist"] == "full-transform"
            assert d["fused_transform"] is True
            assert d["fused_dispatches"] == stats["batches"]  # ONE per batch
            assert d["coef_frames"] == stats["frames_processed"]
            assert d["entropy_ms"] > 0
            assert stats["egress"]["entropy_ms"] > 0
            corrupt, clean = 0, {}
            for i, payload in app.results.items():
                try:
                    clean[i] = verify_wire(bytes(payload), hop="app")
                except WireIntegrityError:
                    corrupt += 1
            assert corrupt == 1  # the injected flip, caught at verify
            assert len(clean) == n - 1
            out = np.empty((32, 64, 3), np.uint8)
            from dvf_tpu.transport.codec import _DELTA_HEADER

            for _i, b in sorted(clean.items(),
                                key=lambda kv: _DELTA_HEADER.unpack_from(
                                    kv[1])[3]):
                app_dec.decode_into(b, out)  # framing intact end-to-end
        finally:
            worker.close()
            app.close()
            app_enc.close()
            app_dec.close()
