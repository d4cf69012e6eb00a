"""Streamed shard-level egress + asynchronous codec plane (runtime/egress.py).

Mirror of test_ingest_stream.py for the delivery side. Three properties
guard the tentpole:

1. **Equivalence** — the streamed fetch (per-shard copy_to_host_async →
   preallocated slab) and the async codec plane produce BIT-IDENTICAL,
   identically-ordered output vs the monolithic np.asarray + serial
   encode path, across shardings, padded batches, and slot aliasing.
2. **Allocation regression** — the steady-state delivery path performs
   ZERO per-batch multi-100KB host allocations (the slab pool is reused).
3. **Chaos interplay** — an injected d2h fault mid-streamed-egress is
   classified and contained (and degrades to monolithic through the
   budget); a frozen consumer cannot wedge the encode plane; watchdog
   recovery still drains with streamed egress in the path.
"""

import threading
import time

import numpy as np
import pytest

from dvf_tpu.io import NullSink, SyntheticSource
from dvf_tpu.obs.metrics import EgressStats
from dvf_tpu.ops import get_filter
from dvf_tpu.parallel import MeshConfig, make_mesh
from dvf_tpu.runtime import Engine, Pipeline, PipelineConfig
from dvf_tpu.runtime import egress as egress_mod
from dvf_tpu.runtime.egress import AsyncCodecPlane, ShardedBatchFetcher


@pytest.fixture(autouse=True)
def _force_streaming(monkeypatch):
    """This suite exercises the streamed-egress machinery on the CPU test
    backend, where both fallbacks would (correctly) fire: np.asarray is a
    zero-copy view (zero_copy_backend) and the calibrated blocking fetch
    is far below MIN_STREAM_D2H_MS (cheap_transfer). Disable both gates
    so the streamed path actually runs."""
    monkeypatch.setattr(egress_mod, "STREAM_ON_CPU", True)
    monkeypatch.setattr(egress_mod, "MIN_STREAM_D2H_MS", 0.0)


def _rng_frames(n, h, w, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 255, size=(h, w, 3), dtype=np.uint8)
            for _ in range(n)]


# ---------------------------------------------------------------------------
# Fetcher unit level: streamed fetch equals np.asarray for every layout
# ---------------------------------------------------------------------------


class TestFetcherEquivalence:

    @pytest.mark.parametrize("cfg,batch", [
        (MeshConfig(data=1), 4),           # single device
        (MeshConfig(data=4), 8),           # batch-sharded
        (MeshConfig(data=2, space=2), 4),  # batch + H sharded
        (MeshConfig(data=8), 8),           # one row per device
        (MeshConfig(data=8), 4),           # replicated (batch < data ways)
    ])
    def test_fetch_matches_asarray(self, cfg, batch):
        h, w = 16, 24
        eng = Engine(get_filter("invert"), mesh=make_mesh(cfg))
        eng.ensure_compiled((batch, h, w, 3), np.uint8)
        fetcher = ShardedBatchFetcher(
            eng.out_shape, eng.out_dtype, eng.output_sharding, slots=3)
        assert fetcher.effective_mode == "streamed"
        packed = cfg.data == 1  # one shard on one device: the packed
        #   transfer layout, the landed buffer handed out, no pool
        # Several batches across aliasing pool slots.
        for slot in range(5):
            frames = np.stack(_rng_frames(batch, h, w, seed=slot))
            result = eng.submit(frames.copy())
            ref = np.asarray(result)
            out = fetcher.fetch(fetcher.prefetch(result), slot)
            np.testing.assert_array_equal(out, ref)
            assert fetcher.owns(out) == (not packed)
        s = fetcher.stats.summary()
        assert s["batches"] == 5
        assert s["pool_allocs"] == (0 if packed else 1)
        assert s["packed_batches"] == (5 if packed else 0)
        assert s["transfer_layout"] == ("u32rows" if packed else "plain")

    def test_monolithic_mode_is_classic_fetch(self):
        eng = Engine(get_filter("invert"), mesh=make_mesh(MeshConfig(data=1)))
        eng.ensure_compiled((4, 8, 8, 3), np.uint8)
        fetcher = ShardedBatchFetcher(
            eng.out_shape, eng.out_dtype, eng.output_sharding,
            mode="monolithic", slots=3)
        assert fetcher.effective_mode == "monolithic"
        result = eng.submit(np.zeros((4, 8, 8, 3), np.uint8))
        out = fetcher.fetch(result, 0)
        assert not fetcher.owns(out)  # fresh per-batch array: views safe
        np.testing.assert_array_equal(out, np.full((4, 8, 8, 3), 255))

    def test_zero_copy_backend_fallback(self, monkeypatch):
        """Default on CPU: np.asarray is free, the slab copy is not —
        the fetcher must degrade and say so."""
        monkeypatch.setattr(egress_mod, "STREAM_ON_CPU", False)
        eng = Engine(get_filter("invert"), mesh=make_mesh(MeshConfig(data=1)))
        eng.ensure_compiled((4, 8, 8, 3), np.uint8)
        fetcher = ShardedBatchFetcher(
            eng.out_shape, eng.out_dtype, eng.output_sharding)
        assert fetcher.effective_mode == "monolithic"
        assert fetcher.stats.fallback_reason == "zero_copy_backend"

    def test_cheap_transfer_fallback(self, monkeypatch):
        monkeypatch.setattr(egress_mod, "MIN_STREAM_D2H_MS", 2.0)
        eng = Engine(get_filter("invert"), mesh=make_mesh(MeshConfig(data=1)))
        eng.ensure_compiled((4, 8, 8, 3), np.uint8)
        stats = EgressStats(d2h_block_ms=0.1)  # sub-threshold calibration
        fetcher = ShardedBatchFetcher(
            eng.out_shape, eng.out_dtype, eng.output_sharding, stats=stats)
        assert fetcher.effective_mode == "monolithic"
        assert stats.fallback_reason == "cheap_transfer"
        stats2 = EgressStats(d2h_block_ms=50.0)
        fetcher2 = ShardedBatchFetcher(
            eng.out_shape, eng.out_dtype, eng.output_sharding, stats=stats2)
        assert fetcher2.effective_mode == "streamed"
        assert stats2.fallback_reason is None

    def test_geometry_mismatch_falls_back_per_batch(self):
        """A result compiled at another signature (mid-stream geometry
        change) must not corrupt the slab — per-batch np.asarray."""
        eng = Engine(get_filter("invert"), mesh=make_mesh(MeshConfig(data=1)))
        eng.ensure_compiled((4, 8, 8, 3), np.uint8)
        fetcher = ShardedBatchFetcher(
            (4, 16, 16, 3), np.uint8, eng.output_sharding, slots=2)
        result = eng.submit(np.zeros((4, 8, 8, 3), np.uint8))
        out = fetcher.fetch(result, 0)
        assert out.shape == (4, 8, 8, 3)
        assert not fetcher.owns(out)

    def test_bad_mode_rejected(self):
        with pytest.raises(ValueError, match="egress mode"):
            ShardedBatchFetcher((4, 8, 8, 3), np.uint8, None, mode="bogus")

    def test_engine_calibrates_d2h(self, monkeypatch):
        eng = Engine(get_filter("invert"))
        assert eng.d2h_block_ms is None and eng.out_shape is None
        eng.ensure_compiled((4, 16, 16, 3), np.uint8)
        assert eng.d2h_block_ms is not None and eng.d2h_block_ms >= 0
        assert eng.out_shape == (4, 16, 16, 3)
        assert eng.output_sharding is not None
        # Above the size cap the calibration is skipped (one more
        # blocking whole-batch fetch inside compile()).
        from dvf_tpu.runtime import engine as engine_mod

        monkeypatch.setattr(engine_mod, "_D2H_CALIBRATION_CAP_BYTES", 1)
        eng2 = Engine(get_filter("invert"))
        eng2.ensure_compiled((4, 16, 16, 3), np.uint8)
        assert eng2.d2h_block_ms is None
        assert eng2.out_shape == (4, 16, 16, 3)


# ---------------------------------------------------------------------------
# The packed transfer layout (PR 26): results leave the device as 32-bit
# words, the landed buffer is handed out as frames
# ---------------------------------------------------------------------------


def _packing_fetcher(shape, **kw):
    import jax
    from jax.sharding import SingleDeviceSharding

    dev = jax.devices()[0]
    f = ShardedBatchFetcher(shape, np.uint8, SingleDeviceSharding(dev), **kw)
    return f, dev


class TestPackedTransferLayout:

    @pytest.mark.parametrize("shape", [
        (8, 32, 48, 3),     # the toy: 36864 bytes = 9 x 4096
        (1, 32, 48, 3),     # one frame: 4608 bytes, no multiple of 4096
        (3, 30, 52, 3),     # three frames of an odd geometry, 39-word rows
        (2, 8, 1028, 3),    # two full 512-pixel chunks and a 4-pixel tail
        (2, 8, 516, 1),     # one channel
        (2, 8, 640, 4),     # four channels: a pixel is a word
    ])
    def test_pack_then_fetch_is_byte_identical(self, shape):
        import jax

        f, dev = _packing_fetcher(shape)
        assert f.effective_mode == "streamed"
        assert f.stats.transfer_layout == "u32rows"
        assert f.slab_bytes() == 0 and f.stats.pool_allocs == 0
        rng = np.random.default_rng(shape[2])
        for slot in range(3):
            frames = rng.integers(0, 256, shape, dtype=np.uint8)
            result = jax.device_put(frames, dev)
            handle = f.prefetch(result)
            assert isinstance(handle, egress_mod.PackedBatch)
            assert len(handle.rows) == shape[0]  # one device array a row
            assert all(r.dtype == np.uint32 and r.shape == (
                shape[1], shape[2] * shape[3] // 4) for r in handle.rows)
            out = f.fetch(handle, slot)
            assert isinstance(out, egress_mod.LandedRows)
            assert len(out) == shape[0]
            assert all(r.dtype == np.uint8 and r.shape == shape[1:]
                       for r in out)
            np.testing.assert_array_equal(np.stack(out), frames)
            np.testing.assert_array_equal(np.stack(out), np.asarray(result))
            # each row is the buffer it landed in, viewed: read-only,
            # and no two of them are one buffer
            assert not any(r.flags.writeable for r in out)
            assert not any(np.shares_memory(a, b)
                           for i, a in enumerate(out) for b in out[i + 1:])
            assert not f.owns(out)
            # What the lane's in-flight handle waits on, and its span's
            # layout: a row of the words, not a disguise of the result.
            assert egress_mod.device_side(handle) == (handle.rows[0],
                                                      "u32rows")
        s = f.stats.summary()
        assert s["batches"] == 3 and s["packed_batches"] == 3
        assert s["row_landed_batches"] == 3
        assert s["rows_landed_total"] == 3 * shape[0]
        assert s["rows_skipped_total"] == 0
        assert s["bytes_total"] == 3 * int(np.prod(shape))
        assert s["copy_ms_total"] == 0.0
        assert s["transfer_layout"] == "u32rows"

    @pytest.mark.parametrize("valid", [1, 3, 8])
    def test_padding_rows_never_land(self, valid):
        """``prefetch(result, valid)``: the rows past ``valid`` are
        dropped on the device and start no transfer; the counters say
        how many crossed and how many did not, and ``bytes_total`` is
        what landed."""
        import jax

        shape = (8, 16, 24, 3)
        f, dev = _packing_fetcher(shape)
        frames = np.stack(_rng_frames(8, 16, 24, seed=valid))
        handle = f.prefetch(jax.device_put(frames, dev), valid)
        assert len(handle.rows) == valid
        out = f.fetch(handle, 0)
        assert isinstance(out, egress_mod.LandedRows) and len(out) == valid
        np.testing.assert_array_equal(np.stack(out), frames[:valid])
        s = f.stats.summary()
        assert s["row_landed_batches"] == s["packed_batches"] == 1
        assert s["rows_landed_total"] == valid
        assert s["rows_skipped_total"] == 8 - valid
        assert s["bytes_total"] == valid * 16 * 24 * 3

    def test_a_kept_row_keeps_one_row_alive(self):
        """Holding one landed row after the batch, its handle and the
        other rows are gone keeps that row's buffer and no other: weak
        references to the other rows' landed words die."""
        import gc
        import weakref

        import jax

        shape = (4, 16, 24, 3)
        f, dev = _packing_fetcher(shape)
        frames = np.stack(_rng_frames(4, 16, 24, seed=2))
        handle = f.prefetch(jax.device_put(frames, dev))
        out = f.fetch(handle, 0)
        landed = [weakref.ref(r.base if r.base is not None else r)
                  for r in out]  # the uint32 words under each view
        kept = out[2]
        del out, handle
        gc.collect()
        assert [r() is not None for r in landed] == [False, False, True,
                                                     False]
        np.testing.assert_array_equal(kept, frames[2])

    @pytest.mark.parametrize("shape,dtype,why", [
        ((2, 8, 9, 3), np.uint8, "27-byte rows are no whole words"),
        ((2, 8, 8, 3), np.float32, "not uint8"),
        ((2, 8, 24), np.uint8, "not NHWC"),
    ])
    def test_results_that_cannot_pack_keep_the_slab_path(self, shape, dtype,
                                                         why):
        import jax
        from jax.sharding import SingleDeviceSharding

        dev = jax.devices()[0]
        f = ShardedBatchFetcher(shape, dtype, SingleDeviceSharding(dev),
                                slots=2)
        assert f.effective_mode == "streamed", why
        assert f.stats.transfer_layout == "plain", why
        assert f.stats.pool_allocs == 1 and f.slab_bytes() > 0
        x = (np.arange(np.prod(shape)) % 251).astype(dtype).reshape(shape)
        result = jax.device_put(x, dev)
        handle = f.prefetch(result)
        assert handle is result
        assert egress_mod.device_side(handle) == (result, "plain")
        out = f.fetch(handle, 0)
        np.testing.assert_array_equal(out, x)
        assert f.owns(out)
        assert f.stats.summary()["packed_batches"] == 0

    def test_several_distinct_shards_keep_the_slab_path(self):
        eng = Engine(get_filter("invert"), mesh=make_mesh(MeshConfig(data=4)))
        eng.ensure_compiled((8, 16, 24, 3), np.uint8)
        f = ShardedBatchFetcher(eng.out_shape, eng.out_dtype,
                                eng.output_sharding, slots=2)
        assert f.stats.transfer_layout == "plain"
        assert f.stats.pool_allocs == 1
        frames = np.stack(_rng_frames(8, 16, 24, seed=5))
        result = eng.submit(frames.copy())
        handle = f.prefetch(result)
        assert handle is result
        out = f.fetch(handle, 0)
        np.testing.assert_array_equal(out, 255 - frames)
        assert f.owns(out) and f.stats.packed_batches == 0

    def test_monolithic_mode_never_packs(self):
        f, dev = _packing_fetcher((4, 8, 8, 3), mode="monolithic")
        import jax

        assert f.effective_mode == "monolithic"
        assert f.stats.transfer_layout == "plain"
        result = jax.device_put(np.full((4, 8, 8, 3), 7, np.uint8), dev)
        assert f.prefetch(result) is result
        np.testing.assert_array_equal(f.fetch(result, 0), 7)
        assert f.stats.packed_batches == 0 and f.stats.pool_allocs == 0

    def test_geometry_mismatch_is_not_packed(self):
        """A batch compiled at another signature goes back as it came:
        no pack at the wrong shape, the classic per-batch fetch."""
        import jax

        f, dev = _packing_fetcher((4, 16, 16, 3))
        other = np.full((4, 8, 8, 3), 9, np.uint8)
        result = jax.device_put(other, dev)
        handle = f.prefetch(result)
        assert handle is result
        out = f.fetch(handle, 0)
        np.testing.assert_array_equal(out, other)
        assert f.stats.packed_batches == 0 and f.stats.batches == 1

    def test_release_mid_flight(self):
        """A batch packed before release() still unpacks (its buffer is
        its own: there is no pool to free under it); the next batch goes
        back plain. Any fetcher unpacks a packed batch, whatever became
        of the one that packed it."""
        import jax

        shape = (4, 8, 16, 3)
        f, dev = _packing_fetcher(shape)
        x = np.stack(_rng_frames(4, 8, 16, seed=9))
        handle = f.prefetch(jax.device_put(x, dev))
        f.release()
        out = f.fetch(handle, 0)
        assert isinstance(out, egress_mod.LandedRows)
        np.testing.assert_array_equal(np.stack(out), x)
        after = jax.device_put(x, dev)
        assert f.prefetch(after) is after
        plain = f.fetch(after, 1)
        assert isinstance(plain, np.ndarray)  # rows are views again
        np.testing.assert_array_equal(plain, x)
        assert f.stats.packed_batches == 1 and f.stats.batches == 2
        assert f.stats.row_landed_batches == 1
        other, _ = _packing_fetcher((2, 4, 4, 3), mode="monolithic")
        handle2 = _packing_fetcher(shape)[0].prefetch(
            jax.device_put(x, dev), 3)
        out2 = other.fetch(handle2, 0)
        assert isinstance(out2, egress_mod.LandedRows) and len(out2) == 3
        np.testing.assert_array_equal(np.stack(out2), x[:3])

    def test_pack_is_compiled_when_the_fetcher_is_built(self):
        """Never on a batch: the first prefetch finds the executable, and
        a second fetcher of the same signature compiles nothing."""
        shape = (2, 8, 20, 3)
        egress_mod._compiled_pack.cache_clear()
        f, dev = _packing_fetcher(shape)
        info = egress_mod._compiled_pack.cache_info()
        assert (info.misses, info.hits) == (1, 0)
        assert f._pack is not None
        _packing_fetcher(shape)
        info = egress_mod._compiled_pack.cache_info()
        assert (info.misses, info.hits) == (1, 1)

    def test_d2h_chaos_fires_once_per_packed_batch(self):
        import jax

        from dvf_tpu.resilience import FaultPlan
        from dvf_tpu.resilience.chaos import ChaosFault

        chaos = FaultPlan().add("d2h", at=(1,))
        f, dev = _packing_fetcher((2, 8, 8, 3), chaos=chaos)
        x = np.full((2, 8, 8, 3), 3, np.uint8)
        np.testing.assert_array_equal(
            np.stack(f.fetch(f.prefetch(jax.device_put(x, dev)), 0)), x)
        with pytest.raises(ChaosFault):  # two rows, one batch, one firing
            f.fetch(f.prefetch(jax.device_put(x, dev)), 1)
        np.testing.assert_array_equal(
            np.stack(f.fetch(f.prefetch(jax.device_put(x, dev)), 2)), x)
        assert chaos.summary()["events"]["d2h"] == 3

    def test_trace_span_names_the_layout(self):
        import jax

        from dvf_tpu.obs.trace import Tracer

        tracer = Tracer(enabled=True)
        f, dev = _packing_fetcher((2, 8, 8, 3), tracer=tracer)
        f.fetch(f.prefetch(jax.device_put(
            np.zeros((2, 8, 8, 3), np.uint8), dev)), 0)
        f.fetch(f.prefetch(jax.device_put(
            np.zeros((2, 8, 8, 3), np.uint8), dev), 1), 1)
        spans = [e for e in tracer._events if e["name"] == "egress_d2h"]
        assert len(spans) == 2  # one a batch, carrying the rows that landed
        assert all(e["args"]["layout"] == "u32rows" for e in spans)
        assert [e["args"]["rows"] for e in spans] == ["0:2", "0:1"]
        assert [e["args"]["bytes"] for e in spans] == [2 * 8 * 8 * 3,
                                                        8 * 8 * 3]


def test_pack_table_is_a_permutation():
    """Every byte of the interleaved stream comes from exactly one
    (plane, pixel), with weight 1 or 256 by its place in the half-word."""
    for width, channels in [(48, 3), (512, 3), (640, 3), (8, 4), (12, 1)]:
        t = egress_mod.pack_table(width, channels)
        p = min(width, egress_mod.PACK_CHUNK_PX)
        pw = p * channels // 4
        assert t.shape == (channels, p, 2 * pw)
        assert np.count_nonzero(t) == p * channels
        assert set(np.unique(t)) == {0.0, 1.0, 256.0}
        # each output column takes exactly two bytes: one of each weight
        assert (np.count_nonzero(t == 1.0, axis=(0, 1)) == 1).all()
        assert (np.count_nonzero(t == 256.0, axis=(0, 1)) == 1).all()


def test_overlap_efficiency_formula():
    s = EgressStats(requested_mode="streamed", d2h_block_ms=10.0)
    s.effective_mode = "streamed"
    s.record_fetch(wait_ms=1.5, copy_ms=0.5)
    # exposed = 2.0 of a 10.0 blocking baseline → 80% hidden.
    assert s.overlap_efficiency() == pytest.approx(0.8)
    s2 = EgressStats(d2h_block_ms=1.0)
    s2.record_fetch(wait_ms=5.0, copy_ms=0.0)
    assert s2.overlap_efficiency() == 0.0  # clamped, never negative
    s3 = EgressStats(requested_mode="monolithic", d2h_block_ms=10.0)
    s3.effective_mode = "monolithic"
    s3.record_fetch(1, 1)
    assert s3.overlap_efficiency() is None
    assert EgressStats(d2h_block_ms=None).overlap_efficiency() is None
    # Encode accounting lands in the summary.
    s.record_encode(encode_ms=4.0, wait_ms=0.5)
    out = s.summary()
    assert out["encode_ms"] == 4.0 and out["encode_wait_ms"] == 0.5


# ---------------------------------------------------------------------------
# Async codec plane
# ---------------------------------------------------------------------------


class TestAsyncCodecPlane:

    def test_ordered_delivery_and_roundtrip(self):
        from dvf_tpu.transport.codec import make_codec

        codec = make_codec()
        try:
            plane = AsyncCodecPlane(codec, jpeg=True, depth=2)
            frames = _rng_frames(6, 24, 32, seed=1)
            plane.submit(frames[:3], [0, 1, 2])
            plane.submit(frames[3:5], [3, 4])
            plane.submit(frames[5:], [5])
            rows = [r for b in plane.flush() for r in b]
            assert [m for m, _, _ in rows] == [0, 1, 2, 3, 4, 5]
            for (meta, payload, err), src in zip(rows, frames):
                assert err is None
                # Same-codec re-encode is deterministic: the payload must
                # equal a direct synchronous encode of the same frame.
                assert payload == codec.encode(src)
        finally:
            codec.close()

    def test_raw_path_is_zero_copy_memoryview(self):
        plane = AsyncCodecPlane(codec=None, jpeg=False, depth=1)
        slab = np.stack(_rng_frames(2, 8, 8, seed=2))
        plane.submit([slab[0], slab[1]], ["a", "b"])
        [rows] = plane.flush()
        (_, p0, _), (_, p1, _) = rows
        assert isinstance(p0, memoryview)
        assert bytes(p0) == slab[0].tobytes()
        # Zero-copy: mutating the slab mutates the payload (which is why
        # the window bound must cover the send, as the worker's does).
        slab[1][:] = 0
        assert bytes(p1) == b"\x00" * slab[1].nbytes

    def test_encode_error_surfaces_per_row(self):
        class _BoomCodec:
            def encode_batch_async(self, frames):
                from concurrent.futures import Future

                futs = []
                for i, _ in enumerate(frames):
                    f = Future()
                    if i == 1:
                        f.set_exception(ValueError("boom"))
                    else:
                        f.set_result(b"ok")
                    futs.append(f)
                return futs

        plane = AsyncCodecPlane(_BoomCodec(), jpeg=True, depth=1)
        plane.submit([None, None, None], [0, 1, 2])
        [rows] = plane.flush()
        assert rows[0][1] == b"ok" and rows[0][2] is None
        assert rows[1][1] is None and isinstance(rows[1][2], ValueError)
        assert rows[2][1] == b"ok"


def test_codec_close_joins_pool_threads():
    """The satellite: codec pools are JOINED on close — no lingering
    dvf-jpeg threads (the conftest session guard enforces this globally;
    this pins the prompt-join property directly)."""
    from dvf_tpu.transport.codec import JpegCodec

    codec = JpegCodec(quality=90, threads=3)
    frames = _rng_frames(6, 16, 16, seed=3)
    codec.encode_batch(frames)  # spawn the pool threads
    mine = {t for t in threading.enumerate()
            if t.name.startswith("dvf-jpeg")}
    assert mine  # the pool actually ran
    codec.close()
    deadline = time.time() + 5.0
    while any(t.is_alive() for t in mine) and time.time() < deadline:
        time.sleep(0.02)
    assert not any(t.is_alive() for t in mine)


# ---------------------------------------------------------------------------
# End-to-end equivalence: streamed vs monolithic egress
# ---------------------------------------------------------------------------


class _CapturingSink(NullSink):
    def __init__(self):
        super().__init__()
        self.frames = {}
        self.order = []

    def emit(self, index, frame, ts):
        super().emit(index, frame, ts)
        self.frames[index] = frame.copy()
        self.order.append(index)


def _run_capture(filt, egress, mesh_cfg, batch, n_frames, h=24, w=32,
                 max_inflight=4, frame_delay=0, slow_submit_s=0.0):
    sink = _CapturingSink()
    engine = Engine(filt, mesh=make_mesh(mesh_cfg))
    pipe = Pipeline(
        SyntheticSource(height=h, width=w, n_frames=n_frames),
        filt, sink,
        PipelineConfig(batch_size=batch, queue_size=1000,
                       frame_delay=frame_delay,
                       max_inflight=max_inflight, egress=egress),
        engine=engine,
    )
    if slow_submit_s:
        orig_r, orig_s = engine.submit_resident, engine.submit

        def slow_resident(b):
            time.sleep(slow_submit_s)
            return orig_r(b)

        def slow_submit(b):
            time.sleep(slow_submit_s)
            return orig_s(b)

        engine.submit_resident = slow_resident
        engine.submit = slow_submit
    stats = pipe.run()
    return sink, stats


class TestStreamedPipelineEquivalence:

    @pytest.mark.parametrize("mesh_cfg,batch,n_frames", [
        (MeshConfig(data=1), 4, 30),           # single device, padded tail
        (MeshConfig(data=4), 8, 37),           # sharded, padded
        (MeshConfig(data=2, space=2), 4, 18),  # H-sharded output
    ])
    def test_bit_identical_ordered(self, mesh_cfg, batch, n_frames):
        runs = {}
        for egress in ("monolithic", "streamed"):
            sink, stats = _run_capture(get_filter("invert"), egress,
                                       mesh_cfg, batch, n_frames)
            assert stats["delivered"] == n_frames, (egress, stats)
            runs[egress] = sink
        mono, stream = runs["monolithic"], runs["streamed"]
        assert stream.order == sorted(stream.order)
        assert stream.order == mono.order
        for idx in mono.frames:
            np.testing.assert_array_equal(
                stream.frames[idx], mono.frames[idx],
                err_msg=f"frame {idx} diverged between egress paths")

    def test_slab_reuse_with_reorder_residency(self):
        """frame_delay holds delivered rows in the reorder buffer across
        slot cycles — rows must own their bytes (the collect-side copy),
        or slab reuse would corrupt the delayed frames."""
        runs = {}
        for egress in ("monolithic", "streamed"):
            sink, stats = _run_capture(
                get_filter("invert"), egress, MeshConfig(data=1),
                batch=2, n_frames=24, max_inflight=2, frame_delay=8,
                slow_submit_s=0.005)
            assert stats["delivered"] == 24
            runs[egress] = sink
        for idx in runs["monolithic"].frames:
            np.testing.assert_array_equal(
                runs["streamed"].frames[idx],
                runs["monolithic"].frames[idx])

    def test_streamed_is_default_and_reported(self):
        sink, stats = _run_capture(get_filter("invert"), "streamed",
                                   MeshConfig(data=1), 4, 12)
        eg = stats["egress"]
        assert eg["mode"] == "streamed"
        assert eg["batches"] >= 3
        assert eg["d2h_block_ms"] is not None
        assert eg["overlap_efficiency"] is None or \
            0.0 <= eg["overlap_efficiency"] <= 1.0
        assert PipelineConfig().egress == "streamed"

    def test_bad_egress_mode_rejected(self):
        with pytest.raises(ValueError, match="egress"):
            Pipeline(SyntheticSource(height=8, width=8, n_frames=2),
                     get_filter("invert"), NullSink(),
                     PipelineConfig(egress="bogus"))


def test_egress_trace_spans_emitted(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)  # run() exports the trace into the CWD
    filt = get_filter("invert")
    engine = Engine(filt, mesh=make_mesh(MeshConfig(data=1)))
    pipe = Pipeline(
        SyntheticSource(height=16, width=16, n_frames=8),
        filt, NullSink(),
        PipelineConfig(batch_size=4, queue_size=100, frame_delay=0,
                       trace=True),
        engine=engine,
    )
    pipe.run()
    names = [e["name"] for e in pipe.tracer._events]
    assert "egress_d2h" in names


# ---------------------------------------------------------------------------
# Serving frontend: streamed vs monolithic egress
# ---------------------------------------------------------------------------


def _serve_roundtrip(egress, n_frames=24, batch=4):
    from dvf_tpu.serve import ServeConfig, ServeFrontend

    filt = get_filter("invert")
    engine = Engine(filt, mesh=make_mesh(MeshConfig(data=2)))
    config = ServeConfig(batch_size=batch, max_inflight=2, queue_size=64,
                         egress=egress)
    frames = _rng_frames(n_frames, 16, 24, seed=3)
    got = []
    with ServeFrontend(filt, config, engine=engine) as fe:
        sid = fe.open_stream()
        for f in frames:
            fe.submit(sid, f)
        fe.close(sid, drain=True)
        deadline = time.time() + 20.0
        while time.time() < deadline:
            got.extend(fe.poll(sid))
            if len(got) == n_frames:
                break
            time.sleep(0.005)
        stats = fe.stats()
    assert len(got) == n_frames, (egress, len(got))
    return frames, got, stats


def test_serve_streamed_matches_monolithic():
    frames, got_s, stats_s = _serve_roundtrip("streamed")
    _, got_m, _ = _serve_roundtrip("monolithic")
    assert [d.index for d in got_s] == list(range(len(frames)))
    assert [d.index for d in got_m] == [d.index for d in got_s]
    for d_s, d_m, src in zip(got_s, got_m, frames):
        np.testing.assert_array_equal(d_s.frame, 255 - src)
        np.testing.assert_array_equal(d_s.frame, d_m.frame)
    assert stats_s["egress"]["mode"] == "streamed"
    assert stats_s["faults"]["by_kind"] == {}


def _serve_packed(n_frames=24, batch=4, chaos=None, resize_to=None,
                  trace=False, **kw):
    """One tenant through a one-device frontend (the one-chip replica's
    shape): the fetcher packs. Returns (frames, deliveries, stats, fe).
    With ``resize_to`` the session stays busy until the resize has
    committed and a few batches went through the successor, so
    ``frames`` may come back longer than ``n_frames``."""
    from dvf_tpu.serve import ServeConfig, ServeFrontend

    filt = get_filter("invert")
    engine = Engine(filt, mesh=make_mesh(MeshConfig(data=1)))
    config = ServeConfig(batch_size=batch, max_inflight=2, queue_size=64,
                         slo_ms=60_000.0, chaos=chaos, trace=trace, **kw)
    frames = _rng_frames(n_frames, 16, 24, seed=11)
    got = []
    fe = ServeFrontend(filt, config, engine=engine)
    with fe:
        sid = fe.open_stream()
        for i, f in enumerate(frames):
            fe.submit(sid, f)
            if resize_to is not None and i == n_frames // 3:
                label = next(iter(fe.stats()["buckets"]))
                assert fe.request_batch_size(label, resize_to)
            if resize_to is not None:
                time.sleep(0.002)
        if resize_to is not None:
            # The successor compiles aside on another thread, for longer
            # than the frames above take: keep batches in flight until
            # its commit has landed (so it swings under them), then push
            # a few batches through the successor.
            rng = np.random.default_rng(12)
            after_swap = 4 * resize_to
            deadline = time.time() + 120.0
            while after_swap and time.time() < deadline:
                after_swap -= fe.stats()["swaps"] >= 1
                frames.append(rng.integers(0, 255, (16, 24, 3), np.uint8))
                fe.submit(sid, frames[-1])
                got.extend(fe.poll(sid))
                time.sleep(0.002)
            n_frames = len(frames)
        fe.close(sid, drain=True)
        deadline = time.time() + 30.0
        while time.time() < deadline and len(got) < n_frames:
            got.extend(fe.poll(sid))
            time.sleep(0.005)
        stats = fe.stats()
    assert len(got) == n_frames, len(got)
    assert [d.index for d in got] == list(range(n_frames))
    return frames, got, stats, fe


def test_serve_packed_layout_delivers_and_reports():
    frames, got, stats, _ = _serve_packed(n_frames=26)
    for d, src in zip(got, frames):
        np.testing.assert_array_equal(d.frame, 255 - src)
        assert not d.frame.flags.writeable  # the buffer its row landed
        #   in, as the runtime handed it over: nobody copied it
    # ... and a buffer of its own: no delivery pins another's bytes
    assert not any(np.shares_memory(a.frame, b.frame)
                   for i, a in enumerate(got) for b in got[i + 1:])
    assert stats["faults"]["by_kind"] == {}
    (row,) = stats["buckets"].values()
    eg = row["egress"]
    assert eg["mode"] == "streamed" and eg["transfer_layout"] == "u32rows"
    assert eg["packed_batches"] == eg["batches"] >= 7
    assert eg["row_landed_batches"] == eg["batches"]
    # 26 frames in batches of 4: at least one batch is short, and its
    # padding never crossed
    assert eg["rows_landed_total"] == len(frames)
    assert eg["rows_skipped_total"] == 4 * eg["batches"] - len(frames) > 0
    assert eg["bytes_total"] == len(frames) * 16 * 24 * 3
    assert stats["rows_handed_total"] == len(frames)
    assert stats["rows_copied_total"] == 0
    assert eg["copy_ms_total"] == 0.0
    assert eg["pool_allocs"] == 0  # no slab pool on the packed path
    assert stats["egress"]["transfer_layout"] == "u32rows"
    assert row["stages"]["components"]["d2h"]["frames"] == len(frames)


def test_serve_packed_layout_under_corrupt_device_chaos():
    """The corrupt_device site writes into a copy: the landed view is
    read-only. Perturbed batches deliver (one element of row 0 off by
    the site's xor), every other frame is exact, nothing is lost."""
    from dvf_tpu.resilience import FaultPlan

    chaos = FaultPlan(seed=3).add("corrupt_device", every=2)
    frames, got, stats, _ = _serve_packed(chaos=chaos)
    touched = 0
    for d, src in zip(got, frames):
        want = 255 - src
        diff = np.argwhere(d.frame != want)
        if len(diff):
            touched += 1
            assert len(diff) == 1 and tuple(diff[0]) == (0, 0, 0)
            assert d.frame[0, 0, 0] == want[0, 0, 0] ^ 0x40
            assert d.frame.flags.writeable  # row 0's perturbed copy
        else:
            assert not d.frame.flags.writeable  # as it landed
    assert touched >= 2
    assert stats["rows_copied_total"] == 0  # the site copied row 0, the
    #   router nothing
    assert sum(n for k, n in stats["chaos"]["fired"].items()
               if k.startswith("corrupt_device")) == touched
    assert stats["errors"] == 0
    (row,) = stats["buckets"].values()
    assert row["egress"]["packed_batches"] == row["egress"]["batches"]


def test_serve_packed_layout_across_a_hot_swap():
    """A batch resize swaps the output signature under batches in
    flight: the old fetcher has no pool to release late, its packed
    batches unpack on their own, the successor packs at the new shape;
    delivery stays ordered and bit-exact."""
    frames, got, stats, fe = _serve_packed(n_frames=48, resize_to=2)
    assert len(got) == len(frames) >= 48
    for d, src in zip(got, frames):
        np.testing.assert_array_equal(d.frame, 255 - src)
    assert stats["swaps"] >= 1 and stats["swap_aborts"] == 0
    (row,) = stats["buckets"].values()
    assert row["batch_size"] == 2
    eg = row["egress"]  # the successor's stats
    assert eg["transfer_layout"] == "u32rows" and eg["pool_allocs"] == 0
    assert eg["packed_batches"] == eg["row_landed_batches"] \
        == eg["batches"] >= 1
    assert stats["rows_handed_total"] == len(frames)
    assert stats["rows_copied_total"] == 0
    assert all(b.lane.slab_bytes() == 0 for b in fe._buckets)
    assert all(f.slab_bytes() == 0 for f in egress_mod.live_fetchers())


def test_serve_packed_layout_under_the_audit_replay():
    """The shadow replay takes its own copy of a sampled row
    (``np.array(out[row], copy=True)``) from the landed rows and judges
    it clean; the delivery itself is still the landed buffer."""
    _, got, stats, _ = _serve_packed(audit=True, audit_sample_every=1)
    assert not any(d.frame.flags.writeable for d in got)
    st = stats["audit"]
    assert st["replays_sampled_total"] >= len(got) // 2
    assert st["replay_mismatches_total"] == 0
    assert st["replay_errors_total"] == 0
    assert st["confirmed_corruptions_total"] == 0
    assert stats["rows_copied_total"] == 0


def test_serve_packed_layout_under_d2h_chaos():
    """One firing a batch on the row-landed path: the faulted batch's
    frames are lost to the fault counters, every other frame arrives
    exact and in order, as the landed buffer."""
    from dvf_tpu.resilience import FaultPlan
    from dvf_tpu.serve import ServeConfig, ServeFrontend

    chaos = FaultPlan().add("d2h", at=(2,))
    filt = get_filter("invert")
    engine = Engine(filt, mesh=make_mesh(MeshConfig(data=1)))
    fe = ServeFrontend(filt, ServeConfig(
        batch_size=4, max_inflight=2, queue_size=64, slo_ms=60_000.0,
        chaos=chaos), engine=engine)
    frames = _rng_frames(24, 16, 24, seed=13)
    got = []
    with fe:
        sid = fe.open_stream()
        for f in frames:
            fe.submit(sid, f)
        fe.close(sid, drain=True)
        deadline = time.time() + 30.0
        while time.time() < deadline:
            got.extend(fe.poll(sid))
            st = fe.stats()["sessions"][sid]
            if st["delivered"] + st["failed"] == len(frames):
                got.extend(fe.poll(sid))
                break
            time.sleep(0.005)
        stats = fe.stats()
    lost = stats["sessions"][sid]["failed"]
    assert 1 <= lost <= 4 and len(got) == len(frames) - lost
    assert stats["faults"]["by_kind"] == {"d2h": 1}
    assert [d.index for d in got] == sorted(d.index for d in got)
    for d in got:
        np.testing.assert_array_equal(d.frame, 255 - frames[d.index])
        assert not d.frame.flags.writeable
    assert stats["rows_copied_total"] == 0


def test_serve_trace_spans_name_the_layout():
    _, _, _, fe = _serve_packed(n_frames=8, trace=True)
    spans = [e for e in fe.tracer._events if e["name"] == "collect:d2h"]
    assert spans and all(e["args"]["layout"] == "u32rows" for e in spans)
    d2h = [e for e in fe.tracer._events if e["name"] == "egress_d2h"]
    assert d2h and all(e["args"]["layout"] == "u32rows" for e in d2h)


def test_serve_bad_egress_rejected():
    from dvf_tpu.serve import ServeConfig, ServeFrontend

    with pytest.raises(ValueError, match="egress"):
        ServeFrontend(get_filter("invert"), ServeConfig(egress="bogus"))


# ---------------------------------------------------------------------------
# ZMQ worker: streamed egress + async codec plane (driven directly)
# ---------------------------------------------------------------------------


def _zmq_worker_process(egress, use_jpeg, batches=4, batch=4, size=16,
                        tracer=None):
    zmq = pytest.importorskip("zmq")
    del zmq
    from dvf_tpu.transport.codec import make_codec
    from dvf_tpu.transport.zmq_ingress import TpuZmqWorker

    filt = get_filter("invert")
    worker = TpuZmqWorker(
        filt, engine=Engine(filt, mesh=make_mesh(MeshConfig(data=1))),
        batch_size=batch, use_jpeg=use_jpeg, raw_size=size, egress=egress,
        egress_depth=2, tracer=tracer)
    sent = []

    class _StubPush:
        def send_multipart(self, parts):
            sent.append([bytes(p) for p in parts])  # zmq copies at send

        def close(self, *a):
            pass

    worker.push.close(0)
    worker.push = _StubPush()
    enc = make_codec(quality=90) if use_jpeg else None
    try:
        idx = 0
        frames = {}
        for b in range(batches):
            valid = batch if b % 2 == 0 else batch - 1  # padded too
            pending = []
            for _ in range(valid):
                f = _rng_frames(1, size, size, seed=idx)[0]
                frames[idx] = f
                payload = enc.encode(f) if use_jpeg else f.tobytes()
                pending.append((idx, payload))
                idx += 1
            worker._process_batch(pending, b"pid")
        worker.drain_egress(b"pid")
        stats = worker.stats()
        out = {}
        order = []
        for parts in sent:
            i = int(parts[0].decode())
            order.append(i)
            out[i] = parts[4]
        return frames, out, order, stats
    finally:
        if enc is not None:
            enc.close()
        worker.close()


def test_zmq_worker_raw_streamed_matches_monolithic():
    src_s, out_s, order_s, stats_s = _zmq_worker_process("streamed", False)
    src_m, out_m, order_m, _ = _zmq_worker_process("monolithic", False)
    assert order_s == sorted(src_s)  # ordered delivery through the plane
    assert order_s == order_m
    for i in out_s:
        got = np.frombuffer(out_s[i], np.uint8).reshape(16, 16, 3)
        np.testing.assert_array_equal(got, 255 - src_s[i])
        assert out_s[i] == out_m[i]
    assert stats_s["egress"]["mode"] == "streamed"
    assert stats_s["egress"]["batches"] == 4


def test_zmq_worker_jpeg_streamed_matches_monolithic():
    from dvf_tpu.obs.trace import Tracer

    tracer = Tracer(enabled=True)
    src_s, out_s, order_s, stats_s = _zmq_worker_process(
        "streamed", True, tracer=tracer)
    _, out_m, order_m, _ = _zmq_worker_process("monolithic", True)
    assert order_s == sorted(src_s)
    assert order_s == order_m
    for i in out_s:
        assert out_s[i] == out_m[i]  # same-codec encode is deterministic
    assert stats_s["egress"]["encode_batches"] == 4
    names = [e["name"] for e in tracer._events]
    assert "egress_encode" in names and "egress_send" in names


def test_zmq_worker_stalled_peer_cannot_wedge_encode_plane():
    """A consumer that rejects every send (the frozen-peer case) must
    not deadlock the plane or the worker: rows are dropped at-most-once,
    counted under transport, and the drain completes in bounded time."""
    zmq = pytest.importorskip("zmq")
    from dvf_tpu.transport.zmq_ingress import TpuZmqWorker

    filt = get_filter("invert")
    worker = TpuZmqWorker(
        filt, engine=Engine(filt, mesh=make_mesh(MeshConfig(data=1))),
        batch_size=2, use_jpeg=False, raw_size=16, egress="streamed",
        egress_depth=1, fault_budget=1000)

    class _DeadPush:
        def send_multipart(self, parts):
            raise zmq.Again("peer stalled")

        def close(self, *a):
            pass

    worker.push.close(0)
    worker.push = _DeadPush()
    try:
        t0 = time.time()
        idx = 0
        for b in range(6):
            pending = []
            for _ in range(2):
                f = _rng_frames(1, 16, 16, seed=idx)[0]
                pending.append((idx, f.tobytes()))
                idx += 1
            worker._process_batch(pending, b"pid")
        worker.drain_egress(b"pid")
        assert time.time() - t0 < 20.0
        # Every batch's send failed once (batch remainder dropped).
        assert worker.faults.count("transport") == 6
        assert worker.errors == 6
        assert worker.frames_processed == 12  # the engine kept serving
    finally:
        worker.close()


# ---------------------------------------------------------------------------
# Allocation regression: the steady-state delivery path must not allocate
# ---------------------------------------------------------------------------

_BIG = 300_000  # bytes; slabs/staging sit above, frames below


class _EmptyCounter:
    def __init__(self):
        self.real = np.empty
        self.big = []

    def __call__(self, shape, dtype=float, **kw):
        arr = self.real(shape, dtype, **kw)
        if arr.nbytes >= _BIG:
            self.big.append(arr.nbytes)
        return arr


def _count_delivery_allocs(monkeypatch, n_frames, data=2):
    counter = _EmptyCounter()
    monkeypatch.setattr(np, "empty", counter)
    try:
        filt = get_filter("invert")
        engine = Engine(filt, mesh=make_mesh(MeshConfig(data=data)))
        pipe = Pipeline(
            SyntheticSource(height=256, width=256, n_frames=n_frames),
            filt, NullSink(),
            # ingest pinned monolithic: at this size the ingest side's
            # cheap-transfer calibration sits right at its 2 ms threshold
            # and flips mode (and slab-pool size) run to run — this test
            # isolates the DELIVERY path's allocations.
            PipelineConfig(batch_size=8, queue_size=1000, frame_delay=0,
                           ingest="monolithic", egress="streamed"),
            engine=engine,
        )
        stats = pipe.run()
    finally:
        monkeypatch.setattr(np, "empty", counter.real)
    assert stats["delivered"] == n_frames
    assert stats["egress"]["mode"] == "streamed"
    # One slab pool, reused, where results come back in shards; none at
    # all on the packed layout (one device), whose fetch hands out the
    # buffer the transfer landed in.
    assert stats["egress"]["pool_allocs"] == (0 if data == 1 else 1)
    assert stats["egress"]["packed_batches"] == (
        stats["egress"]["batches"] if data == 1 else 0)
    return len(counter.big)


@pytest.mark.parametrize("data", [2, 1])
def test_delivery_path_steady_state_allocates_nothing(monkeypatch, data):
    """Tripling the stream length must not change the number of big host
    allocations numpy is asked for: the egress slab pool is built once
    and reused (sharded results), or never built (the packed layout:
    the only per-batch buffer is the one the runtime lands the transfer
    in), so the delivery hot loop allocates nothing of its own per
    batch. An uncounted warmup run first: the process's first compile at
    this signature performs one-time big host allocations that would
    skew whichever counted run went first."""
    _count_delivery_allocs(monkeypatch, n_frames=16, data=data)
    short = _count_delivery_allocs(monkeypatch, n_frames=24, data=data)
    long = _count_delivery_allocs(monkeypatch, n_frames=72, data=data)
    assert long == short, (short, long)


# ---------------------------------------------------------------------------
# Chaos interplay
# ---------------------------------------------------------------------------


class TestEgressChaos:

    def test_d2h_fault_classified_and_contained(self):
        from dvf_tpu.resilience import FaultPlan

        chaos = FaultPlan().add("d2h", at=(1,))
        filt = get_filter("invert")
        pipe = Pipeline(
            SyntheticSource(height=16, width=16, n_frames=32),
            filt, NullSink(),
            PipelineConfig(batch_size=4, frame_delay=0, queue_size=64,
                           resilient=True, chaos=chaos),
            engine=Engine(filt, mesh=make_mesh(MeshConfig(data=1))))
        stats = pipe.run()
        # Exactly one batch lost to the injected fetch fault; classified
        # under the d2h kind, stream healthy otherwise.
        assert stats["faults"]["by_kind"] == {"d2h": 1}
        assert stats["errors"] == 1
        assert 32 - 4 <= stats["delivered"] < 32
        assert stats["chaos"]["fired"] == {"d2h:d2h": 1}

    def test_d2h_budget_degrades_streamed_to_monolithic(self):
        from dvf_tpu.resilience import FaultPlan

        chaos = FaultPlan().add("d2h", every=1, count=64)
        filt = get_filter("invert")
        pipe = Pipeline(
            # 12 batches: more than the in-flight window holds beyond
            # the third fault, so at least one is dispatched after the
            # degrade and rebuilds the fetcher (at 6 it was a race the
            # first batch's compiles decided).
            SyntheticSource(height=16, width=16, n_frames=96),
            filt, NullSink(),
            PipelineConfig(batch_size=8, frame_delay=0, queue_size=64,
                           resilient=True, chaos=chaos, fault_budget=2),
            engine=Engine(filt, mesh=make_mesh(MeshConfig(data=1))))
        stats = pipe.run()
        # Budget (2) overflowed at the 3rd d2h fault → streamed degraded
        # to monolithic (reason recorded), stream finished healthy.
        assert stats["faults"]["by_kind"] == {"d2h": 3}
        assert stats["egress"]["mode"] == "monolithic"
        assert stats["egress"]["fallback_reason"] == "d2h_fault_budget"
        assert stats["delivered"] > 0

    def test_watchdog_recovery_drains_with_streamed_egress(self):
        """The PR 4 supervision story survives streamed egress in the
        collect path: a frozen collect thread trips the watchdog, the
        engine (and fetcher — re-calibrated) are rebuilt, and the stream
        keeps delivering."""
        from dvf_tpu.resilience import FaultPlan

        chaos = FaultPlan().add("freeze", at=(2,), delay_s=1.2)
        filt = get_filter("invert")
        pipe = Pipeline(
            SyntheticSource(height=16, width=16, n_frames=200, rate=100.0),
            filt, NullSink(),
            PipelineConfig(batch_size=4, frame_delay=0, queue_size=1000,
                           resilient=True, chaos=chaos, egress="streamed",
                           stall_timeout_s=0.3, collect_mode="thread"),
            engine=Engine(filt, mesh=make_mesh(MeshConfig(data=1))))
        stats = pipe.run()
        assert stats["recoveries"] >= 1
        assert stats["faults"]["by_kind"].get("stall", 0) >= 1
        assert stats["delivered"] > 0
        assert stats["egress"]["mode"] == "streamed"


# ---------------------------------------------------------------------------
# The device lane (runtime/lane.py), egress side: who decides when a
# fetcher is built, parked, degraded and freed
# ---------------------------------------------------------------------------


def _lane(cfg=MeshConfig(data=1), inflight=2, **options):
    import types

    from dvf_tpu.runtime.lane import DeviceLane

    opts = types.SimpleNamespace(**{"ingest": "streamed", "ingest_depth": 4,
                                    "egress": "streamed", **options})
    eng = Engine(get_filter("invert"), mesh=make_mesh(cfg))
    return DeviceLane(eng, opts, inflight), eng, opts


def _through(lane, frames, seq):
    """One batch through the lane: (the frames, its in-flight handle)."""
    builder = lane.begin(frames.shape, frames.dtype, seq)
    for row, f in enumerate(frames):
        builder.write_row(row, f)
    return lane.prefetch(lane.submit(builder, len(frames)))


class TestDeviceLaneEgress:

    def test_fetcher_is_rebuilt_on_a_new_output_signature_or_mode(self):
        lane, eng, opts = _lane()
        a = np.stack(_rng_frames(4, 16, 24, seed=1))
        np.testing.assert_array_equal(_through(lane, a, 0).fetch(0), 255 - a)
        f1, s1 = lane._fetcher, lane.egress_stats
        assert f1.out_shape == (4, 16, 24, 3) and f1.slots == 3
        assert s1.d2h_block_ms == eng.d2h_block_ms is not None
        _through(lane, a, 1).fetch(1)
        assert lane._fetcher is f1 and lane.egress_stats is s1  # kept
        b = np.stack(_rng_frames(2, 16, 24, seed=2))  # a new signature
        np.testing.assert_array_equal(_through(lane, b, 2).fetch(2), 255 - b)
        f2 = lane._fetcher
        assert f2 is not f1 and f2.out_shape == (2, 16, 24, 3)
        assert lane.egress_stats is not s1 and lane.egress_stats.batches == 1
        opts.egress = "monolithic"  # a planned mode reaches the lane
        np.testing.assert_array_equal(_through(lane, b, 3).fetch(3), 255 - b)
        assert lane._fetcher is not f2
        assert lane.egress_stats.summary()["mode"] == "monolithic"
        assert lane.egress_stats.requested_mode == "monolithic"

    def test_handle_pins_its_fetcher_across_retarget(self):
        """A hot swap retargets the lane with batches in flight: each
        comes back through the fetcher its transfer was issued on, and
        that fetcher's slabs go with the last of them, not before."""
        lane, eng, _ = _lane(MeshConfig(data=2))  # two shards: slabs
        x = [np.stack(_rng_frames(4, 16, 24, seed=s)) for s in range(3)]
        h0, h1 = _through(lane, x[0], 0), _through(lane, x[1], 1)
        old = lane._fetcher
        assert old.slab_bytes() > 0 and h0.layout == "plain"
        lane.retarget(eng)
        assert lane._fetcher is None and lane._assembler is None
        assert old.slab_bytes() > 0  # parked: two batches pin it
        assert lane.slab_bytes() == old.slab_bytes()
        h2 = _through(lane, x[2], 2)
        new = lane._fetcher
        assert new is not old and new.slab_bytes() > 0
        out0 = h0.fetch(0)
        assert h0.owns(out0) and old.owns(out0)
        np.testing.assert_array_equal(out0, 255 - x[0])
        assert old.slab_bytes() > 0  # one batch still pins it
        np.testing.assert_array_equal(h1.fetch(1), 255 - x[1])
        assert old.slab_bytes() == 0  # freed with its last batch
        np.testing.assert_array_equal(out0, 255 - x[0])  # rows stay valid
        np.testing.assert_array_equal(h2.fetch(2), 255 - x[2])
        assert new.slab_bytes() > 0  # the live fetcher keeps its pool
        assert lane.slab_bytes() == (new.slab_bytes()
                                     + lane._assembler.slab_bytes())

    def test_an_idle_fetcher_is_freed_at_retarget(self):
        lane, eng, _ = _lane(MeshConfig(data=2))
        x = np.stack(_rng_frames(4, 16, 24))
        _through(lane, x, 0).fetch(0)
        old = lane._fetcher
        lane.retarget(eng)
        assert old.slab_bytes() == 0 and lane.slab_bytes() == 0

    def test_shed_batches_do_not_pin_a_parked_fetcher(self):
        """A recovery drops in-flight handles unfetched: the parked
        fetcher must die with them, not live on in the lane."""
        import gc
        import weakref

        lane, eng, _ = _lane(MeshConfig(data=2))
        shed = _through(lane, np.stack(_rng_frames(4, 16, 24)), 0)
        ref = weakref.ref(lane._fetcher)
        lane.retarget(eng)
        assert ref() is not None and ref().slab_bytes() > 0
        del shed
        gc.collect()
        assert ref() is None and lane.slab_bytes() == 0

    def test_degrade_d2h_applies_once_and_is_recorded(self, capsys):
        lane, eng, _ = _lane(MeshConfig(data=2))
        x = np.stack(_rng_frames(4, 16, 24, seed=4))
        inflight = _through(lane, x, 0)
        streamed = lane._fetcher
        assert lane.egress_stats.summary()["mode"] == "streamed"
        assert lane.degrade("compute") is False  # not a transfer fault
        assert lane.degrade("d2h") is True
        assert "repeated d2h faults" in capsys.readouterr().err
        assert streamed.slab_bytes() == 0  # freed at once
        assert lane.degrade("d2h") is False  # once
        np.testing.assert_array_equal(inflight.fetch(0), 255 - x)
        np.testing.assert_array_equal(_through(lane, x, 1).fetch(1), 255 - x)
        s = lane.egress_stats.summary()
        assert s["mode"] == "monolithic"
        assert s["requested_mode"] == "streamed"
        assert s["fallback_reason"] == "d2h_fault_budget"
        assert lane.degrade("h2d") is True  # the other side's, untouched

    def test_degrade_needs_a_streamed_request(self):
        lane, _, _ = _lane(egress="monolithic")
        assert lane.degrade("d2h") is False

    def test_release_frees_both_sides_and_the_lane_rebuilds(self):
        import gc

        from dvf_tpu.runtime import ingest as ingest_mod

        gc.collect()
        base = (egress_mod.occupied_slab_bytes(),
                ingest_mod.occupied_slab_bytes())
        lane, eng, _ = _lane(MeshConfig(data=2))
        x = np.stack(_rng_frames(4, 16, 24, seed=6))
        parked = _through(lane, x, 0)
        lane.retarget(eng)  # one parked fetcher, one live
        live = _through(lane, x, 1)
        assert egress_mod.occupied_slab_bytes() > base[0]
        assert ingest_mod.occupied_slab_bytes() > base[1]
        lane.release()
        assert lane.slab_bytes() == 0
        assert egress_mod.occupied_slab_bytes() <= base[0]
        assert ingest_mod.occupied_slab_bytes() <= base[1]
        # Batches in flight fall back to a per-batch fetch.
        np.testing.assert_array_equal(parked.fetch(0), 255 - x)
        np.testing.assert_array_equal(live.fetch(1), 255 - x)
        np.testing.assert_array_equal(_through(lane, x, 2).fetch(2), 255 - x)
        assert lane.slab_bytes() > 0
        lane.release()

    def test_bad_modes_are_rejected_when_the_lane_is_built(self):
        with pytest.raises(ValueError, match="egress"):
            _lane(egress="bogus")
        with pytest.raises(ValueError, match="ingest"):
            _lane(ingest="bogus")


@pytest.mark.parametrize("host", ["serve", "pipeline", "worker"])
def test_lane_contract_through_its_hosts(host, monkeypatch):
    """Whoever hosts it, the lane is all that stands between frames and
    the engine: the host builds no assembler or fetcher of its own, its
    ``ingest`` / ``egress`` stats blocks are the lane's, a degradation
    applied to the lane is what the host then runs and reports, and the
    frames come back bit-exact throughout."""
    from dvf_tpu.runtime import ingest as ingest_mod
    from dvf_tpu.runtime import lane as lane_mod

    monkeypatch.setattr(ingest_mod, "MIN_STREAM_H2D_MS", 0.0)
    built = []
    for name in ("ShardedBatchAssembler", "ShardedBatchFetcher"):
        real = getattr(lane_mod, name)
        monkeypatch.setattr(
            lane_mod, name,
            lambda *a, _real=real, **kw: built.append(_real(*a, **kw))
            or built[-1])
    filt = get_filter("invert")
    engine = Engine(filt, mesh=make_mesh(MeshConfig(data=1)))
    frames = _rng_frames(16, 16, 16, seed=8)
    got = {}
    if host == "serve":
        from dvf_tpu.serve import ServeConfig, ServeFrontend

        fe = ServeFrontend(filt, ServeConfig(batch_size=4, max_inflight=2,
                                             queue_size=64, slo_ms=60_000.0),
                           engine=engine)
        lane = fe._buckets[0].lane
        assert lane.degrade("d2h")
        with fe:
            sid = fe.open_stream()
            for f in frames:
                fe.submit(sid, f)
            fe.close(sid, drain=True)
            deadline = time.time() + 30.0
            while time.time() < deadline and len(got) < len(frames):
                got.update((d.index, d.frame) for d in fe.poll(sid))
                time.sleep(0.005)
            stats = fe.stats()
        assert lane.slab_bytes() == 0  # stop() released it
    elif host == "pipeline":
        class _Sink(NullSink):
            def emit(self, idx, frame, ts):
                got[idx] = np.array(frame)

        pipe = Pipeline(
            ((f, time.time()) for f in frames), filt, _Sink(),
            PipelineConfig(batch_size=4, max_inflight=2, queue_size=64,
                           frame_delay=0), engine=engine)
        lane = pipe._lane
        assert lane.degrade("d2h")
        stats = pipe.run()
    else:
        pytest.importorskip("zmq")
        from dvf_tpu.transport.zmq_ingress import TpuZmqWorker

        worker = TpuZmqWorker(filt, engine=engine, batch_size=4,
                              use_jpeg=False, raw_size=16, egress_depth=2)

        class _StubPush:
            def send_multipart(self, parts):
                got[int(parts[0].decode())] = np.frombuffer(
                    bytes(parts[4]), np.uint8).reshape(16, 16, 3)

            def close(self, *a):
                pass

        worker.push.close(0)
        worker.push = _StubPush()
        lane = worker._lane
        assert lane.degrade("d2h")
        try:
            for b in range(0, len(frames), 4):
                worker._process_batch(
                    [(b + i, frames[b + i].tobytes()) for i in range(4)],
                    b"pid")
            worker.drain_egress(b"pid")
            stats = worker.stats()
        finally:
            worker.close()
    assert sorted(got) == list(range(len(frames)))
    for i, f in enumerate(frames):
        np.testing.assert_array_equal(got[i], 255 - f)
    # Built by the lane and nobody else: one assembler, one fetcher.
    assert [type(o).__name__ for o in built] == [
        "ShardedBatchAssembler", "ShardedBatchFetcher"]
    assert stats["ingest"] == lane.ingest_stats.summary()
    assert stats["egress"] == lane.egress_stats.summary()
    assert stats["ingest"]["mode"] == "streamed"
    assert stats["ingest"]["batches"] == len(frames) // 4
    assert stats["egress"]["mode"] == "monolithic"
    assert stats["egress"]["requested_mode"] == "streamed"
    assert stats["egress"]["fallback_reason"] == "d2h_fault_budget"
    assert stats["egress"]["batches"] == len(frames) // 4
    lane.release()
    assert lane.slab_bytes() == 0


@pytest.mark.parametrize("host", ["pipeline", "worker"])
def test_rows_land_through_the_other_hosts(host):
    """``Pipeline`` and ``TpuZmqWorker`` pass their batch's ``valid`` to
    the lane as the serve frontend does: on one device every batch lands
    a buffer a row, a short batch's padding stays on the chip, and the
    frames come back bit-exact (the hosts index ``out[row]`` as ever)."""
    filt = get_filter("invert")
    if host == "pipeline":
        sink, stats = _run_capture(filt, "streamed", MeshConfig(data=1),
                                   batch=4, n_frames=30)
        assert stats["delivered"] == 30
        src = iter(SyntheticSource(height=24, width=32, n_frames=30))
        for idx, (frame, _) in zip(range(30), src):
            np.testing.assert_array_equal(sink.frames[idx], 255 - frame)
        eg, frame_bytes, landed = stats["egress"], 24 * 32 * 3, 30
    else:
        pytest.importorskip("zmq")
        from dvf_tpu.transport.zmq_ingress import TpuZmqWorker

        frames = _rng_frames(7, 16, 16, seed=4)
        got = {}
        worker = TpuZmqWorker(
            filt, engine=Engine(filt, mesh=make_mesh(MeshConfig(data=1))),
            batch_size=4, use_jpeg=False, raw_size=16, egress_depth=2)

        class _StubPush:
            def send_multipart(self, parts):
                got[int(parts[0].decode())] = np.frombuffer(
                    bytes(parts[4]), np.uint8).reshape(16, 16, 3)

            def close(self, *a):
                pass

        worker.push.close(0)
        worker.push = _StubPush()
        try:
            for b in (0, 4):  # a full batch, then three frames of four
                worker._process_batch(
                    [(i, frames[i].tobytes())
                     for i in range(b, min(b + 4, len(frames)))], b"pid")
            worker.drain_egress(b"pid")
            stats = worker.stats()
        finally:
            worker.close()
        assert sorted(got) == list(range(7))
        for i, f in enumerate(frames):
            np.testing.assert_array_equal(got[i], 255 - f)
        eg, frame_bytes, landed = stats["egress"], 16 * 16 * 3, 7
    assert eg["transfer_layout"] == "u32rows"
    assert eg["row_landed_batches"] == eg["packed_batches"] == eg["batches"]
    assert eg["rows_landed_total"] == landed
    assert eg["rows_skipped_total"] == 4 * eg["batches"] - landed > 0
    assert eg["bytes_total"] == landed * frame_bytes
    assert eg["copy_ms_total"] == 0.0 and eg["pool_allocs"] == 0
