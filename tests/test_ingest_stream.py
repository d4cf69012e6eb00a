"""Streamed shard-level ingest (runtime/ingest.py).

Three properties guard the tentpole:

1. **Equivalence** — the streamed path (per-shard slabs, per-shard
   device_put, make_array_from_single_device_arrays, submit_resident)
   produces BIT-IDENTICAL, identically-ordered results vs the monolithic
   path, across shardings, short/padded batches, and slot aliasing under
   a full in-flight window.
2. **Overlap plumbing** — the depth knob, the per-shard trace spans, and
   the overlap_efficiency metric exist and are sane.
3. **Allocation regression** — the steady-state hot loop performs ZERO
   per-batch multi-100KB host allocations (the staging pools are actually
   reused) across the pipeline, serve, and zmq paths.
4. **The row path** (PR 47) — ``put_rows`` + ``ingest_join`` make the
   slab path's batch byte for byte from the clients' own arrays;
   ``put_rows`` takes a batch only where it may, writes to no frame, and
   everything else stays on the slabs, counted under
   ``rows_staged_total``.
"""

import time

import numpy as np
import pytest

from dvf_tpu.io import NullSink, SyntheticSource
from dvf_tpu.obs.metrics import IngestStats
from dvf_tpu.ops import get_filter
from dvf_tpu.parallel import MeshConfig, make_mesh
from dvf_tpu.parallel.mesh import batch_sharding
from dvf_tpu.runtime import Engine, Pipeline, PipelineConfig
from dvf_tpu.runtime import ingest as ingest_mod
from dvf_tpu.runtime.ingest import ShardedBatchAssembler


@pytest.fixture(autouse=True)
def _force_streaming(monkeypatch):
    """This suite exercises the streaming machinery at test-sized frames,
    where the calibrated blocking put is far below MIN_STREAM_H2D_MS and
    the assembler would (correctly) degrade to monolithic — disable the
    cheap-transfer fallback so the streamed path actually runs."""
    monkeypatch.setattr(ingest_mod, "MIN_STREAM_H2D_MS", 0.0)


def _rng_frames(n, h, w, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 255, size=(h, w, 3), dtype=np.uint8)
            for _ in range(n)]


def _padded_ref(frames, batch_size):
    """What any correct assembler must produce: valid rows then
    repeat-last padding."""
    out = np.empty((batch_size, *frames[0].shape), frames[0].dtype)
    for i, f in enumerate(frames):
        out[i] = f
    for i in range(len(frames), batch_size):
        out[i] = frames[-1]
    return out


class TestAssemblerEquivalence:
    """Unit level: the assembler's device array equals the padded host
    reference for every supported shard layout."""

    @pytest.mark.parametrize("cfg,batch,depth", [
        (MeshConfig(data=1), 4, 1),    # single device, no sub-chunking
        (MeshConfig(data=1), 8, 4),    # single device, chunk streaming
        (MeshConfig(data=4), 8, 2),    # batch-sharded
        (MeshConfig(data=2, space=2), 4, 2),   # batch + H sharded
        (MeshConfig(data=8), 8, 3),    # one row per device
    ])
    def test_write_row_matches_reference(self, cfg, batch, depth):
        h, w = 16, 24
        shape = (batch, h, w, 3)
        sharding = batch_sharding(make_mesh(cfg), shape)
        asm = ShardedBatchAssembler(shape, np.uint8, sharding,
                                    depth=depth, slots=3)
        assert asm.effective_mode == "streamed"
        # Several batches across aliasing pool slots, including short
        # (padded) ones.
        for slot, valid in enumerate([batch, max(1, batch - 1), 1, batch]):
            frames = _rng_frames(valid, h, w, seed=slot)
            b = asm.begin(slot)
            for row, f in enumerate(frames):
                b.write_row(row, f)
            arr, resident = b.finish(valid)
            assert resident
            np.testing.assert_array_equal(
                np.asarray(arr), _padded_ref(frames, batch))

    @pytest.mark.parametrize("cfg", [
        MeshConfig(data=1), MeshConfig(data=4), MeshConfig(data=2, space=2),
    ])
    def test_window_decode_path_matches_reference(self, cfg):
        """The bulk-decode API (windows/window_view/commit_window — the
        ring and JPEG route) is equivalent to per-row writes."""
        batch, h, w = 8, 16, 24
        shape = (batch, h, w, 3)
        sharding = batch_sharding(make_mesh(cfg), shape)
        asm = ShardedBatchAssembler(shape, np.uint8, sharding,
                                    depth=3, slots=2)
        for slot, valid in enumerate([batch, 5, 2]):
            frames = _rng_frames(valid, h, w, seed=10 + slot)
            b = asm.begin(slot)
            windows = b.windows(valid)
            assert windows[0][0] == 0 and windows[-1][1] == valid
            assert all(s2 == e1 for (_, e1), (s2, _)
                       in zip(windows, windows[1:]))  # contiguous
            for start, stop in windows:
                view = b.window_view(start, stop)
                assert view.shape == (stop - start, h, w, 3)
                for i in range(stop - start):
                    np.copyto(view[i], frames[start + i])
                b.commit_window(start, stop)
            arr, resident = b.finish(valid)
            assert resident
            np.testing.assert_array_equal(
                np.asarray(arr), _padded_ref(frames, batch))

    def test_replicated_layout_falls_back_to_monolithic(self):
        """A batch the mesh cannot partition (4 frames over 8 data ways)
        replicates — per-device host puts would multiply the transfer, so
        the assembler must degrade to the whole-batch path and say so."""
        shape = (4, 16, 16, 3)
        sharding = batch_sharding(make_mesh(MeshConfig(data=8)), shape)
        asm = ShardedBatchAssembler(shape, np.uint8, sharding, slots=2)
        assert asm.effective_mode == "monolithic"
        assert asm.stats.fallback_reason == "replicated_layout"
        frames = _rng_frames(3, 16, 16)
        b = asm.begin(0)
        for row, f in enumerate(frames):
            b.write_row(row, f)
        arr, resident = b.finish(3)
        assert not resident  # host buffer for the classic engine.submit
        np.testing.assert_array_equal(arr, _padded_ref(frames, 4))

    def test_monolithic_mode_reuses_slot_buffers(self):
        shape = (4, 8, 8, 3)
        asm = ShardedBatchAssembler(shape, np.uint8, None,
                                    mode="monolithic", slots=2)
        builder = asm.begin(0)
        builder.write_row(0, np.zeros((8, 8, 3), np.uint8))
        a0, _ = builder.finish(1)
        builder = asm.begin(2)  # slot 2 % 2 == slot 0: same buffer
        builder.write_row(0, np.ones((8, 8, 3), np.uint8))
        a1, _ = builder.finish(1)
        assert a0 is a1

    def test_cheap_transfer_falls_back_to_monolithic(self, monkeypatch):
        """When the calibrated blocking put costs less than the fixed
        per-batch streaming overhead, streaming cannot win — the
        assembler must stay monolithic and record why (measured on the
        CPU backend: 5× throughput regression at 128×128 without this)."""
        monkeypatch.setattr(ingest_mod, "MIN_STREAM_H2D_MS", 2.0)
        shape = (8, 16, 16, 3)
        sharding = batch_sharding(make_mesh(MeshConfig(data=1)), shape)
        stats = IngestStats(h2d_block_ms=0.1)   # sub-threshold calibration
        asm = ShardedBatchAssembler(shape, np.uint8, sharding, stats=stats)
        assert asm.effective_mode == "monolithic"
        assert stats.fallback_reason == "cheap_transfer"
        # An expensive transfer streams.
        stats2 = IngestStats(h2d_block_ms=50.0)
        asm2 = ShardedBatchAssembler(shape, np.uint8, sharding, stats=stats2)
        assert asm2.effective_mode == "streamed"
        assert stats2.fallback_reason is None

    def test_bad_args_rejected(self):
        shape = (4, 8, 8, 3)
        with pytest.raises(ValueError, match="ingest mode"):
            ShardedBatchAssembler(shape, np.uint8, None, mode="bogus")
        with pytest.raises(ValueError, match="depth"):
            ShardedBatchAssembler(shape, np.uint8, None, depth=0)
        asm = ShardedBatchAssembler(shape, np.uint8, None,
                                    mode="monolithic")
        with pytest.raises(ValueError, match="valid"):
            asm.begin(0).finish(0)


def _frames_c(n, h, w, c, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, size=(h, w, c), dtype=np.uint8)
            for _ in range(n)]


class TestRowPath:
    """``put_rows`` / ``ingest_join``: the batch from the clients' own
    arrays, byte for byte what ``np.stack`` of the frames is."""

    @pytest.mark.parametrize("channels", [3, 4])
    @pytest.mark.parametrize("width", [1920, 1280, 960, 48])
    def test_matches_np_stack_byte_for_byte(self, width, channels):
        h, b = 8, 3
        shape = (b, h, width, channels)
        frames = _frames_c(b, h, width, channels, seed=width + channels)
        # every byte value, and the extremes side by side
        frames[0].reshape(-1)[:256] = np.arange(256, dtype=np.uint8)
        frames[1][0, :2] = [[0] * channels, [255] * channels]
        asm = ShardedBatchAssembler(
            shape, np.uint8,
            batch_sharding(make_mesh(MeshConfig(data=1)), shape), slots=1)
        builder = asm.begin(0)
        assert builder.put_rows(frames)
        out, resident = builder.finish(b)
        assert resident and out.shape == shape and out.dtype == np.uint8
        np.testing.assert_array_equal(np.asarray(out), np.stack(frames))

    @pytest.mark.parametrize("valid", [4, 3, 1])
    def test_put_rows_makes_the_slab_paths_batch(self, valid):
        """Full and short batches: a padding row is the last valid row
        again, and the result equals ``write_row``'s bit for bit."""
        shape = (4, 16, 48, 3)
        sharding = batch_sharding(make_mesh(MeshConfig(data=1)), shape)
        asm = ShardedBatchAssembler(shape, np.uint8, sharding, slots=2)
        assert asm.effective_mode == "streamed" and asm.stats.row_path
        frames = _rng_frames(valid, 16, 48, seed=valid)
        keep = [f.copy() for f in frames]
        for f in frames:
            f.flags.writeable = False       # nothing may write to them
        direct = asm.begin(0)
        assert direct.put_rows(frames) is True
        got, resident = direct.finish(valid)
        assert resident and direct.direct
        slab = asm.begin(1)
        for row, f in enumerate(frames):
            slab.write_row(row, f)
        want, _ = slab.finish(valid)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(got),
                                      _padded_ref(keep, 4))
        assert got.sharding.is_equivalent_to(sharding, 4)
        for f, k in zip(frames, keep):
            np.testing.assert_array_equal(f, k)
        s = asm.stats.summary()
        assert (s["rows_direct_total"], s["direct_batches"]) == (valid, 1)
        assert (s["rows_staged_total"], s["staged_batches"]) == (valid, 1)
        # what crossed the link: the valid rows, and a whole padded slab
        assert s["bytes_total"] == (valid + 4) * 16 * 48 * 3
        assert direct._rows is None         # the device rows went at finish

    @pytest.mark.parametrize("case", [
        "strided_frame", "readonly_ok", "float_frame", "wrong_geometry",
        "two_devices", "float_batch", "monolithic", "too_many", "empty",
    ])
    def test_eligibility_is_read_off_the_input_and_the_lane(self, case):
        """Everything the row path cannot take goes through the slabs
        as before, and is counted there."""
        dtype = np.float32 if case == "float_batch" else np.uint8
        cfg = MeshConfig(data=2) if case == "two_devices" \
            else MeshConfig(data=1)
        shape = (4, 16, 48, 3)
        asm = ShardedBatchAssembler(
            shape, dtype, batch_sharding(make_mesh(cfg), shape), slots=2,
            mode="monolithic" if case == "monolithic" else "streamed")
        frames = _rng_frames(4, 16, 48, seed=5)
        if case == "strided_frame":     # the door's downscale view
            big = _rng_frames(1, 32, 96, seed=6)[0]
            frames[2] = big[::2, ::2]
            assert not frames[2].flags.c_contiguous
        elif case == "readonly_ok":
            frames[1].flags.writeable = False
        elif case == "float_frame":
            frames[0] = frames[0].astype(np.float32)
        elif case == "wrong_geometry":
            frames[3] = _rng_frames(1, 16, 24, seed=7)[0]
        elif case == "float_batch":
            frames = [f.astype(np.float32) for f in frames]
        elif case == "too_many":
            frames = frames + frames[:1]
        elif case == "empty":
            frames = []
        built = case not in ("two_devices", "float_batch", "monolithic")
        assert asm.stats.summary()["row_path"] is built
        b = asm.begin(0)
        took = b.put_rows(frames)
        assert took is (case == "readonly_ok")
        if case in ("float_frame", "wrong_geometry", "too_many", "empty"):
            return                      # no batch of this assembler's
        if not took:
            for row, f in enumerate(frames):
                b.write_row(row, f)
        arr, _ = b.finish(4)
        np.testing.assert_array_equal(
            np.asarray(arr), np.stack([np.asarray(f) for f in frames]))
        s = asm.stats.summary()
        assert s["rows_direct_total"] == (4 if took else 0)
        assert s["rows_staged_total"] == (0 if took else 4)
        assert s["batches"] == 1

    def test_h2d_chaos_site_fires_once_a_row_batch(self):
        from dvf_tpu.resilience import FaultPlan
        from dvf_tpu.resilience.faults import FaultKind, classify

        shape = (4, 16, 48, 3)
        chaos = FaultPlan().add("h2d", at=(1,))
        asm = ShardedBatchAssembler(
            shape, np.uint8,
            batch_sharding(make_mesh(MeshConfig(data=1)), shape),
            slots=2, chaos=chaos)
        frames = _rng_frames(4, 16, 48)
        assert asm.begin(0).put_rows(frames)            # firing 0
        with pytest.raises(Exception) as e:             # firing 1
            asm.begin(1).put_rows(frames)
        assert classify(e.value, "dispatch") == FaultKind.H2D
        assert chaos.summary()["fired"] == {"h2d:h2d": 1}

    def test_compiled_join_is_cached_a_signature(self):
        shape = (2, 8, 48, 3)
        sharding = batch_sharding(make_mesh(MeshConfig(data=1)), shape)
        a = ShardedBatchAssembler(shape, np.uint8, sharding, slots=1)
        b = ShardedBatchAssembler(shape, np.uint8, sharding, slots=1)
        assert a._join is not None and a._join is b._join
        builder = a.begin(0)
        assert builder.put_rows(_rng_frames(2, 8, 48))
        a.release()         # takes nothing from a batch under way
        assert a._join is None and b._join is not None
        assert not a.begin(0).put_rows(_rng_frames(2, 8, 48))
        assert np.asarray(builder.finish(2)[0]).shape == shape


def test_assembler_equivalence_property():
    """Property sweep: random (mesh, batch, valid, depth, slot) draws all
    reduce to the padded reference bit-for-bit."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    del hypothesis

    cfgs = [MeshConfig(data=1), MeshConfig(data=2), MeshConfig(data=4),
            MeshConfig(data=2, space=2)]

    @settings(max_examples=25, deadline=None)
    @given(
        cfg_i=st.integers(0, len(cfgs) - 1),
        batch=st.sampled_from([4, 8]),
        valid_frac=st.floats(0.1, 1.0),
        depth=st.integers(1, 6),
        slot=st.integers(0, 7),
        seed=st.integers(0, 1000),
    )
    def check(cfg_i, batch, valid_frac, depth, slot, seed):
        valid = max(1, int(round(valid_frac * batch)))
        shape = (batch, 8, 12, 3)
        sharding = batch_sharding(make_mesh(cfgs[cfg_i]), shape)
        asm = ShardedBatchAssembler(shape, np.uint8, sharding,
                                    depth=depth, slots=3)
        frames = _rng_frames(valid, 8, 12, seed=seed)
        b = asm.begin(slot)
        for row, f in enumerate(frames):
            b.write_row(row, f)
        arr, _ = b.finish(valid)
        np.testing.assert_array_equal(
            np.asarray(arr), _padded_ref(frames, batch))

    check()


class TestEngineResidentEntry:
    def test_submit_resident_matches_submit(self):
        import jax

        eng = Engine(get_filter("invert"), mesh=make_mesh(MeshConfig(data=2)))
        batch = np.random.default_rng(0).integers(
            0, 255, size=(8, 16, 16, 3), dtype=np.uint8)
        ref = np.asarray(eng.submit(batch.copy()))
        eng.ensure_compiled(batch.shape, batch.dtype)
        resident = jax.device_put(batch, eng.input_sharding)
        out = np.asarray(eng.submit_resident(resident))
        np.testing.assert_array_equal(out, ref)
        assert eng.stats.batches == 2

    def test_compile_calibrates_h2d(self):
        eng = Engine(get_filter("invert"))
        assert eng.h2d_block_ms is None
        eng.ensure_compiled((4, 16, 16, 3), np.uint8)
        assert eng.h2d_block_ms is not None and eng.h2d_block_ms >= 0
        assert eng.input_sharding is not None


# ---------------------------------------------------------------------------
# End-to-end equivalence: streamed vs monolithic pipelines
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


def _run_capture(filt, ingest, mesh_cfg, batch, n_frames, h=24, w=32,
                 depth=4, max_inflight=4, slow_submit_s=0.0):
    sink = _CapturingSink()
    engine = Engine(filt, mesh=make_mesh(mesh_cfg))
    pipe = Pipeline(
        SyntheticSource(height=h, width=w, n_frames=n_frames),
        filt, sink,
        PipelineConfig(batch_size=batch, queue_size=1000, frame_delay=0,
                       max_inflight=max_inflight, ingest=ingest,
                       ingest_depth=depth),
        engine=engine,
    )
    if slow_submit_s:
        # Throttle the device so the in-flight window actually FILLS —
        # the staging-slot aliasing case the pool contract protects.
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
    """The acceptance property: streamed and monolithic ingest produce
    bit-identical, identically-ordered output."""

    @pytest.mark.parametrize("filt_spec,mesh_cfg,batch,n_frames", [
        (("invert", {}), MeshConfig(data=1), 4, 30),      # pointwise, pad
        (("invert", {}), MeshConfig(data=4), 8, 37),      # sharded, pad
        (("invert", {}), MeshConfig(data=2, space=2), 4, 18),  # H-sharded
        (("flow_warp", dict(levels=1, win_size=7, n_iters=1, flow_scale=1)),
         MeshConfig(data=1), 4, 14),                      # stateful, pad
    ])
    def test_bit_identical_ordered(self, filt_spec, mesh_cfg, batch,
                                   n_frames):
        name, kw = filt_spec
        h, w = (32, 48) if name == "flow_warp" else (24, 32)
        runs = {}
        for ingest in ("monolithic", "streamed"):
            sink, stats = _run_capture(get_filter(name, **kw), ingest,
                                       mesh_cfg, batch, n_frames, h=h, w=w)
            assert stats["delivered"] == n_frames, (ingest, stats)
            runs[ingest] = sink
        mono, stream = runs["monolithic"], runs["streamed"]
        assert stream.order == sorted(stream.order)  # in-order delivery
        assert stream.order == mono.order
        for idx in mono.frames:
            np.testing.assert_array_equal(
                stream.frames[idx], mono.frames[idx],
                err_msg=f"frame {idx} diverged between ingest paths")

    def test_slot_aliasing_under_full_inflight_window(self):
        """A slow device keeps max_inflight batches outstanding, so the
        staging pool wraps while older slabs' batches are still queued —
        results must stay bit-identical."""
        filt = get_filter("invert")
        runs = {}
        for ingest in ("monolithic", "streamed"):
            sink, stats = _run_capture(
                filt, ingest, MeshConfig(data=1), batch=2, n_frames=24,
                max_inflight=2, depth=1, slow_submit_s=0.01)
            assert stats["delivered"] == 24
            runs[ingest] = sink
        for idx in runs["monolithic"].frames:
            np.testing.assert_array_equal(
                runs["streamed"].frames[idx],
                runs["monolithic"].frames[idx])

    def test_depth_one_and_large_depth_identical(self):
        filt = get_filter("invert")
        outs = []
        for depth in (1, 16):
            sink, stats = _run_capture(filt, "streamed", MeshConfig(data=1),
                                       batch=8, n_frames=20, depth=depth)
            assert stats["delivered"] == 20
            outs.append(sink.frames)
        for idx in outs[0]:
            np.testing.assert_array_equal(outs[0][idx], outs[1][idx])

    def test_streamed_is_default_and_reported(self):
        sink, stats = _run_capture(get_filter("invert"), "streamed",
                                   MeshConfig(data=1), 4, 12)
        ing = stats["ingest"]
        assert ing["mode"] == "streamed"
        assert ing["batches"] >= 3
        assert ing["h2d_block_ms"] is not None
        assert ing["overlap_efficiency"] is None or \
            0.0 <= ing["overlap_efficiency"] <= 1.0
        assert PipelineConfig().ingest == "streamed"

    def test_bad_ingest_mode_rejected(self):
        with pytest.raises(ValueError, match="ingest"):
            Pipeline(SyntheticSource(height=8, width=8, n_frames=2),
                     get_filter("invert"), NullSink(),
                     PipelineConfig(ingest="bogus"))


def test_ingest_trace_spans_emitted(tmp_path, monkeypatch):
    """The streamed path lands its per-shard h2d spans on the transfer
    lane of the host trace, and neither of the two spans PR 54 took away
    (``ingest_overlap`` ended at the join's dispatch, ``ingest_stage``
    repeated ``dispatch:assemble_h2d``'s ``stage_ms``)."""
    monkeypatch.chdir(tmp_path)  # run() exports the trace into the CWD
    filt = get_filter("invert")
    engine = Engine(filt, mesh=make_mesh(MeshConfig(data=1)))
    pipe = Pipeline(
        SyntheticSource(height=16, width=16, n_frames=8),
        filt, NullSink(),
        PipelineConfig(batch_size=4, queue_size=100, frame_delay=0,
                       trace=True, ingest_depth=2),
        engine=engine,
    )
    pipe.run()
    names = [e["name"] for e in pipe.tracer._events]
    assert "ingest_h2d" in names
    assert "ingest_overlap" not in names and "ingest_stage" not in names


def test_overlap_efficiency_formula():
    s = IngestStats(requested_mode="streamed", depth=4, h2d_block_ms=10.0)
    s.effective_mode = "streamed"
    s.record_batch(stage_ms=1.0, put_ms=1.5, wait_ms=0.5)
    # exposed = 2.0 of a 10.0 blocking baseline → 80% hidden.
    assert s.overlap_efficiency() == pytest.approx(0.8)
    # Exposed beyond the baseline clamps to 0, never negative.
    s2 = IngestStats(h2d_block_ms=1.0)
    s2.record_batch(stage_ms=0, put_ms=5.0, wait_ms=0)
    assert s2.overlap_efficiency() == 0.0
    # Monolithic / uncalibrated → None (no overlap claim).
    s3 = IngestStats(requested_mode="monolithic", h2d_block_ms=10.0)
    s3.effective_mode = "monolithic"
    s3.record_batch(1, 1, 1)
    assert s3.overlap_efficiency() is None
    assert IngestStats(h2d_block_ms=None).overlap_efficiency() is None


# ---------------------------------------------------------------------------
# Serving frontend: streamed vs monolithic
# ---------------------------------------------------------------------------


def _serve_roundtrip(ingest, n_frames=24, batch=4):
    from dvf_tpu.serve import ServeConfig, ServeFrontend

    filt = get_filter("invert")
    engine = Engine(filt, mesh=make_mesh(MeshConfig(data=1)))
    config = ServeConfig(batch_size=batch, max_inflight=2, queue_size=64,
                         ingest=ingest)
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
    assert len(got) == n_frames, (ingest, len(got))
    return frames, got, stats


def test_serve_streamed_matches_monolithic():
    frames, got_s, stats_s = _serve_roundtrip("streamed")
    _, got_m, _ = _serve_roundtrip("monolithic")
    assert [d.index for d in got_s] == list(range(len(frames)))
    assert [d.index for d in got_m] == [d.index for d in got_s]
    for d_s, d_m, src in zip(got_s, got_m, frames):
        np.testing.assert_array_equal(d_s.frame, 255 - src)
        np.testing.assert_array_equal(d_s.frame, d_m.frame)
    assert stats_s["ingest"]["mode"] == "streamed"


def test_serve_bad_ingest_rejected():
    from dvf_tpu.serve import ServeConfig, ServeFrontend

    with pytest.raises(ValueError, match="ingest"):
        ServeFrontend(get_filter("invert"), ServeConfig(ingest="bogus"))


# ---------------------------------------------------------------------------
# ZMQ worker: streamed vs monolithic (driven directly, no peer app)
# ---------------------------------------------------------------------------


def _zmq_process(ingest, batches=3, batch=4, size=16):
    zmq = pytest.importorskip("zmq")
    del zmq
    from dvf_tpu.transport.zmq_ingress import TpuZmqWorker

    filt = get_filter("invert")
    worker = TpuZmqWorker(
        filt, engine=Engine(filt, mesh=make_mesh(MeshConfig(data=1))),
        batch_size=batch, use_jpeg=False, raw_size=size, ingest=ingest)
    sent = []

    class _StubPush:
        def send_multipart(self, parts):
            # Copy: raw-mode payloads are zero-copy memoryviews over the
            # egress slab (real zmq copies at send; a capturing stub must
            # too, or slab reuse would mutate earlier captures).
            sent.append([bytes(p) for p in parts])

        def close(self, *a):
            pass

    worker.push.close(0)       # no peer: capture instead of blocking
    worker.push = _StubPush()
    try:
        idx = 0
        frames = {}
        for b in range(batches):
            valid = batch if b % 2 == 0 else batch - 1  # padded batches too
            pending = []
            for _ in range(valid):
                f = _rng_frames(1, size, size, seed=idx)[0]
                frames[idx] = f
                pending.append((idx, f.tobytes()))
                idx += 1
            worker._process_batch(pending, b"pid")
        # The asynchronous codec plane may still hold the tail batches;
        # a direct driver flushes explicitly (the run loop does this on
        # exit).
        worker.drain_egress(b"pid")
        out = {}
        for parts in sent:
            i = int(parts[0].decode())
            out[i] = np.frombuffer(parts[4], np.uint8).reshape(size, size, 3)
        return frames, out
    finally:
        worker.close()


def test_zmq_worker_streamed_matches_monolithic():
    src_s, out_s = _zmq_process("streamed")
    src_m, out_m = _zmq_process("monolithic")
    assert sorted(out_s) == sorted(src_s)
    assert sorted(out_s) == sorted(out_m)
    for i in out_s:
        np.testing.assert_array_equal(out_s[i], 255 - src_s[i])
        np.testing.assert_array_equal(out_s[i], out_m[i])


# ---------------------------------------------------------------------------
# Allocation regression: the steady-state hot loop must not allocate
# ---------------------------------------------------------------------------

_BIG = 300_000  # bytes; staging slabs/buffers sit above, frames below


class _EmptyCounter:
    """Counts multi-100KB np.empty calls — the allocation the staging
    pools exist to remove from the hot loop."""

    def __init__(self):
        self.real = np.empty
        self.big = []

    def __call__(self, shape, dtype=float, **kw):
        arr = self.real(shape, dtype, **kw)
        if arr.nbytes >= _BIG:
            self.big.append(arr.nbytes)
        return arr


def _count_pipeline_allocs(monkeypatch, n_frames):
    counter = _EmptyCounter()
    monkeypatch.setattr(np, "empty", counter)
    try:
        filt = get_filter("invert")
        engine = Engine(filt, mesh=make_mesh(MeshConfig(data=1)))
        pipe = Pipeline(
            SyntheticSource(height=256, width=256, n_frames=n_frames),
            filt, NullSink(),
            PipelineConfig(batch_size=8, queue_size=1000, frame_delay=0),
            engine=engine,
        )
        stats = pipe.run()
    finally:
        monkeypatch.setattr(np, "empty", counter.real)
    assert stats["delivered"] == n_frames
    assert stats["ingest"]["pool_allocs"] == 1  # one pool build, reused
    return len(counter.big)


def test_pipeline_steady_state_allocates_nothing(monkeypatch):
    """Tripling the stream length must not change the number of big host
    allocations: the staging pool is built once and reused, so the hot
    loop is allocation-free per batch."""
    short = _count_pipeline_allocs(monkeypatch, n_frames=24)
    long = _count_pipeline_allocs(monkeypatch, n_frames=72)
    assert long == short, (short, long)


def test_serve_steady_state_allocates_nothing(monkeypatch):
    from dvf_tpu.serve import ServeConfig, ServeFrontend

    def run(n_frames):
        counter = _EmptyCounter()
        monkeypatch.setattr(np, "empty", counter)
        try:
            filt = get_filter("invert")
            engine = Engine(filt, mesh=make_mesh(MeshConfig(data=1)))
            frames = _rng_frames(n_frames, 256, 256, seed=1)
            got = 0
            with ServeFrontend(filt, ServeConfig(batch_size=8,
                                                 max_inflight=2,
                                                 queue_size=256),
                               engine=engine) as fe:
                sid = fe.open_stream()
                for f in frames:
                    fe.submit(sid, f)
                fe.close(sid, drain=True)
                deadline = time.time() + 30.0
                while time.time() < deadline and got < n_frames:
                    got += len(fe.poll(sid))
                    time.sleep(0.005)
                stats = fe.stats()
        finally:
            monkeypatch.setattr(np, "empty", counter.real)
        assert got == n_frames
        assert stats["ingest"]["pool_allocs"] == 1
        return len(counter.big)

    assert run(48) == run(16)


def test_zmq_worker_steady_state_allocates_nothing(monkeypatch):
    zmq = pytest.importorskip("zmq")
    del zmq
    from dvf_tpu.transport.zmq_ingress import TpuZmqWorker

    def run(batches):
        counter = _EmptyCounter()
        monkeypatch.setattr(np, "empty", counter)
        try:
            filt = get_filter("invert")
            worker = TpuZmqWorker(
                filt, engine=Engine(filt, mesh=make_mesh(MeshConfig(data=1))),
                batch_size=8, use_jpeg=False, raw_size=256)

            class _StubPush:
                def send_multipart(self, parts):
                    pass

                def close(self, *a):
                    pass

            worker.push.close(0)
            worker.push = _StubPush()
            try:
                idx = 0
                for b in range(batches):
                    pending = []
                    for _ in range(8):
                        f = np.full((256, 256, 3), idx % 251, np.uint8)
                        pending.append((idx, f.tobytes()))
                        idx += 1
                    worker._process_batch(pending, b"pid")
                worker.drain_egress(b"pid")
            finally:
                worker.close()
        finally:
            monkeypatch.setattr(np, "empty", counter.real)
        return len(counter.big)

    assert run(6) == run(2)


def test_batcher_default_staging_is_bounded(monkeypatch):
    """plan() without a caller buffer must reuse the batcher's internal
    ring, not np.empty a multi-MB array per tick."""
    from dvf_tpu.serve.batcher import ContinuousBatcher
    from dvf_tpu.serve.session import StreamSession

    counter = _EmptyCounter()
    monkeypatch.setattr(np, "empty", counter)
    try:
        batcher = ContinuousBatcher(batch_size=8)
        s = StreamSession("s0")
        seen = []
        for tick in range(12):
            for _ in range(8):
                s.submit(np.zeros((256, 256, 3), np.uint8))
            plan = batcher.plan([s], now=0.0)
            assert plan is not None and plan.valid == 8
            seen.append(id(plan.batch))
            s.discard_inflight(8)  # release the claims; frames consumed
    finally:
        monkeypatch.setattr(np, "empty", counter.real)
    assert len(set(seen)) <= 2          # bounded ring, cycled
    assert len(counter.big) <= 2, counter.big  # built once, reused


# ---------------------------------------------------------------------------
# The device lane (runtime/lane.py), ingest side: who decides when an
# assembler is built, degraded and freed
# ---------------------------------------------------------------------------


def _lane(cfg=MeshConfig(data=1), inflight=2, **kw):
    import types

    from dvf_tpu.runtime.lane import DeviceLane

    opts = types.SimpleNamespace(ingest="streamed", ingest_depth=4,
                                 egress="streamed")
    eng = Engine(get_filter("invert"), mesh=make_mesh(cfg))
    return DeviceLane(eng, opts, inflight, **kw), eng, opts


def _run(lane, frames, seq, valid=None):
    builder = lane.begin(frames.shape, frames.dtype, seq)
    for row, f in enumerate(frames[:valid]):
        builder.write_row(row, f)
    return lane.prefetch(
        lane.submit(builder, len(frames) if valid is None else valid)
    ).fetch(seq)


class TestDeviceLaneIngest:

    def test_assembler_is_rebuilt_on_signature_depth_or_mode(self):
        compiled = []
        lane, eng, opts = _lane(
            compile=lambda shape, dtype: (compiled.append(shape),
                                          eng.ensure_compiled(shape, dtype)))
        a = np.stack(_rng_frames(4, 16, 24, seed=1))
        np.testing.assert_array_equal(_run(lane, a, 0), 255 - a)
        a1, s1 = lane._assembler, lane.ingest_stats
        assert a1.batch_shape == (4, 16, 24, 3) and a1.slots == 3
        assert (a1.depth, s1.depth) == (4, 4)
        assert s1.h2d_block_ms == eng.h2d_block_ms is not None
        assert compiled == [(4, 16, 24, 3)]  # the caller's compile step
        _run(lane, a, 1)
        assert lane._assembler is a1 and lane.ingest_stats is s1  # kept
        assert len(compiled) == 1
        b = np.stack(_rng_frames(2, 16, 24, seed=2))  # a new signature
        np.testing.assert_array_equal(_run(lane, b, 2), 255 - b)
        a2 = lane._assembler
        assert a2 is not a1 and a2.batch_shape == (2, 16, 24, 3)
        assert a1.slab_bytes() == 0  # the old one's slabs went
        assert eng.signature[0] == (2, 16, 24, 3)
        opts.ingest_depth = 2  # a planned depth reaches the lane
        np.testing.assert_array_equal(_run(lane, b, 3), 255 - b)
        a3 = lane._assembler
        assert a3 is not a2 and (a3.depth, lane.ingest_stats.depth) == (2, 2)
        assert lane.ingest_stats.batches == 1  # a fresh block
        opts.ingest = "monolithic"  # and a planned mode
        np.testing.assert_array_equal(_run(lane, b, 4), 255 - b)
        assert lane._assembler is not a3
        assert lane.ingest_stats.summary()["mode"] == "monolithic"
        assert len(compiled) == 4  # asked at every rebuild, never between

    def test_padded_batch_and_float_dtype_key(self):
        lane, _, _ = _lane()
        a = np.stack(_rng_frames(4, 16, 24, seed=3))
        out = _run(lane, a, 0, valid=3)
        np.testing.assert_array_equal(out[:3], 255 - a[:3])
        np.testing.assert_array_equal(out[3], 255 - a[2])  # repeat-last
        first = lane._assembler
        lane.begin(a.shape, np.float32, 1)  # same shape, another dtype
        assert lane._assembler is not first
        assert lane._assembler.dtype == np.float32

    def test_staging_slots_follow_the_callers_bound(self):
        lane, _, _ = _lane(inflight=2, staging_inflight=0)  # the worker's
        a = np.stack(_rng_frames(4, 16, 24))
        _run(lane, a, 0)
        assert lane._assembler.slots == 1 and lane._fetcher.slots == 3

    def test_degrade_h2d_applies_once_and_is_recorded(self, capsys):
        lane, _, _ = _lane(name=lambda: "host X")
        a = np.stack(_rng_frames(4, 16, 24, seed=5))
        _run(lane, a, 0)
        streamed = lane._assembler
        assert lane.ingest_stats.summary()["mode"] == "streamed"
        assert lane.degrade("oom") is False  # not a transfer fault
        assert lane.degrade("h2d") is True
        assert "[host X] repeated h2d faults: degrading ingest" \
            in capsys.readouterr().err
        assert lane._assembler is None and streamed.slab_bytes() == 0
        assert lane.degrade("h2d") is False  # once
        np.testing.assert_array_equal(_run(lane, a, 1), 255 - a)
        s = lane.ingest_stats.summary()
        assert s["mode"] == "monolithic"
        assert s["requested_mode"] == "streamed"
        assert s["fallback_reason"] == "h2d_fault_budget"
        # A rebuild at another signature stays degraded.
        b = np.stack(_rng_frames(2, 16, 24))
        _run(lane, b, 2)
        assert lane.ingest_stats.fallback_reason == "h2d_fault_budget"

    def test_restage_and_release_free_the_slabs(self):
        import gc

        gc.collect()
        base = ingest_mod.occupied_slab_bytes()
        lane, _, _ = _lane()
        a = np.stack(_rng_frames(4, 16, 24, seed=7))
        half = lane.begin(a.shape, a.dtype, 0)
        half.write_row(0, a[0])  # a batch abandoned mid-staging
        asm = lane._assembler
        assert asm.slab_bytes() > 0
        assert ingest_mod.occupied_slab_bytes() > base
        lane.restage()
        assert asm.slab_bytes() == 0 and lane._assembler is None
        np.testing.assert_array_equal(_run(lane, a, 1), 255 - a)
        assert lane.slab_bytes() > 0
        lane.release()
        assert lane.slab_bytes() == 0
        assert ingest_mod.occupied_slab_bytes() <= base
        np.testing.assert_array_equal(_run(lane, a, 2), 255 - a)
        lane.release()
