"""Mechanics tests for the benchmark harnesses (tiny shapes, CPU).

These guard the *measurement* code paths — transfer microbench fields,
rate-controlled latency mode, adaptive knobs — not performance numbers.
"""

import numpy as np
import pytest

from dvf_tpu.benchmarks import (
    bench_device_resident,
    bench_e2e_latency,
    bench_e2e_streaming,
    bench_transfer,
)
from dvf_tpu.ops import get_filter


def test_transfer_microbench_fields():
    r = bench_transfer(2, 16, 16, reps=2)
    assert r["h2d_mbps"] > 0 and r["d2h_mbps"] > 0
    assert r["batch_mb"] == 2 * 16 * 16 * 3 / 1e6
    # The fixed-cost correction is clamped below the bulk time — d2h_mbps
    # can be huge on CPU but must stay finite and positive.
    assert np.isfinite(r["d2h_mbps"]) and r["d2h_fixed_ms"] >= 0


def test_device_resident_counts_frames():
    r = bench_device_resident(get_filter("invert"), iters=3, batch_size=2,
                              height=16, width=16)
    assert r["frames"] == 6
    assert r["fps"] > 0 and r["ms_per_frame"] > 0


def test_e2e_streaming_throughput_mode():
    r = bench_e2e_streaming(get_filter("invert"), 24, 4, 16, 16)
    assert r["frames"] > 0 and r["fps"] > 0


def test_e2e_latency_mode_is_rate_controlled():
    """Latency mode throttles the source and bounds the ingest queue: with
    a target far below capacity there must be no drops, and p50 must be a
    transit time (well under the 100 ms inter-frame period — queue-depth
    artifacts would exceed it)."""
    r = bench_e2e_latency(get_filter("invert"), 16, 4, 16, 16, target_fps=10.0)
    assert r["target_fps"] == 10.0
    assert r["dropped"] == 0
    assert r["frames"] == 16
    assert 0 < r["p50_ms"] < 1000.0


def test_e2e_streaming_ring_transport_variants():
    """bench plumbing for --transport ring / --wire jpeg (tiny shapes)."""
    for wire in ("raw", "jpeg"):
        r = bench_e2e_streaming(get_filter("invert"), 16, 4, 24, 32,
                                transport="ring", wire=wire)
        assert r["frames"] == 16, (wire, r)


def test_latency_bench_accepts_mesh():
    import dvf_tpu
    from dvf_tpu.benchmarks import bench_e2e_latency
    from dvf_tpu.parallel.mesh import MeshConfig, make_mesh

    r = bench_e2e_latency(dvf_tpu.get_filter("invert"), n_frames=24,
                          batch_size=8, height=32, width=32,
                          target_fps=500.0,
                          mesh=make_mesh(MeshConfig(data=2)))
    assert r["frames"] > 0 and r["p50_ms"] > 0


def test_stage_decomposition_fields():
    from dvf_tpu.benchmarks import bench_stage_decomposition

    d = bench_stage_decomposition(get_filter("invert"), (1, 2), 16, 16, reps=3)
    # Self-describing keys (the pre-r06 payload published opaque "1"/"2")
    # with the measured transfer mode recorded in-band, plus the codec
    # provenance for the encode leg (r06: quality/threads/backend must
    # travel with the encode_ms they produced).
    assert set(d) == {"batch_1", "batch_2", "codec"}
    # r08 adds "wire": bench rows must say WHICH wire mode (full-frame
    # jpeg vs temporal-delta) produced the encode numbers beside them.
    # r15 adds "assist": which codec-assist tier (none / ycbcr /
    # full-transform) the encode numbers were produced under.
    assert set(d["codec"]) == {"backend", "wire", "quality", "threads",
                               "assist"}
    assert d["codec"]["wire"] == "jpeg"
    assert d["codec"]["assist"] == "none"
    assert d["codec"]["threads"] == 1  # per-frame serialized cost
    for b in ("batch_1", "batch_2"):
        legs = d[b]
        for k in ("staging_ms", "h2d_ms", "compute_ms", "d2h_ms",
                  "encode_ms"):
            assert legs[k] >= 0, (b, k, legs)
        # encode_ms is reported beside the four serialized-transfer legs
        # but excluded from their total (the codec plane overlaps it).
        assert legs["total_ms"] == pytest.approx(
            legs["staging_ms"] + legs["h2d_ms"] + legs["compute_ms"]
            + legs["d2h_ms"], abs=0.01)
        assert legs["total_ms"] >= legs["compute_ms"]
        assert legs["transfer_mode"] == "whole_batch"
        assert legs["per_frame_compute_ms"] == round(
            legs["compute_ms"] / int(b.removeprefix("batch_")), 4)


def test_roofline_fields_models():
    """The roofline columns use XLA's own cost analysis: invert reads +
    writes one uint8 frame, so bytes accessed must be exactly 2× the frame
    bytes, and the HBM fraction must follow fps/(BW/bytes) with the peaks
    of the result's own device_kind times its mesh's device count."""
    from dvf_tpu.benchmarks import DEVICE_PEAKS, roofline_fields

    r = bench_device_resident(get_filter("invert"), iters=3, batch_size=2,
                              height=16, width=16)
    assert r["bytes_accessed_per_frame"] == 2 * 16 * 16 * 3
    # Every result names the device it ran on; a CPU one claims no roofline.
    assert (r["platform"], r["device_kind"]) == ("cpu", "cpu")
    assert r["n_devices"] >= 1
    assert roofline_fields(r) == {}
    peaks = DEVICE_PEAKS["TPU v5 lite"]
    for n in (1, 4):
        fake = dict(r, fps=1000.0, platform="tpu",
                    device_kind="TPU v5 lite", n_devices=n)
        out = roofline_fields(fake)
        ceil = n * peaks["hbm_gbps"] * 1e9 / r["bytes_accessed_per_frame"]
        assert abs(out["hbm_roofline_fps"] - round(ceil, 1)) < 0.2
        assert out["hbm_roofline_frac"] == round(1000.0 / ceil, 3)
    # A device that is not in the table is an error, not a default.
    with pytest.raises(ValueError, match="TPU v9"):
        roofline_fields(dict(r, platform="tpu", device_kind="TPU v9"))


def test_stream_congested_verdicts():
    from dvf_tpu.benchmarks import stream_congested

    assert not stream_congested(9.0, 10.0, 0, 100)     # kept up
    # Steady-state delivery shortfall IS congestion even with zero drops:
    # a stream shorter than the pipeline's total buffering never
    # overflows the drop-oldest queue, yet frames are accumulating (the
    # slow-link case — invert_1080p once measured 146 s 'transit' with 0
    # drops before this signal existed). The rate is first→last delivery,
    # so startup/compile/drain overhead cannot fake a shortfall.
    assert stream_congested(5.0, 10.0, 0, 100)
    assert stream_congested(10.0, 10.0, 10, 100)       # ingest dropped
    assert not stream_congested(10.0, 10.0, 1, 100)    # one startup drop ok
    # No percentage allowance: a steady trickle of drops = the queue sat
    # full for a stretch = queue residency leaked into the percentiles.
    assert stream_congested(10.0, 10.0, 2, 512)
    assert stream_congested(1.0, 0.0, 0, 100)          # no target = no claim
    assert stream_congested(0.0, 10.0, 0, 0)           # nothing delivered


def test_latency_backoff_halves_until_uncongested(monkeypatch):
    """The rate-controlled leg must not publish queue-residency numbers:
    when delivery falls short of the offered rate (capacity flapped below
    0.8× the earlier throughput measurement — round-3 verdict, weak item
    1), it halves the rate until the pipeline provably kept up."""
    import dvf_tpu.benchmarks as B

    calls = []

    def fake_run_pipeline(filt, source, batch_size, h, w, max_inflight,
                          queue_size, **kw):
        calls.append((source.rate, source.n_frames))
        if source.rate > 3.0:  # congested until the rate drops under 3 fps
            return {"fps": source.rate * 0.5,
                    "delivery_fps": source.rate * 0.5,
                    "frames": source.n_frames,
                    "wall_s": 1.0, "p50_ms": 99999.0, "p99_ms": 99999.0,
                    "dropped": 10}
        return {"fps": source.rate, "delivery_fps": source.rate,
                "frames": source.n_frames, "wall_s": 1.0,
                "p50_ms": 12.0, "p99_ms": 20.0, "dropped": 0}

    monkeypatch.setattr(B, "_run_pipeline", fake_run_pipeline)
    r = B.bench_e2e_latency(object(), n_frames=96, batch_size=8, height=8,
                            width=8, target_fps=8.0)
    assert [c[0] for c in calls] == [8.0, 4.0, 2.0]
    # Frame count halves with the rate so a backoff keeps the wall budget.
    assert [c[1] for c in calls] == [96, 48, 24]
    assert r["congested"] is False and r["backoffs"] == 2
    assert r["target_fps"] == 2.0 and r["p50_ms"] == 12.0


def test_latency_backoff_exhausted_flags_congested(monkeypatch):
    import dvf_tpu.benchmarks as B

    def always_congested(filt, source, *a, **kw):
        return {"fps": source.rate * 0.3, "delivery_fps": source.rate * 0.3,
                "frames": source.n_frames,
                "wall_s": 1.0, "p50_ms": 5000.0, "p99_ms": 9000.0,
                "dropped": 50}

    monkeypatch.setattr(B, "_run_pipeline", always_congested)
    r = B.bench_e2e_latency(object(), n_frames=64, batch_size=8, height=8,
                            width=8, target_fps=8.0, max_backoffs=2)
    assert r["congested"] is True and r["backoffs"] == 2
    assert r["target_fps"] == 2.0  # the lowest rate actually tried


def test_latency_backoff_never_inflates_frames(monkeypatch):
    """Large batch must not raise the retry's frame count above the
    original leg's (a batch-derived floor would multiply wall time on
    exactly the slow links that back off)."""
    import dvf_tpu.benchmarks as B

    frames_seen = []

    def always_congested(filt, source, *a, **kw):
        frames_seen.append(source.n_frames)
        return {"fps": 0.1, "delivery_fps": 0.1, "frames": source.n_frames,
                "wall_s": 1.0,
                "p50_ms": 5000.0, "p99_ms": 9000.0, "dropped": 50}

    monkeypatch.setattr(B, "_run_pipeline", always_congested)
    B.bench_e2e_latency(object(), n_frames=48, batch_size=64, height=8,
                        width=8, target_fps=2.4, max_backoffs=2)
    assert frames_seen == [48, 24, 16]  # monotonically non-increasing


def test_latency_backoff_floor_never_exceeds_original(monkeypatch):
    """A 12-frame leg must not be raised to 16 frames by the retry floor —
    on a 0.1 fps config that inflation (plus the halved rate) projects to
    a 28-minute leg that burns the harness child's whole timeout."""
    import dvf_tpu.benchmarks as B

    frames_seen = []

    def always_congested(filt, source, *a, **kw):
        frames_seen.append(source.n_frames)
        return {"fps": 0.01, "delivery_fps": 0.01, "frames": source.n_frames,
                "wall_s": 1.0, "p50_ms": 5000.0, "p99_ms": 9000.0,
                "dropped": 50}

    monkeypatch.setattr(B, "_run_pipeline", always_congested)
    r = B.bench_e2e_latency(object(), n_frames=12, batch_size=8, height=8,
                            width=8, target_fps=8.0, max_backoffs=2)
    assert frames_seen == [12, 12, 12]
    assert r["congested"] is True


def test_latency_backoff_respects_wall_budget(monkeypatch):
    """When the halved-rate retry's offered stream alone would outlast
    max_retry_stream_s, the leg stops and reports congested instead of
    running it."""
    import dvf_tpu.benchmarks as B

    calls = []

    def always_congested(filt, source, *a, **kw):
        calls.append(source.rate)
        return {"fps": 0.01, "delivery_fps": 0.01, "frames": source.n_frames,
                "wall_s": 1.0, "p50_ms": 5000.0, "p99_ms": 9000.0,
                "dropped": 50}

    monkeypatch.setattr(B, "_run_pipeline", always_congested)
    # 12 frames at 0.08 fps: first retry projects 12/0.04 = 300 s (ok at
    # the 400 s default), second projects 12/0.02 = 600 s (skipped).
    r = B.bench_e2e_latency(object(), n_frames=12, batch_size=8, height=8,
                            width=8, target_fps=0.08, max_backoffs=2)
    assert calls == [0.08, 0.04]
    assert r["congested"] is True and r["backoffs"] == 1


def test_latency_backoff_zero_target_returns_congested(monkeypatch):
    """target_fps=0 (a broken throughput leg) must yield the congested
    verdict, not a ZeroDivisionError in the retry projection."""
    import dvf_tpu.benchmarks as B

    def run(filt, source, *a, **kw):
        return {"fps": 0.0, "delivery_fps": 0.0, "frames": 0, "wall_s": 1.0,
                "p50_ms": float("nan"), "p99_ms": float("nan"), "dropped": 0}

    monkeypatch.setattr(B, "_run_pipeline", run)
    r = B.bench_e2e_latency(object(), n_frames=12, batch_size=8, height=8,
                            width=8, target_fps=0.0)
    assert r["congested"] is True


def test_latency_backoff_invariants_property(monkeypatch):
    """Property check over arbitrary congestion patterns: the backoff
    loop always terminates within max_backoffs+1 attempts, rates halve
    monotonically, frame counts never increase (floored at
    min(16, original)), the returned numbers are the LAST attempt's, and
    the congested flag matches that attempt's verdict."""
    import pytest

    pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    import dvf_tpu.benchmarks as B

    @settings(max_examples=60, deadline=None)
    @given(
        congested_seq=st.lists(st.booleans(), min_size=1, max_size=8),
        n_frames=st.integers(min_value=1, max_value=200),
        target=st.floats(min_value=0.05, max_value=500.0),
        max_backoffs=st.integers(min_value=0, max_value=4),
    )
    def check(congested_seq, n_frames, target, max_backoffs):
        attempts = []

        def scripted(filt, source, *a, **kw):
            i = len(attempts)
            attempts.append((source.rate, source.n_frames))
            cong = congested_seq[min(i, len(congested_seq) - 1)]
            return {"fps": source.rate, "frames": source.n_frames,
                    "delivery_fps": (source.rate * 0.1 if cong
                                     else source.rate),
                    "wall_s": 1.0, "p50_ms": 100.0 + i, "p99_ms": 200.0 + i,
                    "dropped": 50 if cong else 0}

        monkeypatch.setattr(B, "_run_pipeline", scripted)
        r = B.bench_e2e_latency(object(), n_frames=n_frames, batch_size=8,
                                height=8, width=8, target_fps=target,
                                max_backoffs=max_backoffs)
        assert 1 <= len(attempts) <= max_backoffs + 1
        rates = [a[0] for a in attempts]
        frames = [a[1] for a in attempts]
        for j in range(1, len(attempts)):
            assert rates[j] == rates[j - 1] / 2.0
            assert frames[j] <= frames[j - 1]
            assert frames[j] >= min(16, n_frames)
        assert r["backoffs"] == len(attempts) - 1
        assert r["target_fps"] == rates[-1]
        assert r["p50_ms"] == 100.0 + len(attempts) - 1  # last attempt's
        last_cong = congested_seq[min(len(attempts) - 1,
                                      len(congested_seq) - 1)]
        assert r["congested"] is last_cong

    check()
