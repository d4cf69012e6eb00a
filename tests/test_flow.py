"""Tests for Farneback-style optical flow and the flow_warp filter."""

import cv2
import numpy as np
import pytest
import jax.numpy as jnp

from dvf_tpu.ops import get_filter
from dvf_tpu.ops.flow import bilinear_sample, farneback_flow, warp_by_flow


def _textured(rng, h, w):
    img = rng.random((h, w), dtype=np.float32)
    return cv2.GaussianBlur(img, (7, 7), 2.0)


class TestWarp:
    def test_identity_flow(self, rng):
        img = rng.random((2, 16, 24, 3), dtype=np.float32)
        flow = np.zeros((2, 16, 24, 2), dtype=np.float32)
        out = warp_by_flow(jnp.asarray(img), jnp.asarray(flow))
        np.testing.assert_allclose(np.asarray(out), img, atol=1e-6)

    def test_integer_shift(self, rng):
        img = rng.random((1, 16, 24, 1), dtype=np.float32)
        flow = np.zeros((1, 16, 24, 2), dtype=np.float32)
        flow[..., 0] = 3.0  # sample from x+3
        out = np.asarray(warp_by_flow(jnp.asarray(img), jnp.asarray(flow)))
        np.testing.assert_allclose(out[0, :, :-3, 0], img[0, :, 3:, 0], atol=1e-6)

    def test_bilinear_midpoint(self):
        img = np.zeros((1, 4, 4, 1), dtype=np.float32)
        img[0, 1, 1, 0] = 1.0
        ys = jnp.full((1, 1, 1), 1.0)
        xs = jnp.full((1, 1, 1), 1.5)
        val = bilinear_sample(jnp.asarray(img), ys, xs)
        assert abs(float(val[0, 0, 0, 0]) - 0.5) < 1e-6


class TestFarneback:
    def test_recovers_translation(self, rng):
        """curr = roll(prev, -2, x): features move −2 px in x (cv2 convention),
        so flow ≈ (−2, 0)."""
        base = _textured(rng, 64, 96)
        shift = np.roll(base, -2, axis=1)
        prev = jnp.asarray(base)[None, ..., None]
        curr = jnp.asarray(shift)[None, ..., None]
        flow = np.asarray(farneback_flow(prev, curr, levels=3, win_size=15, n_iters=3))
        inner = flow[0, 16:-16, 16:-16]
        assert abs(inner[..., 0].mean() - (-2.0)) < 0.5, inner[..., 0].mean()
        assert abs(inner[..., 1].mean()) < 0.5

    def test_comparable_to_cv2(self, rng):
        """Like-for-like: our Gaussian-window path vs cv2 with
        OPTFLOW_FARNEBACK_GAUSSIAN (the matching window). Measured EPE
        0.004 px — near-exact parity; 0.05 leaves float/impl headroom."""
        base = _textured(rng, 64, 96)
        shift = np.roll(np.roll(base, -1, axis=1), -2, axis=0)
        prev_u8 = (base * 255).astype(np.uint8)
        curr_u8 = (shift * 255).astype(np.uint8)
        ref = cv2.calcOpticalFlowFarneback(
            prev_u8, curr_u8, None, 0.5, 3, 15, 3, 5, 1.1,
            cv2.OPTFLOW_FARNEBACK_GAUSSIAN)
        ours = np.asarray(farneback_flow(
            jnp.asarray(base)[None, ..., None], jnp.asarray(shift)[None, ..., None],
            levels=3, win_size=15, n_iters=3))[0]
        inner = np.s_[16:-16, 16:-16]
        err = np.linalg.norm(ours[inner] - ref[inner], axis=-1).mean()
        assert err < 0.05, f"mean EPE vs cv2 (gaussian window) = {err}"

    def test_zero_motion(self, rng):
        base = _textured(rng, 48, 48)
        g = jnp.asarray(base)[None, ..., None]
        flow = np.asarray(farneback_flow(g, g, levels=2, win_size=11, n_iters=2))
        assert np.abs(flow).max() < 0.1


class TestFlowWarpFilter:
    def test_first_batch_passthrough(self, rng):
        batch = rng.random((3, 32, 32, 3), dtype=np.float32)
        filt = get_filter("flow_warp", levels=2, win_size=11, n_iters=2, flow_scale=1)
        state = filt.init_state(batch.shape, jnp.float32)
        out, state = filt(jnp.asarray(batch), state)
        # A stream's first FRAME has no predecessor and passes through;
        # the first batch's later rows follow a real frame and are warped
        # exactly as the pairwise form warps them (what a frame gets does
        # not depend on which batch it rode in).
        np.testing.assert_allclose(np.asarray(out[0]), batch[0], atol=1e-6)
        one = get_filter("flow_warp", levels=2, win_size=11, n_iters=2,
                         flow_scale=1)
        s1 = one.init_state(batch[:1].shape, jnp.float32)
        for i in range(3):
            o1, s1 = one(jnp.asarray(batch[i:i + 1]), s1)
            np.testing.assert_allclose(np.asarray(out[i]), np.asarray(o1[0]),
                                       atol=1e-5)
        assert bool(state["initialized"])
        np.testing.assert_allclose(np.asarray(state["prev"]), batch[-1], atol=1e-6)

    def test_static_scene_reproduces_prev(self, rng):
        """With zero motion, warp(prev) == prev, and prev chains across batches."""
        frame = cv2.GaussianBlur(rng.random((32, 32, 3), dtype=np.float32), (5, 5), 1.5)
        batch = np.broadcast_to(frame, (3, 32, 32, 3)).copy()
        filt = get_filter("flow_warp", levels=2, win_size=11, n_iters=2, flow_scale=1)
        state = filt.init_state(batch.shape, jnp.float32)
        _, state = filt(jnp.asarray(batch), state)
        out2, _ = filt(jnp.asarray(batch), state)
        np.testing.assert_allclose(np.asarray(out2), batch, atol=0.05)

    def test_stateful_flag(self):
        filt = get_filter("flow_warp")
        assert filt.stateful
        assert not get_filter("invert").stateful


class TestEmaSmooth:
    def test_matches_numpy_recurrence_across_batches(self, rng):
        import jax.numpy as jnp

        from dvf_tpu.ops import get_filter

        filt = get_filter("ema_smooth", alpha=0.5)
        b1 = rng.random((3, 8, 8, 3)).astype(np.float32)
        b2 = rng.random((3, 8, 8, 3)).astype(np.float32)
        state = filt.init_state(b1.shape, np.float32)
        out1, state = filt.fn(jnp.asarray(b1), state)
        out2, state = filt.fn(jnp.asarray(b2), state)
        # numpy golden: seeded with the first frame, chained across batches
        ema = b1[0]
        want = []
        for x in list(b1) + list(b2):
            ema = 0.5 * x + 0.5 * ema
            want.append(ema)
        got = np.concatenate([np.asarray(out1), np.asarray(out2)])
        np.testing.assert_allclose(got, np.stack(want), atol=1e-6)

    def test_engine_keeps_h_sharding_when_pointwise_stateful(self, rng):
        """halo==0 + stateful: the engine must keep GSPMD H-sharding
        (ADVICE r2 item 3) and still match single-device numerics."""
        from dvf_tpu.ops import get_filter
        from dvf_tpu.parallel.mesh import MeshConfig, make_mesh
        from dvf_tpu.runtime.engine import Engine

        x = rng.integers(0, 255, (4, 32, 32, 3), np.uint8)
        mesh = make_mesh(MeshConfig(data=2, space=4))
        eng = Engine(get_filter("ema_smooth"), mesh=mesh)
        eng.compile(x.shape, np.uint8)
        assert eng._exec_filter is eng.filter  # no halo wrap, no H replication
        got = np.asarray(eng.submit(x))
        ref = Engine(get_filter("ema_smooth"),
                     mesh=make_mesh(MeshConfig(data=1)))
        want = np.asarray(ref.submit(x))
        assert np.abs(got.astype(int) - want.astype(int)).max() <= 1

    def test_pipeline_delivers(self):
        from dvf_tpu.io import NullSink, SyntheticSource
        from dvf_tpu.ops import get_filter
        from dvf_tpu.runtime import Pipeline, PipelineConfig

        pipe = Pipeline(
            SyntheticSource(height=24, width=24, n_frames=17),
            get_filter("ema_smooth"),
            NullSink(),
            PipelineConfig(batch_size=4, queue_size=64, frame_delay=0),
        )
        stats = pipe.run()
        assert stats["delivered"] == 17  # pad-safe: 17 % 4 != 0 exercised

    def test_pad_invariance_across_batch_partitions(self):
        """6 frames through batch_size=4 (one 2-valid+2-pad batch) and
        batch_size=2 (no pads) must deliver IDENTICAL frames — the exact
        pad_safe contract (repeat->no-op makes state pad-count free)."""
        import jax.numpy as jnp

        from dvf_tpu.io import NullSink, SyntheticSource
        from dvf_tpu.ops import get_filter
        from dvf_tpu.runtime import Pipeline, PipelineConfig

        def run(batch_size):
            delivered = {}

            class Cap(NullSink):
                def emit(self, i, f, ts):
                    super().emit(i, f, ts)
                    delivered[i] = f.copy()

            pipe = Pipeline(
                SyntheticSource(height=16, width=16, n_frames=6),
                get_filter("ema_smooth", alpha=0.4),
                Cap(),
                PipelineConfig(batch_size=batch_size, queue_size=64,
                               frame_delay=0),
            )
            stats = pipe.run()
            assert stats["delivered"] == 6
            return delivered

        a, b = run(4), run(2)
        for i in range(6):
            np.testing.assert_array_equal(a[i], b[i])

    def test_rejects_bad_alpha(self):
        import pytest as _pytest

        from dvf_tpu.ops import get_filter

        with _pytest.raises(ValueError):
            get_filter("ema_smooth", alpha=0.0)


def test_poly_expansion_matches_unfused_sep_convs():
    """The fused moment computation (one pad, shared vertical passes) must
    be bit-identical to six independent sep_conv2d(impl='shift') calls —
    same taps, same accumulation order."""
    import numpy as np

    from dvf_tpu.ops.conv import sep_conv2d
    from dvf_tpu.ops.flow import _poly_exp_setup, poly_expansion

    rng = np.random.default_rng(3)
    gray = jnp.asarray(rng.random((2, 24, 31, 1), dtype=np.float32))
    n, sigma = 5, 1.1
    k0, k1, k2, Ginv = _poly_exp_setup(n, sigma)
    v = jnp.stack([
        sep_conv2d(gray, k0, k0), sep_conv2d(gray, k0, k1),
        sep_conv2d(gray, k1, k0), sep_conv2d(gray, k0, k2),
        sep_conv2d(gray, k2, k0), sep_conv2d(gray, k1, k1),
    ], axis=-1)
    r = jnp.einsum("...i,ji->...j", v, Ginv)
    want = (r[..., 3], r[..., 5] * 0.5, r[..., 4], r[..., 1], r[..., 2])
    got = poly_expansion(gray, n, sigma)
    for g, w, name in zip(got, want, ("A11", "A12", "A22", "b1", "b2")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-7, err_msg=name)


def test_farneback_seq_matches_pairwise():
    """farneback_flow_seq dedups the overlapping prev/curr roles of a
    consecutive-frame batch; its flows must match the pairwise form."""
    import numpy as np

    from dvf_tpu.ops.flow import farneback_flow, farneback_flow_seq

    rng = np.random.default_rng(11)
    seq = jnp.asarray(rng.random((4, 32, 40, 1), dtype=np.float32))
    want = farneback_flow(seq[:-1], seq[1:], levels=2, win_size=9, n_iters=2)
    got = farneback_flow_seq(seq, levels=2, win_size=9, n_iters=2)
    # Same per-frame math, but XLA fuses the stacked sequence differently
    # than two pair stacks; the reassociation noise passes through the
    # regularized 2x2 solve. 1e-4 px is far below any visible flow.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0.0, atol=1e-4)


def test_box_filter_matches_uniform_sep_conv():
    """The running-sum box filter must equal a uniform-kernel sep conv
    (same reflect borders) — only the summation algorithm differs."""
    import pytest

    from dvf_tpu.ops.conv import box_filter, sep_conv2d

    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.random((2, 21, 34, 5), dtype=np.float32))
    for win in (3, 9, 15):
        k = jnp.ones((win,), jnp.float32) / win
        want = sep_conv2d(x, k, k)
        got = box_filter(x, win)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, err_msg=f"win={win}")
    with pytest.raises(ValueError, match="odd"):
        box_filter(x, 4)


def test_box_filter_matches_uniform_sep_conv_720p_scale():
    """ADVICE r4: the cumsum running sums reach O(H) before differencing,
    and the small-geometry test above couldn't bound the drift at the
    geometry the filter is advertised for. At 720p the measured deviation
    is ~2e-5 (XLA's cumsum is an associative scan — ~O(log H) error);
    assert an order of magnitude of headroom below one uint8 half-step so
    a lowering change can't silently regress it."""
    from dvf_tpu.ops.conv import box_filter, sep_conv2d

    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.random((1, 720, 1280, 3), dtype=np.float32))
    k = jnp.ones((5,), jnp.float32) / 5.0
    want = sep_conv2d(x, k, k)
    got = box_filter(x, 5)
    diff = float(jnp.abs(got - want).max())
    assert diff < 2e-4, f"cumsum drift {diff} at 720p"


def test_box_window_flow_recovers_translation(rng):
    """The box-window variant (cv2's flags=0 default) estimates the same
    uniform translation the Gaussian-window path does."""
    base = _textured(rng, 64, 96)
    shift = np.roll(base, -2, axis=1)
    prev = jnp.asarray(base)[None, ..., None]
    curr = jnp.asarray(shift)[None, ..., None]
    flow = np.asarray(farneback_flow(prev, curr, levels=3, win_size=15,
                                     n_iters=3, win_type="box"))
    inner = flow[0, 16:-16, 16:-16]
    assert abs(inner[..., 0].mean() - (-2.0)) < 0.5, inner[..., 0].mean()
    assert abs(inner[..., 1].mean()) < 0.5


def test_box_window_comparable_to_cv2_default_flags(rng):
    """cv2.calcOpticalFlowFarneback with flags=0 uses the box window —
    the win_type='box' variant is its parity surface. Measured EPE
    0.002 px; 0.05 leaves float/impl headroom."""
    base = _textured(rng, 64, 96)
    shift = np.roll(np.roll(base, -1, axis=1), -2, axis=0)
    prev_u8 = (base * 255).astype(np.uint8)
    curr_u8 = (shift * 255).astype(np.uint8)
    ref = cv2.calcOpticalFlowFarneback(
        prev_u8, curr_u8, None, 0.5, 3, 15, 3, 5, 1.1, 0)
    ours = np.asarray(farneback_flow(
        jnp.asarray(base)[None, ..., None], jnp.asarray(shift)[None, ..., None],
        levels=3, win_size=15, n_iters=3, win_type="box"))[0]
    inner = np.s_[16:-16, 16:-16]
    err = np.linalg.norm(ours[inner] - ref[inner], axis=-1).mean()
    assert err < 0.05, f"mean EPE vs cv2 (flags=0, box window) = {err}"


def test_inner_warp_pallas_recovers_translation(rng):
    """The bounded Pallas inner warp (opt-in approximation: each
    refinement step's displacement clipped to ±max_disp) must still
    recover a small uniform translation like the exact gather path."""
    base = _textured(rng, 64, 96)
    shift = np.roll(base, -2, axis=1)
    prev = jnp.asarray(base)[None, ..., None]
    curr = jnp.asarray(shift)[None, ..., None]
    flow = np.asarray(farneback_flow(prev, curr, levels=2, win_size=11,
                                     n_iters=2, inner_warp="pallas"))
    inner = flow[0, 16:-16, 16:-16]
    assert abs(inner[..., 0].mean() - (-2.0)) < 0.5, inner[..., 0].mean()
    assert abs(inner[..., 1].mean()) < 0.5


def test_inner_warp_close_to_gather_for_small_motion(rng):
    """Within the clip bound the two inner warps sample the same values,
    so the flows must agree closely."""
    base = _textured(rng, 48, 64)
    shift = np.roll(base, -1, axis=1)
    prev = jnp.asarray(base)[None, ..., None]
    curr = jnp.asarray(shift)[None, ..., None]
    a = np.asarray(farneback_flow(prev, curr, levels=2, win_size=11,
                                  n_iters=2, inner_warp="gather"))
    b = np.asarray(farneback_flow(prev, curr, levels=2, win_size=11,
                                  n_iters=2, inner_warp="pallas"))
    inner = np.s_[:, 12:-12, 12:-12, :]
    assert np.abs(a[inner] - b[inner]).mean() < 0.05


def test_inner_warp_validated_at_construction():
    import pytest

    with pytest.raises(ValueError, match="inner_warp"):
        get_filter("flow_warp", inner_warp="scatter")


@pytest.mark.parametrize("backend, warp_impl, inner", [
    ("tpu", None, True),        # flow_warp() on the chip: the cell's program
    ("tpu", "pallas", True),
    ("tpu", "gather", False),   # no displacement bound anywhere
    ("cpu", None, False),
    ("cpu", "pallas", False),   # no A/B off the chip: the exact gathers
])
def test_inner_warp_default_follows_the_final_warp(monkeypatch, backend,
                                                   warp_impl, inner):
    """``inner_warp=None``: the bounded kernel inside the iteration only
    where the final warp is bounded already, and there the measured
    per-backend winner (MEASURED_DEFAULTS["flow_inner"])."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    name = get_filter("flow_warp", warp_impl=warp_impl).name
    assert ("pallas-inner" in name) == inner
    assert "pallas-inner" in get_filter(
        "flow_warp", warp_impl="gather", inner_warp="pallas").name
