"""Golden-numerics tests for the filter library vs cv2 / numpy references.

SURVEY.md §4: the reference ships zero tests; our unit-test model is
golden-image numerics against the cv2 ops the reference (and its configs)
are defined by — invert == cv2.bitwise_not (inverter.py:41), Gaussian ==
cv2.GaussianBlur, Sobel == cv2.Sobel, bilateral vs a direct numpy
implementation.
"""

import cv2
import numpy as np
import jax.numpy as jnp
import pytest

from dvf_tpu.ops import get_filter
from dvf_tpu.utils.image import to_float, to_uint8


def apply_one(filt, frame_f32):
    """Run a stateless filter on a single frame via a batch of 1."""
    out, _ = filt(jnp.asarray(frame_f32)[None], None)
    return np.asarray(out[0])


class TestInvert:
    def test_matches_bitwise_not_uint8(self, frame_u8):
        filt = get_filter("invert")
        out, _ = filt(jnp.asarray(frame_u8)[None], None)
        np.testing.assert_array_equal(np.asarray(out[0]), cv2.bitwise_not(frame_u8))

    def test_float_path(self, batch_f32):
        filt = get_filter("invert")
        out, _ = filt(jnp.asarray(batch_f32), None)
        np.testing.assert_allclose(np.asarray(out), 1.0 - batch_f32, atol=1e-6)

    def test_involution(self, frame_u8):
        filt = get_filter("invert")
        once, _ = filt(jnp.asarray(frame_u8)[None], None)
        twice, _ = filt(once, None)
        np.testing.assert_array_equal(np.asarray(twice[0]), frame_u8)


class TestGaussianBlur:
    @pytest.mark.parametrize("ksize,sigma", [(3, 0.0), (9, 0.0), (9, 2.0), (5, 1.5)])
    def test_matches_cv2(self, frame_u8, ksize, sigma):
        f = to_float(jnp.asarray(frame_u8))
        filt = get_filter("gaussian_blur", ksize=ksize, sigma=sigma)
        ours = apply_one(filt, np.asarray(f))
        ref = cv2.GaussianBlur(
            np.asarray(f, dtype=np.float32), (ksize, ksize), sigma,
            borderType=cv2.BORDER_REFLECT_101,
        )
        np.testing.assert_allclose(ours, ref, atol=2e-5)

    def test_preserves_mean(self, batch_f32):
        filt = get_filter("gaussian_blur", ksize=9, sigma=2.0)
        out, _ = filt(jnp.asarray(batch_f32), None)
        # Blur is an average with reflect borders: interior mass preserved.
        assert abs(float(jnp.mean(out)) - float(np.mean(batch_f32))) < 1e-2


class TestSobel:
    def test_gradients_match_cv2(self, frame_u8):
        from dvf_tpu.ops.conv import sobel_gradients

        gray = cv2.cvtColor(frame_u8, cv2.COLOR_RGB2GRAY).astype(np.float32) / 255.0
        gx, gy = sobel_gradients(jnp.asarray(gray)[None, ..., None])
        ref_gx = cv2.Sobel(gray, cv2.CV_32F, 1, 0, ksize=3, borderType=cv2.BORDER_REFLECT_101)
        ref_gy = cv2.Sobel(gray, cv2.CV_32F, 0, 1, ksize=3, borderType=cv2.BORDER_REFLECT_101)
        np.testing.assert_allclose(np.asarray(gx[0, ..., 0]), ref_gx, atol=1e-4)
        np.testing.assert_allclose(np.asarray(gy[0, ..., 0]), ref_gy, atol=1e-4)

    def test_flat_image_is_zero(self):
        flat = np.full((1, 32, 32, 3), 0.5, dtype=np.float32)
        filt = get_filter("sobel")
        out, _ = filt(jnp.asarray(flat), None)
        np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-6)


def _bilateral_numpy(img, d, sigma_color, sigma_space):
    r = d // 2
    pad = np.pad(img, ((r, r), (r, r), (0, 0)), mode="reflect")
    h, w, _ = img.shape
    num = np.zeros_like(img)
    den = np.zeros((h, w, 1), dtype=img.dtype)
    for dy in range(-r, r + 1):
        for dx in range(-r, r + 1):
            sw = np.exp(-(dy * dy + dx * dx) / (2 * sigma_space ** 2))
            shifted = pad[r + dy : r + dy + h, r + dx : r + dx + w]
            diff = shifted - img
            wgt = sw * np.exp(-np.sum(diff * diff, -1, keepdims=True) / (2 * sigma_color ** 2))
            num += wgt * shifted
            den += wgt
    return num / den


class TestBilateral:
    def test_matches_numpy_reference(self, frame_u8):
        f = np.asarray(frame_u8, dtype=np.float32) / 255.0
        filt = get_filter("bilateral", d=5, sigma_color=0.1, sigma_space=2.0)
        ours = apply_one(filt, f)
        ref = _bilateral_numpy(f, 5, 0.1, 2.0)
        np.testing.assert_allclose(ours, ref, atol=1e-5)

    def test_large_sigma_color_approaches_gaussian(self, frame_u8):
        """As sigma_color→∞ the range kernel is 1 and bilateral == spatial blur."""
        f = np.asarray(frame_u8, dtype=np.float32) / 255.0
        ours = apply_one(get_filter("bilateral", d=5, sigma_color=1e3, sigma_space=2.0), f)
        ref = _bilateral_numpy(f, 5, 1e3, 2.0)
        np.testing.assert_allclose(ours, ref, atol=1e-5)

    def test_edge_preserved_vs_gaussian(self):
        """A hard edge should survive bilateral better than Gaussian blur."""
        img = np.zeros((1, 32, 32, 3), dtype=np.float32)
        img[:, :, 16:, :] = 1.0
        bi, _ = get_filter("bilateral", d=5, sigma_color=0.05, sigma_space=2.0)(jnp.asarray(img), None)
        ga, _ = get_filter("gaussian_blur", ksize=5, sigma=2.0)(jnp.asarray(img), None)
        edge_col = 15
        bi_softening = float(jnp.abs(bi[0, 16, edge_col, 0] - img[0, 16, edge_col, 0]))
        ga_softening = float(jnp.abs(ga[0, 16, edge_col, 0] - img[0, 16, edge_col, 0]))
        assert bi_softening < ga_softening


class TestChains:
    def test_sobel_bilateral_runs(self, batch_f32):
        filt = get_filter("sobel_bilateral")
        out, _ = filt(jnp.asarray(batch_f32), None)
        assert out.shape == batch_f32.shape
        assert np.isfinite(np.asarray(out)).all()


class TestPointwiseExtras:
    def test_grayscale_matches_cv2(self, frame_u8):
        f = np.asarray(frame_u8, dtype=np.float32) / 255.0
        ours = apply_one(get_filter("grayscale"), f)
        ref = cv2.cvtColor(f, cv2.COLOR_RGB2GRAY)
        np.testing.assert_allclose(ours[..., 0], ref, atol=1e-4)

    def test_uint8_roundtrip(self, frame_u8):
        f = to_float(jnp.asarray(frame_u8))
        back = to_uint8(f)
        np.testing.assert_array_equal(np.asarray(back), frame_u8)


class TestPosterize:
    def test_matches_formula(self, batch_f32):
        filt = get_filter("posterize", levels=4)
        out, _ = filt.fn(jnp.asarray(batch_f32), None)
        want = np.round(np.clip(batch_f32, 0, 1) * 3) / 3
        np.testing.assert_allclose(np.asarray(out), want, atol=1e-6)

    def test_level_count(self, batch_f32):
        filt = get_filter("posterize", levels=3)
        out, _ = filt.fn(jnp.asarray(batch_f32), None)
        assert len(np.unique(np.asarray(out))) <= 3

    def test_rejects_bad_levels(self):
        import pytest as _pytest

        with _pytest.raises(ValueError):
            get_filter("posterize", levels=1)


class TestEmboss:
    def test_matches_numpy_correlation(self, frame_u8):
        from dvf_tpu.utils.image import rgb_to_gray as _gray_jnp

        filt = get_filter("emboss")
        f32 = frame_u8.astype(np.float32) / 255.0
        out = apply_one(filt.fn, f32)
        # Reference: direct correlation on luma with reflect-101 borders.
        kern = np.array([[-2, -1, 0], [-1, 1, 1], [0, 1, 2]], np.float32)
        gray = np.asarray(_gray_jnp(jnp.asarray(f32), keepdims=False))
        pad = np.pad(gray, 1, mode="reflect")
        want = np.zeros_like(gray)
        for dy in range(3):
            for dx in range(3):
                want += kern[dy, dx] * pad[dy:dy + gray.shape[0], dx:dx + gray.shape[1]]
        want = np.clip(want + 0.5, 0, 1)
        np.testing.assert_allclose(out[..., 0], want, atol=1e-5)
        # Broadcast to 3 identical channels.
        assert np.array_equal(out[..., 0], out[..., 1])


class TestCartoon:
    def test_structure(self, frame_u8):
        """Cartoon output: fewer distinct colors than input away from
        edges, darkened along strong edges."""
        filt = get_filter("cartoon", levels=4)
        f32 = frame_u8.astype(np.float32) / 255.0
        out = apply_one(filt.fn, f32)
        assert out.shape == f32.shape
        assert out.min() >= 0.0 and out.max() <= 1.0
        # Edge darkening: mean output <= mean of the posterized smooth
        # (multiplying by (1-edge) can only darken).
        smooth_only = apply_one(
            get_filter("bilateral", d=5, sigma_color=0.15, sigma_space=3.0).fn, f32)
        quant = np.round(np.clip(smooth_only, 0, 1) * 3) / 3
        assert out.mean() <= quant.mean() + 1e-6


def test_cartoon_rejects_bad_levels():
    with pytest.raises(ValueError):
        get_filter("cartoon", levels=1)


def test_cartoon_halo_never_pointwise():
    assert get_filter("cartoon", d=1).halo == 1  # Sobel term needs it
    assert get_filter("cartoon", d=5).halo == 2


def test_sep_conv_impls_agree():
    """The shifted-FMA lowering (default) and the XLA depthwise-conv
    lowering are the same mathematical operator — any divergence means a
    shift/border bug in one of them."""
    import jax

    from dvf_tpu.ops.conv import gaussian_kernel_1d, sep_conv2d

    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.random((2, 37, 53, 3), np.float32))
    for ksize in (3, 5, 9):
        k = gaussian_kernel_1d(ksize, 0.0)
        a = jax.jit(lambda b: sep_conv2d(b, k, k, impl="shift"))(x)
        d = jax.jit(lambda b: sep_conv2d(b, k, k, impl="depthwise"))(x)
        np.testing.assert_allclose(np.asarray(a), np.asarray(d),
                                   atol=1e-5, rtol=1e-5)


def test_equalize_matches_cv2_on_gray():
    """Global histogram equalization reproduces cv2.equalizeHist exactly
    (same cdf-min LUT rounding), per sample in the batch."""
    rng = np.random.default_rng(3)
    img = (rng.normal(120, 40, (3, 40, 56)).clip(0, 255)).astype(np.uint8)
    rgb = np.repeat(img[..., None], 3, -1)
    f = get_filter("equalize", on_gray=True)
    out = np.asarray(f.fn(jnp.asarray(rgb), None)[0])
    for b in range(img.shape[0]):
        want = cv2.equalizeHist(img[b])
        np.testing.assert_array_equal(out[b, :, :, 0], want)
    # Degenerate constant frame: cv2 leaves it unchanged; so do we.
    const = np.full((1, 8, 8, 3), 77, np.uint8)
    np.testing.assert_array_equal(np.asarray(f.fn(jnp.asarray(const), None)[0]), const)


def test_equalize_per_channel_flattens_histogram():
    rng = np.random.default_rng(4)
    # Low-contrast input: values squeezed into [100, 156).
    x = (rng.integers(100, 156, (2, 32, 32, 3))).astype(np.uint8)
    f = get_filter("equalize")
    out = np.asarray(f.fn(jnp.asarray(x), None)[0])
    assert out.shape == x.shape and out.dtype == np.uint8
    # Equalization stretches the squeezed range toward full scale.
    assert out.min() < 20 and out.max() > 235
    # Monotonic: pixel ordering within a channel is preserved.
    b, c = 0, 0
    xv, ov = x[b, :, :, c].ravel(), out[b, :, :, c].ravel()
    order = np.argsort(xv, kind="stable")
    assert (np.diff(ov[order]) >= 0).all()


def test_measured_per_backend_defaults():
    """impl=None resolves to the declared winner for this backend (the
    fused Pallas programs on the CPU for sobel_bilateral and gauss-k9);
    an explicit impl always pins, and unmeasured cases keep the
    conservative default."""
    import pytest

    from dvf_tpu.ops import get_filter

    # CPU winners (this suite forces the cpu backend in conftest).
    assert "pallas" in get_filter("sobel_bilateral").name
    assert "pallas" in get_filter("gaussian_blur").name          # k=9
    # Small kernel: shift on both backends.
    assert "pallas" not in get_filter("gaussian_blur", ksize=3).name
    # Explicit impl pins.
    assert "pallas" not in get_filter("sobel_bilateral", impl="chain").name
    assert "pallas" not in get_filter("gaussian_blur", impl="shift").name
    with pytest.raises(ValueError, match="impl"):
        get_filter("sobel_bilateral", impl="nope")

    from dvf_tpu.ops.registry import measured_default

    assert measured_default({"cpu": "a"}, fallback="b") == "a"
    assert measured_default({"tpu": "a"}, fallback="b") == "b"
    with pytest.raises(ValueError, match="pallas"):
        get_filter("gaussian_blur", impl="palas")


# MEASURED_DEFAULTS key -> (factory, fixed kwargs, the argument the
# entry's impl goes to, what impl=None resolves to on the CPU).
_DEFAULT_SITES = {
    "bilateral": ("bilateral", {}, "impl", "jnp"),
    "sobel_bilateral": ("sobel_bilateral", {}, "impl", "pallas"),
    "flow_warp": ("flow_warp", {}, "warp_impl", "gather"),
    "flow_inner": ("flow_warp", {"warp_impl": "pallas"}, "inner_warp",
                   "gather"),
    "gaussian_blur_k9": ("gaussian_blur", {"ksize": 9}, "impl", "pallas"),
    "gaussian_blur_small": ("gaussian_blur", {"ksize": 3}, "impl", "shift"),
    "espcn_fast": ("super_resolution", {}, "fast_convs", "ref"),
    "clahe": ("clahe", {}, "impl", "sort"),
    "equalize": ("equalize", {}, "impl", "sort"),
}


def _built(key, impl=None):
    """What the factory behind ``key`` builds with ``impl`` pinned (None =
    the default), as something two builds can be compared by: the name,
    and for ESPCN (one name for both forms) the traced program."""
    import jax

    name, kwargs, arg, _ = _DEFAULT_SITES[key]
    if impl is not None:
        kwargs = {**kwargs,
                  arg: (impl == "fast") if key == "espcn_fast" else impl}
    filt = get_filter(name, **kwargs)
    if key != "espcn_fast":
        return filt.name
    state = filt.init_state((1, 8, 8, 3), jnp.float32)
    return str(jax.make_jaxpr(filt.fn)(
        jnp.zeros((1, 8, 8, 3), jnp.float32), state))


def test_default_sites_cover_the_table():
    from dvf_tpu.ops.registry import MEASURED_DEFAULTS

    assert set(_DEFAULT_SITES) == set(MEASURED_DEFAULTS)


@pytest.mark.parametrize("key", sorted(_DEFAULT_SITES))
def test_measured_default_entry_holds_winners_its_factory_accepts(key):
    """An entry is ``winners`` and ``fallback`` and nothing else, every
    impl it names is one its factory builds, and ``impl=None`` on the CPU
    builds what it built before the table lost its harness fields."""
    from dvf_tpu.ops.registry import MEASURED_DEFAULTS

    entry = MEASURED_DEFAULTS[key]
    assert set(entry) == {"winners", "fallback"}
    assert set(entry["winners"]) <= {"tpu", "cpu"}
    impls = set(entry["winners"].values()) | {entry["fallback"]}
    built = {impl: _built(key, impl) for impl in sorted(impls)}
    if len(impls) > 1:
        assert len(set(built.values())) == len(impls)   # pins tell apart
    on_cpu = _DEFAULT_SITES[key][3]
    assert entry["winners"].get("cpu", entry["fallback"]) == on_cpu
    assert _built(key) == _built(key, on_cpu)


def test_median_blur_matches_cv2():
    """median_blur == cv2.medianBlur(k=3) exactly (BORDER_REPLICATE,
    median-of-9 sorting network; median commutes with the uint8<->float
    mapping, so the float path reproduces the uint8 golden bit-exactly)."""
    rng = np.random.RandomState(3)
    f = get_filter("median_blur")
    for shape in [(48, 64), (31, 37)]:
        img = rng.randint(0, 255, (*shape, 3), np.uint8)
        want = cv2.medianBlur(img, 3)
        got, _ = f(jnp.asarray(img[None], jnp.float32) / 255.0, None)
        got8 = np.round(np.asarray(got[0]) * 255.0).astype(np.uint8)
        np.testing.assert_array_equal(got8, want)
    with pytest.raises(ValueError, match="ksize=3"):
        get_filter("median_blur", ksize=5)


def test_clahe_matches_cv2():
    """CLAHE == cv2.createCLAHE to within the 1-step interpolation
    rounding tolerance (cv2 interpolates LUT values in float and
    saturate-casts): per-tile sort-based histograms, cv2's exact
    clip/redistribute (uniform batch + strided residual), bilinear
    tile-LUT lattice, reflect pad-and-crop for non-divisible geometry."""
    rng = np.random.RandomState(7)
    for clip, grid, shape in [(2.0, 8, (64, 64)), (2.0, 8, (96, 128)),
                              (4.0, 4, (100, 120)), (40.0, 8, (64, 96)),
                              (2.0, 8, (61, 83))]:
        img = (rng.randint(0, 255, shape, np.uint8) // 3 + 60).astype(np.uint8)
        ref = cv2.createCLAHE(clipLimit=clip,
                              tileGridSize=(grid, grid)).apply(img)
        f = get_filter("clahe", clip_limit=clip, grid=grid, on_gray=True)
        got, _ = f(jnp.asarray(img, jnp.float32)[None, ..., None] / 255.0,
                   None)
        got8 = np.round(np.asarray(got[0, ..., 0]) * 255).astype(np.uint8)
        diff = np.abs(got8.astype(int) - ref.astype(int))
        assert diff.max() <= 1, (clip, grid, shape, diff.max())

    # Color path: per-channel, uint8 passthrough, shape-preserving.
    batch = rng.randint(0, 255, (2, 40, 48, 3), np.uint8)
    out, _ = get_filter("clahe")(jnp.asarray(batch), None)
    assert out.shape == batch.shape and out.dtype == jnp.uint8

    with pytest.raises(ValueError, match="grid"):
        get_filter("clahe", grid=0)
    with pytest.raises(ValueError, match="clip_limit"):
        get_filter("clahe", clip_limit=0.0)


def test_canny_matches_cv2():
    """Canny vs cv2.Canny: interior IoU >= 0.99 across thresholds, L1/L2
    magnitudes, and swapped-threshold normalization (bit-exactness is not
    the contract — cv2's integer NMS tangent ties and its BORDER_REPLICATE
    internal Sobel differ from this library's conventions at the 1-px
    frame), plus the structural properties NMS/hysteresis guarantee."""
    rng = np.random.RandomState(3)
    for t1, t2, l2, blur in [(100, 200, True, 3), (50, 150, True, 5),
                             (100, 200, False, 3), (200, 100, True, 3)]:
        img = cv2.GaussianBlur(
            rng.randint(0, 255, (90, 130), np.uint8), (blur, blur), 0)
        ref = cv2.Canny(img, t1, t2, L2gradient=l2) > 0
        f = get_filter("canny", threshold1=t1, threshold2=t2,
                       l2_gradient=l2)
        rgb = np.repeat(img[..., None], 3, -1).astype(np.float32) / 255.0
        got, _ = f(jnp.asarray(rgb)[None], None)
        ours = np.asarray(got[0, ..., 0]) > 0.5
        ri, oi = ref[2:-2, 2:-2], ours[2:-2, 2:-2]
        iou = (ri & oi).sum() / max(1, (ri | oi).sum())
        assert iou >= 0.99, (t1, t2, l2, blur, iou)
        # Binary white-on-black output, broadcast across channels.
        vals = np.unique(np.asarray(got))
        assert set(vals.tolist()) <= {0.0, 1.0}
        assert np.array_equal(np.asarray(got[0, ..., 0]),
                              np.asarray(got[0, ..., 1]))

    # Flat image -> no edges; a strong step -> edges survive hysteresis.
    flat = np.full((1, 32, 32, 3), 0.5, np.float32)
    out, _ = get_filter("canny")(jnp.asarray(flat), None)
    assert float(out.sum()) == 0.0
    step = np.zeros((1, 32, 32, 3), np.float32)
    step[:, :, 16:] = 1.0
    out, _ = get_filter("canny")(jnp.asarray(step), None)
    assert float(out.sum()) > 0.0
