"""Spatial parallelism (halo exchange) and Pallas kernel tests.

Golden rule: an H-sharded filter must produce bit-comparable output to the
same filter unsharded — the halo exchange plus reflect-101 edge handling
must be invisible to the user (reference semantics are single-device).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dvf_tpu.ops import get_filter
from dvf_tpu.ops.bilateral import bilateral_nhwc
from dvf_tpu.ops.pallas_kernels import bilateral_nhwc_pallas, _pick_tile_h
from dvf_tpu.parallel.halo import spatial_filter
from dvf_tpu.parallel.mesh import MeshConfig, make_mesh


@pytest.fixture(scope="module")
def batch():
    return jax.random.uniform(jax.random.PRNGKey(7), (2, 32, 40, 3), jnp.float32)


SPATIAL_CASES = [
    ("gaussian_blur", dict(ksize=9)),
    ("gaussian_blur", dict(ksize=3)),
    ("sobel", {}),
    ("bilateral", {}),
    ("sharpen", {}),
    ("sobel_bilateral", {}),   # chained radii compose (1 + 2)
    ("invert", {}),            # halo 0: no exchange at all
]


@pytest.mark.parametrize("name,kw", SPATIAL_CASES)
def test_spatial_filter_matches_unsharded(name, kw, batch):
    mesh = make_mesh(MeshConfig(data=2, space=4))
    f = get_filter(name, **kw)
    sf = spatial_filter(f, mesh)
    want, _ = f.fn(batch, None)
    got, _ = jax.jit(lambda b: sf.fn(b, None))(batch)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_spatial_filter_space_only_mesh():
    tall = jax.random.uniform(jax.random.PRNGKey(8), (2, 64, 40, 3), jnp.float32)
    mesh = make_mesh(MeshConfig(space=8))
    f = get_filter("gaussian_blur", ksize=9)
    sf = spatial_filter(f, mesh)
    want, _ = f.fn(tall, None)
    got, _ = jax.jit(lambda b: sf.fn(b, None))(tall)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_spatial_filter_slab_too_thin_raises():
    mesh = make_mesh(MeshConfig(space=8))
    f = get_filter("gaussian_blur", ksize=9)  # r=4, but 32/8 = 4 rows/shard
    sf = spatial_filter(f, mesh)
    thin = jnp.zeros((2, 32, 40, 3))
    with pytest.raises(ValueError, match="stencil radius"):
        jax.jit(lambda b: sf.fn(b, None))(thin)


def test_spatial_filter_requires_halo():
    mesh = make_mesh(MeshConfig(space=2))
    from dvf_tpu.api.filter import stateless

    unknown = stateless("mystery", lambda b: b)  # halo=None
    with pytest.raises(ValueError, match="halo"):
        spatial_filter(unknown, mesh)


def test_spatial_filter_rejects_stateful():
    mesh = make_mesh(MeshConfig(space=2))
    with pytest.raises(ValueError, match="stateless"):
        spatial_filter(get_filter("flow_warp"), mesh)


def test_chain_halo_composition():
    assert get_filter("invert").halo == 0
    assert get_filter("gaussian_blur", ksize=9).halo == 4
    assert get_filter("sobel").halo == 1
    assert get_filter("bilateral", d=5).halo == 2
    assert get_filter("sobel_bilateral", d=5).halo == 3


def test_chain_per_stage_exchange_exact_for_asymmetric_stages():
    """A fused summed-radius exchange is NOT exact at the global border
    when an intermediate isn't reflection-symmetric (a directional shift
    is the canonical counterexample). Per-stage exchange (default for
    chains) must match the unsharded chain bit-for-bit everywhere."""
    from dvf_tpu.api.filter import FilterChain, stateless

    def shift_down(batch):
        # y[i] = x[i-1] with reflect-101 border — asymmetric on purpose.
        ext = jnp.pad(batch, ((0, 0), (1, 1), (0, 0), (0, 0)), mode="reflect")
        return ext[:, :-2]

    shift = stateless("shift_down", shift_down, halo=1)
    chain = FilterChain(shift, shift)
    x = jax.random.uniform(jax.random.PRNGKey(3), (2, 32, 8, 3), jnp.float32)
    want, _ = chain.fn(x, None)

    mesh = make_mesh(MeshConfig(data=2, space=4))
    per_stage = spatial_filter(chain, mesh)  # auto: per-stage for chains
    got, _ = jax.jit(lambda b: per_stage.fn(b, None))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)

    fused = spatial_filter(chain, mesh, per_stage=False)
    got_fused, _ = jax.jit(lambda b: fused.fn(b, None))(x)
    # The fused shortcut is demonstrably wrong at the border for this
    # chain — the per-stage default exists because of exactly this.
    assert not np.allclose(np.asarray(got_fused), np.asarray(want), atol=1e-6)


# ------------------------------------------------------- engine halo path

ENGINE_HALO_CASES = [
    ("gaussian_blur", dict(ksize=9)),
    ("sobel_bilateral", {}),
]


@pytest.mark.parametrize("name,kw", ENGINE_HALO_CASES)
def test_engine_routes_stencils_through_explicit_halo(name, kw, rng):
    """On a space>1 mesh the Engine must run stencil filters via the
    explicit ppermute halo path (not GSPMD auto-partitioning), with output
    equal to the single-device engine."""
    from dvf_tpu.runtime.engine import Engine

    x = rng.integers(0, 255, (4, 64, 48, 3), np.uint8)
    mesh = make_mesh(MeshConfig(data=2, space=4))
    eng = Engine(get_filter(name, **kw), mesh=mesh)
    eng.compile(x.shape, np.uint8)
    assert eng._exec_filter.name.startswith("spatial("), eng._exec_filter.name
    got = np.asarray(eng.submit(x))

    ref = Engine(get_filter(name, **kw), mesh=make_mesh(MeshConfig()))
    want = np.asarray(ref.submit(x))
    # uint8 out; sharded vs unsharded may differ by 1 on float->u8 ties.
    assert np.abs(got.astype(int) - want.astype(int)).max() <= 1


def test_engine_replicates_h_when_halo_unusable(rng):
    """Stateful / unknown-radius filters on a space mesh keep H replicated
    (correctness first) instead of GSPMD-partitioning the stencil."""
    from dvf_tpu.runtime.engine import Engine

    x = rng.integers(0, 255, (4, 48, 32, 3), np.uint8)
    mesh = make_mesh(MeshConfig(data=2, space=4))
    eng = Engine(get_filter("flow_warp"), mesh=mesh)
    eng.compile(x.shape, np.uint8)
    assert eng._exec_filter is eng.filter
    spec = eng._sharding.spec
    assert len(spec) < 2 or spec[1] is None  # H axis not sharded
    got = np.asarray(eng.submit(x))

    ref = Engine(get_filter("flow_warp"), mesh=make_mesh(MeshConfig()))
    want = np.asarray(ref.submit(x))
    assert np.abs(got.astype(int) - want.astype(int)).max() <= 1


def test_engine_pointwise_keeps_gspmd_sharding(rng):
    """halo == 0: no halo traffic exists, plain GSPMD H-sharding stays."""
    from dvf_tpu.runtime.engine import Engine

    x = rng.integers(0, 255, (4, 64, 32, 3), np.uint8)
    mesh = make_mesh(MeshConfig(data=2, space=4))
    eng = Engine(get_filter("invert"), mesh=mesh)
    eng.compile(x.shape, np.uint8)
    assert eng._exec_filter is eng.filter
    got = np.asarray(eng.submit(x))
    np.testing.assert_array_equal(got, 255 - x)


# ---------------------------------------------------------------- pallas

def test_pallas_bilateral_matches_jnp(batch):
    want = bilateral_nhwc(batch)
    got = bilateral_nhwc_pallas(batch, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_pallas_bilateral_params(batch):
    want = bilateral_nhwc(batch, d=3, sigma_color=0.2, sigma_space=5.0)
    got = bilateral_nhwc_pallas(batch, d=3, sigma_color=0.2, sigma_space=5.0, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_pallas_tile_picker():
    # Mosaic rejects output blocks whose second-to-last dim is neither a
    # multiple of the 8-row sublane tile nor the whole dimension (the
    # round-3 on-chip A/Bs all ERR'd on tile 15 over 1080) — every pick
    # must be 8-aligned, whole-H, or trigger row padding.
    assert _pick_tile_h(1080) == (24, 1080)   # largest 8-aligned divisor
    assert _pick_tile_h(720) == (24, 720)
    assert _pick_tile_h(32) == (32, 32)       # short image: one whole tile
    assert _pick_tile_h(7) == (7, 7)
    assert _pick_tile_h(540) == (32, 544)     # no aligned divisor: pad
    assert _pick_tile_h(68) == (32, 96)


def test_pallas_bilateral_padded_rows():
    """H with no 8-aligned divisor exercises the row-padding path; the
    pad must be invisible in the output (sliced off, never read by a
    valid row)."""
    rng = np.random.default_rng(7)
    batch = jnp.asarray(rng.random((1, 68, 40, 3), dtype=np.float32))
    want = bilateral_nhwc(batch)
    got = bilateral_nhwc_pallas(batch, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_pallas_sep_blur_padded_rows():
    """Row/col alignment-padding path of the fused separable blur (H=68
    has no 8-aligned divisor; W=40 is no lane multiple)."""
    from dvf_tpu.ops.conv import gaussian_kernel_1d, sep_conv2d
    from dvf_tpu.ops.pallas_kernels import sep_blur_nhwc_pallas

    rng = np.random.default_rng(11)
    batch = jnp.asarray(rng.random((1, 68, 40, 3), dtype=np.float32))
    kern = gaussian_kernel_1d(9, 0.0)
    want = sep_conv2d(batch, kern, kern)
    got = sep_blur_nhwc_pallas(batch, kern, kern, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_pallas_fused_sobel_bilateral_padded_rows():
    """Same padded path for the fused kernel — it is the one kernel that
    slices relative to the (now oversized) slab END for Sobel, so border
    rows at an unaligned H are the regression surface."""
    from dvf_tpu.ops.pallas_kernels import sobel_bilateral_nhwc_pallas

    rng = np.random.default_rng(13)
    batch = jnp.asarray(rng.random((1, 68, 40, 3), dtype=np.float32))
    chain = get_filter("sobel_bilateral", impl="chain")
    want, _ = chain.fn(batch, None)
    got = sobel_bilateral_nhwc_pallas(batch, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# geometry -> (batch, h, w or None for "W + 2R a lane multiple", tile_h)
_FUSED_GEOMETRIES = {
    "padded_rows": (2, 36, 48, None),          # h_pad > h: filler rows under the last tile, filler columns
    "no_filler_cols": (2, 32, None, None),     # the column concatenate has no fourth part
    "w100_tile8": (2, 16, 100, 8),             # one lane tile, cut at column 100; a tile of one 8-row strip
    "w1968_tile24": (1, 48, 1968, 24),         # the cell's tile; five whole 384-lane chunks and a 48-column rest
    "whole_h_20": (2, 20, 130, None),          # one whole-H tile: two 8-row strips and a 4-row rest
}


@pytest.mark.parametrize("form", ["whole_tile", "strips"])
@pytest.mark.parametrize("d", [3, 5, 9])
@pytest.mark.parametrize("geometry", list(_FUSED_GEOMETRIES))
def test_pallas_fused_sobel_bilateral_one_plane_form(d, geometry, form, monkeypatch):
    """The one-plane form (PR 44: luma first, then the reflected strips and
    zero filler an axis a concatenate) against the jnp chain, where the
    filler is on the path (36 rows: ``h_pad > h``, filler rows under the
    last tile and filler columns beside every one) and where the column
    filler has zero width (W + 2R a multiple of 128, so the column
    concatenate has no fourth part). The map is made once: the three
    channels of the result are bit-equal.

    ``strips`` is the form the compiled kernel takes (PR 46: the taps run
    over 8 x 384 pieces of the tile, on column-shifted copies of the edge
    map), pinned here in interpret mode, which by itself keeps the whole
    tile at once: at strip edges that do not divide evenly (a width under
    one chunk, 1920 + 48, a last row strip of 4) and at every ``dy`` of a
    3, 5 and 9 window (the rows' sublane offsets)."""
    from dvf_tpu.ops import pallas_kernels as pk

    if form == "strips":
        monkeypatch.setattr(pk, "_strip_shape", lambda interpret: pk._STRIP)
    R = d // 2 + 1
    b, h, w, tile_h = _FUSED_GEOMETRIES[geometry]
    w = w or 128 - 2 * R
    plan = pk.sobel_bilateral_plan((b, h, w, 3), d, tile_h, interpret=True)
    assert (plan["h_pad"] > h) == (geometry == "padded_rows")
    assert (plan["w_aligned"] == w + 2 * R) == (geometry == "no_filler_cols")
    assert plan["strip"] == (list(pk._STRIP) if form == "strips" else None)
    rng = np.random.default_rng(17 + d)
    batch = jnp.asarray(rng.random((b, h, w, 3), dtype=np.float32))
    want, _ = get_filter("sobel_bilateral", d=d, impl="chain").fn(batch, None)
    got = np.asarray(pk.sobel_bilateral_nhwc_pallas(batch, d=d, tile_h=tile_h, interpret=True))
    assert got.shape == (b, h, w, 3)
    np.testing.assert_allclose(got, np.asarray(want), atol=1e-5)
    assert np.array_equal(got[..., 0], got[..., 1]) and np.array_equal(got[..., 0], got[..., 2])


def test_pallas_filter_registered(batch):
    f = get_filter("bilateral_pallas", interpret=True)
    got, _ = f.fn(batch, None)
    want = bilateral_nhwc(batch)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_pallas_fused_sobel_bilateral_matches_chain(batch):
    """The fused kernel reproduces FilterChain(sobel, bilateral) exactly —
    including borders (Sobel magnitude commutes with reflect-101)."""
    from dvf_tpu.ops.pallas_kernels import sobel_bilateral_nhwc_pallas

    # impl="chain" pinned: the unpinned name resolves to the measured
    # per-backend winner, which on CPU IS the pallas kernel — unpinned,
    # this equivalence test would compare pallas to itself.
    chain = get_filter("sobel_bilateral", impl="chain")
    want, _ = chain.fn(jnp.asarray(batch), None)
    got = sobel_bilateral_nhwc_pallas(jnp.asarray(batch), interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_pallas_fused_sobel_bilateral_registered(batch):
    f = get_filter("sobel_bilateral_pallas", interpret=True)
    got, _ = f.fn(jnp.asarray(batch), None)
    chain = get_filter("sobel_bilateral", impl="chain")
    want, _ = chain.fn(jnp.asarray(batch), None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert f.halo == 3  # bilateral r=2 + sobel support 1


def test_pallas_warp_matches_gather_golden(rng):
    from dvf_tpu.ops.flow import warp_by_flow
    from dvf_tpu.ops.pallas_kernels import warp_bounded_pallas

    img = rng.random((2, 24, 32, 3)).astype(np.float32)
    flow = (rng.random((2, 24, 32, 2)).astype(np.float32) - 0.5) * 7.0
    want = warp_by_flow(jnp.asarray(img), jnp.clip(jnp.asarray(flow), -4, 4))
    got = warp_bounded_pallas(jnp.asarray(img), jnp.asarray(flow),
                              max_disp=4, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-6)


def test_pallas_warp_unaligned_height_and_width(rng):
    """H with no 8-aligned divisor + W that is no lane multiple exercise
    both alignment-padding paths (incl. the flow input's col pad — the
    flow DMA copies full width, so its width must be lane-aligned on
    TPU; round-4 code-review finding)."""
    from dvf_tpu.ops.flow import warp_by_flow
    from dvf_tpu.ops.pallas_kernels import warp_bounded_pallas

    img = rng.random((2, 36, 40, 3)).astype(np.float32)
    flow = (rng.random((2, 36, 40, 2)).astype(np.float32) - 0.5) * 6.0
    want = warp_by_flow(jnp.asarray(img), jnp.clip(jnp.asarray(flow), -4, 4))
    got = warp_bounded_pallas(jnp.asarray(img), jnp.asarray(flow),
                              max_disp=4, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-6)


def test_pallas_warp_border_clamp_matches(rng):
    """Edge-padding reproduces the golden's coordinate clamping."""
    from dvf_tpu.ops.flow import warp_by_flow
    from dvf_tpu.ops.pallas_kernels import warp_bounded_pallas

    img = rng.random((1, 8, 16, 3)).astype(np.float32)
    flow = np.full((1, 8, 16, 2), 3.7, np.float32)
    want = warp_by_flow(jnp.asarray(img), jnp.asarray(flow))
    got = warp_bounded_pallas(jnp.asarray(img), jnp.asarray(flow),
                              max_disp=4, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-6)


def test_flow_warp_pallas_impl_delivers(rng):
    """flow_warp(warp_impl='pallas') runs end-to-end through the Engine."""
    from dvf_tpu.runtime.engine import Engine

    eng = Engine(get_filter("flow_warp", levels=1, win_size=7, n_iters=1,
                            flow_scale=1, warp_impl="pallas", max_disp=2))
    x = rng.integers(0, 255, (2, 32, 32, 3), np.uint8)
    out1 = np.asarray(eng.submit(x))
    np.testing.assert_array_equal(out1[0], x[0])   # the stream's first
    #   frame passes through; row 1 is row 0 warped onto it
    out2 = np.asarray(eng.submit(x))
    assert out2.shape == x.shape


def test_pallas_sep_blur_matches_sep_conv2d(batch):
    """The fused Pallas separable blur reproduces ops.conv.sep_conv2d
    (same reflect-101 borders, same tap accumulation order)."""
    from dvf_tpu.ops.conv import gaussian_kernel_1d, sep_conv2d
    from dvf_tpu.ops.pallas_kernels import sep_blur_nhwc_pallas

    for ksize in (3, 9):
        k = gaussian_kernel_1d(ksize, 0.0)
        want = sep_conv2d(jnp.asarray(batch), k, k)
        got = sep_blur_nhwc_pallas(jnp.asarray(batch), k, k, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # Asymmetric taps: rh != rw exercises the per-axis halo/slice paths —
    # an H/W swap in the kernel would pass every square-kernel case.
    k3, k9 = gaussian_kernel_1d(3, 0.0), gaussian_kernel_1d(9, 0.0)
    want = sep_conv2d(jnp.asarray(batch), k3, k9)
    got = sep_blur_nhwc_pallas(jnp.asarray(batch), k3, k9, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_pallas_gaussian_filter_registered(batch):
    f = get_filter("gaussian_blur_pallas", ksize=9, interpret=True)
    got, _ = f.fn(jnp.asarray(batch), None)
    # impl="shift" pinned: unpinned k=9 resolves to pallas on CPU — the
    # equivalence would be vacuous (see sobel_bilateral test above).
    ref = get_filter("gaussian_blur", ksize=9, impl="shift")
    want, _ = ref.fn(jnp.asarray(batch), None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert f.halo == 4


def test_equalize_space_sharded_matches_replicated():
    """The global-reduction parallel pattern: per-shard partial cdf + one
    psum over 'space' must equal the single-device whole-frame result
    EXACTLY (counts are additive integers; the LUT sees identical cdfs)."""
    from dvf_tpu.parallel.mesh import MeshConfig, make_mesh
    from dvf_tpu.runtime.engine import Engine

    x = np.random.default_rng(5).integers(0, 255, (4, 64, 48, 3), np.uint8)
    mesh = make_mesh(MeshConfig(data=2, space=4))
    eng = Engine(get_filter("equalize"), mesh=mesh)
    eng.compile(x.shape, np.uint8)
    assert eng._exec_filter.name.startswith("space("), eng._exec_filter.name
    got = np.asarray(eng.submit(x))
    want = np.asarray(
        Engine(get_filter("equalize"), mesh=make_mesh(MeshConfig())).submit(x))
    np.testing.assert_array_equal(got, want)

    # Indivisible H falls back to the replicated path, still exact.
    x2 = np.random.default_rng(6).integers(0, 255, (4, 62, 48, 3), np.uint8)
    eng2 = Engine(get_filter("equalize"), mesh=mesh)
    eng2.compile(x2.shape, np.uint8)
    assert not eng2._exec_filter.name.startswith("space(")
    got2 = np.asarray(eng2.submit(x2))
    want2 = np.asarray(
        Engine(get_filter("equalize"), mesh=make_mesh(MeshConfig())).submit(x2))
    np.testing.assert_array_equal(got2, want2)

    # Indivisible BATCH keeps the space sharding (only the batch axis
    # degrades — the psum scheme needs just H % space == 0).
    x3 = np.random.default_rng(7).integers(0, 255, (3, 64, 48, 3), np.uint8)
    eng3 = Engine(get_filter("equalize"), mesh=mesh)
    eng3.compile(x3.shape, np.uint8)
    assert eng3._exec_filter.name.startswith("space(")
    got3 = np.asarray(eng3.submit(x3))
    want3 = np.asarray(
        Engine(get_filter("equalize"), mesh=make_mesh(MeshConfig())).submit(x3))
    np.testing.assert_array_equal(got3, want3)


def test_pallas_tile_h_variants_numerically_identical(batch):
    """tile_h only changes the grid, never the numerics — the guarantee
    an on-chip tile sweep relies on to wire a measured winner as the
    default tile target."""
    want = np.asarray(bilateral_nhwc_pallas(batch, interpret=True))
    h = batch.shape[1]
    for th in (8, 16, h):  # 8-aligned divisors of the test H, plus whole-H
        if h % th:
            continue
        got = bilateral_nhwc_pallas(batch, tile_h=th, interpret=True)
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-6,
                                   err_msg=f"tile_h={th}")

    from dvf_tpu.ops.pallas_kernels import sobel_bilateral_nhwc_pallas
    want = np.asarray(sobel_bilateral_nhwc_pallas(batch, interpret=True))
    for th in (8, 16, h):
        if h % th:
            continue
        got = sobel_bilateral_nhwc_pallas(batch, tile_h=th, interpret=True)
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-6,
                                   err_msg=f"tile_h={th}")
