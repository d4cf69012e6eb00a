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


# The two forms the flow step serves: the final warp (3 planes, ±4 px, 100
# taps) and the inner warps of the polynomial stack (5 planes, ±2 px, 36).
WARP_FORMS = {"final": (3, 4), "inner": (5, 2)}
# (H, W) -> (a pinned tile or None, what the strip walk meets there); interpret
# mode runs the same 8 x 128 strips as the chip (``warp_plan``'s ``strip``).
WARP_GEOMETRIES = {
    (24, 32): (None, "one tile, one strip a row of strips"),
    (36, 40): (None, "an H that is no multiple of 8 (padded to 40), a W that is no lane multiple"),
    (56, 200): (8, "seven tiles of one row of strips, two strips across (W 200 in 256 lanes)"),
    (100, 136): (None, "one tile of 104 rows (H padded), thirteen strips down, two across"),
}


def _warp_flow(rng, kind, shape, R):
    """Flows that meet the hats' corners: ``random`` straddles the clip,
    ``clip`` sits at and beyond it on both sides, ``zeros`` is +0.0 and
    -0.0, ``integers`` are exact displacements (one hat 1, its neighbours
    0). Multiples of 1/64: the golden adds the flow to the pixel's
    coordinate in float32, and past column 128 a finer fraction is rounded
    there (1.5e-5 a step) where the kernel, which never forms the
    coordinate, keeps it."""
    if kind == "random":
        return np.round((rng.random(shape) - 0.5) * (2 * R + 3) * 64).astype(np.float32) / 64
    if kind == "clip":
        return rng.choice(np.float32([-R - 2.5, -R, R, R + 0.75]), shape)
    if kind == "zeros":
        return rng.choice(np.float32([0.0, -0.0]), shape)
    return rng.integers(-R, R + 1, shape).astype(np.float32)


def _assert_warp_matches(img, flow, R, tile_h=None):
    from dvf_tpu.ops.flow import warp_by_flow
    from dvf_tpu.ops.pallas_kernels import warp_bounded_pallas

    want = warp_by_flow(jnp.asarray(img), jnp.clip(jnp.asarray(flow), -R, R))
    got = warp_bounded_pallas(jnp.asarray(img), jnp.asarray(flow),
                              max_disp=R, tile_h=tile_h, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-6)


@pytest.mark.parametrize("hw", sorted(WARP_GEOMETRIES))
@pytest.mark.parametrize("form", sorted(WARP_FORMS))
def test_pallas_warp_matches_gather_golden(rng, form, hw):
    c, R = WARP_FORMS[form]
    img = rng.random((2, *hw, c)).astype(np.float32)
    _assert_warp_matches(img, _warp_flow(rng, "random", (2, *hw, 2), R), R,
                         tile_h=WARP_GEOMETRIES[hw][0])


@pytest.mark.parametrize("form", sorted(WARP_FORMS))
def test_pallas_warp_under_a_small_vmem_budget(rng, form, monkeypatch):
    """The plan's own pick where a frame's copies outgrow the budget, as
    720 rows do on the chip: several tiles and an H padded to whole ones
    (100 rows as three tiles of 40)."""
    from dvf_tpu.ops import pallas_kernels as pk

    c, R = WARP_FORMS[form]
    shape = (1, 100, 136, c)
    monkeypatch.setattr(pk, "_WARP_VMEM_BUDGET", sum(pk._warp_vmem_bytes(
        40, 16 if R == 4 else 8, c, 2 * R + 2, 256, 256)))
    plan = pk.warp_plan(shape, R, interpret=True)
    assert (plan["tile_h"], plan["h_pad"], plan["grid"]) == (40, 120, [1, 3])
    jax.clear_caches()      # the wrapper's jit was traced under the other budget
    img = rng.random(shape).astype(np.float32)
    _assert_warp_matches(img, _warp_flow(rng, "random", (1, 100, 136, 2), R), R)
    jax.clear_caches()


@pytest.mark.parametrize("hw", [(36, 40), (45, 130)])
@pytest.mark.parametrize("form", sorted(WARP_FORMS))
def test_pallas_warp_unaligned_height_and_width(rng, form, hw):
    """H with no 8-aligned divisor + W that is no lane multiple exercise
    both alignment-padding paths (incl. the flow input's col pad: the
    flow block is the lane-aligned width, so the flow is padded to it)."""
    c, R = WARP_FORMS[form]
    img = rng.random((2, *hw, c)).astype(np.float32)
    _assert_warp_matches(img, _warp_flow(rng, "random", (2, *hw, 2), R), R)


@pytest.mark.parametrize("kind", ["constant", "clip", "zeros", "integers"])
@pytest.mark.parametrize("form", sorted(WARP_FORMS))
def test_pallas_warp_border_clamp_matches(rng, form, kind):
    """Edge-padding reproduces the golden's coordinate clamping, also
    where every weight sits on a hat's corner."""
    c, R = WARP_FORMS[form]
    img = rng.random((1, 16, 136, c)).astype(np.float32)
    flow = (np.full((1, 16, 136, 2), R - 0.3125, np.float32) if kind == "constant"
            else _warp_flow(rng, kind, (1, 16, 136, 2), R))
    _assert_warp_matches(img, flow, R)


@pytest.mark.parametrize("c,R,hw", [(1, 1, (16, 32)), (4, 3, (20, 36)), (2, 8, (24, 40))],
                         ids=["R1", "R3_window_of_one_halo_tile", "R8_window_of_four_row_tiles"])
def test_pallas_warp_other_bounds(rng, c, R, hw):
    """One walk whatever the bound: a window of 2 to 4 row tiles."""
    img = rng.random((1, *hw, c)).astype(np.float32)
    _assert_warp_matches(img, _warp_flow(rng, "random", (1, *hw, 2), R), R)


def _pallas_calls(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_calls(sub, found)
    return found


@pytest.mark.parametrize("hw", [(36, 40), (100, 136)])
@pytest.mark.parametrize("form", sorted(WARP_FORMS))
def test_warp_plan_is_what_the_call_is_built_with(form, hw):
    """The plan a served step states is the ``pallas_call`` that runs: its
    grid, the slab and the copies it holds in VMEM, its operands, its
    limit."""
    from dvf_tpu.ops.pallas_kernels import warp_bounded_pallas, warp_plan

    c, R = WARP_FORMS[form]
    img = jax.ShapeDtypeStruct((2, *hw, c), jnp.float32)
    flow = jax.ShapeDtypeStruct((2, *hw, 2), jnp.float32)
    plan = warp_plan(img.shape, R, interpret=True)
    (call,) = _pallas_calls(jax.make_jaxpr(
        lambda i, f: warp_bounded_pallas(i, f, max_disp=R, interpret=True))(img, flow).jaxpr, [])
    assert call.params["name"] == "warp_bounded"
    assert tuple(call.params["grid_mapping"].grid) == tuple(plan["grid"])
    th, h_pad, slab = plan["tile_h"], plan["h_pad"], plan["slab_rows"]
    assert th % 8 == 0 and h_pad % th == 0 and h_pad >= hw[0] and plan["grid"][1] == h_pad // th
    assert plan["taps"] == (2 * R + 2) ** 2 and plan["planes"] == c
    x, fl = (v.aval.shape for v in call.invars)
    assert x == (2, c, h_pad - th + slab, plan["w_aligned"])
    assert fl == (2, 2, h_pad, plan["w_out"])
    assert call.outvars[0].aval.shape == (2, c, h_pad, plan["w_out"])
    scratch = [a.shape for a in call.params["grid_mapping"].scratch_avals][:2]     # then the semaphore
    assert [int(np.prod(shape)) * 4 for shape in scratch] == [
        plan["vmem_scratch_bytes"], plan["vmem_shifted_bytes"]]
    rows, lanes = plan["strip"]
    assert scratch[1] == (2 * R + 2, plan["w_out"] // lanes, c, slab, lanes) and rows == 8
    assert all(0 < ky < 8 for ky in plan["rows_in_registers"])
    assert plan["vmem_limit_bytes"] is None and not dict(call.params["compiler_params"])


def test_warp_plan_states_the_limit_the_call_compiles_under():
    """Unpinned, the plan shrinks the tile until a grid step fits Mosaic's
    default scoped VMEM (a 4K-wide frame: 8 rows); a pinned tile that
    outgrows it (96 rows: 41 MB of copies) gets the raised limit, stated
    in the plan and carried by the call."""
    from dvf_tpu.ops.pallas_kernels import warp_bounded_pallas, warp_plan

    img = jax.ShapeDtypeStruct((1, 96, 4000, 3), jnp.float32)
    flow = jax.ShapeDtypeStruct((1, 96, 4000, 2), jnp.float32)
    auto = warp_plan(img.shape, 4, interpret=False)
    assert auto["tile_h"] == 8 and auto["vmem_limit_bytes"] is None
    plan = warp_plan(img.shape, 4, tile_h=96, interpret=False)
    assert plan["vmem_shifted_bytes"] > 16 * 2 ** 20 and plan["vmem_limit_bytes"] == 64 * 2 ** 20
    (call,) = _pallas_calls(jax.make_jaxpr(lambda i, f: warp_bounded_pallas(
        i, f, max_disp=4, tile_h=96, interpret=False))(img, flow).jaxpr, [])
    (params,) = dict(call.params["compiler_params"]).values()
    assert params.vmem_limit_bytes == plan["vmem_limit_bytes"]
    assert warp_plan(img.shape, 4, tile_h=96, interpret=True)["vmem_limit_bytes"] is None


def test_warp_plan_at_the_cell_shapes():
    """What the plan picks from the four shapes flow_720p's step calls the
    kernel with: the tile that costs least among those whose grid step fits
    Mosaic's default scoped VMEM (tests/test_tpu_compile.py compiles them),
    the padded H, the strip."""
    from dvf_tpu.ops.pallas_kernels import warp_plan

    got = {hw: warp_plan((64, *hw, c), R) for hw, (c, R) in {
        (720, 1280): (3, 4), (360, 640): (5, 2), (180, 320): (5, 2), (90, 160): (5, 2)}.items()}
    assert [(p["tile_h"], p["h_pad"], p["grid"]) for p in got.values()] == [
        (48, 720, [64, 15]), (72, 360, [64, 5]), (184, 184, [64, 1]), (96, 96, [64, 1])]
    assert got[(720, 1280)]["rows_in_registers"] == [2, 6]
    assert got[(360, 640)]["rows_in_registers"] == [2, 4]
    for plan in got.values():
        assert plan["strip"] == [8, 128] and plan["vmem_limit_bytes"] is None
        assert plan["vmem_scratch_bytes"] + plan["vmem_shifted_bytes"] < 12 * 2 ** 20
    # a 1080p frame's copies are 0.26 MB a row of the tile: 24 rows fit the budget, 48 would not
    assert warp_plan((64, 1080, 1920, 3), 4)["tile_h"] == 24


@pytest.mark.parametrize("tile_h,ok", [(8, True), (24, True), (12, False), (7, False)])
def test_warp_tile_pins(tile_h, ok):
    from dvf_tpu.ops.pallas_kernels import warp_plan

    if ok:
        assert warp_plan((1, 48, 32, 3), 2, tile_h=tile_h)["tile_h"] == tile_h
    else:
        with pytest.raises(ValueError):
            warp_plan((1, 48, 32, 3), 2, tile_h=tile_h)


def test_flow_warp_states_its_kernels():
    """``flow_warp`` lists its step's ``warp_bounded`` calls in step order:
    three inner shapes, coarsest first, then the final warp; none where
    the warps are gathers."""
    from dvf_tpu.ops.pallas_kernels import warp_plan

    shape = (4, 64, 96, 3)
    block = get_filter("flow_warp", warp_impl="pallas", inner_warp="pallas").kernel_plan(shape)
    assert block["kernel"] == "warp_bounded" and block["kernels"] == ["warp_bounded"]
    assert block["impl"] == "pallas"
    assert [(k["role"], k["level"], k["count"], k["planes"], k["max_disp"], k["taps"])
            for k in block["calls"]] == [
        ("inner", 2, 3, 5, 2, 36), ("inner", 1, 3, 5, 2, 36), ("inner", 0, 3, 5, 2, 36),
        ("final", None, 1, 3, 4, 100)]
    interpret = jax.default_backend() != "tpu"
    want = warp_plan((4, 32, 48, 5), 2, interpret=interpret)
    assert {k: v for k, v in block["calls"][2].items() if k in want} == want
    assert {k: v for k, v in block["calls"][3].items()
            if k not in ("role", "level", "count")} == warp_plan(shape, 4, interpret=interpret)
    only_final = get_filter("flow_warp", warp_impl="pallas", inner_warp="gather").kernel_plan(shape)
    assert [k["role"] for k in only_final["calls"]] == ["final"]
    assert get_filter("flow_warp", warp_impl="gather").kernel_plan(shape) is None


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
