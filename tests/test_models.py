"""Model-family tests: style net forward, VGG features, TP sharding specs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dvf_tpu.models import (
    StyleNetConfig,
    apply_style_net,
    init_style_net,
    param_pspecs,
)
from dvf_tpu.models.layers import gram_matrix, upsample_nearest
from dvf_tpu.models.vgg import VGGConfig, init_vgg, vgg_features, vgg_param_pspecs
from dvf_tpu.parallel.mesh import MeshConfig, make_mesh

SMALL = StyleNetConfig(base_channels=8, n_residual=2)


def test_style_net_shape_and_range():
    params = init_style_net(jax.random.PRNGKey(0), SMALL)
    x = jnp.linspace(0, 1, 2 * 32 * 32 * 3, dtype=jnp.float32).reshape(2, 32, 32, 3)
    y = apply_style_net(params, x, SMALL)
    assert y.shape == x.shape and y.dtype == x.dtype
    assert float(y.min()) >= 0.0 and float(y.max()) <= 1.0


def test_style_net_preserves_arbitrary_hw():
    # Fully-conv net: any H, W divisible by 4 (two stride-2 downs) round-trips.
    params = init_style_net(jax.random.PRNGKey(0), SMALL)
    y = apply_style_net(params, jnp.zeros((1, 48, 64, 3)), SMALL)
    assert y.shape == (1, 48, 64, 3)


def test_style_net_jit_once():
    params = init_style_net(jax.random.PRNGKey(0), SMALL)
    traces = 0

    @jax.jit
    def f(p, x):
        nonlocal traces
        traces += 1
        return apply_style_net(p, x, SMALL)

    x = jnp.zeros((1, 32, 32, 3))
    f(params, x)
    f(params, x + 1)
    assert traces == 1


def test_param_pspecs_cover_params_and_are_valid():
    params = init_style_net(jax.random.PRNGKey(0), SMALL)
    specs = param_pspecs(SMALL)
    flat_p = jax.tree_util.tree_leaves_with_path(params)
    flat_s = jax.tree_util.tree_leaves_with_path(specs, is_leaf=lambda x: isinstance(x, P))
    assert {jax.tree_util.keystr(k) for k, _ in flat_p} == {
        jax.tree_util.keystr(k) for k, _ in flat_s
    }
    # Each spec must be placeable: sharded dims divide evenly on a model=2 mesh.
    mesh = make_mesh(MeshConfig(model=2))
    placed = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params,
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    jax.block_until_ready(placed)


def test_tp_sharded_forward_matches_replicated():
    params = init_style_net(jax.random.PRNGKey(0), SMALL)
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 32, 32, 3))
    want = apply_style_net(params, x, SMALL)

    mesh = make_mesh(MeshConfig(model=2))
    specs = param_pspecs(SMALL)
    sharded = jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
        params,
        specs,
        is_leaf=lambda s: isinstance(s, P),
    )
    got = jax.jit(lambda p, b: apply_style_net(p, b, SMALL))(sharded, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-2)


def test_style_engine_tp_matches_replicated():
    """VERDICT item 7: style-transfer inference must get real TP *through
    the Engine* — the Engine honors the filter's state PartitionSpecs and
    swaps in the shard_map'd TP forward on a model-sharded mesh, matching
    the replicated single-device forward."""
    import numpy as np

    from dvf_tpu.ops import get_filter
    from dvf_tpu.runtime.engine import Engine

    x = np.random.default_rng(0).integers(0, 255, (2, 32, 32, 3), np.uint8)

    mesh = make_mesh(MeshConfig(data=2, model=4))
    eng = Engine(get_filter("style_transfer", base_channels=8, n_residual=2),
                 mesh=mesh)
    eng.compile(x.shape, np.uint8)
    assert eng._exec_filter.name.startswith("tp("), eng._exec_filter.name
    # Weight pytree actually lands model-sharded on device:
    stem_w = eng._state["stem"]["w"]
    assert stem_w.sharding.spec == P(None, None, None, "model"), stem_w.sharding
    got = np.asarray(eng.submit(x))

    ref = Engine(get_filter("style_transfer", base_channels=8, n_residual=2),
                 mesh=make_mesh(MeshConfig()))
    want = np.asarray(ref.submit(x))
    # bfloat16 trunk: sharded psum order differs; uint8 outputs may differ
    # by a couple of levels.
    assert np.abs(got.astype(int) - want.astype(int)).max() <= 3


@pytest.mark.parametrize("dtype,levels", [("bfloat16", 4), ("float32", 1)])
def test_style_engine_tp_with_space_axis_and_odd_batch(dtype, levels):
    """The TP fold must degrade to whatever the batch divides: B=2 on a
    (data=1, space=4, model=2) mesh can't fold over data*space=4 — it must
    still compile (batch replicated over the fold) and match: to the uint8
    rounding in float32, to bfloat16's rounding of the row convs' partial
    sums before the psum otherwise."""
    import numpy as np

    from dvf_tpu.ops import get_filter
    from dvf_tpu.runtime.engine import Engine

    x = np.random.default_rng(1).integers(0, 255, (2, 32, 32, 3), np.uint8)
    mesh = make_mesh(MeshConfig(data=1, space=4, model=2))
    kwargs = dict(base_channels=8, n_residual=2, dtype=dtype)
    eng = Engine(get_filter("style_transfer", **kwargs), mesh=mesh)
    got = np.asarray(eng.submit(x))

    ref = Engine(get_filter("style_transfer", **kwargs),
                 mesh=make_mesh(MeshConfig()))
    want = np.asarray(ref.submit(x))
    assert np.abs(got.astype(int) - want.astype(int)).max() <= levels


def test_upsample_nearest():
    x = jnp.arange(4.0).reshape(1, 2, 2, 1)
    y = upsample_nearest(x, 2)
    assert y.shape == (1, 4, 4, 1)
    np.testing.assert_array_equal(
        np.asarray(y[0, :, :, 0]),
        [[0, 0, 1, 1], [0, 0, 1, 1], [2, 2, 3, 3], [2, 2, 3, 3]],
    )


def test_gram_matrix_properties():
    f = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 4))
    g = gram_matrix(f)
    assert g.shape == (2, 4, 4)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g).transpose(0, 2, 1), rtol=1e-5)
    # PSD: eigenvalues >= 0 (up to fp error).
    eig = np.linalg.eigvalsh(np.asarray(g[0], dtype=np.float64))
    assert eig.min() > -1e-5


def test_vgg_features_shapes():
    cfg = VGGConfig(blocks=((1, 8), (1, 16)))
    params = init_vgg(jax.random.PRNGKey(0), cfg)
    feats = vgg_features(params, jnp.zeros((2, 32, 32, 3)), cfg)
    assert [tuple(f.shape) for f in feats] == [(2, 32, 32, 8), (2, 16, 16, 16)]
    specs = vgg_param_pspecs(cfg)
    assert set(specs) == set(params)


def test_style_filter_registered():
    from dvf_tpu.ops import get_filter

    filt = get_filter("style_transfer", base_channels=8, n_residual=1, seed=3)
    assert filt.stateful
    state = filt.init_state((2, 32, 32, 3), jnp.float32)
    y, state2 = filt.fn(jnp.full((2, 32, 32, 3), 0.5), state)
    assert y.shape == (2, 32, 32, 3)
    assert state2 is state  # inference: weights unchanged


# ------------------------------------------------------------- ESPCN (SR)

def test_depth_to_space_dcr_order():
    from dvf_tpu.models.layers import depth_to_space

    # x[b,h,w,(i*r+j)*C+c] -> y[b,h*r+i,w*r+j,c], spelled out for r=2, C=1.
    x = jnp.arange(8.0).reshape(1, 1, 2, 4)  # two w-positions, 4=r*r chans
    y = depth_to_space(x, 2)
    assert y.shape == (1, 2, 4, 1)
    np.testing.assert_array_equal(
        np.asarray(y[0, :, :, 0]),
        [[0, 1, 4, 5], [2, 3, 6, 7]],
    )
    with pytest.raises(ValueError, match="divisible"):
        depth_to_space(jnp.zeros((1, 2, 2, 6)), 2)


def test_espcn_upscales_and_stays_in_range():
    from dvf_tpu.models.espcn import EspcnConfig, apply_espcn, init_espcn

    cfg = EspcnConfig(scale=3)
    params = init_espcn(jax.random.PRNGKey(0), cfg)
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 16, 24, 3))
    y = apply_espcn(params, x, cfg)
    assert y.shape == (2, 48, 72, 3) and y.dtype == x.dtype
    assert float(y.min()) >= 0.0 and float(y.max()) <= 1.0


def test_espcn_pspecs_cover_params_and_tp_matches_replicated():
    from dvf_tpu.models.espcn import (
        EspcnConfig, apply_espcn, init_espcn, param_pspecs, tp_inner_apply,
    )

    cfg = EspcnConfig()
    params = init_espcn(jax.random.PRNGKey(0), cfg)
    specs = param_pspecs(cfg)
    flat_p = jax.tree_util.tree_leaves_with_path(params)
    flat_s = jax.tree_util.tree_leaves_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert {jax.tree_util.keystr(k) for k, _ in flat_p} == {
        jax.tree_util.keystr(k) for k, _ in flat_s
    }

    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 16, 16, 3))
    want = apply_espcn(params, x, cfg)

    mesh = make_mesh(MeshConfig(model=2))
    sharded = jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
        params, specs, is_leaf=lambda s: isinstance(s, P),
    )
    got = jax.jit(jax.shard_map(
        tp_inner_apply(cfg), mesh=mesh,
        in_specs=(specs, P(None)),
        out_specs=P(None), check_vma=False,
    ))(sharded, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-2)


def test_sr_engine_tp_matches_replicated():
    """The SR family gets real TP through the Engine, like style does —
    and its 2x output geometry flows through engine submit unchanged."""
    from dvf_tpu.ops import get_filter
    from dvf_tpu.runtime.engine import Engine

    x = np.random.default_rng(0).integers(0, 255, (2, 16, 16, 3), np.uint8)

    mesh = make_mesh(MeshConfig(data=2, model=4))
    eng = Engine(get_filter("super_resolution"), mesh=mesh)
    eng.compile(x.shape, np.uint8)
    assert eng._exec_filter.name.startswith("tp("), eng._exec_filter.name
    feat_w = eng._state["feat"]["w"]
    assert feat_w.sharding.spec == P(None, None, None, "model"), feat_w.sharding
    got = np.asarray(eng.submit(x))
    assert got.shape == (2, 32, 32, 3)

    ref = Engine(get_filter("super_resolution"), mesh=make_mesh(MeshConfig()))
    want = np.asarray(ref.submit(x))
    assert np.abs(got.astype(int) - want.astype(int)).max() <= 3


def test_sr_through_pipeline_delivers_upscaled_frames():
    import dvf_tpu
    from dvf_tpu.io import NullSink, SyntheticSource
    from dvf_tpu.runtime import Pipeline, PipelineConfig

    shapes = []

    class ShapeSink(NullSink):
        def emit(self, index, frame, capture_ts):
            shapes.append(frame.shape)
            super().emit(index, frame, capture_ts)

    src = SyntheticSource(height=32, width=48, n_frames=16)
    # queue_size >= n_frames: the first-compile stall must not trigger the
    # (by-design) drop-oldest ingest path — this test is about geometry.
    stats = Pipeline(src, dvf_tpu.get_filter("super_resolution"), ShapeSink(),
                     PipelineConfig(batch_size=8, queue_size=32)).run()
    assert stats["delivered"] == 16
    assert shapes and all(s == (64, 96, 3) for s in shapes)


def test_fast_conv_rewrites_match_reference_lowering():
    """conv2d_s2d (space-to-depth phase decomposition) and upsample2_conv
    (phase-collapsed subpixel decoder) are EXACT rearrangements of the
    reference convs — parity in f32 at tap-noise tolerance, reflect and
    zero-pad borders both (models.analysis has the MXU-utilization case)."""
    import jax.numpy as jnp
    import numpy as np

    from dvf_tpu.models.layers import (
        conv2d_nb, conv2d_s2d, upsample2_conv, upsample_nearest)

    rng = np.random.RandomState(0)
    for k, cin, cout, h, w in [(9, 3, 5, 12, 16), (9, 32, 3, 20, 24),
                               (3, 4, 6, 10, 14), (5, 3, 8, 16, 12)]:
        p = {"w": jnp.asarray(rng.randn(k, k, cin, cout).astype(np.float32))}
        x = jnp.asarray(rng.rand(2, h, w, cin).astype(np.float32))
        for reflect in (True, False):
            a = conv2d_nb(p, x, compute_dtype=jnp.float32, reflect=reflect)
            b = conv2d_s2d(p, x, compute_dtype=jnp.float32, reflect=reflect)
            assert float(jnp.abs(a - b).max()) < 1e-4, (k, cin, cout, reflect)
    # Odd geometry falls back to the reference path (still correct).
    p = {"w": jnp.asarray(rng.randn(9, 9, 3, 4).astype(np.float32))}
    x = jnp.asarray(rng.rand(1, 13, 17, 3).astype(np.float32))
    a = conv2d_nb(p, x, compute_dtype=jnp.float32, reflect=True)
    b = conv2d_s2d(p, x, compute_dtype=jnp.float32, reflect=True)
    assert float(jnp.abs(a - b).max()) == 0.0

    for k, cin, cout, h, w in [(3, 5, 7, 9, 11), (3, 3, 3, 8, 8)]:
        p = {"w": jnp.asarray(rng.randn(k, k, cin, cout).astype(np.float32))}
        x = jnp.asarray(rng.rand(2, h, w, cin).astype(np.float32))
        a = conv2d_nb(p, upsample_nearest(x, 2), compute_dtype=jnp.float32,
                      reflect=True)
        b = upsample2_conv(p, x, compute_dtype=jnp.float32)
        assert float(jnp.abs(a - b).max()) < 1e-4, (k, cin, cout)
    # k=5 has no exact low-res border mapping: must fall back, still exact.
    p = {"w": jnp.asarray(rng.randn(5, 5, 4, 6).astype(np.float32))}
    x = jnp.asarray(rng.rand(2, 10, 12, 4).astype(np.float32))
    a = conv2d_nb(p, upsample_nearest(x, 2), compute_dtype=jnp.float32,
                  reflect=True)
    b = upsample2_conv(p, x, compute_dtype=jnp.float32)
    assert float(jnp.abs(a - b).max()) == 0.0


def _two_pass_norm(p, x, eps=1e-5):
    """The independent reference of the one-pass norms of models.layers:
    the textbook two dependent float32 passes (mean, then the mean of the
    squared centred values), as layers.py had them before PR 36."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=(1, 2), keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=(1, 2), keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return y.astype(x.dtype)


def _plain_style_forward(params, x, config, two_pass=False):
    """The style net as the plain composition of the reference layers
    (conv2d_nb + instance_norm + upsample_nearest at full resolution):
    what every stage of ``_forward`` computes, whatever form it runs in.
    ``two_pass``: with the independent two-pass norm in place of
    ``layers.instance_norm`` about its corner pivot."""
    from dvf_tpu.models.layers import conv2d_nb, corner_pivot, instance_norm

    cd = config.compute_dtype

    def cv(name, stride=1):
        p = params[name]
        return lambda x: conv2d_nb(p, x, stride=stride, compute_dtype=cd,
                                   reflect=True) + p["b"].astype(cd)

    def norm(name, conv, x):
        if two_pass:
            return _two_pass_norm(params[name], conv(x))
        return instance_norm(params[name], conv(x), corner_pivot(conv, x))

    def nr(name, conv, x):
        return jax.nn.relu(norm(name, conv, x))

    x = nr("stem_norm", cv("stem"), x.astype(cd))
    x = nr("down1_norm", cv("down1", 2), x)
    x = nr("down2_norm", cv("down2", 2), x)
    for i in range(config.n_residual):
        h = nr(f"res{i}_an", cv(f"res{i}_a"), x)
        x = x + norm(f"res{i}_bn", cv(f"res{i}_b"), h)
    x = nr("up1_norm", cv("up1"), upsample_nearest(x, 2))
    x = nr("up2_norm", cv("up2"), upsample_nearest(x, 2))
    return 0.5 * (jnp.tanh(cv("out")(x).astype(jnp.float32)) + 1.0)


def _random_style_params(config, seed=0):
    """init_style_net with the zero biases and unit norm scales perturbed,
    so a dropped or mis-tiled bias/scale shows."""
    params = init_style_net(jax.random.PRNGKey(seed), config)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree.unflatten(tree, [
        a + 0.1 * jax.random.normal(k, a.shape) for a, k in zip(leaves, keys)])


F32_SMALL = StyleNetConfig(base_channels=8, n_residual=2,
                           compute_dtype=jnp.float32)


@pytest.mark.parametrize("two_pass,tol", [(False, 1e-5), (True, 2e-5)])
@pytest.mark.parametrize("hw,form", [
    ((64, 96), "phase"),     # out conv at phase factor 4
    ((66, 98), "phase"),     # even, not a multiple of 4: out at factor 2
    ((65, 97), "plain"),     # odd geometry keeps the plain path
])
def test_style_net_phase_forward_matches_plain_composition(hw, form, two_pass,
                                                           tol):
    """The forward with its full-resolution stages in the phase domain is
    the plain composition of reference layers (f32 pins the comparison to
    the re-indexing, not rounding), at each geometry class — and the same
    composition over the independent two-pass norm, to what two float32
    roundings of nine norms and ten convs differ by (either is 0.7-1.5e-5
    from the same composition in float64, over three geometries and three
    seeds; the two are 0.5-1.1e-5 apart)."""
    from dvf_tpu.models.style_transfer import stage_forms

    params = _random_style_params(F32_SMALL)
    x = jnp.asarray(np.random.RandomState(1).rand(2, *hw, 3)
                    .astype(np.float32))
    forms = stage_forms(F32_SMALL, x.shape)
    assert {forms[k] for k in ("stem", "down1", "up2", "out")} == {form}
    want = _plain_style_forward(params, x, F32_SMALL, two_pass)
    got = apply_style_net(params, x, F32_SMALL)
    assert got.shape == want.shape
    assert float(jnp.abs(got - want).max()) < tol


@pytest.mark.parametrize("k,cin,cout,stride,fold", [
    (9, 3, 8, 1, 1),      # stem: 5x5 over 12 input phases
    (3, 8, 16, 2, 1),     # down1: stride 2 on a phase tensor = 2x2, plain out
    (9, 8, 3, 1, 1),      # out at phase factor 2
    (9, 8, 3, 1, 2),      # out at phase factor 4 (nested input phases)
    (5, 4, 6, 1, 2),
])
def test_conv2d_phase_matches_reference_conv(k, cin, cout, stride, fold):
    from dvf_tpu.models.layers import (
        conv2d_nb, conv2d_phase, depth_to_space, space_to_depth)

    rng = np.random.RandomState(0)
    p = {"w": jnp.asarray(rng.randn(k, k, cin, cout).astype(np.float32))}
    x = jnp.asarray(rng.rand(2, 16, 24, cin).astype(np.float32))
    want = conv2d_nb(p, x, stride=stride, compute_dtype=jnp.float32,
                     reflect=True)
    got = conv2d_phase(p, space_to_depth(x, 2), stride=stride, fold=fold,
                       compute_dtype=jnp.float32)
    fo = 2 * fold // stride
    if fo > 1:
        assert got.shape[-1] == fo * fo * cout
        got = depth_to_space(got, fo)
    assert float(jnp.abs(got - want).max()) < 1e-4


@pytest.mark.parametrize("r", [1, 4])
def test_phase_reflect_pad_matches_full_resolution_reflect(r):
    """The reflect border built from neighbouring phases IS
    jnp.pad(mode="reflect") of the full-resolution tensor — for down1's
    radius (one low-res row, top/left only) and out's (two, all round)."""
    from dvf_tpu.models.layers import phase_reflect_pad, space_to_depth

    x = jnp.asarray(np.random.RandomState(0).rand(2, 12, 16, 5)
                    .astype(np.float32))
    lo = (r + 1) // 2                    # low-res rows a radius-r border needs
    hi = 0 if r == 1 else lo
    got = phase_reflect_pad(space_to_depth(x, 2), lo, hi, lo, hi)
    want = space_to_depth(jnp.pad(
        x, ((0, 0), (2 * lo, 2 * hi), (2 * lo, 2 * hi), (0, 0)),
        mode="reflect"), 2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _norm_case(hw, dtype, mean=1.0, spread=3.0, c=6, seed=0):
    rng = np.random.RandomState(seed)
    p = {"scale": jnp.asarray(rng.rand(c).astype(np.float32) + 0.5),
         "bias": jnp.asarray(rng.rand(c).astype(np.float32))}
    x = (spread * rng.randn(2, *hw, c) + mean).astype(np.float32)
    return p, jnp.asarray(x).astype(dtype)


def _one_pass_norm(p, x, form, pivot):
    """``layers.instance_norm`` of ``x``, or ``instance_norm_phase`` of
    its phase image brought back, sums about ``pivot`` (None: each
    channel's first position)."""
    from dvf_tpu.models.layers import (
        depth_to_space, instance_norm, instance_norm_phase, space_to_depth)

    if form == "plain":
        return instance_norm(p, x, pivot)
    if pivot is not None:
        pivot = jnp.tile(pivot, 4)       # the pivot's phase image
    return depth_to_space(instance_norm_phase(p, space_to_depth(x, 2), pivot), 2)


@pytest.mark.parametrize("pivot", ["first_position", "bias"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("form,hw", [
    ("plain", (8, 12)), ("plain", (9, 13)),
    ("phase", (8, 12)), ("phase", (10, 14)),   # the phase image 4x6, 5x7
])
def test_one_pass_norm_matches_two_pass_reference(form, hw, dtype, pivot):
    """The norms' one pass (sum and sum of squares about a pivot) gives
    the two dependent float32 passes' result: to 1e-5 on float32 input,
    to a bfloat16 rounding of the same float32 values on bfloat16 input."""
    p, x = _norm_case(hw, dtype)
    piv = None if pivot == "first_position" else jnp.full((6,), 0.3)
    got = _one_pass_norm(p, x, form, piv)
    want = _two_pass_norm(p, x)
    assert got.dtype == x.dtype and got.shape == x.shape
    err = float(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)).max())
    assert err < (1e-5 if dtype == "float32" else 2.0 ** -5)


def _ill_conditioned(hw=(32, 48)):
    """Channel 0: mean 100, spread 1 (a bare E[x^2] - E[x]^2 loses five
    of float32's seven digits there); channel 1: mean 0, spread 1e-3."""
    rng = np.random.RandomState(0)
    x = rng.randn(2, *hw, 2).astype(np.float32) * np.float32([1.0, 1e-3])
    p = {"scale": jnp.ones((2,)), "bias": jnp.zeros((2,))}
    return p, jnp.asarray(x + np.float32([100.0, 0.0]))


def _two_pass_norm64(x, eps=1e-5):
    """Two passes in float64 numpy (unit scale, zero bias): where the mean
    is large the float32 two-pass form is itself 6e-5 off (its mean is
    rounded at the mean's size), the one pass about a pivot is not."""
    x = np.asarray(x, np.float64)
    mean = x.mean(axis=(1, 2), keepdims=True)
    var = np.square(x - mean).mean(axis=(1, 2), keepdims=True)
    return (x - mean) / np.sqrt(var + eps)


def test_bare_one_pass_variance_fails_where_the_mean_is_large():
    """What the pivot is for: the same one pass about 0."""
    from dvf_tpu.models.layers import instance_norm

    p, x = _ill_conditioned()
    bare = instance_norm(p, x, jnp.zeros((2,)))
    assert np.abs(np.asarray(bare) - _two_pass_norm64(x)).max() > 1e-3


@pytest.mark.parametrize("form", ["plain", "phase"])
@pytest.mark.parametrize("pivot", ["first_position", "bias"])
def test_one_pass_norm_is_well_conditioned_about_its_pivot(form, pivot):
    """About the first position, or about the bias the conv before added
    (here what makes the mean large), the difference does not cancel."""
    p, x = _ill_conditioned()
    piv = None if pivot == "first_position" else jnp.asarray([100.0, 0.0])
    got = _one_pass_norm(p, x, form, piv)
    assert np.abs(np.asarray(got) - _two_pass_norm64(x)).max() < 1e-5


@pytest.mark.parametrize("two_pass,tol", [(False, 1e-3), (True, 1e-4)])
def test_style_net_gradient_step_through_phase_forward(two_pass, tol):
    """One gradient step through the phase-domain forward moves the
    params as the plain composition's gradient does (train/style.py
    differentiates the same ``_forward``) — and as the gradient through
    the two-pass norms does, to 1e-4 of each leaf's largest entry."""
    params = _random_style_params(F32_SMALL)
    x = jnp.asarray(np.random.RandomState(2).rand(1, 32, 48, 3)
                    .astype(np.float32))
    target = jnp.asarray(np.random.RandomState(3).rand(1, 32, 48, 3)
                         .astype(np.float32))

    def loss(fwd):
        return lambda p: jnp.mean((fwd(p, x, F32_SMALL) - target) ** 2)

    g_phase = jax.grad(loss(apply_style_net))(params)
    g_plain = jax.grad(loss(
        lambda p, x, cfg: _plain_style_forward(p, x, cfg, two_pass)))(params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_phase),
                            jax.tree.leaves(g_plain)):
        scale = float(jnp.abs(b).max()) + 1e-8
        assert float(jnp.abs(a - b).max()) < tol * scale + 1e-7, (
            jax.tree_util.keystr(path))
    stepped = jax.tree.map(lambda p, g: p - 0.1 * g, params, g_phase)
    assert float(loss(apply_style_net)(stepped)) < float(
        loss(apply_style_net)(params))


def test_style_net_720p_jaxpr_holds_no_lane_starved_tensor():
    """Structure at the cell's shape, no compile: between stem and down1
    and between up2 and out the activation exists only as a 128-channel
    phase tensor — no (16, 720, 1280, c) intermediate with 3 < c < 128 —
    and no materialised nearest-x2 broadcast ahead of up2; stage_forms
    says so, and says plain for an odd geometry."""
    from dvf_tpu.models.style_transfer import stage_forms

    cfg = StyleNetConfig()
    shape = (16, 720, 1280, 3)
    forms = stage_forms(cfg, shape)
    assert [forms[k] for k in ("stem", "down1", "up1", "up2", "out")] == ["phase"] * 5
    assert forms["down2"] == forms["trunk"] == "plain"
    odd = stage_forms(cfg, (16, 721, 1280, 3))
    assert set(odd.values()) == {"plain"}

    params = jax.eval_shape(lambda: init_style_net(jax.random.PRNGKey(0), cfg))
    jaxpr = jax.make_jaxpr(lambda p, x: apply_style_net(p, x, cfg))(
        params, jax.ShapeDtypeStruct(shape, jnp.float32))

    shapes = set()

    def walk(jp):
        for eqn in jp.eqns:
            shapes.update(tuple(v.aval.shape) for v in eqn.outvars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    starved = [s for s in shapes
               if len(s) == 4 and s[:3] == (16, 720, 1280) and 3 < s[3] < 128]
    assert not starved, starved
    assert (16, 360, 2, 640, 2, 64) not in shapes
    assert (16, 180, 2, 320, 2, 128) not in shapes       # nor ahead of up1
    assert (16, 360, 640, 128) in shapes         # the phase tensors themselves


def _big_reduces(jaxpr, in_taint, scope, found):
    """Walk a jaxpr in order. A variable's taint is the set of full-tensor
    reductions (rank-4 operand of more positions than a norm's pivot is
    taken from) it depends on without a convolution in between (a conv's
    output starts clean: the next norm's activation). ``found`` collects,
    per such reduction, (scope, operand's taint)."""
    from dvf_tpu.models.layers import CORNER

    def full_tensor(aval):
        return aval.ndim == 4 and aval.shape[1] * aval.shape[2] > CORNER ** 2

    taint = dict(zip(jaxpr.invars, in_taint))
    get = lambda v: frozenset() if type(v).__name__ == "Literal" else taint.get(v, frozenset())
    for eqn in jaxpr.eqns:
        ins = [get(v) for v in eqn.invars]
        here = f"{scope}/{eqn.source_info.name_stack}"
        subs = list(jax.core.jaxprs_in_params(eqn.params))
        if eqn.primitive.name == "conv_general_dilated":
            outs = [frozenset()] * len(eqn.outvars)
        elif eqn.primitive.name.startswith("reduce_") and full_tensor(eqn.invars[0].aval):
            found.append((here, ins[0]))
            outs = [frozenset([len(found)])]
        elif len(subs) == 1 and len(subs[0].invars) == len(ins):
            outs = _big_reduces(subs[0], ins, here, found)
        else:
            outs = [frozenset().union(*ins)] * len(eqn.outvars)
        taint.update(zip(eqn.outvars, outs))
    return [get(v) for v in jaxpr.outvars]


@pytest.mark.parametrize("hw", [(64, 96), (65, 97)])
def test_style_step_norms_take_one_reduction_pass_each(hw):
    """Structure of the lowered step at a toy shape, no compile: every
    norm has ONE reduction pass over its activation — the sum and the sum
    of squares, siblings over the conv's output about a pivot that is no
    reduction's result (so no reduce reads a centred ``x - mean`` of the
    full tensor, which would be a second, dependent pass) — and every such
    pass sits under a ``norm_stats`` scope inside its stage's."""
    from dvf_tpu.ops import get_filter
    from dvf_tpu.utils.image import to_float, to_uint8

    filt = get_filter("style_transfer", base_channels=8, n_residual=2)
    shape = (2, *hw, 3)
    state = jax.eval_shape(lambda: filt.init_state(shape, jnp.float32))

    def step(batch, state):            # the body of Engine._build_step
        y, new_state = filt.fn(to_float(batch, filt.compute_dtype), state)
        return to_uint8(y), new_state

    jaxpr = jax.make_jaxpr(step)(jax.ShapeDtypeStruct(shape, jnp.uint8), state)
    found = []
    _big_reduces(jaxpr.jaxpr, [frozenset()] * len(jaxpr.jaxpr.invars), "", found)
    n_norms = 5 + 2 * 2
    assert len(found) == 2 * n_norms, [s for s, _ in found]
    stages = ("stem", "down1", "down2", "trunk", "up1", "up2")
    for scope, operand_taint in found:
        parts = scope.split("/")
        assert "norm_stats" in parts, scope
        assert any(st in parts[:parts.index("norm_stats")] for st in stages), scope
        assert not operand_taint, f"{scope}: a reduce over another reduce's result"


def test_espcn_fast_convs_parity():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dvf_tpu.models.espcn import EspcnConfig, apply_espcn, init_espcn

    ref_cfg = EspcnConfig(compute_dtype=jnp.float32)
    fast_cfg = EspcnConfig(compute_dtype=jnp.float32, fast_convs=True)
    params = init_espcn(jax.random.PRNGKey(0), ref_cfg)
    x = jnp.asarray(np.random.RandomState(1).rand(2, 18, 22, 3)
                    .astype(np.float32))
    a = apply_espcn(params, x, ref_cfg)
    b = apply_espcn(params, x, fast_cfg)
    assert float(jnp.abs(a - b).max()) < 1e-4


@pytest.mark.parametrize("name", ["style_transfer", "super_resolution"])
def test_neural_filter_factory_knobs(name):
    """The dtype knob resolves through the factories; ESPCN's fast_convs
    resolves through the measured-defaults table (no committed winner yet
    -> 'ref' lowering), and the style net has no such knob: the form of
    each stage is a function of its shape (stage_forms)."""
    from dvf_tpu.ops import get_filter

    f = get_filter(name)                      # defaults: bf16
    f_f32 = get_filter(name, dtype="float32")
    assert f.name and f_f32.name
    with pytest.raises(ValueError, match="dtype"):
        get_filter(name, dtype="float16")
    if name == "style_transfer":
        with pytest.raises(TypeError, match="fast_convs"):
            get_filter(name, fast_convs=True)
    else:
        assert get_filter(name, fast_convs=True).name


@pytest.mark.parametrize("schedule", ["tp", "pp"])
def test_shard_map_forward_with_phase_stages(schedule):
    """The phase-domain stages must compose with the model-axis
    schedules. TP: a column conv's Cout shard is sliced INSIDE each phase
    group (the kernel gather is over the shard's own slice), the norm's
    phase statistics are per local channel, and a row conv's psum runs on
    the phase tensor before the (tiled) bias. PP: stem/decoder run
    replicated around the pipelined trunk. Either explicit shard_map
    forward must match the replicated forward AND the plain composition."""
    from dvf_tpu.models.style_transfer import (
        pp_inner_apply, pp_param_pspecs, stage_forms, to_pp_params,
        tp_inner_apply)

    cfg = F32_SMALL       # f32: the comparison is the wiring, not rounding
    params = _random_style_params(cfg)
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 32, 32, 3))
    assert stage_forms(cfg, x.shape)["out"] == "phase"
    want_plain = _plain_style_forward(params, x, cfg)
    want = apply_style_net(params, x, cfg)

    mesh = make_mesh(MeshConfig(model=2))
    if schedule == "tp":
        specs, inner, placed = param_pspecs(cfg), tp_inner_apply(cfg), params
    else:
        specs, inner = pp_param_pspecs(cfg), pp_inner_apply(cfg)
        placed = to_pp_params(params, cfg)
    got = jax.jit(jax.shard_map(
        lambda p, b: inner(p, b),
        mesh=mesh,
        in_specs=(specs, P()),
        out_specs=P(),
        check_vma=False,
    ))(placed, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_plain),
                               atol=1e-4)


def test_espcn_tp_shard_map_forward_with_fast_convs():
    import dataclasses

    from dvf_tpu.models.espcn import (
        EspcnConfig, apply_espcn, init_espcn, param_pspecs as e_pspecs,
        tp_inner_apply as e_tp)

    cfg = EspcnConfig()
    fast = dataclasses.replace(cfg, fast_convs=True)
    params = init_espcn(jax.random.PRNGKey(0), cfg)
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 16, 24, 3))
    want = apply_espcn(params, x, cfg)

    mesh = make_mesh(MeshConfig(model=2))
    inner = e_tp(fast)
    got = jax.jit(jax.shard_map(
        lambda p, b: inner(p, b),
        mesh=mesh,
        in_specs=(e_pspecs(cfg), P()),
        out_specs=P(),
        check_vma=False,
    ))(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-2)


# -- ESPCN in the carried phase form (zero-SAME phase convs) ------------------

def _plain_espcn_forward(params, x, cfg):
    """The plain composition the carried form re-indexes: three zero-SAME
    convs at the input's resolution, then the sub-pixel shuffle."""
    from dvf_tpu.models.layers import conv2d_nb, depth_to_space

    cd = cfg.compute_dtype
    h = x.astype(cd)
    for name, relu in (("feat", True), ("map", True), ("head", False)):
        h = conv2d_nb(params[name], h, compute_dtype=cd) + params[name]["b"].astype(cd)
        h = jax.nn.relu(h) if relu else h
    y = depth_to_space(h.astype(jnp.float32), cfg.scale)
    return jnp.clip(y, 0.0, 1.0).astype(x.dtype)


def _random_espcn_params(cfg, seed=0):
    """Random biases too: a bias tiled over the wrong phases must show."""
    from dvf_tpu.models.espcn import init_espcn

    params = init_espcn(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 3))
    return {k: {"w": v["w"], "b": 0.1 * jax.random.normal(next(keys), v["b"].shape)}
            for k, v in params.items()}


@pytest.mark.parametrize("k,fi,fo", [
    (3, 1, 2), (5, 1, 2), (3, 1, 4), (5, 1, 4),               # plain → phase: the strided emission
    (3, 2, 2), (5, 2, 2),                                     # phase → phase
    (5, 1, (1, 2)), (3, (1, 2), (2, 2)), (3, (2, 2), (2, 4)),  # the cell's three convs
    (3, (1, 2), (1, 4)), (3, (2, 1), (4, 2)), (3, 2, 4), (3, 1, 1),
])
def test_conv2d_zero_phase_is_the_plain_conv_seen_through_space_to_depth(k, fi, fo):
    from dvf_tpu.models.layers import (_pair, conv2d_nb, conv2d_zero_phase, conv_init,
                                       space_to_depth, zero_phase_kernel)

    p = conv_init(jax.random.PRNGKey(k), k, 5, 7)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16, 5))
    want = space_to_depth(conv2d_nb(p, x, compute_dtype=jnp.float32), fo)
    got = conv2d_zero_phase(p, space_to_depth(x, fi), fi, fo, compute_dtype=jnp.float32)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    kern, pads, strides = zero_phase_kernel(p["w"], fi, fo)
    (fih, fiw), (foh, fow) = _pair(fi), _pair(fo)
    assert strides == (foh // fih, fow // fiw)
    assert kern.shape[2:] == (fih * fiw * 5, foh * fow * 7)
    if fi == 1:        # a (k+f-1)-tap kernel, phase (β, β') holding w at rows β.., columns β'..
        assert kern.shape[:2] == (k + foh - 1, k + fow - 1) and pads == ((k // 2,) * 2,) * 2
        block = kern.reshape(*kern.shape[:3], foh, fow, 7)
        np.testing.assert_array_equal(
            np.asarray(block[foh - 1:foh - 1 + k, fow - 1:fow - 1 + k, :, -1, -1]), np.asarray(p["w"]))


def test_zero_phase_kernel_refuses_a_tensor_going_back():
    from dvf_tpu.models.layers import conv_init, zero_phase_kernel

    w = conv_init(jax.random.PRNGKey(0), 3, 4, 4)["w"]
    for fi, fo in ((2, 1), ((2, 2), (1, 2)), (4, 2), ((1, 4), (2, 2))):
        with pytest.raises(ValueError, match="multiple"):
            zero_phase_kernel(w, fi, fo)


@pytest.mark.parametrize("factor", [(2, 3), (4, 8), 2])
def test_space_to_depth_and_back_with_a_factor_an_axis(factor):
    from dvf_tpu.models.layers import _pair, depth_to_space, space_to_depth

    fh, fw = _pair(factor)
    x = jnp.arange(2 * 8 * 24 * 3, dtype=jnp.float32).reshape(2, 8, 24, 3)
    y = space_to_depth(x, factor)
    assert y.shape == (2, 8 // fh, 24 // fw, fh * fw * 3)
    assert float(y[1, 1, 2, (1 * fw + 1) * 3 + 2]) == float(x[1, fh + 1, 2 * fw + 1, 2])
    np.testing.assert_array_equal(np.asarray(depth_to_space(y, factor)), np.asarray(x))


@pytest.mark.parametrize("scale", [2, 3])
@pytest.mark.parametrize("fi,fo", [(1, 2), (2, 2), ((2, 2), (2, 4)), (1, (2, 4))])
def test_head_in_subpixel_order_leaves_one_depth_to_space(scale, fi, fo):
    """head's columns permuted on the kernel + ONE depth_to_space equal the
    shuffle (DCR order) of the plain head."""
    from dvf_tpu.models.layers import (_pair, conv2d_nb, conv2d_zero_phase, conv_init,
                                       depth_to_space, space_to_depth, subpixel_order)

    p = conv_init(jax.random.PRNGKey(0), 3, 8, 3 * scale * scale)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 12, 8))
    want = depth_to_space(conv2d_nb(p, x, compute_dtype=jnp.float32), scale)
    foh, fow = _pair(fo)
    cols = subpixel_order(fo, scale, 3)
    assert sorted(cols) == list(range(foh * fow * 3 * scale * scale))
    xi = space_to_depth(x, fi)
    y = conv2d_zero_phase(p, xi, fi, fo, cols, compute_dtype=jnp.float32)
    one = (foh * scale, fow * scale)
    np.testing.assert_allclose(np.asarray(depth_to_space(y, one)), np.asarray(want), atol=1e-5)
    # the unpermuted columns under the same rearrangement are another picture
    y0 = conv2d_zero_phase(p, xi, fi, fo, compute_dtype=jnp.float32)
    assert float(jnp.abs(depth_to_space(y0, one) - want).max()) > 0.1


def test_espcn_stage_phases_is_a_function_of_the_shapes():
    from dvf_tpu.models.espcn import EspcnConfig, stage_forms, stage_phases

    cfg = EspcnConfig()
    cell = {"feat": (1, 2), "map": (2, 2), "head": (2, 4)}
    plain = dict.fromkeys(cell, (1, 1))
    assert stage_phases(cfg, (16, 540, 960, 3)) == cell
    assert stage_forms(cfg, (16, 540, 960, 3)) == {
        "feat": "phase", "map": "phase", "head": "phase", "shuffle": "phase"}
    # even H and W is all it needs; head's fourth column phase wants W a multiple of 4
    assert stage_phases(cfg, (1, 2, 2, 3)) == {**cell, "head": (2, 2)}
    assert stage_phases(cfg, (4, 10, 6, 3)) == {**cell, "head": (2, 2)}
    assert stage_phases(cfg, (4, 6, 12, 3)) == cell
    for odd in ((16, 541, 960, 3), (16, 540, 961, 3)):
        assert stage_phases(cfg, odd) == plain
        assert set(stage_forms(cfg, odd).values()) == {"plain"}
    assert stage_phases(EspcnConfig(fast_convs=True), (16, 540, 960, 3)) == plain
    # a conv emits a multiple of what it reads: a stage is capped by its reader
    assert stage_phases(EspcnConfig(c2=128), (2, 8, 8, 3)) == {**plain, "head": (2, 4)}
    assert stage_phases(EspcnConfig(c1=128), (2, 8, 8, 3)) == {**cell, "feat": (1, 1)}
    assert stage_phases(EspcnConfig(c1=16), (2, 8, 8, 3)) == {**cell, "feat": (2, 2)}
    assert stage_phases(EspcnConfig(scale=4), (2, 8, 8, 3)) == dict.fromkeys(cell, (1, 2))
    assert stage_phases(EspcnConfig(scale=7), (2, 8, 8, 3)) == plain    # 147 columns


@pytest.mark.parametrize("shape,cfg_kw,form", [
    ((2, 16, 24, 3), {}, "phase"),
    ((1, 2, 2, 3), {}, "phase"),              # the smallest geometry admitted
    ((1, 10, 6, 3), {}, "phase"),             # a batch of one; W no multiple of 4: head at (2, 2)
    ((2, 8, 12, 3), {"scale": 3}, "phase"),
    ((2, 8, 12, 3), {"scale": 4}, "phase"),   # 48 sub-pixel channels: (1, 2) throughout
    ((2, 8, 12, 3), {"c1": 16}, "phase"),
    ((2, 8, 12, 3), {"c1": 128}, "mixed"),    # plain feat → emitted map
    ((2, 8, 12, 3), {"c2": 128}, "mixed"),    # plain feat, map → emitted head
    ((2, 15, 24, 3), {}, "plain"),
    ((2, 16, 23, 3), {}, "plain"),
], ids=["even", "smallest", "batch1", "scale3", "scale4", "narrow_feat", "wide_feat", "wide_map",
        "odd_h", "odd_w"])
def test_espcn_carried_form_matches_plain_body(shape, cfg_kw, form):
    from dvf_tpu.models.espcn import EspcnConfig, apply_espcn, stage_forms

    cfg = EspcnConfig(compute_dtype=jnp.float32, **cfg_kw)
    forms = set(stage_forms(cfg, shape).values())
    assert forms == ({"plain", "phase"} if form == "mixed" else {form})
    params = _random_espcn_params(cfg)
    x = jax.random.uniform(jax.random.PRNGKey(7), shape)
    got = apply_espcn(params, x, cfg)
    want = _plain_espcn_forward(params, x, cfg)
    assert got.shape == want.shape == (shape[0], shape[1] * cfg.scale, shape[2] * cfg.scale, 3)
    if form == "plain":        # an unadmitted geometry runs the plain body as it was
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        assert float(jnp.abs(want - 0.5).mean()) > 0.05       # not all clipped away
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_espcn_tp_shard_map_forward_in_the_carried_form():
    """feat's Cout shard is sliced inside each emitted phase, map's psum
    runs on the phase tensor's pre-bias partial sums and its bias is tiled
    after it: the explicit shard_map forward equals the replicated one and
    the plain composition."""
    from dvf_tpu.models.espcn import (
        EspcnConfig, apply_espcn, param_pspecs as e_pspecs, stage_forms,
        tp_inner_apply as e_tp)

    cfg = EspcnConfig(compute_dtype=jnp.float32)
    params = _random_espcn_params(cfg)
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 16, 24, 3))
    assert set(stage_forms(cfg, x.shape).values()) == {"phase"}
    mesh = make_mesh(MeshConfig(model=2))
    inner = e_tp(cfg)
    got = jax.jit(jax.shard_map(
        lambda p, b: inner(p, b), mesh=mesh, in_specs=(e_pspecs(cfg), P()),
        out_specs=P(), check_vma=False))(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(apply_espcn(params, x, cfg)),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_plain_espcn_forward(params, x, cfg)), atol=1e-5)


def test_espcn_gradient_through_the_carried_form():
    """train/sr.py differentiates through apply_espcn: the re-indexings are
    gathers on the weights, and the gradient is the plain body's."""
    from dvf_tpu.models.espcn import EspcnConfig, apply_espcn

    cfg = EspcnConfig(compute_dtype=jnp.float32)
    params = _random_espcn_params(cfg)
    x = jax.random.uniform(jax.random.PRNGKey(3), (2, 12, 16, 3))
    target = jax.random.uniform(jax.random.PRNGKey(4), (2, 24, 32, 3))

    def loss(fn):
        return lambda p: jnp.mean((fn(p, x, cfg) - target) ** 2)

    g_carried = jax.grad(loss(apply_espcn))(params)
    g_plain = jax.grad(loss(_plain_espcn_forward))(params)
    for path, g in jax.tree_util.tree_leaves_with_path(g_carried):
        want = g_plain
        for key in path:
            want = want[key.key]
        assert float(jnp.abs(want).max()) > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(np.asarray(g), np.asarray(want), atol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))


def test_espcn_540p_jaxpr_holds_no_lane_starved_tensor():
    """Structure at the cell's shape, no compile: no (16, 540, 960, c)
    activation with 3 < c < 128 exists (the frame's own 3 channels apart);
    the three convs make the phase images (16, 540, 480, 128), (16, 270,
    480, 128) and (16, 270, 240, 96), and the one rearrangement is
    depth_to_space((4, 8))'s transpose."""
    from dvf_tpu.models.espcn import EspcnConfig, apply_espcn, init_espcn

    cfg = EspcnConfig()
    shape = (16, 540, 960, 3)
    params = jax.eval_shape(lambda: init_espcn(jax.random.PRNGKey(0), cfg))
    jaxpr = jax.make_jaxpr(lambda p, x: apply_espcn(p, x, cfg))(
        params, jax.ShapeDtypeStruct(shape, jnp.float32))
    made = [(eqn.primitive.name, tuple(v.aval.shape))
            for eqn in jaxpr.jaxpr.eqns for v in eqn.outvars]
    starved = [s for _, s in made if len(s) == 4 and s[:3] == shape[:3] and 3 < s[3] < 128]
    assert not starved, starved
    assert [s for name, s in made if name == "conv_general_dilated"] == [
        (16, 540, 480, 128), (16, 270, 480, 128), (16, 270, 240, 96)]
    assert [s for name, s in made if name == "transpose" and s[0] == 16] == [
        (16, 270, 4, 240, 8, 3)]


@pytest.mark.parametrize("phases,want_m", [
    (None, (8.29, 41.47, 24.88)),
    ({"feat": (2, 2), "map": (2, 2), "head": (2, 2)}, (4.15, 37.32, 18.66)),
    ("served", (4.15, 24.88, 12.44)),
], ids=["plain", "2x2", "served"])
def test_espcn_form_passes_counts_what_the_mxu_runs(phases, want_m):
    """The pass arithmetic PERF.md's candidate table rests on: M rows x
    K tiles x N tiles, structural zeros included, for a batch of 16."""
    from dvf_tpu.models.analysis import espcn_form_passes
    from dvf_tpu.models.espcn import EspcnConfig, stage_phases

    if phases == "served":
        phases = stage_phases(EspcnConfig(), (16, 540, 960, 3))
    got = espcn_form_passes(540, 960, phases)
    for name, want in zip(("feat", "map", "head"), want_m):
        assert round(16 * got[name]["passes"] / 1e6, 2) == want, (name, got[name])
    if phases is not None and phases["map"] == (2, 2) and phases["feat"] == (1, 2):
        assert (got["map"]["k"], got["map"]["n"]) == (1536, 128)
        assert (got["head"]["k"], got["head"]["n"]) == (1536, 96)
