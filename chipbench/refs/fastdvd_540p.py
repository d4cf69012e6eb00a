"""Plain reference for fastdvd_540p: FastDVDnet (Tassano, Delon, Veit, CVPR
2020, arXiv:1907.01361; the authors' models.py) in straightforward
jax.numpy float32 at matmul precision "highest", one five-frame window at
a time, in the UNCACHED form: four DenBlocks a window (three of stage 1 on
the window's triplets, one of stage 2 on their results), nothing kept from
one window to the next.

Imports nothing of the program and takes nothing the program made: the
weights are made here, on the device, in one jitted call from the seed
(``make_params``), and handed to the program as its ``params``.

Equations (NHWC; every convolution 3 x 3, zero padding 1, no bias, weights
HWIO; BN(y) = gamma (y - mean) / sqrt(var + 1e-5) + beta per channel;
PS = PixelShuffle(2) in PyTorch's order: out[2h + dy, 2w + dx, c] =
in[h, w, 4 c + 2 dy + dx]):
  CvBlock(c)  = [conv c->c, BN, ReLU] x 2
  Input       = concat(f0, m, f1, m, f2, m) (12 channels); conv 12->90 in
                three groups (channels 4g..4g+3 -> 30g..30g+29), BN, ReLU;
                conv 90->32, BN, ReLU
  Down(a->b)  = conv a->b stride 2, BN, ReLU; CvBlock(b)
  Up(a->b)    = CvBlock(a); conv a->4b; PS
  Output      = conv 32->32, BN, ReLU; conv 32->3
  DenBlock(f0, f1, f2, m): x0 = Input; x1 = Down(32->64)(x0);
                x2 = Down(64->128)(x1); x2 = Up(128->64)(x2);
                x1 = Up(64->32)(x1 + x2); return f1 - Output(x0 + x1)
  FastDVDnet(f0..f4, m): d_k = DenBlock_1(f_k, f_k+1, f_k+2, m), k = 0, 1, 2;
                return DenBlock_2(d_0, d_1, d_2, m), stage-1 results unclipped
  x = uint8 / 255; m = sigma (filter.kwargs.sigma) everywhere;
  out = round(clip(., 0, 1) * 255) as uint8

``reference(pool, ...)`` answers per pool entry: session k's frame i is pool
entry (k + i) mod n (chipbench/frames.py), so the delivery for a frame
carrying entry j is the denoised CENTRE of the window of entries j-4 .. j
(mod n), entry j-2: the service's delivery for frame n is the denoised frame
n - 2 (the configuration's guarantees.output_lag_frames). A session's first
four frames (a window not yet five deep, filled with the session's first
frame) lie in the ramp and are never sampled; ``stream`` walks one session
from its first frame with that rule, for the tests.

``control`` is the same net with every convolution's operands rounded to
float8_e4m3fn under per-tensor amax scaling (the best case of an fp8 path,
the precision one step below the configuration's bfloat16), accumulated in
float32. ``stale_cache`` is the structural fault the cached form can have:
stage 2 fed d(n-2) three times (the cache read at one lag for all).
"""

import functools

import numpy as np

WIDTHS = (32, 64, 128)
EPS = 1e-5


def _conv_shapes():
    """{path: (cin of the kernel, cout)} and {path: channels} of one DenBlock."""
    c0, c1, c2 = WIDTHS
    convs = {("inc", "conv0"): (4, 90), ("inc", "conv1"): (90, c0),
             ("down0", "conv"): (c0, c1), ("down1", "conv"): (c1, c2),
             ("up2", "conv"): (c2, 4 * c1), ("up1", "conv"): (c1, 4 * c0),
             ("out", "conv0"): (c0, c0), ("out", "conv1"): (c0, 3)}
    norms = {("inc", "bn0"): 90, ("inc", "bn1"): c0, ("down0", "bn"): c1,
             ("down1", "bn"): c2, ("out", "bn0"): c0}
    for where, c in (("down0", c1), ("down1", c2), ("up2", c2), ("up1", c1)):
        for i in (0, 1):
            convs[(where, "cv", f"conv{i}")] = (c, c)
            norms[(where, "cv", f"bn{i}")] = c
    return convs, norms


def _put(tree, path, leaf):
    for key in path[:-1]:
        tree = tree.setdefault(key, {})
    tree[path[-1]] = leaf


def make_params(seed, config):
    """{"stage1": DenBlock, "stage2": DenBlock}, float32: He-scaled kernels,
    seeded norm statistics (gamma about 1, beta and mean about 0, var about
    1, none at its default: a dropped norm term shows). The last
    convolution's scale is cut to 0.06 of it so that the residual is a
    fraction of the frame and little of the result clips."""
    import jax
    import jax.numpy as jnp

    convs, norms = _conv_shapes()

    @jax.jit
    def build(key):
        params = {}
        for s, stage in enumerate(("stage1", "stage2")):
            block = params.setdefault(stage, {})
            for i, (path, (cin, cout)) in enumerate(sorted(convs.items())):
                k = jax.random.fold_in(jax.random.fold_in(key, s), i)
                std = (2.0 / (9 * cin)) ** 0.5 * (0.06 if path == ("out", "conv1") else 1.0)
                _put(block, path, jax.random.normal(k, (3, 3, cin, cout), jnp.float32) * std)
            for i, (path, c) in enumerate(sorted(norms.items())):
                k = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, s), 1000 + i), 4)
                _put(block, path, {
                    "gamma": 1.0 + 0.1 * jax.random.normal(k[0], (c,), jnp.float32),
                    "beta": 0.1 * jax.random.normal(k[1], (c,), jnp.float32),
                    "mean": 0.1 * jax.random.normal(k[2], (c,), jnp.float32),
                    "var": jnp.exp(0.2 * jax.random.normal(k[3], (c,), jnp.float32))})
        return params

    return build(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))


def _ops(precision):
    import jax
    import jax.numpy as jnp
    from jax import lax

    def q8(t):
        s = jnp.maximum(jnp.max(jnp.abs(t)), 1e-12) / 448.0
        return (t / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s

    def conv(w, x, stride=1, groups=1):
        if precision == "fp8":
            x, w = q8(x), q8(w)
        elif precision == "bfloat16":
            x, w = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
        return lax.conv_general_dilated(
            x, w, (stride, stride), ((1, 1), (1, 1)), dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups, preferred_element_type=jnp.float32)

    def bn_relu(p, y):
        return jax.nn.relu(p["gamma"] * (y - p["mean"]) / jnp.sqrt(p["var"] + EPS) + p["beta"])

    def cvblock(p, x):
        x = bn_relu(p["bn0"], conv(p["conv0"], x))
        return bn_relu(p["bn1"], conv(p["conv1"], x))

    def pixel_shuffle(x):
        b, h, w, c4 = x.shape
        x = x.reshape(b, h, w, c4 // 4, 2, 2)                 # [..., c, dy, dx]
        return x.transpose(0, 1, 4, 2, 5, 3).reshape(b, 2 * h, 2 * w, c4 // 4)

    def denblock(p, f0, f1, f2, sigma):
        m = jnp.full(f1.shape[:3] + (1,), sigma, jnp.float32)
        x = jnp.concatenate([f0, m, f1, m, f2, m], axis=-1)
        x = bn_relu(p["inc"]["bn0"], conv(p["inc"]["conv0"], x, groups=3))
        x0 = bn_relu(p["inc"]["bn1"], conv(p["inc"]["conv1"], x))
        x1 = cvblock(p["down0"]["cv"], bn_relu(p["down0"]["bn"], conv(p["down0"]["conv"], x0, 2)))
        x2 = cvblock(p["down1"]["cv"], bn_relu(p["down1"]["bn"], conv(p["down1"]["conv"], x1, 2)))
        x2 = pixel_shuffle(conv(p["up2"]["conv"], cvblock(p["up2"]["cv"], x2)))
        x1 = pixel_shuffle(conv(p["up1"]["conv"], cvblock(p["up1"]["cv"], x1 + x2)))
        x = bn_relu(p["out"]["bn0"], conv(p["out"]["conv0"], x0 + x1))
        return f1 - conv(p["out"]["conv1"], x)

    return denblock


def _window(params, frames_u8, sigma, precision, stale):
    """(5, H, W, 3) uint8 -> (H, W, 3) uint8: four DenBlocks."""
    import jax.numpy as jnp

    denblock = _ops(precision)
    f = frames_u8.astype(jnp.float32)[:, None] * (1.0 / 255.0)
    d = [denblock(params["stage1"], f[k], f[k + 1], f[k + 2], sigma) for k in range(3)]
    if stale:
        d = [d[1], d[1], d[1]]
    y = denblock(params["stage2"], d[0], d[1], d[2], sigma)[0]
    return jnp.round(jnp.clip(y, 0.0, 1.0) * 255.0).astype(jnp.uint8)


@functools.lru_cache(maxsize=None)
def _jitted(sigma, precision, stale):
    import jax

    return jax.jit(lambda p, w: _window(p, w, sigma, precision, stale))


def _windows(windows, config, params, precision="float32", stale=False):
    """[five frames] -> [uint8 frame], one window at a time through one
    compiled program."""
    import jax

    fn = _jitted(float(config["filter"]["kwargs"]["sigma"]), precision, stale)
    with jax.default_matmul_precision("highest"):
        return [np.asarray(fn(params, np.stack([np.asarray(f) for f in w]))) for w in windows]


def _pool_windows(frames):
    n = len(frames)
    return [[frames[(j - 4 + t) % n] for t in range(5)] for j in range(n)]


def reference(frames, config, params):
    return _windows(_pool_windows(frames), config, params)


def control(frames, config, params):
    return _windows(_pool_windows(frames), config, params, precision="fp8")


def stale_cache(frames, config, params):
    return _windows(_pool_windows(frames), config, params, stale=True)


def bfloat16_run(frames, config, params):
    """The reference with bfloat16 convolution operands: what a sound
    program's rounding looks like, for tests that have no program."""
    return _windows(_pool_windows(frames), config, params, precision="bfloat16")


def stream(frames, config, params):
    """One session's deliveries from its first frame on: delivery n is the
    window of frames n-4 .. n, an index before the stream's start holding
    frame 0 (the configuration's departures.warm_up), so its centre is
    frame max(n - 2, 0)."""
    return _windows([[frames[max(n - 4 + t, 0)] for t in range(5)] for n in range(len(frames))],
                    config, params)
