"""Plain reference for clahe_1080p: contrast-limited adaptive histogram
equalisation as ``cv2.createCLAHE(clipLimit, tileGridSize)`` defines it
(OpenCV's tutorial "Histograms - 2: Histogram Equalization"; the algorithm
of modules/imgproc/src/clahe.cpp), written again in straightforward
jax.numpy, a frame at a time, each RGB channel a plane of its own.

Imports nothing of the program. There are no weights (``make_params``
returns None); the data is the frames. For one uint8 plane (H, W), with the
configuration's ``filter.kwargs`` (clip_limit, grid):

  pad    right and bottom to a multiple of grid, BORDER_REFLECT_101
  tile   (H / grid) x (W / grid) pixels, grid x grid of them; area = th tw
  hist   of each tile, 256 bins, BY COUNTING (a compare with every bin
         value and a sum: no sort, no kernel of the program's)
  clip   clip_abs = max(1, int(clip_limit * area / 256)); every bin over
         it is cut to it; the cut mass goes back as excess // 256 to every
         bin, and the residual excess % 256 one count each to bins 0,
         step, 2 step, ... with step = max(256 // residual, 1), in INTEGER
         arithmetic (cv2's redistribution, loop for loop)
  lut    lut[v] = round(cumsum(hist)[v] * (255 / area)), float32, half to
         even (cv2's cvRound), saturated to uint8
  blend  a pixel (y, x) lies between tile centres: ty = y / th - 0.5,
         ty1 = floor(ty), ty2 = ty1 + 1, ya = ty - ty1 (tiles clamped to
         the grid); the same along x; with v the pixel's value
           out = (lut[ty1, tx1][v] (1 - xa) + lut[ty1, tx2][v] xa) (1 - ya)
               + (lut[ty2, tx1][v] (1 - xa) + lut[ty2, tx2][v] xa) ya
         by plain indexing, float32, then ONE rounding (half to even) and
         a clip to uint8; the pad is cropped

Departures from the cv2 call of the tutorial, each the deployment's own:
per-channel RGB (three planes a frame) where the tutorial's image is
gray; the tile coordinate is a division ``y / th`` where cv2 multiplies by
a float32 reciprocal (the fractions differ in the last bit); where only
one of H and W is a multiple of the grid cv2 pads the other by a whole
grid more, and this pads each axis to its own next multiple (1080 x 1920
divides by 8 both ways: no pad in the cell); a float32 blend and one
rounding, as cv2's.

Where it runs: on jax's default device (the chip in a benchmark run: the
four image-sized lookups are XLA gathers there, and the pool's eight
1080p frames with the comparison take 4 to 6.4 s a run, PR 49's chip
runs: no need for the CPU's devices).

``control`` is the same mathematics with the blend in bfloat16 (tables,
weights, products and sums one precision step below the configuration's
float32). ``residual_dropped`` is a structural fault for the table: the
residual pass of the redistribution left out, in float32.

Limits (configs/clahe_1080p.json ``limits``; worst sampled frame, in uint8
steps; the readings are chip runs at the cell's own size, 1080 x 1920;
PERF.md section 2 has the table): see LIMITS_ARITHMETIC below.
"""

import functools

import numpy as np

BINS = 256

# The chip runs the limits were set by (PR 49, at the cell's own size), so
# that the arithmetic travels with the reference. Each limit stands near
# the geometric middle of the largest sound reading and the smallest
# control reading.
LIMITS_ARITHMETIC = {
    "mean_abs_steps": {
        "sound": (0.000474, 0.000534),        # 14 runs, 7 seeds
        "control_bfloat16": (0.1748, 0.1749),  # 2 seeds
        "residual_dropped": (0.636, 0.653),    # 2 seeds
        "geometric_middle": 0.0097,            # sqrt(0.000534 * 0.1748)
        "limit": 0.0097,                       # 18 times over sound, 18 times under the control
    },
    "max_abs_steps": {
        "sound": (1, 1),                       # a blend that ties rounds the other way: one step, never two
        "control_bfloat16": (1, 1),            # not told apart here: mean_abs_steps fails it
        "residual_dropped": (3, 3),
        "geometric_middle": 1.7,               # sqrt(1 * 3)
        "limit": 2,
    },
}


def make_params(seed, config):
    return None


def _luts(tiles, area, clip_limit, residual_pass):
    """(T, area) int32 tile pixels -> (T, 256) float32 tables."""
    import jax.numpy as jnp

    bins = jnp.arange(BINS, dtype=jnp.int32)
    hist = (tiles[:, :, None] == bins[None, None, :]).sum(axis=1, dtype=jnp.int32)
    clip_abs = max(1, int(clip_limit * area / BINS))
    excess = jnp.maximum(hist - clip_abs, 0).sum(axis=1, keepdims=True)
    hist = jnp.minimum(hist, clip_abs) + excess // BINS
    if residual_pass:
        residual = excess % BINS
        step = jnp.maximum(BINS // jnp.maximum(residual, 1), 1)
        hist = hist + ((bins[None] % step == 0) & (bins[None] // step < residual))
    scale = jnp.float32(BINS - 1) / jnp.float32(area)
    lut = jnp.round(jnp.cumsum(hist, axis=1).astype(jnp.float32) * scale)
    return jnp.clip(lut, 0.0, 255.0)


def _axis(size, tile, grid):
    """Tile indices either side of each pixel centre and the fraction."""
    import jax.numpy as jnp

    t = jnp.arange(size, dtype=jnp.float32) / jnp.float32(tile) - jnp.float32(0.5)
    t1 = jnp.floor(t)
    a = t - t1
    t1 = t1.astype(jnp.int32)
    return jnp.clip(t1, 0, grid - 1), jnp.clip(t1 + 1, 0, grid - 1), a


def _plane(plane, clip_limit, grid, blend_dtype, residual_pass):
    """One uint8 plane (H, W) -> its CLAHE, uint8."""
    import jax.numpy as jnp

    h, w = plane.shape
    hp, wp = -(-h // grid) * grid, -(-w // grid) * grid
    x = jnp.pad(plane, ((0, hp - h), (0, wp - w)), mode="reflect").astype(jnp.int32)
    th, tw = hp // grid, wp // grid
    tiles = x.reshape(grid, th, grid, tw).transpose(0, 2, 1, 3).reshape(grid * grid, th * tw)
    lut = _luts(tiles, th * tw, clip_limit, residual_pass).reshape(grid, grid, BINS)
    lut = lut.astype(blend_dtype)
    ty1, ty2, ya = _axis(hp, th, grid)
    tx1, tx2, xa = _axis(wp, tw, grid)
    ya, xa = ya[:, None].astype(blend_dtype), xa[None, :].astype(blend_dtype)
    one = jnp.asarray(1.0, blend_dtype)
    at = lambda ty, tx: lut[ty[:, None], tx[None, :], x]
    out = ((at(ty1, tx1) * (one - xa) + at(ty1, tx2) * xa) * (one - ya)
           + (at(ty2, tx1) * (one - xa) + at(ty2, tx2) * xa) * ya)
    out = jnp.clip(jnp.round(out.astype(jnp.float32)), 0.0, 255.0).astype(jnp.uint8)
    return out[:h, :w]


def _forward(frame_u8, kwargs, precision, residual_pass):
    import jax.numpy as jnp

    dtype = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    if kwargs.get("on_gray"):
        raise ValueError("the reference is per channel: on_gray is not this deployment's")
    planes = [_plane(frame_u8[..., c], float(kwargs["clip_limit"]), int(kwargs["grid"]),
                     dtype, residual_pass) for c in range(frame_u8.shape[-1])]
    return jnp.stack(planes, axis=-1)


@functools.lru_cache(maxsize=None)
def _jitted(kwargs_key, precision, residual_pass):
    import json

    import jax

    kwargs = json.loads(kwargs_key)
    return jax.jit(lambda f: _forward(f, kwargs, precision, residual_pass))


def _run(frames, config, precision, residual_pass=True):
    """One frame at a time, one compiled program for all of them."""
    import json

    import jax

    fn = _jitted(json.dumps(config["filter"]["kwargs"], sort_keys=True), precision,
                 residual_pass)
    with jax.default_matmul_precision("highest"):
        return [np.asarray(fn(np.asarray(f))) for f in frames]


def reference(frames, config, params=None):
    return _run(frames, config, "float32")


def control(frames, config, params=None):
    return _run(frames, config, "bfloat16")


def residual_dropped(frames, config, params=None):
    """The reference with the redistribution's residual pass left out."""
    return _run(frames, config, "float32", residual_pass=False)
