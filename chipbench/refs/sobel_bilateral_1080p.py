"""Plain reference for sobel_bilateral_1080p: Sobel edge magnitude, then a
bilateral filter over it (BASELINE.json configs[2]), in straightforward
jax.numpy float32 at matmul precision "highest", a frame at a time.

Imports nothing of the program. There are no weights (``make_params``
returns None); the data is the frames. For one uint8 frame (H, W, 3), with
the configuration's ``filter.kwargs`` (d, sigma_color, sigma_space,
magnitude_scale):

  x     = uint8 / 255
  gray  = 0.299 r + 0.587 g + 0.114 b                       (Rec.601)
  gx    = cv2.Sobel(gray, dx=1, dy=0, ksize=3): rows smoothed by [1, 2, 1],
          columns differenced by [-1, 0, 1]; gy the transpose of it; both
          over the BORDER_REFLECT_101 extension of gray (g[-1] = g[1])
  e     = clip(sqrt(gx^2 + gy^2) * magnitude_scale, 0, 1), the edge map,
          on all three channels alike
  out   = cv2.bilateralFilter semantics on e: over the d x d window about
          each pixel p, on the BORDER_REFLECT_101 extension of e,
            w(q) = exp(-|q - p|^2 / (2 sigma_space^2))
                 * exp(-|e(q) - e(p)|_rgb^2 / (2 sigma_color^2))
            out(p) = sum_q w(q) e(q) / sum_q w(q)
          where |.|_rgb^2 is the squared Euclidean distance over the three
          channels: 3 (e(q) - e(p))^2, the channels being equal
  uint8 = round(clip(out, 0, 1) * 255), the one rounding, on three channels

Three departures from two cv2 calls in a row, each the deployment's own
definition (the semantics of ``FilterChain(sobel, bilateral)`` as
dvf_tpu/ops/bilateral.py documents them, written again from that
description) and each listed in the configuration
(``departures_from_cv2``): no rounding to uint8 between the two filters;
the full square d x d window where cv2 masks its corners to a disc; the
sigmas in [0, 1] intensity units.

``control`` is the same mathematics with a bfloat16 body (every array and
every accumulation one precision step below the configuration's float32).
``ring_dropped`` is a structural fault for the table: the window's outer
ring of taps left out (a (d - 2) x (d - 2) window), in float32.

Limits (configs/sobel_bilateral_1080p.json ``limits``; worst sampled frame,
in uint8 steps; the readings are chip runs at the cell's own size, 1080 x
1920, PERF.md section 2 has the table): see LIMITS_ARITHMETIC below.
"""

import functools
import math

import numpy as np

LUMA = (0.299, 0.587, 0.114)

# The chip runs the limits were set by (PR 43, at the cell's own size), so
# that the arithmetic travels with the reference. Each limit stands near
# the geometric middle of the largest sound reading and the smallest
# control reading: limit ~ sqrt(sound_max * control_min).
LIMITS_ARITHMETIC = {
    "mean_abs_steps": {
        "sound": (1.74e-05, 2.22e-05),        # 17 runs, 12 seeds, change and parent
        "control_bfloat16": (0.562, 0.566),   # 2 seeds
        "ring_dropped": (0.268, 0.269),       # 2 seeds
        "geometric_middle": 0.0035,           # sqrt(2.22e-05 * 0.562)
        "limit": 0.0035,                      # 158 times over sound, 160 times under the control
    },
    "max_abs_steps": {
        "sound": (1, 1),
        "control_bfloat16": (9, 11),
        "ring_dropped": (5, 6),
        "geometric_middle": 3.0,              # sqrt(1 * 9)
        "limit": 3,
    },
}


def make_params(seed, config):
    return None


def _reflect101(a, r):
    """(H, W) -> (H + 2r, W + 2r), BORDER_REFLECT_101: a[-k] = a[k]."""
    import jax.numpy as jnp

    return jnp.pad(a, ((r, r), (r, r)), mode="reflect")


def _edge_map(x, magnitude_scale, dt):
    """x (H, W, 3) in [0, 1] -> the clipped Sobel magnitude (H, W)."""
    import jax.numpy as jnp

    h, w = x.shape[:2]
    gray = (dt(LUMA[0]) * x[..., 0] + dt(LUMA[1]) * x[..., 1] + dt(LUMA[2]) * x[..., 2])
    g = _reflect101(gray, 1)
    # g[1 + y + dy, 1 + x + dx] is gray(y + dy, x + dx)
    at = lambda dy, dx: g[1 + dy:1 + dy + h, 1 + dx:1 + dx + w]
    gx = ((at(-1, 1) - at(-1, -1)) + dt(2.0) * (at(0, 1) - at(0, -1))
          + (at(1, 1) - at(1, -1)))
    gy = ((at(1, -1) - at(-1, -1)) + dt(2.0) * (at(1, 0) - at(-1, 0))
          + (at(1, 1) - at(-1, 1)))
    mag = jnp.sqrt(gx * gx + gy * gy) * dt(magnitude_scale)
    return jnp.clip(mag, dt(0.0), dt(1.0))


def _bilateral(e, d, sigma_color, sigma_space, dt, window=None):
    """cv2.bilateralFilter semantics on the single-valued image ``e``
    (H, W) standing for three equal channels. ``window`` (odd, <= d) keeps
    only the taps of the inner window x window square."""
    import jax.numpy as jnp

    h, w = e.shape
    r = d // 2
    keep = (window if window is not None else d) // 2
    p = _reflect101(e, r)
    inv2sc = dt(3.0 / (2.0 * sigma_color * sigma_color))     # |.|_rgb^2 = 3 delta^2
    num = jnp.zeros_like(e)
    den = jnp.zeros_like(e)
    for dy in range(-keep, keep + 1):
        for dx in range(-keep, keep + 1):
            ws = dt(math.exp(-(dy * dy + dx * dx) / (2.0 * sigma_space * sigma_space)))
            q = p[r + dy:r + dy + h, r + dx:r + dx + w]
            delta = q - e
            wgt = ws * jnp.exp(-(delta * delta) * inv2sc)
            num = num + wgt * q
            den = den + wgt
    return num / den


def _forward(frame_u8, kwargs, precision, window):
    import jax.numpy as jnp

    dtype = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    dt = lambda v: jnp.asarray(v, dtype)
    x = frame_u8.astype(dtype) * dt(1.0 / 255.0)
    e = _edge_map(x, float(kwargs["magnitude_scale"]), dt)
    out = _bilateral(e, int(kwargs["d"]), float(kwargs["sigma_color"]),
                     float(kwargs["sigma_space"]), dt, window)
    y = jnp.round(jnp.clip(out.astype(jnp.float32), 0.0, 1.0) * 255.0).astype(jnp.uint8)
    return jnp.broadcast_to(y[..., None], frame_u8.shape)


@functools.lru_cache(maxsize=None)
def _jitted(kwargs_key, precision, window):
    import json

    import jax

    kwargs = json.loads(kwargs_key)
    return jax.jit(lambda f: _forward(f, kwargs, precision, window))


def _run(frames, config, precision, window=None):
    """One frame at a time, one compiled program for all of them."""
    import json

    import jax

    fn = _jitted(json.dumps(config["filter"]["kwargs"], sort_keys=True), precision, window)
    with jax.default_matmul_precision("highest"):
        return [np.asarray(fn(np.asarray(f))) for f in frames]


def reference(frames, config, params=None):
    return _run(frames, config, "float32")


def control(frames, config, params=None):
    return _run(frames, config, "bfloat16")


def ring_dropped(frames, config, params=None):
    """The reference with the window's outer ring of taps left out."""
    return _run(frames, config, "float32", int(config["filter"]["kwargs"]["d"]) - 2)
