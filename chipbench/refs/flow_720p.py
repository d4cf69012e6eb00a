"""Plain reference for flow_720p: Farneback (SCIA 2003) two-frame motion
estimation as ``cv2.calcOpticalFlowFarneback`` with the Gaussian window
runs it, and the previous frame warped onto the current one by that flow,
in straightforward jax.numpy float32 at matmul precision "highest".

Imports nothing of the program. There are no weights (``make_params``
returns None); the data is the frames. For one pair (prev, cur) of uint8
frames, with the configuration's ``filter.kwargs``:

  x      = uint8 / 255;  gray = 0.299 r + 0.587 g + 0.114 b
  g0     = gray resized to (H / flow_scale, W / flow_scale), bilinear with
           the antialiasing triangle (jax.image.resize "linear")
  level l of ``levels``: g0 resized the same way to round(size * 0.5**l)
  poly   = per pixel, the quadratic f(x) ~ x'Ax + b'x + c fitted by least
           squares under the Gaussian applicability exp(-r^2 / (2 * 1.1^2))
           over the 11 x 11 neighbourhood (n = 5), reflect-101 borders:
           moments by one 2-D correlation with the six weighted basis
           kernels [1, x, y, x^2, y^2, xy], coefficients by the inverse of
           the 6 x 6 normal matrix
  coarse to fine, d = 0 at the coarsest level, ``n_iters`` times a level:
           poly2 sampled at x + d (bilinear, coordinates clamped to the
           border; where inner_warp is "pallas" d is clipped there to
           +- ceil(max_disp / flow_scale) first, as the program's bounded
           kernel does)
           A = (A1 + A2) / 2;  db = -(b2 - b1) / 2 + A d
           G = A'A, h = A'db, both averaged over the ``win_size``-tap
           Gaussian window (sigma = win_size / 6, separable, reflect-101)
           d = (G + lam I)^-1 h with lam = 1e-3 trace(G) + 1e-12
           (the filter's own relative Tikhonov term: image values are
           O(1), the tensor entries O(1e-4))
           between levels d is resized bilinearly and scaled by the ratio
  flow   = d resized bilinearly to (H, W), times flow_scale
  out    = prev sampled at x + clip(flow, -max_disp, +max_disp), bilinear,
           border replicated; round(clip(., 0, 1) * 255) as uint8

Three departures from cv2, each the filter's own definition and each
listed in the configuration (``departures_from_cv2``): the clip of the
final warp (the bounded warp moves a pixel at most ``max_disp``; cv2's
remap is unbounded), the clip inside the iteration (the same bound on the
half-resolution grid, where ``inner_warp`` is "pallas": what
``flow_warp()`` is on a TPU, and so what ``BENCH_CONFIGS["flow_720p"]``
runs there), and the relative Tikhonov term of the 2 x 2 solve. With
``inner_warp`` "gather" the iteration is cv2's. The configuration states
``warp_impl``, ``max_disp``, ``inner_warp`` and ``win_type`` itself;
nothing here depends on a per-backend default.

``reference(pool, ...)`` answers per pool entry: session k's frame i is
pool entry (k + i) mod n (chipbench/frames.py), so a session's frame
carrying entry j follows the one carrying entry j - 1, and entry j's
answer is entry j - 1 warped onto it. A session's frame 0 (no previous
frame: passed through) lies in the ramp and is never sampled.

``control`` is the same mathematics with a bfloat16 body (every array and
every accumulation one precision step down). ``leaky`` is the fault the
session table exists to prevent: each row of a shared batch takes its
previous frame from the batch row before it, whoever that belongs to.
"""

import functools
import math

import numpy as np


def make_params(seed, config):
    return None


def _gauss(taps, sigma):
    half = (taps - 1) / 2.0
    vals = [math.exp(-((i - half) ** 2) / (2.0 * sigma * sigma)) for i in range(taps)]
    return np.asarray(vals, np.float64) / sum(vals)


def _poly_kernels(n=5, sigma=1.1):
    """(11, 11, 1, 6) weighted basis kernels and the 6 x 6 inverse."""
    xs = np.arange(-n, n + 1, dtype=np.float64)
    g = np.exp(-(xs ** 2) / (2.0 * sigma * sigma))
    g /= g.sum()
    wgt = np.outer(g, g)                                  # rows y, columns x
    X, Y = np.meshgrid(xs, xs, indexing="xy")
    basis = np.stack([np.ones_like(X), X, Y, X * X, Y * Y, X * Y], axis=-1)
    gram = np.einsum("yx,yxi,yxj->ij", wgt, basis, basis)
    kernels = (wgt[..., None] * basis)[:, :, None, :]
    return kernels, np.linalg.inv(gram)


def _pair(prev_u8, cur_u8, kwargs, dtype):
    import jax
    import jax.numpy as jnp
    from jax import lax

    levels, win, iters = kwargs["levels"], kwargs["win_size"], kwargs["n_iters"]
    scale, max_disp = kwargs["flow_scale"], kwargs["max_disp"]
    if kwargs["win_type"] != "gaussian":
        raise SystemExit("refs/flow_720p.py: only the Gaussian window is written down")
    inner_clip = (max(1, -(-max_disp // max(1, scale)))
                  if kwargs["inner_warp"] == "pallas" else None)
    h, w, _ = prev_u8.shape
    kernels, gram_inv = _poly_kernels()
    kernels, gram_inv = jnp.asarray(kernels, dtype), jnp.asarray(gram_inv, dtype)
    win_k = jnp.asarray(_gauss(win, win / 6.0), dtype)

    def resize(a, hh, ww):
        return jax.image.resize(a, (hh, ww) + a.shape[2:], method="linear").astype(dtype)

    def correlate(a, k4):
        """(H, W, C) with an (kh, kw, 1, O) kernel per channel, reflect-101."""
        rh, rw = k4.shape[0] // 2, k4.shape[1] // 2
        a = jnp.pad(a, ((rh, rh), (rw, rw), (0, 0)), mode="reflect")
        a = jnp.transpose(a, (2, 0, 1))[..., None]        # channels as batch
        out = lax.conv_general_dilated(a, k4, (1, 1), "VALID",
                                       dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return out.astype(dtype)                          # (C, H, W, O)

    def poly(gray):
        """(H, W) -> (H, W, 5): A11, A12, A22, b1, b2."""
        v = correlate(gray[..., None], kernels)[0]        # (H, W, 6)
        r = jnp.einsum("hwi,ji->hwj", v, gram_inv).astype(dtype)
        return jnp.stack([r[..., 3], 0.5 * r[..., 5], r[..., 4], r[..., 1], r[..., 2]],
                         axis=-1)

    def sample(img, dx, dy):
        """img (H, W, C) at (x + dx, y + dy), bilinear, border replicated."""
        hh, ww = img.shape[:2]
        ys = jnp.clip(jnp.arange(hh, dtype=jnp.float32)[:, None] + dy, 0.0, hh - 1.0)
        xs = jnp.clip(jnp.arange(ww, dtype=jnp.float32)[None, :] + dx, 0.0, ww - 1.0)
        y0, x0 = jnp.floor(ys), jnp.floor(xs)
        wy, wx = (ys - y0).astype(dtype)[..., None], (xs - x0).astype(dtype)[..., None]
        y0, x0 = y0.astype(jnp.int32), x0.astype(jnp.int32)
        y1, x1 = jnp.minimum(y0 + 1, hh - 1), jnp.minimum(x0 + 1, ww - 1)
        top = img[y0, x0] * (1 - wx) + img[y0, x1] * wx
        bot = img[y1, x0] * (1 - wx) + img[y1, x1] * wx
        return (top * (1 - wy) + bot * wy).astype(dtype)

    def smooth(a):
        a = correlate(a, win_k.reshape(-1, 1, 1, 1))[..., 0]          # (C, H, W)
        a = correlate(jnp.transpose(a, (1, 2, 0)), win_k.reshape(1, -1, 1, 1))[..., 0]
        return jnp.transpose(a, (1, 2, 0))

    def gray_of(u8):
        x = u8.astype(dtype) * jnp.asarray(1.0 / 255.0, dtype)
        g = 0.299 * x[..., 0] + 0.587 * x[..., 1] + 0.114 * x[..., 2]
        g = g.astype(dtype)
        return x, (resize(g, h // scale, w // scale) if scale > 1 else g)

    prev, g_prev = gray_of(prev_u8)
    _, g_cur = gray_of(cur_u8)
    eh, ew = g_prev.shape
    shapes = [(max(8, int(round(eh * 0.5 ** lv))), max(8, int(round(ew * 0.5 ** lv))))
              for lv in range(levels)]
    d = None
    for lv in range(levels - 1, -1, -1):
        lh, lw = shapes[lv]
        p1, p2 = poly(resize(g_prev, lh, lw)), poly(resize(g_cur, lh, lw))
        if d is None:
            d = jnp.zeros((lh, lw, 2), dtype)
        else:
            ph, pw = shapes[lv + 1]
            d = resize(d, lh, lw) * jnp.asarray([lw / pw, lh / ph], dtype)
        for _ in range(iters):
            dd = d if inner_clip is None else jnp.clip(d, -inner_clip, inner_clip)
            q = sample(p2, dd[..., 0].astype(jnp.float32), dd[..., 1].astype(jnp.float32))
            a11, a12, a22 = (0.5 * (p1[..., i] + q[..., i]) for i in range(3))
            db1 = -0.5 * (q[..., 3] - p1[..., 3]) + a11 * d[..., 0] + a12 * d[..., 1]
            db2 = -0.5 * (q[..., 4] - p1[..., 4]) + a12 * d[..., 0] + a22 * d[..., 1]
            sm = smooth(jnp.stack([a11 * a11 + a12 * a12, a12 * (a11 + a22),
                                   a12 * a12 + a22 * a22, a11 * db1 + a12 * db2,
                                   a12 * db1 + a22 * db2], axis=-1).astype(dtype))
            g11, g12, g22, s1, s2 = (sm[..., i] for i in range(5))
            lam = 1e-3 * (g11 + g22) + 1e-12
            g11, g22 = g11 + lam, g22 + lam
            det = g11 * g22 - g12 * g12
            d = jnp.stack([(g22 * s1 - g12 * s2) / det, (g11 * s2 - g12 * s1) / det],
                          axis=-1).astype(dtype)
    if scale > 1:
        d = resize(d, h, w) * jnp.asarray(float(scale), dtype)
    d = jnp.clip(d.astype(jnp.float32), -float(max_disp), float(max_disp))
    out = sample(prev, d[..., 0], d[..., 1]).astype(jnp.float32)
    return jnp.round(jnp.clip(out, 0.0, 1.0) * 255.0).astype(jnp.uint8)


@functools.lru_cache(maxsize=None)
def _jitted(kwargs_key, precision):
    import json

    import jax
    import jax.numpy as jnp

    kwargs = json.loads(kwargs_key)
    dtype = jnp.float32 if precision == "float32" else jnp.bfloat16
    return jax.jit(lambda p, c: _pair(p, c, kwargs, dtype))


def _pairs(pairs, config, precision):
    """[(prev, cur)] -> [uint8 frame], one pair at a time through one
    compiled program; equal pairs (the pool has few frames) computed once."""
    import json

    import jax

    fn = _jitted(json.dumps(config["filter"]["kwargs"], sort_keys=True), precision)
    done, out = {}, []
    with jax.default_matmul_precision("highest"):
        for prev, cur in pairs:
            key = (id(prev), id(cur))
            if key not in done:
                done[key] = np.asarray(fn(np.asarray(prev), np.asarray(cur)))
            out.append(done[key])
    return out


def _previous(frames):
    return [(frames[j - 1], frames[j]) for j in range(len(frames))]


def reference(frames, config, params):
    return _pairs(_previous(frames), config, "float32")


def control(frames, config, params):
    return _pairs(_previous(frames), config, "bfloat16")


def leaky(frames, config, params, sessions, rows):
    """What a shared batch gives when state follows the batch and not the
    session: ``rows`` consecutive batch rows in the order the service fills
    them (frame index major, session minor, from frame 1 on; session k's
    frame i is pool entry (k + i) mod n), each warped from the frame in the
    row before it. Returns check.compare_numbers' samples, (k, i, frame)."""
    n = len(frames)
    order = [(r % sessions, 1 + r // sessions) for r in range(-1, rows)]   # -1: the row before
    carried = [frames[(k + i) % n] for k, i in order]
    outs = _pairs(list(zip(carried[:-1], carried[1:])), config, "float32")
    return [(k, i, out) for (k, i), out in zip(order[1:], outs)]
