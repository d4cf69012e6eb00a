"""Convolutional filters: separable Gaussian blur, box blur, Sobel edges.

These cover BASELINE.json configs[1] (3x3 / 9x9 separable Gaussian, 1080p)
and the Sobel half of configs[2]. The reference has no conv ops — its only op
is invert (inverter.py:41) — so these are capability extensions specified by
the north-star configs.

TPU mapping: the default lowering is stencil-as-shifted-FMAs
(``_shifted_sep_conv``) — k static shifted slices of one padded buffer,
multiply-added per axis. A C=3 depthwise conv can't fill the MXU's
128-wide reduction and XLA's depthwise path is slow on TPU and CPU alike;
the shift formulation is pure VPU elementwise work XLA fuses into one
pass per axis (measured ~13× on the CPU backend at 1080p k=9). The depthwise
``lax.conv_general_dilated`` form is kept for A/B benchmarking
(``impl="depthwise"``). Separability keeps arithmetic O(k) per pixel
either way. Borders use reflect-101 padding (``jnp.pad(mode="reflect")``),
matching cv2's default ``BORDER_REFLECT_101`` so golden tests compare
exactly.
"""

from __future__ import annotations

import math
from typing import Optional

import jax.numpy as jnp
import numpy as np
from jax import lax

from dvf_tpu.api.filter import Filter, stateless
from dvf_tpu.ops.registry import get_filter, measured_default_for, register_filter
from dvf_tpu.utils.image import rgb_to_gray

_DN = ("NHWC", "HWIO", "NHWC")  # conv dimension numbers used throughout


_CV2_SMALL_GAUSS = {
    1: (1.0,),
    3: (0.25, 0.5, 0.25),
    5: (0.0625, 0.25, 0.375, 0.25, 0.0625),
    7: (0.03125, 0.109375, 0.21875, 0.28125, 0.21875, 0.109375, 0.03125),
    9: (0.015625, 0.05078125, 0.1171875, 0.19921875, 0.234375,
        0.19921875, 0.1171875, 0.05078125, 0.015625),
}


def gaussian_kernel_1d(ksize: int, sigma: float, dtype=jnp.float32) -> jnp.ndarray:
    """Match cv2.getGaussianKernel: fixed 1/256-quantized taps for small
    ksize with sigma<=0, else sigma<=0 -> 0.3*((k-1)*0.5 - 1) + 0.8."""
    if sigma <= 0 and ksize in _CV2_SMALL_GAUSS:
        return jnp.array(_CV2_SMALL_GAUSS[ksize], dtype=dtype)
    if sigma <= 0:
        sigma = 0.3 * ((ksize - 1) * 0.5 - 1) + 0.8
    half = (ksize - 1) / 2.0
    xs = [i - half for i in range(ksize)]
    vals = [math.exp(-(x * x) / (2.0 * sigma * sigma)) for x in xs]
    total = sum(vals)
    return jnp.array([v / total for v in vals], dtype=dtype)


def _depthwise_sep_conv(batch: jnp.ndarray, kh: jnp.ndarray, kw: jnp.ndarray) -> jnp.ndarray:
    """Two depthwise 1-D convs (H then W) with reflect-101 borders."""
    c = batch.shape[-1]
    rh, rw = kh.shape[0] // 2, kw.shape[0] // 2
    x = jnp.pad(batch, ((0, 0), (rh, rh), (rw, rw), (0, 0)), mode="reflect")
    kh4 = jnp.tile(kh.astype(batch.dtype).reshape(-1, 1, 1, 1), (1, 1, 1, c))
    kw4 = jnp.tile(kw.astype(batch.dtype).reshape(1, -1, 1, 1), (1, 1, 1, c))
    x = lax.conv_general_dilated(
        x, kh4, window_strides=(1, 1), padding="VALID",
        dimension_numbers=_DN, feature_group_count=c,
    )
    x = lax.conv_general_dilated(
        x, kw4, window_strides=(1, 1), padding="VALID",
        dimension_numbers=_DN, feature_group_count=c,
    )
    return x


def _shifted_sep_conv(batch: jnp.ndarray, kh: jnp.ndarray, kw: jnp.ndarray) -> jnp.ndarray:
    """Separable conv as k static shifted-slice FMAs per axis.

    A C=3 depthwise conv can never fill the MXU's 128-wide reduction, and
    XLA's depthwise lowering is the slow path on both TPU and CPU. The
    stencil-as-shifts formulation is pure elementwise multiply-adds over
    views of one padded buffer — VPU work that XLA fuses into a single
    pass per axis. Numerically identical accumulation order to a 1-D conv
    (taps accumulated in index order), so cv2 golden tests are unaffected.
    """
    rh, rw = kh.shape[0] // 2, kw.shape[0] // 2
    x = jnp.pad(batch, ((0, 0), (rh, rh), (rw, rw), (0, 0)), mode="reflect")
    h = batch.shape[1]
    acc = kh[0].astype(x.dtype) * x[:, : h, :, :]
    for i in range(1, kh.shape[0]):
        acc = acc + kh[i].astype(x.dtype) * x[:, i : i + h, :, :]
    w = batch.shape[2]
    out = kw[0].astype(x.dtype) * acc[:, :, : w, :]
    for j in range(1, kw.shape[0]):
        out = out + kw[j].astype(x.dtype) * acc[:, :, j : j + w, :]
    return out


def sep_conv2d(
    batch: jnp.ndarray,
    kh: jnp.ndarray,
    kw: jnp.ndarray,
    impl: str = "shift",
) -> jnp.ndarray:
    """Public separable-conv helper (used by flow and tests).

    ``impl``: "shift" (default — stencil-as-shifted-FMAs, the fast path
    for 3-channel images on TPU and CPU) or "depthwise" (XLA conv op,
    kept for A/B comparison).
    """
    if impl == "shift":
        return _shifted_sep_conv(batch, kh, kw)
    if impl == "depthwise":
        return _depthwise_sep_conv(batch, kh, kw)
    raise ValueError(f"impl must be 'shift' or 'depthwise', got {impl!r}")


@register_filter("gaussian_blur")
def gaussian_blur(ksize: int = 9, sigma: float = 0.0,
                  impl: Optional[str] = None) -> Filter:
    """Separable Gaussian blur matching cv2.GaussianBlur taps.

    ``impl=None`` picks the per-backend winner declared in
    ``MEASURED_DEFAULTS`` (:mod:`dvf_tpu.ops.registry`). Current winners:
    **TPU = "shift" at every ksize** — the gauss9_1080p A/B had shift at
    1022 vs pallas_fused 186 fps (1080p batch 8) and gauss3_1080p had
    shift 1861 vs pallas 1613 (at 3 taps XLA's single fused pass is
    already one HBM round-trip, and the Pallas kernel's DMA-slab staging
    costs more than the fusion saves); both captured 2026-07-31 through
    a shared chip that no longer exists (table removed in PR 21), and the
    pallas leg's 0.043 HBM fraction makes that gauss9 capture suspect —
    ROADMAP D4 re-runs it on the ledger. **CPU = "pallas" at ksize≥9**
    (interpret mode lowers to one fused XLA pass instead of two),
    "shift" below. An explicit ``impl`` pins the choice. Halo is
    ksize//2 for every impl, so spatial sharding is unaffected.
    """
    if impl is None:
        impl = measured_default_for(
            "gaussian_blur_k9" if ksize >= 9 else "gaussian_blur_small")
    if impl == "pallas":
        return get_filter("gaussian_blur_pallas", ksize=ksize, sigma=sigma)
    if impl not in ("shift", "depthwise"):
        # Validate at construction: deferring to trace time would surface
        # a typo deep inside sep_conv2d, far from the misconfiguration.
        raise ValueError(
            f"impl must be 'shift', 'depthwise', or 'pallas', got {impl!r}")
    kern = gaussian_kernel_1d(ksize, sigma)

    def fn(batch: jnp.ndarray) -> jnp.ndarray:
        return sep_conv2d(batch, kern, kern, impl=impl)

    return stateless(f"gaussian_blur(k={ksize},s={sigma})", fn, halo=ksize // 2)


def box_filter(x: jnp.ndarray, win: int) -> jnp.ndarray:
    """Uniform win×win windowed MEAN via running sums — O(1) per pixel in
    the window size (vs win taps/axis for the FMA formulation), NHWC,
    reflect borders like :func:`dvf_tpu.ops.conv.sep_conv2d`.

    This is cv2's Farneback default window (``flags=0`` runs a box blur
    over the structure-tensor images; the Gaussian window is opt-in via
    OPTFLOW_FARNEBACK_GAUSSIAN) — the parity surface behind
    ``flow_warp(win_type="box")`` and ``box_blur(impl="cumsum")``.

    Precision: the float32 running sums reach O(H) before the hi-lo
    difference, but XLA lowers ``cumsum`` as an associative scan, so the
    rounding error grows ~O(log H), not O(H) — measured 2.2e-5 max
    deviation vs the FMA formulation at 720p (win=5), ~200× below one
    uint8 quantum. test_box_filter_matches_uniform_sep_conv_720p_scale
    bounds it at full geometry so a lowering change can't silently
    regress it."""
    if win % 2 != 1 or win < 1:
        raise ValueError(f"win must be odd and positive, got {win}")
    r = win // 2
    xp = jnp.pad(x, ((0, 0), (r, r), (r, r), (0, 0)), mode="reflect")

    def running(axis, c):
        zeros = jnp.zeros_like(lax.slice_in_dim(c, 0, 1, axis=axis))
        hi = lax.slice_in_dim(c, win - 1, None, axis=axis)
        lo = jnp.concatenate(
            [zeros, lax.slice_in_dim(c, 0, c.shape[axis] - win, axis=axis)],
            axis=axis)
        return hi - lo

    s = running(1, jnp.cumsum(xp, axis=1))
    s = running(2, jnp.cumsum(s, axis=2))
    return s / float(win * win)


@register_filter("box_blur")
def box_blur(ksize: int = 3, impl: str = "shift") -> Filter:
    """Separable box (mean) blur.

    ``impl``: "shift"/"depthwise" (sep_conv2d lowerings) or "cumsum"
    (:func:`box_filter` running sums — O(1) per pixel in ksize, though
    measured SLOWER than the fused shift pass on CPU at ksize 15: the
    scan's dependency chain defeats fusion; kept for A/B measurement)."""
    if impl not in ("shift", "depthwise", "cumsum"):
        raise ValueError(
            f"impl must be 'shift', 'depthwise' or 'cumsum', got {impl!r}")
    if impl == "cumsum" and (ksize % 2 != 1 or ksize < 1):
        # Validate at construction (the pattern gaussian_blur documents):
        # deferring surfaces the error deep inside box_filter's trace.
        raise ValueError(f"ksize must be odd for impl='cumsum', got {ksize}")
    kern = np.full((ksize,), 1.0 / ksize, dtype=np.float32)

    def fn(batch: jnp.ndarray) -> jnp.ndarray:
        if impl == "cumsum":
            return box_filter(batch, ksize)
        return sep_conv2d(batch, kern, kern, impl=impl)

    return stateless(f"box_blur(k={ksize})", fn, halo=ksize // 2)


# Sobel ksize=3 taps, separable: d = [-1, 0, 1], s = [1, 2, 1].
# Host numpy, NOT jnp: module-level jnp.array() would initialize the JAX
# backend at import time — before any entry point has chosen a platform,
# and taking the chip for a process that may only orchestrate children
# (tests/test_import_hygiene.py). Constants convert during tracing.
_SOBEL_D = np.array([-1.0, 0.0, 1.0], dtype=np.float32)
_SOBEL_S = np.array([1.0, 2.0, 1.0], dtype=np.float32)


def sobel_gradients(batch: jnp.ndarray):
    """Per-channel Sobel dx, dy (cv2.Sobel ksize=3, reflect-101 borders)."""
    gx = _shifted_sep_conv(batch, _SOBEL_S, _SOBEL_D)
    gy = _shifted_sep_conv(batch, _SOBEL_D, _SOBEL_S)
    return gx, gy


@register_filter("sobel")
def sobel(magnitude_scale: float = 1.0, on_gray: bool = True) -> Filter:
    """Sobel edge magnitude, broadcast back to 3 channels when ``on_gray``."""

    def fn(batch: jnp.ndarray) -> jnp.ndarray:
        x = rgb_to_gray(batch) if on_gray else batch
        gx, gy = sobel_gradients(x)
        mag = jnp.sqrt(gx * gx + gy * gy) * magnitude_scale
        mag = jnp.clip(mag, 0.0, 1.0)
        if on_gray:
            mag = jnp.broadcast_to(mag, batch.shape)
        return mag.astype(batch.dtype)

    return stateless(f"sobel(scale={magnitude_scale})", fn, halo=1)


@register_filter("sharpen")
def sharpen(amount: float = 1.0, ksize: int = 5, sigma: float = 1.0) -> Filter:
    """Unsharp mask: x + amount * (x - blur(x))."""
    kern = gaussian_kernel_1d(ksize, sigma)

    def fn(batch: jnp.ndarray) -> jnp.ndarray:
        blurred = _shifted_sep_conv(batch, kern, kern)
        return jnp.clip(batch + amount * (batch - blurred), 0.0, 1.0)

    return stateless(f"sharpen(a={amount})", fn, halo=ksize // 2)


@register_filter("emboss")
def emboss(strength: float = 1.0) -> Filter:
    """Classic 3x3 emboss (directional relief) on luma, +0.5 gray offset.

    Non-separable kernel — lowered as 9 shifted-slice FMAs (the same
    stencil-as-shifts policy as :func:`_shifted_sep_conv`: a C=1
    depthwise conv is the slow XLA path on TPU and CPU alike; zero taps
    are skipped entirely). Reflect-101 borders like every other stencil.
    """
    kern = np.array(
        [[-2.0, -1.0, 0.0],
         [-1.0, 1.0, 1.0],
         [0.0, 1.0, 2.0]],
        dtype=np.float32,
    ) * strength

    def fn(batch: jnp.ndarray) -> jnp.ndarray:
        gray = rgb_to_gray(batch)
        h, w = gray.shape[1], gray.shape[2]
        x = jnp.pad(gray, ((0, 0), (1, 1), (1, 1), (0, 0)), mode="reflect")
        y = jnp.zeros_like(gray)
        for dy in range(3):
            for dx in range(3):
                tap = float(kern[dy, dx])
                if tap != 0.0:
                    y = y + tap * x[:, dy : dy + h, dx : dx + w, :]
        out = jnp.clip(y + 0.5, 0.0, 1.0)
        return jnp.broadcast_to(out, batch.shape).astype(batch.dtype)

    return stateless(f"emboss(s={strength})", fn, halo=1)
