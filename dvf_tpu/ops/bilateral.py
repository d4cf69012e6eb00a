"""Bilateral filter — edge-preserving smoothing (BASELINE.json configs[2]).

Not present in the reference (its only op is invert, inverter.py:41); required
by the Sobel+bilateral 1080p batch=16 north-star config.

TPU mapping: the d×d window is unrolled at trace time into shifted-view
elementwise work (25 shifts for d=5) — pure VPU math that XLA fuses into a
single pass over HBM; no gathers, no data-dependent shapes. The range kernel
uses Euclidean color distance like cv2.bilateralFilter. Two Pallas
counterparts live in :mod:`dvf_tpu.ops.pallas_kernels`: ``bilateral_pallas``
(this op alone, tiled through VMEM: its range distance needs all three
channels, so it carries three planes) and ``sobel_bilateral_pallas`` (the
whole configs[2] Sobel→bilateral chain fused into one kernel over ONE plane,
the luma: the chain's bilateral input is gray broadcast ×3, so nothing after
the luma sees a channel); this module is the jnp reference path and the
numerics golden for both.
"""

from __future__ import annotations

import math

from typing import Optional

import jax.numpy as jnp

from dvf_tpu.api.filter import Filter, stateless
from dvf_tpu.ops.registry import measured_default_for, register_filter


def bilateral_nhwc(
    batch: jnp.ndarray,
    d: int = 5,
    sigma_color: float = 0.1,
    sigma_space: float = 2.0,
) -> jnp.ndarray:
    """Bilateral filter over float NHWC in [0,1].

    ``sigma_color`` is in [0,1] intensity units (cv2 uses [0,255] units; scale
    by 255 to compare).
    """
    if d % 2 != 1:
        raise ValueError(f"window d must be odd, got {d}")
    r = d // 2
    h, w = batch.shape[1], batch.shape[2]
    pad = jnp.pad(batch, ((0, 0), (r, r), (r, r), (0, 0)), mode="reflect")

    inv2sc = 1.0 / (2.0 * sigma_color * sigma_color)
    num = jnp.zeros_like(batch)
    den = jnp.zeros(batch.shape[:-1] + (1,), dtype=batch.dtype)
    for dy in range(-r, r + 1):
        for dx in range(-r, r + 1):
            sw = math.exp(-(dy * dy + dx * dx) / (2.0 * sigma_space * sigma_space))
            shifted = pad[:, r + dy : r + dy + h, r + dx : r + dx + w, :]
            diff = shifted - batch
            dist2 = jnp.sum(diff * diff, axis=-1, keepdims=True)
            wgt = sw * jnp.exp(-dist2 * inv2sc)
            num = num + wgt * shifted
            den = den + wgt
    return num / den


@register_filter("bilateral")
def bilateral(d: int = 5, sigma_color: float = 0.1, sigma_space: float = 2.0,
              impl: Optional[str] = None) -> Filter:
    """Edge-preserving bilateral smoothing (cv2.bilateralFilter semantics).

    ``impl=None`` picks the measured per-backend winner: on TPU the Pallas
    kernel ("pallas", 765 vs 256 fps at 1080p batch 8 — one HBM pass per
    tile, no spilled shifted views); on CPU the unrolled jnp lowering
    ("jnp" — interpret mode pays per-tile overhead with no VMEM to win
    back). Provenance: the TPU figures were captured 2026-07-31 through
    a shared chip that no longer exists (table removed in PR 21); not
    measured on this chip.
    Both impls declare the same halo, so spatial sharding is unaffected.
    """
    if impl is None:
        impl = measured_default_for("bilateral")
    if impl == "pallas":
        from dvf_tpu.ops.registry import get_filter

        return get_filter("bilateral_pallas", d=d, sigma_color=sigma_color,
                          sigma_space=sigma_space)
    if impl != "jnp":
        raise ValueError(f"impl must be 'jnp' or 'pallas', got {impl!r}")

    def fn(batch: jnp.ndarray) -> jnp.ndarray:
        return bilateral_nhwc(batch, d=d, sigma_color=sigma_color, sigma_space=sigma_space)

    return stateless(f"bilateral(d={d},sc={sigma_color},ss={sigma_space})", fn, halo=d // 2)
