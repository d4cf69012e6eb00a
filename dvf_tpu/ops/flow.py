"""Dense optical flow via Farneback polynomial expansion + flow-warp filter.

Covers BASELINE.json configs[3]: "Farneback optical-flow warp filter, 720p,
2-frame temporal window". The reference has no temporal ops (every frame is
independent, worker.py:57); this is the one *stateful* filter family, and it
drives the framework's device-resident-state design
(:class:`dvf_tpu.api.filter.Filter.init_state`).

Algorithm (G. Farneback, "Two-frame motion estimation based on polynomial
expansion", SCIA 2003 — same algorithm as cv2.calcOpticalFlowFarneback):

1. Each gray frame is locally approximated as a quadratic polynomial
   ``f(x) ≈ xᵀAx + bᵀx + c`` by weighted least squares under a Gaussian
   applicability window. With a separable Gaussian weight, the six moment
   images are six **separable cross-correlations** — exactly what XLA's
   depthwise convs tile well on TPU; the 6×6 normal-equation inverse is a
   compile-time constant.
2. Displacement: A = ½(A1 + A2(x+d)), Δb = −½(b2(x+d) − b1) + A d, then the
   per-pixel 2×2 system is averaged over a Gaussian neighborhood
   (more separable convs) and solved in closed form.
3. Coarse-to-fine pyramid with iterative warping (bilinear gather).

Everything is static-shaped, elementwise + depthwise-conv work: no Python
control flow under jit (pyramid levels unroll at trace time).
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from dvf_tpu.api.filter import Filter, take_pred, temporal_filter
from dvf_tpu.ops.conv import box_filter, sep_conv2d, gaussian_kernel_1d
from dvf_tpu.ops.registry import measured_default_for, register_filter
from dvf_tpu.utils.image import rgb_to_gray


# ---------------------------------------------------------------------------
# bilinear sampling (the warp primitive)
# ---------------------------------------------------------------------------

def bilinear_sample(img: jnp.ndarray, ys: jnp.ndarray, xs: jnp.ndarray) -> jnp.ndarray:
    """Sample ``img`` (B,H,W,C) at float coords ``ys``/``xs`` (B,H,W).

    Out-of-range coordinates clamp to the border (cv2 BORDER_REPLICATE
    behavior). Implemented as four flat gathers so XLA lowers to efficient
    dynamic-gather on TPU.
    """
    b, h, w, c = img.shape
    qshape = ys.shape  # (B, qh, qw) — query grid may differ from img size
    ys = jnp.clip(ys, 0.0, h - 1.0)
    xs = jnp.clip(xs, 0.0, w - 1.0)
    y0 = jnp.floor(ys)
    x0 = jnp.floor(xs)
    wy = (ys - y0)[..., None]
    wx = (xs - x0)[..., None]
    y0i = y0.astype(jnp.int32)
    x0i = x0.astype(jnp.int32)
    y1i = jnp.minimum(y0i + 1, h - 1)
    x1i = jnp.minimum(x0i + 1, w - 1)

    flat = img.reshape(b, h * w, c)
    nq = qshape[1] * qshape[2]

    def gather(yi, xi):
        idx = (yi * w + xi).reshape(b, nq, 1)
        return jnp.take_along_axis(flat, idx, axis=1).reshape(qshape + (c,))

    v00 = gather(y0i, x0i)
    v01 = gather(y0i, x1i)
    v10 = gather(y1i, x0i)
    v11 = gather(y1i, x1i)
    top = v00 * (1 - wx) + v01 * wx
    bot = v10 * (1 - wx) + v11 * wx
    return top * (1 - wy) + bot * wy


def warp_by_flow(img: jnp.ndarray, flow: jnp.ndarray) -> jnp.ndarray:
    """Backward-warp ``img`` by ``flow`` (B,H,W,2; flow[...,0]=dx, [...,1]=dy).

    Returns out(x) = img(x + flow(x)) — the standard cv2.remap convention for
    Farneback flow (flow maps frame1 coords to frame2 positions).
    """
    b, h, w, _ = img.shape
    gy = lax.broadcasted_iota(jnp.float32, (b, h, w), 1)
    gx = lax.broadcasted_iota(jnp.float32, (b, h, w), 2)
    return bilinear_sample(img, gy + flow[..., 1], gx + flow[..., 0])


# ---------------------------------------------------------------------------
# polynomial expansion
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _poly_exp_setup(n: int, sigma: float):
    """Precompute (numpy, trace-time) the 1-D moment kernels and the 6x6
    normal-equation inverse for basis [1, x, y, x², y², xy]."""
    xs = np.arange(-n, n + 1, dtype=np.float64)
    g = np.exp(-(xs ** 2) / (2.0 * sigma * sigma))
    g /= g.sum()
    # 1-D moment kernels (correlation kernels, not flipped — XLA convs are
    # cross-correlations, matching).
    k0, k1, k2 = g, xs * g, (xs ** 2) * g

    # G[i,j] = sum_{x,y} w(x,y) b_i(x,y) b_j(x,y), b = [1, x, y, x^2, y^2, xy]
    X, Y = np.meshgrid(xs, xs, indexing="xy")
    wgt = np.outer(g, g)  # rows=y, cols=x
    basis = [np.ones_like(X), X, Y, X ** 2, Y ** 2, X * Y]
    G = np.zeros((6, 6))
    for i in range(6):
        for j in range(6):
            G[i, j] = np.sum(wgt * basis[i] * basis[j])
    Ginv = np.linalg.inv(G)
    # Return numpy (not jnp): this function is lru_cached, and jnp arrays
    # materialized inside a jit trace must not outlive it.
    return (
        np.asarray(k0, np.float32),
        np.asarray(k1, np.float32),
        np.asarray(k2, np.float32),
        np.asarray(Ginv, np.float32),
    )


def poly_expansion(gray: jnp.ndarray, n: int = 5, sigma: float = 1.1):
    """Quadratic polynomial coefficients per pixel.

    Args:
      gray: (B, H, W, 1) float frames.
    Returns:
      (A11, A12, A22, b1, b2): each (B, H, W, 1). A is the symmetric quadratic
      form matrix, b the linear term, in (x, y) = (col, row) coordinates.
    """
    k0, k1, k2, Ginv = _poly_exp_setup(n, float(sigma))
    # v_i = correlation of f with w * b_i; separable into row (x) and col (y)
    # factors: b=1 -> k0⊗k0 ; x -> k0(y)k1(x) ; y -> k1(y)k0(x);
    # x² -> k0(y)k2(x) ; y² -> k2(y)k0(x) ; xy -> k1(y)k1(x).
    #
    # The six correlations share ONE input and only three distinct 1-D
    # kernels per axis, so instead of six independent sep_conv2d calls
    # (6 pads, 6 vertical + 6 horizontal passes) this runs the shifted-FMA
    # lowering once with the passes shared: one reflect pad, the three
    # vertical moment passes c0/c1/c2 reading the same shifted slices, and
    # six horizontal passes over those. Tap accumulation order is
    # identical to sep_conv2d(impl="shift"), so results are bit-identical
    # to the unfused formulation (guarded by
    # tests/test_flow.py::test_poly_expansion_matches_unfused_sep_convs).
    h, w = gray.shape[1], gray.shape[2]
    x = jnp.pad(gray, ((0, 0), (n, n), (n, n), (0, 0)), mode="reflect")
    taps = 2 * n + 1
    xs = [x[:, i : i + h, :, :] for i in range(taps)]

    def vert(k):
        a = k[0].astype(x.dtype) * xs[0]
        for i in range(1, taps):
            a = a + k[i].astype(x.dtype) * xs[i]
        return a

    c0, c1, c2 = vert(jnp.asarray(k0)), vert(jnp.asarray(k1)), vert(jnp.asarray(k2))

    def horiz(a, k):
        o = k[0].astype(a.dtype) * a[:, :, :w, :]
        for j in range(1, taps):
            o = o + k[j].astype(a.dtype) * a[:, :, j : j + w, :]
        return o

    v1 = horiz(c0, k0)
    vx = horiz(c0, k1)
    vxx = horiz(c0, k2)
    vy = horiz(c1, k0)
    vxy = horiz(c1, k1)
    vyy = horiz(c2, k0)
    moments = (v1, vx, vy, vxx, vyy, vxy)

    def coeff(j):
        # Row j of the normal-equation solve, coeffs [c, bx, by, axx, ayy,
        # axy], as float32 multiply-adds over the moment planes (the
        # matrix is a trace-time constant, zero off its few couplings
        # but for the inverse's rounding noise). A 6-wide dot
        # would run on the MXU at its default precision — bfloat16 passes
        # on a TPU, three digits of a solve that cancels — and read the
        # moments through a 6-element minor dimension.
        terms = [float(Ginv[j, i]) * moments[i] for i in range(6)
                 if abs(Ginv[j, i]) > 1e-9 * np.abs(Ginv).max()]
        return functools.reduce(lambda acc, t: acc + t, terms)

    return coeff(3), 0.5 * coeff(5), coeff(4), coeff(1), coeff(2)


# ---------------------------------------------------------------------------
# displacement estimation
# ---------------------------------------------------------------------------

def _flow_level(
    poly1, poly2, flow: jnp.ndarray, smooth, n_iters: int,
    warp_fn=warp_by_flow,
) -> jnp.ndarray:
    """Refine ``flow`` at one pyramid level. poly*: stacked (B,H,W,5);
    ``smooth(x)``: the window average applied to the structure-tensor
    images (Gaussian sep-conv or box running-sum); ``warp_fn(img, flow)``:
    how the candidate frame's poly stack is motion-compensated each
    iteration (XLA gather, or the bounded Pallas shift warp on TPU)."""
    A11_1, A12_1, A22_1, b1_1, b2_1 = [poly1[..., i : i + 1] for i in range(5)]

    for _ in range(n_iters):
        poly2w = warp_fn(poly2, flow)
        A11_2, A12_2, A22_2, b1_2, b2_2 = [poly2w[..., i : i + 1] for i in range(5)]
        A11 = 0.5 * (A11_1 + A11_2)
        A12 = 0.5 * (A12_1 + A12_2)
        A22 = 0.5 * (A22_1 + A22_2)
        fx = flow[..., 0:1]
        fy = flow[..., 1:2]
        db1 = -0.5 * (b1_2 - b1_1) + (A11 * fx + A12 * fy)
        db2 = -0.5 * (b2_2 - b2_1) + (A12 * fx + A22 * fy)

        # Per-pixel normal equations, averaged over the window.
        t11 = A11 * A11 + A12 * A12
        t12 = A12 * (A11 + A22)
        t22 = A12 * A12 + A22 * A22
        h1 = A11 * db1 + A12 * db2
        h2 = A12 * db1 + A22 * db2
        stacked = jnp.concatenate([t11, t12, t22, h1, h2], axis=-1)
        sm = smooth(stacked)
        g11, g12, g22 = sm[..., 0:1], sm[..., 1:2], sm[..., 2:3]
        s1, s2 = sm[..., 3:4], sm[..., 4:5]
        # Scale-invariant Tikhonov: image intensities are O(1) but the
        # structure-tensor entries are O(1e-4), so an absolute clamp would
        # swamp the true determinant; regularize relative to the trace,
        # which also damps weak-texture pixels toward zero flow.
        lam = 1e-3 * (g11 + g22) + 1e-12
        g11r = g11 + lam
        g22r = g22 + lam
        det = g11r * g22r - g12 * g12
        fx_new = (g22r * s1 - g12 * s2) / det
        fy_new = (g11r * s2 - g12 * s1) / det
        flow = jnp.concatenate([fx_new, fy_new], axis=-1)
    return flow


def farneback_flow(
    prev_gray: jnp.ndarray,
    curr_gray: jnp.ndarray,
    levels: int = 3,
    pyr_scale: float = 0.5,
    win_size: int = 15,
    n_iters: int = 3,
    poly_n: int = 5,
    poly_sigma: float = 1.1,
    win_type: str = "gaussian",
    inner_warp: str = "gather",
    inner_max_disp: int = 4,
) -> jnp.ndarray:
    """Dense flow (B,H,W,2) mapping prev -> curr, cv2-convention.

    All shapes/levels are static — the pyramid unrolls at trace time.
    ``win_type``: "gaussian" (OPTFLOW_FARNEBACK_GAUSSIAN parity, the
    committed-golden default) or "box" (cv2's flags=0 default window;
    O(1) running-sum smoothing per pixel regardless of win_size).
    """
    b = prev_gray.shape[0]

    def polys_at(lvl, lh, lw):
        p = jax.image.resize(prev_gray, (b, lh, lw, 1), method="linear")
        c = jax.image.resize(curr_gray, (b, lh, lw, 1), method="linear")
        return (jnp.concatenate(poly_expansion(p, poly_n, poly_sigma), axis=-1),
                jnp.concatenate(poly_expansion(c, poly_n, poly_sigma), axis=-1))

    return _coarse_to_fine(polys_at, b, prev_gray.shape[1],
                           prev_gray.shape[2], prev_gray.dtype,
                           levels, pyr_scale, win_size, n_iters, win_type,
                           _inner_warp_fn(inner_warp, inner_max_disp))


def farneback_flow_seq(
    gray_seq: jnp.ndarray,
    levels: int = 3,
    pyr_scale: float = 0.5,
    win_size: int = 15,
    n_iters: int = 3,
    poly_n: int = 5,
    poly_sigma: float = 1.1,
    win_type: str = "gaussian",
    inner_warp: str = "gather",
    inner_max_disp: int = 4,
    pred: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Flow for every CONSECUTIVE pair of a frame sequence.

    ``gray_seq``: (B+1, H, W, 1) — frame i is "prev" of pair i and "curr"
    of pair i-1. :func:`farneback_flow` on the shifted pair stacks
    resizes and poly-expands each interior frame TWICE (once per role);
    the streaming filters' batches are exactly this overlapping case, so
    this entry computes the pyramid and polynomial expansion once per
    unique frame (B+1 expansions instead of 2B) and slices the pair
    views. Per-frame operations are identical to the pairwise form, so
    the flows match it to float tolerance
    (tests/test_flow.py::test_farneback_seq_matches_pairwise).

    Returns (B, H, W, 2) flows mapping gray_seq[i] -> gray_seq[i+1].

    With ``pred`` (int32 ``[B]``, the many-session form of
    ``Filter.rows``) ``gray_seq`` is ``[T previous frames | B current
    frames]`` and pair i is ``gray_seq[pred[i]] -> gray_seq[T + i]``:
    still one expansion per unique frame, the pair views gathered by row.
    """
    n_seq = gray_seq.shape[0]
    n_pairs = n_seq - 1 if pred is None else pred.shape[0]

    def polys_at(lvl, lh, lw):
        g = jax.image.resize(gray_seq, (n_seq, lh, lw, 1), method="linear")
        poly_all = jnp.concatenate(poly_expansion(g, poly_n, poly_sigma),
                                   axis=-1)
        return take_pred(poly_all, pred), poly_all[n_seq - n_pairs:]

    return _coarse_to_fine(polys_at, n_pairs, gray_seq.shape[1],
                           gray_seq.shape[2], gray_seq.dtype,
                           levels, pyr_scale, win_size, n_iters, win_type,
                           _inner_warp_fn(inner_warp, inner_max_disp))


def _inner_warp_fn(inner_warp: str, max_disp: int):
    """Resolve the per-iteration poly-warp implementation.

    "gather" — exact XLA dynamic-gather bilinear sample (no
    displacement bound). "pallas" — the bounded shift warp
    (:func:`dvf_tpu.ops.pallas_kernels.warp_bounded_pallas`): the same
    kernel the on-chip A/B measured 2.3× faster than gather for the
    FINAL frame warp, here applied to the 9 inner-loop warps of the
    5-channel poly stacks that dominate the iteration.

    The clip semantics, stated precisely: at every level and iteration
    the kernel clips the TOTAL accumulated flow (estimation-grid px,
    including the pyramid-upscaled initialization — not just the current
    refinement step) to ±``max_disp`` before sampling. The pallas inner
    warp is therefore only faithful while the TRUE motion at the
    estimation grid stays within ±``max_disp``; beyond it the candidate
    polynomials are sampled short of the real displacement and the
    estimate degrades, where "gather" keeps tracking. An APPROXIMATION,
    sized by the caller so the bound matches the final warp's contract
    (see flow_warp: inner bound = ceil(max_disp / flow_scale)), and
    flow_warp's measured default on a TPU wherever its final warp is the
    bounded kernel: the on-chip A/B (flow_inner_720p; PR 27) read the
    step 17× shorter at 720p batch 64, XLA's gathers of the 5-channel
    stacks being 3.5 s of a 3.78 s step."""
    if inner_warp == "gather":
        return warp_by_flow
    if inner_warp == "pallas":
        from dvf_tpu.ops.pallas_kernels import warp_bounded_pallas

        def inner_warp_pallas(img, f):
            with jax.named_scope("flow_inner_warp"):
                return warp_bounded_pallas(img, f, max_disp=max_disp)

        return inner_warp_pallas
    raise ValueError(
        f"inner_warp must be 'gather' or 'pallas', got {inner_warp!r}")


def _pyramid_shapes(h: int, w: int, levels: int, pyr_scale: float = 0.5):
    """``(h, w)`` of each pyramid level of an ``h`` x ``w`` estimation grid,
    finest first."""
    return [(max(8, int(round(h * pyr_scale ** lvl))),
             max(8, int(round(w * pyr_scale ** lvl)))) for lvl in range(levels)]


def _coarse_to_fine(polys_at, b, h, w, dtype, levels, pyr_scale, win_size,
                    n_iters, win_type: str = "gaussian",
                    warp_fn=warp_by_flow) -> jnp.ndarray:
    """Shared coarse-to-fine pyramid loop: ``polys_at(lvl, lh, lw)``
    supplies the (poly1, poly2) pair stacks per level — the only thing
    that differs between the pairwise and sequence entry points."""
    if win_type == "gaussian":
        win_kern = gaussian_kernel_1d(win_size, win_size / 6.0)
        smooth = lambda x: sep_conv2d(x, win_kern, win_kern)  # noqa: E731
    elif win_type == "box":
        smooth = lambda x: box_filter(x, win_size)  # noqa: E731
    else:
        raise ValueError(
            f"win_type must be 'gaussian' or 'box', got {win_type!r}")
    shapes = _pyramid_shapes(h, w, levels, pyr_scale)

    flow = None
    for lvl in range(levels - 1, -1, -1):
        lh, lw = shapes[lvl]
        poly1, poly2 = polys_at(lvl, lh, lw)
        if flow is None:
            flow = jnp.zeros((b, lh, lw, 2), dtype=dtype)
        else:
            ph, pw = shapes[lvl + 1]
            flow = jax.image.resize(flow, (b, lh, lw, 2), method="linear")
            flow = flow * jnp.asarray([lw / pw, lh / ph], dtype=flow.dtype)
        flow = _flow_level(poly1, poly2, flow, smooth, n_iters, warp_fn)
    return flow


# ---------------------------------------------------------------------------
# filters
# ---------------------------------------------------------------------------

@register_filter("flow_warp")
def flow_warp(
    levels: int = 3,
    win_size: int = 15,
    n_iters: int = 3,
    flow_scale: int = 2,
    warp_impl: Optional[str] = None,
    max_disp: int = 4,
    win_type: str = "gaussian",
    inner_warp: Optional[str] = None,
) -> Filter:
    """Motion-compensate each previous frame onto the current one.

    Output = prev warped by the prev→curr flow — visually "ghost-free onion
    skin". State = (a session's last frame, initialized flag), one per
    session (``Filter.rows``); the 2-frame temporal window of
    BASELINE.json configs[3] lives on-device.
    ``flow_scale``: flow is estimated at 1/flow_scale resolution and
    upsampled (cost dominated by poly expansion at full res otherwise).
    ``win_type``: "gaussian" (default; OPTFLOW_FARNEBACK_GAUSSIAN
    parity — the committed goldens use it) or "box" (cv2's flags=0
    default window, smoothed by an O(1) running-sum box filter — a
    different algorithm variant, not a numerics-identical impl swap, so
    the registry never auto-defaults to it on speed alone).
    ``warp_impl``: "gather" = XLA dynamic-gather bilinear sample;
    "pallas" = gather-free bounded-displacement kernel
    (:func:`dvf_tpu.ops.pallas_kernels.warp_bounded_pallas`), which clips
    flow to ±``max_disp`` px — the table benchmark compares the two.
    ``None`` picks the measured per-backend winner: "pallas" on TPU
    (39.6 vs 17.4 fps at 720p batch 4 — TPU has no fast vector gather),
    "gather" on CPU (it imposes no displacement clip). Provenance: the
    TPU figures were captured 2026-07-31 through a shared chip that no
    longer exists (table removed in PR 21); not measured on this chip.
    With a bounded warp anywhere in the step the filter states its
    ``warp_bounded`` calls as data (``kernel_plan``: one
    ``pallas_kernels.warp_plan`` a distinct shape, in step order), and the
    two call sites carry the scopes ``flow_final_warp`` /
    ``flow_inner_warp`` (``scripts/style_step_probe.py --model flow``).

    NOTE the TPU default is an APPROXIMATION, unlike the other measured
    winners (which are numerics-identical): the Pallas warp clips
    displacements to ±``max_disp`` px (after ``flow_scale`` upsampling
    doubles magnitudes). At video rates Farneback flows are a few px and
    the clip is invisible; for fast motion beyond ±max_disp, pin
    ``warp_impl="gather"`` (full displacement, 2.3× slower on TPU) or
    raise ``max_disp`` (taps grow as (2·max_disp+2)²).
    ``inner_warp``: the nine warps of the polynomial stacks inside the
    iteration (:func:`_inner_warp_fn`): "gather", or "pallas" = the same
    bounded kernel, which also clips the iteration's accumulated flow to
    ±``max_disp`` full-resolution px. ``None`` follows the final warp:
    with ``warp_impl="gather"`` (no displacement bound anywhere) it is
    "gather"; with the bounded final warp, whose contract already is
    |motion| ≤ ``max_disp``, it is the measured per-backend winner —
    "pallas" on TPU (220 vs 3780 ms a step at 720p batch 64, PR 27's
    chip runs), "gather" elsewhere. So on a TPU ``flow_warp()`` is the
    program ``chipbench/configs/flow_720p.json`` spells out.
    """
    if warp_impl is None:
        warp_impl = measured_default_for("flow_warp")
    if inner_warp is None:
        inner_warp = (measured_default_for("flow_inner")
                      if warp_impl == "pallas" else "gather")
    if warp_impl not in ("gather", "pallas"):
        raise ValueError(f"warp_impl must be 'gather' or 'pallas', got {warp_impl!r}")
    if win_type not in ("gaussian", "box"):
        raise ValueError(
            f"win_type must be 'gaussian' or 'box', got {win_type!r}")
    if inner_warp not in ("gather", "pallas"):
        raise ValueError(
            f"inner_warp must be 'gather' or 'pallas', got {inner_warp!r}")
    if win_type == "box" and win_size % 2 != 1:
        # The running-sum window needs an odd extent; fail here with the
        # caller's parameter name, not deep inside box_filter's trace.
        raise ValueError(
            f"win_size must be odd when win_type='box', got {win_size}")

    # The inner warp runs at the 1/flow_scale estimation grid, so
    # ±max_disp full-res px = ±max_disp/flow_scale grid px — scale
    # the bound so pallas-inner carries the SAME |motion| ≤ max_disp
    # full-res contract the final bounded warp documents.
    inner_max_disp = max(1, -(-max_disp // max(1, flow_scale)))

    def init_state(batch_shape: Sequence[int], dtype: Any):
        _, h, w, c = batch_shape
        return {
            "prev": jnp.zeros((h, w, c), dtype=dtype),
            "initialized": jnp.zeros((), dtype=jnp.bool_),
        }

    def kernel_plan(batch_shape) -> Optional[dict]:
        """The step's ``warp_bounded`` calls as data (``Filter.kernel_plan``):
        one entry a distinct shape in step order, each the
        ``pallas_kernels.warp_plan`` its ``pallas_call`` is built from.
        None where no warp is the kernel."""
        from dvf_tpu.ops.pallas_kernels import _auto_interpret, warp_plan

        bsz, h, w, c = (int(v) for v in batch_shape)
        interpret = _auto_interpret(None)
        calls = []
        if inner_warp == "pallas":
            grid = _pyramid_shapes(h // flow_scale, w // flow_scale, levels)
            for lvl in range(levels - 1, -1, -1):
                calls.append({"role": "inner", "level": lvl, "count": n_iters,
                              **warp_plan((bsz, *grid[lvl], 5), inner_max_disp,
                                          interpret=interpret)})
        if warp_impl == "pallas":
            calls.append({"role": "final", "level": None, "count": 1,
                          **warp_plan((bsz, h, w, c), max_disp,
                                      interpret=interpret)})
        if not calls:
            return None
        return {"kernel": "warp_bounded",  # the pallas_calls' name in a trace
                "kernels": ["warp_bounded"],
                "impl": "pallas",
                "calls": calls}

    def rows(batch: jnp.ndarray, prev_states, pred) -> Tuple[jnp.ndarray, Any]:
        bsz, h, w, c = batch.shape
        # Sequence form: a frame may be curr of one pair and prev of the
        # next, so gray conversion, downscale, pyramid, and poly expansion
        # run once per unique frame (the T carried frames + B) instead of
        # once per role (2B); each row's prev is picked out of the same
        # concat by ``pred`` (its session's previous row in this batch,
        # else the frame its session carried in).
        seq = jnp.concatenate([prev_states["prev"], batch], axis=0)
        prev = take_pred(seq, pred)
        sg = rgb_to_gray(seq)
        if flow_scale > 1:
            sh, sw = h // flow_scale, w // flow_scale
            sg = jax.image.resize(sg, (seq.shape[0], sh, sw, 1),
                                  method="linear")
        flow = farneback_flow_seq(
            sg, levels=levels, win_size=win_size, n_iters=n_iters,
            win_type=win_type, inner_warp=inner_warp,
            inner_max_disp=inner_max_disp, pred=pred)
        if flow_scale > 1:
            flow = jax.image.resize(flow, (bsz, h, w, 2), method="linear") * float(flow_scale)
        if warp_impl == "pallas":
            from dvf_tpu.ops.pallas_kernels import warp_bounded_pallas

            # interpret=None → the kernel's own backend policy
            # (compiled on TPU, interpret elsewhere).
            with jax.named_scope("flow_final_warp"):
                warped = warp_bounded_pallas(prev, flow, max_disp=max_disp)
        else:
            warped = warp_by_flow(prev, flow)
        # Until a session's first real previous frame exists, pass the
        # input through.
        out = jnp.where(_started(prev_states, pred, bsz), warped, batch)
        return out.astype(batch.dtype), _last_frame_states(batch)

    return temporal_filter(
        f"flow_warp(levels={levels},win={win_size},warp={warp_impl}"
        f"{',box' if win_type == 'box' else ''}"
        f"{',pallas-inner' if inner_warp == 'pallas' else ''})",
        rows, init_state, kernel_plan=kernel_plan)


def _started(prev_states, pred, bsz: int) -> jnp.ndarray:
    """(B,1,1,1) bool: does row i have a real previous frame? Every row
    that follows a row of this batch does; one that follows a carried
    state does iff that state is initialized."""
    seq = jnp.concatenate([prev_states["initialized"],
                           jnp.ones((bsz,), jnp.bool_)])
    return take_pred(seq, pred)[:, None, None, None]


def _last_frame_states(batch: jnp.ndarray):
    """Per-row states of the two-frame-window filters: after row i its
    session carries frame i."""
    return {"prev": batch,
            "initialized": jnp.ones((batch.shape[0],), jnp.bool_)}


@register_filter("flow_vis")
def flow_vis(levels: int = 3, win_size: int = 15, n_iters: int = 3, max_mag: float = 8.0) -> Filter:
    """Visualize prev→curr flow as HSV (hue=direction, value=magnitude)."""

    def init_state(batch_shape: Sequence[int], dtype: Any):
        _, h, w, c = batch_shape
        return {
            "prev": jnp.zeros((h, w, c), dtype=dtype),
            "initialized": jnp.zeros((), dtype=jnp.bool_),
        }

    def rows(batch: jnp.ndarray, prev_states, pred) -> Tuple[jnp.ndarray, Any]:
        seq = jnp.concatenate([prev_states["prev"], batch], axis=0)
        flow = farneback_flow_seq(rgb_to_gray(seq),
                                  levels=levels, win_size=win_size,
                                  n_iters=n_iters, pred=pred)
        mag = jnp.sqrt(jnp.sum(flow * flow, axis=-1))
        ang = jnp.arctan2(flow[..., 1], flow[..., 0])  # [-pi, pi]
        hue = (ang + jnp.pi) / (2.0 * jnp.pi)          # [0, 1]
        val = jnp.clip(mag / max_mag, 0.0, 1.0)
        # HSV -> RGB with S=1.
        i = jnp.floor(hue * 6.0)
        f = hue * 6.0 - i
        p = jnp.zeros_like(val)
        q = val * (1.0 - f)
        t = val * f
        i = i.astype(jnp.int32) % 6
        r = jnp.select([i == 0, i == 1, i == 2, i == 3, i == 4, i == 5],
                       [val, q, p, p, t, val])
        g = jnp.select([i == 0, i == 1, i == 2, i == 3, i == 4, i == 5],
                       [t, val, val, q, p, p])
        b_ = jnp.select([i == 0, i == 1, i == 2, i == 3, i == 4, i == 5],
                        [p, p, t, val, val, q])
        out = jnp.stack([r, g, b_], axis=-1)
        return out.astype(batch.dtype), _last_frame_states(batch)

    return temporal_filter("flow_vis", rows, init_state)


@register_filter("ema_smooth")
def ema_smooth(alpha: float = 0.35) -> Filter:
    """Temporal exponential smoothing — motion-trail / denoise.

    y_i = alpha·x_i + (1-alpha)·y_{i-1}, chained across batches through
    a session's device-resident state (the second temporal-window filter after
    flow_warp; being pointwise (halo=0) AND stateful it exercises the
    engine's GSPMD H-sharding path for stateful filters).

    Two deliberate design points:

    - **Bit-identical consecutive frames are no-ops** (A=1, B=0 in the
      recurrence). A repeated frame carries no new information, and this
      is what makes the filter EXACTLY pad-safe: the runtime pads short
      batches by repeating the last valid frame, and with repeat→no-op
      the carried state is literally independent of the pad count — the
      Filter.pad_safe contract ('state depends only on the most recent
      valid frame') holds as an identity, not an approximation.
    - The recurrence is resolved by pointer jumping over each row's
      predecessor link (see the body), so the batch dimension stays
      parallel and a batch may interleave any number of sessions.
    """
    if not 0.0 < alpha <= 1.0:
        raise ValueError("alpha must be in (0, 1]")

    def init_state(batch_shape: Sequence[int], dtype: Any):
        _, h, w, c = batch_shape
        return {
            "ema": jnp.zeros((h, w, c), dtype=dtype),
            "prev": jnp.zeros((h, w, c), dtype=dtype),
            "initialized": jnp.zeros((), dtype=jnp.bool_),
        }

    def rows(batch: jnp.ndarray, prev_states, pred) -> Tuple[jnp.ndarray, Any]:
        bsz = batch.shape[0]
        t = prev_states["prev"].shape[0]
        a = jnp.asarray(alpha, batch.dtype)
        started = _started(prev_states, pred, bsz)
        # Per-frame transform y_i = A_i·y_pred(i) + B_i, with repeats
        # (x_i == its session's previous frame, bit-exact) as identity
        # transforms. The carried "prev" frame extends repeat detection
        # across the batch boundary, so the semantics are independent of
        # how a stream was partitioned into batches.
        before = take_pred(
            jnp.concatenate([prev_states["prev"], batch], axis=0), pred)
        same = jnp.logical_and(
            started, jnp.all(batch == before, axis=(1, 2, 3), keepdims=True))
        A = jnp.where(same, 1.0, 1.0 - a).astype(batch.dtype)
        B = jnp.where(same, 0.0, a * batch).astype(batch.dtype)
        ptr = (jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                t + jnp.arange(bsz - 1, dtype=jnp.int32)])
               if pred is None else pred)
        # Rows that follow a carried state resolve at once (a session's
        # first-ever frame seeds the EMA with itself instead of fading in
        # from black) and become constants: A = 0, B = y.
        root = (ptr < t)[:, None, None, None]
        carried = jnp.take(prev_states["ema"], jnp.minimum(ptr, t - 1), axis=0)
        seed = jnp.where(started, carried, batch)
        B = jnp.where(root, A * seed + B, B)
        A = jnp.where(root, 0.0, A).astype(batch.dtype)
        # The rest by pointer jumping: first-order linear recurrences
        # compose associatively, ``(A,B)∘(A',B') = (A·A', A·B' + B)``, so
        # each round splices a row onto its predecessor's predecessor and
        # a chain of L rows resolves in log2(L) rounds with the batch
        # dimension parallel throughout (a sequential scan would
        # serialize across the data-sharded mesh axis), whatever the
        # order in which sessions' rows sit in the batch.
        for _ in range((bsz - 1).bit_length()):
            j = jnp.maximum(ptr - t, 0)
            live = ptr >= t
            live4 = live[:, None, None, None]
            B = jnp.where(live4, A * jnp.take(B, j, axis=0) + B, B)
            A = jnp.where(live4, A * jnp.take(A, j, axis=0), A)
            ptr = jnp.where(live, jnp.take(ptr, j), ptr)
        states = _last_frame_states(batch)
        states["ema"] = B
        return B.astype(batch.dtype), states

    return temporal_filter(f"ema_smooth(a={alpha})", rows, init_state,
                           halo=0)
