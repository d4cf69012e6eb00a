"""Building-block layers for the neural filter models (plain functional JAX).

Design notes (TPU-first):
- NHWC layout throughout — XLA's preferred conv layout on TPU; channels last
  keeps the C dimension on the lane axis for the MXU.
- Convs compute in bfloat16 by default (MXU-native) with float32 params;
  instance-norm statistics accumulate in float32 for stability.
- Params are flat dicts of arrays so tensor-parallel PartitionSpecs can be
  written per-leaf (see style_transfer.param_pspecs).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_DN = ("NHWC", "HWIO", "NHWC")

Params = Dict[str, Any]


def _pair(factor) -> Tuple[int, int]:
    """A phase factor as (H, W): an int is that factor on both axes."""
    return (factor, factor) if isinstance(factor, int) else tuple(factor)


def conv_init(rng, ksize: int, cin: int, cout: int, dtype=jnp.float32) -> Params:
    """He-normal conv weight + zero bias."""
    wkey, _ = jax.random.split(rng)
    fan_in = ksize * ksize * cin
    w = jax.random.normal(wkey, (ksize, ksize, cin, cout), dtype) * jnp.sqrt(2.0 / fan_in)
    return {"w": w, "b": jnp.zeros((cout,), dtype)}


def conv2d_nb(
    p: Params,
    x: jnp.ndarray,
    stride: int = 1,
    padding: str = "SAME",
    compute_dtype=jnp.bfloat16,
    reflect: bool = False,
) -> jnp.ndarray:
    """2-D conv WITHOUT the bias add, in ``compute_dtype`` for the MXU.

    The bias is applied by the caller so tensor-parallel forwards can
    insert a psum between the conv and the bias (row-parallel convs must
    reduce partial sums first, else the bias is counted once per shard).
    ``reflect``: reflect-pad to SAME size (style nets; avoids border halos).
    """
    if reflect:
        r = p["w"].shape[0] // 2
        if r:
            x = jnp.pad(x, ((0, 0), (r, r), (r, r), (0, 0)), mode="reflect")
        padding = "VALID"
    return lax.conv_general_dilated(
        x.astype(compute_dtype),
        p["w"].astype(compute_dtype),
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=_DN,
    )


def instance_norm_init(c: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((c,), dtype), "bias": jnp.zeros((c,), dtype)}


CORNER = 8      # rows and columns of input a norm's pivot is computed from


def corner_pivot(conv, x: jnp.ndarray) -> jnp.ndarray:
    """A pivot for the norm after ``conv`` (any conv of this module with its
    bias, as a function of its input) that waits for no pass over the conv's
    output: the float32 mean, per (sample, channel), of the same conv on the
    input's top-left CORNER×CORNER positions — values of the output's own
    distribution (those near the crop's far edges see the crop's border in
    place of their neighbours; a pivot only has to lie near the mean), for
    a few thousand multiply-adds a channel. Because it does not depend on
    the conv's output, both of the norm's sums can ride in that conv's pass.
    ``(B, 1, 1, C)``; the norms stop the gradient at it (their result does
    not depend on the pivot)."""
    y = conv(x[:, :CORNER, :CORNER, :]).astype(jnp.float32)
    return jnp.mean(y, axis=(1, 2), keepdims=True)


def _norm_stats(x: jnp.ndarray, pivot: jnp.ndarray):
    """A norm's one pass over its activation for the statistics: the float32
    mean and mean square over H, W of ``x - pivot``, ``(B, 1, 1, C)`` each.
    Two sibling reductions over one operand, which XLA emits as one read
    (on a TPU inside the fusion of the conv that makes ``x``, when ``pivot``
    does not depend on ``x``)."""
    with jax.named_scope("norm_stats"):
        d = x.astype(jnp.float32) - pivot
        return (jnp.mean(d, axis=(1, 2), keepdims=True),
                jnp.mean(d * d, axis=(1, 2), keepdims=True))


def _norm_apply(scale, bias, x, pivot, m1, m2, eps):
    """``m1``, ``m2``: first and second moments of ``x - pivot``. The
    variance is ``m2 - m1**2`` whatever the pivot, and the norm is applied
    to ``x - pivot`` too: no term is larger than the pivot's distance from
    the mean, so nothing cancels that a pivot near the mean does not keep
    small. One elementwise pass, which the caller's relu and residual add
    join."""
    a = scale * lax.rsqrt(jnp.maximum(m2 - m1 * m1, 0.0) + eps)
    b = bias - m1 * a
    with jax.named_scope("norm_apply"):
        return ((x.astype(jnp.float32) - pivot) * a + b).astype(x.dtype)


def instance_norm(p: Params, x: jnp.ndarray, pivot=None,
                  eps: float = 1e-5) -> jnp.ndarray:
    """Per-(sample, channel) normalization over H,W; stats in float32, taken
    in ONE pass over ``x`` as the mean and mean square of ``x - pivot``
    (``E[d^2] - E[d]^2`` is the variance about any pivot; the rounding
    error grows with the square of the pivot's distance from the mean in
    spreads, so the pivot has to lie near the mean). ``pivot``: broadcasts
    against ``x`` with H, W of 1 (:func:`corner_pivot`, or a ``(C,)``
    vector); None takes each channel's first position, which costs a pass
    of its own after the conv that makes ``x``."""
    if pivot is None:
        pivot = x[:, :1, :1, :]
    pivot = lax.stop_gradient(pivot.astype(jnp.float32))
    m1, m2 = _norm_stats(x, pivot)
    return _norm_apply(p["scale"], p["bias"], x, pivot, m1, m2, eps)


def conv_norm(p: Params, conv, x: jnp.ndarray,
              phased: bool = False) -> jnp.ndarray:
    """``conv`` (with its bias, as a function of its input) of ``x``, then
    the instance norm ``p`` of the result (:func:`instance_norm_phase` of
    a conv emitting phases): its sums are taken about
    :func:`corner_pivot` of the same conv, which does not wait for the
    conv's output, so they ride in the conv's own pass."""
    norm = instance_norm_phase if phased else instance_norm
    return norm(p, conv(x), corner_pivot(conv, x))


def upsample_nearest(x: jnp.ndarray, factor: int = 2) -> jnp.ndarray:
    """Nearest-neighbor upsample ×factor (resize-conv beats transposed conv
    for checkerboard artifacts, and maps to cheap reshapes on TPU)."""
    b, h, w, c = x.shape
    x = jnp.broadcast_to(x[:, :, None, :, None, :], (b, h, factor, w, factor, c))
    return x.reshape(b, h * factor, w * factor, c)


def depth_to_space(x: jnp.ndarray, factor) -> jnp.ndarray:
    """Subpixel rearrange (B, H, W, C·r²) → (B, H·r, W·r, C), DCR order:
    ``y[b, h*r+i, w*r+j, c] = x[b, h, w, (i*r + j)*C + c]``. ``factor``:
    ``r``, or an (H, W) pair (rh, rw) for a factor an axis.

    The ESPCN upscale head: the conv producing C·r² channels is a dense
    MXU matmul; this rearrange is pure reshape/transpose — zero FLOPs, but
    not free: on a TPU it is layout copies (PERF.md §5).
    """
    rh, rw = _pair(factor)
    b, h, w, crr = x.shape
    c = crr // (rh * rw)
    if c * rh * rw != crr:
        raise ValueError(f"channels {crr} not divisible by r²={rh * rw}")
    x = x.reshape(b, h, w, rh, rw, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)  # b, h, i, w, j, c
    return x.reshape(b, h * rh, w * rw, c)


def gram_matrix(feats: jnp.ndarray) -> jnp.ndarray:
    """Batched Gram matrix of NHWC features: (B, C, C) / (H*W*C)."""
    b, h, w, c = feats.shape
    f = feats.reshape(b, h * w, c).astype(jnp.float32)
    return jnp.einsum("bnc,bnd->bcd", f, f) / (h * w * c)


# ---------------------------------------------------------------------------
# Exact MXU-utilization conv rewrites (see models.analysis for the numbers)
# ---------------------------------------------------------------------------
#
# Full-resolution convs with tiny channel counts starve the MXU: a 9x9 conv
# with Cout=3 can use 3/128 of the systolic array's lanes, Cout=32 a quarter,
# and a decoder conv on a nearest-x2-upsampled activation reads every source
# pixel four times. Two classic, EXACT rearrangements fix the utilization
# without changing the model's math:
#
# - conv2d_s2d: space-to-depth phase decomposition. A stride-1 kxk conv on
#   (H, W, Cin) equals a ceil((k+1)/2)-sized conv on the space-to-depth
#   transform (H/2, W/2, 4*Cin) producing all four output phases (4*Cout
#   channels), followed by depth_to_space. Same multiply-adds (a few
#   structurally-zero taps added), 4x the lane-dimension channels. (ESPCN's
#   opt-in; the style net keeps its tensors in that form instead: "The phase
#   domain", below.)
# - upsample2_conv: nearest-x2-upsample followed by a kxk conv collapses to
#   a per-phase conv at LOW resolution whose taps are the sums of the
#   original taps that landed on the same source pixel — the upsampled
#   activation is never materialized.


def space_to_depth(x: jnp.ndarray, factor=2) -> jnp.ndarray:
    """(B, H, W, C) → (B, H/f, W/f, f²·C); inverse of depth_to_space
    (phase-major channel order: out[..., (a*f + b)*C + c] = x[h*f+a, w*f+b, c]).
    ``factor``: ``f``, or an (H, W) pair for a factor an axis."""
    fh, fw = _pair(factor)
    b, h, w, c = x.shape
    x = x.reshape(b, h // fh, fh, w // fw, fw, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, h // fh, w // fw, fh * fw * c)


def _s2d_kernel(w: jnp.ndarray) -> jnp.ndarray:
    """Rearrange a (k, k, Cin, Cout) stride-1 kernel into the equivalent
    (k2, k2, 4·Cin, 4·Cout) kernel over space-to-depth phases (factor 2).

    Built with one static fancy-index gather (indices are numpy, computed
    from k alone), so tracing costs a single cheap op per step even when
    the weights are runtime state."""
    k = w.shape[0]
    k2 = (k + 1) // 2
    # Wpad's extra k-th row/col is the zero tap for out-of-range phases.
    wpad = jnp.pad(w, ((0, 1), (0, 1), (0, 0), (0, 0)))
    # idy[p, a, i] = dy = 2p + a - i when 0 <= dy < k, else k (zero row).
    idy = np.full((k2, 2, 2), k, dtype=np.int32)
    for p in range(k2):
        for a in range(2):
            for i in range(2):
                dy = 2 * p + a - i
                if 0 <= dy < k:
                    idy[p, a, i] = dy
    g = wpad[idy[:, :, :, None, None, None], idy[None, None, None, :, :, :]]
    # g[p, a, i, q, b, j, ci, co] → (p, q, a, b, ci, i, j, co)
    g = g.transpose(0, 3, 1, 4, 6, 2, 5, 7)
    cin, cout = w.shape[2], w.shape[3]
    return g.reshape(k2, k2, 4 * cin, 4 * cout)


def conv2d_s2d(
    p: Params,
    x: jnp.ndarray,
    compute_dtype=jnp.bfloat16,
    reflect: bool = False,
) -> jnp.ndarray:
    """Stride-1 SAME conv (without bias) computed at half resolution via
    space-to-depth — numerically identical tap arithmetic to
    :func:`conv2d_nb`, ~4× the MXU lane utilization for small-Cout or
    full-resolution layers. Requires even H, W (video geometries are)."""
    k = p["w"].shape[0]
    r = k // 2
    b, h, w_, c = x.shape
    if h % 2 or w_ % 2:
        return conv2d_nb(p, x, compute_dtype=compute_dtype, reflect=reflect)
    xp = jnp.pad(x, ((0, 0), (r, r), (r, r), (0, 0)),
                 mode="reflect" if reflect else "constant")
    x2 = space_to_depth(xp.astype(compute_dtype), 2)
    k5 = _s2d_kernel(p["w"]).astype(compute_dtype)
    y2 = lax.conv_general_dilated(
        x2, k5, window_strides=(1, 1), padding="VALID",
        dimension_numbers=_DN,
    )
    return depth_to_space(y2, 2)


def _upsample2_kernel(w: jnp.ndarray) -> jnp.ndarray:
    """Phase-collapse a (k, k, Cin, Cout) kernel across a preceding
    nearest-×2 upsample: taps of the full-res conv that read the same
    low-res source pixel sum into one tap. Returns a ``(kernel,
    pad_radius)`` tuple — the (kl, kl, Cin, 4·Cout) kernel for a VALID
    conv on the low-res input, and the edge-pad radius that input needs
    (``-e0``, the magnitude of the most-negative low-res tap offset)."""
    k = w.shape[0]
    r = k // 2
    # Low-res tap offset e = floor((i + dy - r) / 2) for dy in [0, k).
    offs = sorted({(i + dy - r) // 2 for dy in range(k) for i in range(2)})
    e0, kl = offs[0], offs[-1] - offs[0] + 1
    # idy[e, i, :] lists the dy landing on low-res tap e of phase i, padded
    # with k (wpad's zero row): one static gather and a sum over the
    # lists, as :func:`_s2d_kernel` (a tap at a time it is k²·4 traced
    # scatter-adds a call, a second of every process's set-up).
    hits = [[[dy for dy in range(k) if (i + dy - r) // 2 - e0 == e]
             for i in range(2)] for e in range(kl)]
    n = max(len(h) for row in hits for h in row)
    idy = np.full((kl, 2, n), k, dtype=np.int32)
    for e in range(kl):
        for i in range(2):
            idy[e, i, :len(hits[e][i])] = hits[e][i]
    wpad = jnp.pad(w, ((0, 1), (0, 1), (0, 0), (0, 0)))
    g = wpad[idy[:, :, :, None, None, None], idy[None, None, None, :, :, :]]
    kl_w = g.sum(axis=(2, 5))                  # (e, i, f, j, ci, co)
    cin, cout = w.shape[2], w.shape[3]
    # (e, i, f, j, ci, co) → (e, f, ci, (i·2+j)·Cout + co)
    kl_w = kl_w.transpose(0, 2, 4, 1, 3, 5).reshape(kl, kl, cin, 4 * cout)
    return kl_w, -e0


def upsample2_conv_phase(
    p: Params,
    x: jnp.ndarray,
    compute_dtype=jnp.bfloat16,
) -> jnp.ndarray:
    """nearest-×2 upsample + reflect-SAME 3×3 conv (without bias), computed
    entirely at LOW resolution and LEFT there: returns
    ``space_to_depth(y, 2)`` of the full-resolution result ``y`` (4·Cout
    dense channels). Exact for k=3 only: edge padding of the low-res input
    reproduces reflect-101 of the upsampled input when the pad radius is 1
    (for r≥2 the reflected full-res rows map to DIFFERENT low-res pixels
    than edge replication)."""
    if p["w"].shape[0] != 3:
        raise ValueError("upsample2_conv_phase is exact for 3x3 kernels only")
    klw, pad = _upsample2_kernel(p["w"])
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)), mode="edge")
    return lax.conv_general_dilated(
        xp.astype(compute_dtype), klw.astype(compute_dtype),
        window_strides=(1, 1), padding="VALID", dimension_numbers=_DN,
    )


def upsample2_conv(
    p: Params,
    x: jnp.ndarray,
    compute_dtype=jnp.bfloat16,
) -> jnp.ndarray:
    """:func:`upsample2_conv_phase` brought back to full resolution;
    kernels other than 3×3 take the materialized-upsample path."""
    if p["w"].shape[0] != 3:
        return conv2d_nb(p, upsample_nearest(x, 2),
                         compute_dtype=compute_dtype, reflect=True)
    return depth_to_space(upsample2_conv_phase(p, x, compute_dtype), 2)


# ---------------------------------------------------------------------------
# The phase domain: a full-resolution tensor kept as its space_to_depth
# ---------------------------------------------------------------------------
#
# A (B, H, W, c) activation with c under the 128 lanes is stored and moved
# 128/c times padded on the TPU. Its space_to_depth(2) image (B, H/2, W/2,
# 4c) holds the same numbers densely. The functions below let such a tensor
# stay in that form from the conv that makes it, through instance norm,
# into the conv that reads it: the conv's taps are re-indexed between
# phases, the norm's statistics sum over a channel's phases, and the
# reflect border is built from neighbouring phases.


def phase_kernel(w: jnp.ndarray, fi: int, fo: int,
                 stride: int = 1) -> Tuple[jnp.ndarray, int, int]:
    """Re-index a (k, k, Cin, Cout) kernel of a reflect-SAME conv with the
    given stride into the kernel of the same conv between phase tensors:
    input ``space_to_depth(x, fi)``, output ``space_to_depth(y, fo)``,
    ``fi == stride·fo``. Output row ``fo·J + β`` reads input row
    ``fi·J + (stride·β + dy − r)``, i.e. phase ``α`` of low-res row
    ``J + e`` with ``fi·e + α = stride·β + dy − r``.

    Returns ``(kernel, lo, hi)``: the (kl, kl, fi²·Cin, fo²·Cout) kernel of
    a VALID stride-1 conv, and the low-res rows/cols of border it needs
    before and after. One static gather, as :func:`_s2d_kernel`."""
    if fi != stride * fo:
        raise ValueError(f"phase factors {fi} -> {fo} do not fit stride {stride}")
    k = w.shape[0]
    r = k // 2
    lo = -((-r) // fi)                                   # -floor(-r / fi)
    hi = (stride * (fo - 1) + k - 1 - r) // fi
    kl = lo + hi + 1
    idy = np.full((kl, fi, fo), k, dtype=np.int32)       # k: the zero tap
    for e in range(kl):
        for a in range(fi):
            for b in range(fo):
                dy = fi * (e - lo) + a - stride * b + r
                if 0 <= dy < k:
                    idy[e, a, b] = dy
    wpad = jnp.pad(w, ((0, 1), (0, 1), (0, 0), (0, 0)))
    g = wpad[idy[:, :, :, None, None, None], idy[None, None, None, :, :, :]]
    # g[e, a, b, e', a', b', ci, co] → (e, e', a, a', ci, b, b', co)
    g = g.transpose(0, 3, 1, 4, 6, 2, 5, 7)
    cin, cout = w.shape[2], w.shape[3]
    return g.reshape(kl, kl, fi * fi * cin, fo * fo * cout), lo, hi


def phase_reflect_pad(x: jnp.ndarray, top: int, bottom: int,
                      left: int, right: int) -> jnp.ndarray:
    """Reflect-101 border of a full-resolution tensor, built on its
    ``space_to_depth(·, 2)`` image ``x``: equals ``space_to_depth(jnp.pad(X,
    2·(top, bottom), 2·(left, right), mode="reflect"), 2)`` without ever
    forming ``X``. Full-res row ``−t`` is row ``t``: low-res border row
    ``−m`` holds, in phase 0, phase 0 of row ``m`` and, in phase 1,
    phase 1 of row ``m − 1`` (mirrored at the far edge)."""
    c = x.shape[-1] // 4
    lane = jnp.arange(4 * c)

    def along(x, axis, before, after, second_phase):
        n = x.shape[axis]

        def border(start0, start1, count):
            take = lambda s: jnp.flip(
                lax.slice_in_dim(x, s, s + count, axis=axis), axis)
            return jnp.where(second_phase, take(start1), take(start0))

        parts = []
        if before:
            parts.append(border(1, 0, before))
        parts.append(x)
        if after:
            parts.append(border(n - after, n - after - 1, after))
        return jnp.concatenate(parts, axis=axis) if len(parts) > 1 else x

    x = along(x, 1, top, bottom, lane // (2 * c) == 1)
    return along(x, 2, left, right, (lane // c) % 2 == 1)


def conv2d_phase(
    p: Params,
    x: jnp.ndarray,
    stride: int = 1,
    fold: int = 1,
    compute_dtype=jnp.bfloat16,
) -> jnp.ndarray:
    """Reflect-SAME k×k conv (without bias) of the full-resolution tensor
    whose ``space_to_depth(·, 2)`` image is ``x``, computed between phase
    tensors. The input factor is ``fi = 2·fold``; the result is
    ``space_to_depth(y, fi // stride)`` of the conv's output ``y`` (factor
    1: ``y`` itself). ``fold > 1`` serves a very small Cout, which fills
    more MXU columns with more output phases: the conv then strides
    ``fold`` over ``x``'s rows and columns with the factor-``fi`` kernel's
    rows laid out over them — the deeper space_to_depth is never formed
    (on a v5e the stride costs nothing, the relayout 7 ms of 16 720p
    frames; PERF.md §6, PR 28)."""
    fi = 2 * fold
    kern, lo, hi = phase_kernel(p["w"], fi, fi // stride, stride)
    xp = phase_reflect_pad(x, lo * fold, hi * fold, lo * fold, hi * fold)
    # Input channels (α, α', c), α = 2·a2 + a1 → kernel rows 2-row-of-x
    # major: (e, a2) over x's rows, (a1, b1, c) over x's channels.
    kl, _, _, n = kern.shape
    kern = kern.reshape(kl, kl, fold, 2, fold, 2, -1, n)
    kern = kern.transpose(0, 2, 1, 4, 3, 5, 6, 7)
    kern = kern.reshape(fold * kl, fold * kl, -1, n)
    return lax.conv_general_dilated(
        xp.astype(compute_dtype), kern.astype(compute_dtype),
        window_strides=(fold, fold), padding="VALID", dimension_numbers=_DN,
    )


def instance_norm_phase(p: Params, x: jnp.ndarray, pivot=None,
                        eps: float = 1e-5) -> jnp.ndarray:
    """:func:`instance_norm` of the full-resolution tensor whose
    ``space_to_depth(·, 2)`` image is ``x``: one pass for each lane's
    moments about its channel's pivot, a channel's moments the mean of its
    four phases' (one pivot a channel, so they add); scale and bias are
    tiled across the phases. ``pivot`` is a phase image too (4·c lanes, as
    the conv before emits it) and is averaged over a channel's phases."""
    c4 = x.shape[-1]

    def per_channel(stat):            # (..., 4c) → per channel, tiled back
        m = jnp.mean(stat.reshape(*stat.shape[:-1], 4, c4 // 4), axis=-2)
        return jnp.tile(m, 4)

    if pivot is None:
        pivot = x[:, :1, :1, :]
    pivot = lax.stop_gradient(per_channel(pivot.astype(jnp.float32)))
    m1, m2 = _norm_stats(x, pivot)
    return _norm_apply(jnp.tile(p["scale"], 4), jnp.tile(p["bias"], 4), x,
                       pivot, per_channel(m1), per_channel(m2), eps)


# ---------------------------------------------------------------------------
# The phase domain under a zero-SAME border, a factor an axis (ESPCN)
# ---------------------------------------------------------------------------
#
# A zero-SAME conv is exact between phase tensors with a plain ``jnp.pad``:
# the full-resolution border is zeros, and what lies beyond it meets only
# structurally zero taps. Here a phase tensor has a factor an axis,
# ``space_to_depth(x, (fh, fw))``, and a conv may emit MORE phases than it
# reads (``fo`` a multiple of ``fi`` on each axis) by striding ``fo // fi``
# over its input: from a plain tensor (``fi = 1``) that is a (k+f−1)-tap
# stride-f conv emitting f phases, 9/16 dense for k = 3, f = 2 where the
# phase → phase kernel is 9/36. So each activation takes the factors that
# fill its 128 lanes and no more, and no tensor goes back to plain.


def zero_phase_taps(k: int, fi: int, fo: int, stride: int = 1):
    """One axis of :func:`zero_phase_kernel`: ``(idy, lo, hi)`` with
    ``idy[e, α, β]`` the tap ``dy`` of the k-tap kernel that output phase β
    reads from input phase α of low-res position ``(stride·fo // fi)·J + e −
    lo`` (output row ``fo·J + β`` reads input row ``stride·(fo·J + β) + dy −
    r``), ``k`` where there is none: the zero tap. :func:`phase_kernel`'s
    table with ``fo`` free of ``fi``: the conv strides ``stride·fo // fi``
    over its low-res input."""
    r = k // 2
    lo = -((-r) // fi)
    hi = (stride * (fo - 1) + k - 1 - r) // fi
    idy = np.full((lo + hi + 1, fi, fo), k, dtype=np.int32)
    for e in range(lo + hi + 1):
        for a in range(fi):
            for b in range(fo):
                dy = fi * (e - lo) + a - stride * b + r
                if 0 <= dy < k:
                    idy[e, a, b] = dy
    return idy, lo, hi


def zero_phase_kernel(w: jnp.ndarray, fi=1, fo=2, stride: int = 1):
    """Re-index a (k, k, Cin, Cout) kernel of a zero-padded (k // 2) conv
    of the given stride into the kernel of the same conv from
    ``space_to_depth(x, fi)`` to ``space_to_depth(y, fo)``; ``fi``, ``fo``:
    a factor or an (H, W) pair, ``stride·fo`` a multiple of ``fi`` on each
    axis (at stride 1: a conv emits a multiple of the phases it reads).
    Returns ``(kernel, pads, strides)``: the (klh, klw, fih·fiw·Cin,
    foh·fow·Cout) kernel of a VALID conv with window strides ``stride·fo //
    fi``, and the ``(lo, hi)`` low-res rows / columns of zeros its input
    needs. At ``fi = 1`` and stride 1 output phase (β, β′) holds ``w`` at
    rows β…β+k−1, columns β′…β′+k−1 of a (k+f−1)² kernel. One static
    gather, as :func:`_s2d_kernel`."""
    fi, fo = _pair(fi), _pair(fo)
    if (stride * fo[0]) % fi[0] or (stride * fo[1]) % fi[1]:
        raise ValueError(f"phase factors {fi} -> {fo} at stride {stride}: a conv emits a "
                         f"multiple of what it reads")
    k = w.shape[0]
    (ih, loh, hih), (iw, low, hiw) = (zero_phase_taps(k, a, b, stride) for a, b in zip(fi, fo))
    wpad = jnp.pad(w, ((0, 1), (0, 1), (0, 0), (0, 0)))
    g = wpad[ih[:, :, :, None, None, None], iw[None, None, None, :, :, :]]
    # g[e, α, β, e', α', β', ci, co] → (e, e', α, α', ci, β, β', co)
    g = g.transpose(0, 3, 1, 4, 6, 2, 5, 7)
    cin, cout = w.shape[2], w.shape[3]
    kern = g.reshape(ih.shape[0], iw.shape[0], fi[0] * fi[1] * cin, fo[0] * fo[1] * cout)
    sh, sw = stride * fo[0] // fi[0], stride * fo[1] // fi[1]
    # The last window starts a stride short of the end: it stops that far short of ``hi``.
    return kern, ((loh, hih - (sh - 1)), (low, hiw - (sw - 1))), (sh, sw)


def conv2d_zero_phase(
    p: Params,
    x: jnp.ndarray,
    fi=1,
    fo=2,
    cols=None,
    compute_dtype=jnp.bfloat16,
    stride: int = 1,
    out_dtype=None,
) -> jnp.ndarray:
    """Zero-padded (k // 2) k×k conv (without bias) of the full-resolution
    tensor whose ``space_to_depth(·, fi)`` image is ``x`` (``fi = 1``: the
    tensor itself), as ONE conv that emits the result's phases: returns
    ``space_to_depth(Y, fo)`` of the conv's result ``Y`` (at ``stride`` 1
    ``conv2d_nb(p, X)``; at 2 PyTorch's ``Conv2d(stride=2, padding=k // 2)``
    of an even H×W). ``cols``: a static permutation of the emitted columns
    (:func:`subpixel_order`), applied to the kernel. ``out_dtype``: the
    result's (``preferred_element_type``), None for ``compute_dtype``. On a
    v5e the window stride costs nothing (PERF.md §6, PRs 28 and 41)."""
    kern, (pad_h, pad_w), strides = zero_phase_kernel(p["w"], fi, fo, stride)
    if cols is not None:
        kern = kern[..., cols]
    xp = jnp.pad(x, ((0, 0), pad_h, pad_w, (0, 0)))
    return lax.conv_general_dilated(
        xp.astype(compute_dtype), kern.astype(compute_dtype),
        window_strides=strides, padding="VALID", dimension_numbers=_DN,
        preferred_element_type=out_dtype,
    )


def subpixel_order(factor, scale: int, c: int) -> np.ndarray:
    """The order of a sub-pixel head's emitted columns that makes shuffle
    and un-phasing ONE rearrangement. The head's ``scale²·c`` channels are
    (i, j, c) of the ×``scale`` shuffle; emitted at phase factors (fh, fw)
    the columns are (β, β′, i, j, c) and the frame's row is ``fh·scale·J +
    scale·β + i``. With ``perm`` = this function's result,
    ``depth_to_space(y[..., perm], (fh·scale, fw·scale)) ==
    depth_to_space(depth_to_space(y, (fh, fw)), scale)``: columns in
    (β, i, β′, j, c) order. Static (numpy): index the kernel's and the
    tiled bias's columns with it."""
    fh, fw = _pair(factor)
    idx = np.arange(fh * fw * scale * scale * c)
    idx = idx.reshape(fh, fw, scale, scale, c)            # (β, β', i, j, c)
    return idx.transpose(0, 2, 1, 3, 4).reshape(-1)       # (β, i, β', j, c)
