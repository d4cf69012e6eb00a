"""Histogram equalization — the region-statistic, table-lookup filter family.

Every other filter here is local (pointwise or a bounded stencil); this
one needs a statistic of a REGION (the per-channel intensity histogram of
the frame, or of each tile of a grid) and a data-dependent table lookup,
all in the integer domain. Under spatial sharding the whole-frame
histogram is a per-shard partial plus one ``psum``, not a neighbor
exchange.

Two forms of the two stages, bit-identical (tests/test_histogram_forms.py):

- ``impl="sort"``: the cdf from SORT + 256 binary searches
  (``cdf[v] = searchsorted(sort(plane), v, 'right')``) and the lookup as
  XLA gathers. The fastest form on the CPU backend, where the kernels
  below run in interpret mode. On a TPU it is the gathers that cost:
  CLAHE's four image-sized scalar gathers take 380 ms each for 4 frames
  of 1080p (15 ns an element), the key+index sort 24 and the searches
  60 (PR 49's chip run,
  ``scripts/style_step_probe.py --model clahe --impl sort``).
- ``impl="pallas"`` (PR 49): two kernels of the repo's own
  (ops/pallas_kernels.py). ``tile_hist_pallas`` COUNTS on the VPU (the
  chip has no vector scatter-add, and no sort is needed): since PR 50 a
  tile is cut into its 8 bit planes, 32 pixels a word, each bin's pixels
  are an AND of the planes and their complements, and the chip's
  population count (``vpcnt``) counts a vreg of them in one instruction:
  about 70 operations a pixel at CLAHE's 1080p tile (the plan's
  ``hist_ops_per_pixel``) where comparing every pixel with each of 256
  bin values took 768. ``lut_apply_pallas`` looks a pixel's entry up with two LANE
  GATHERS (the table's halves, a vreg each) and a select, and fetches
  CLAHE's four neighbouring tile tables in one lookup, a byte each,
  before the float32 blend. On the v5e, 8 frames of 1080p: CLAHE 12.0 ms
  against the sort form's 2864, ``equalize`` 9.1 against 638, the same
  bytes out (PR 49's chip run, while the counting still compared; PERF.md
  section 5 has the step by scope: the counting was 65 of a 94 ms step of
  64 frames then and is 7.6 of 37.6 since PR 50).

``impl=None`` picks the measured per-backend winner
(``MEASURED_DEFAULTS["clahe"]`` / ``["equalize"]``).

Numerics match ``cv2.equalizeHist`` exactly on grayscale (same cdf-min
rounding) and ``cv2.createCLAHE`` within one step, golden-tested.

Reference counterpart: none — the reference's one op is invert
(inverter.py:41); this widens the op families with the region-statistic
shape the stencil/pointwise ops can't represent.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from dvf_tpu.api.filter import Filter, stateless
from dvf_tpu.ops import pallas_kernels as pk
from dvf_tpu.ops.registry import measured_default_for, register_filter
from dvf_tpu.utils.image import rgb_to_gray, to_float, to_uint8


def _resolve_impl(impl: Optional[str], key: str) -> str:
    """``impl``, or the backend's measured winner: "sort" or "pallas"."""
    impl = measured_default_for(key) if impl is None else impl
    if impl not in ("sort", "pallas"):
        raise ValueError(f"impl must be 'sort' or 'pallas', got {impl!r}")
    return impl


def _plane_cdf(flat_i32: jnp.ndarray) -> jnp.ndarray:
    """(B, P) int32 pixels → (B, 256) float32 cdf: cdf[b, v] = #pixels<=v,
    via sort + binary search (the "sort" form; see the module docstring).
    Under spatial sharding this runs on the LOCAL pixels; counts are
    additive, so one psum makes the global cdf."""
    srt = jnp.sort(flat_i32, axis=1)
    bins = jnp.arange(256, dtype=jnp.int32)
    return jax.vmap(
        lambda s: jnp.searchsorted(s, bins, side="right")
    )(srt).astype(jnp.float32)


def _equalize_lut(cdf: jnp.ndarray, n: float) -> jnp.ndarray:
    """cv2.equalizeHist's exact LUT, (B, 256) uint8, from a (B, 256) cdf
    over ``n`` total pixels."""
    hist = jnp.diff(cdf, axis=1, prepend=0.0)
    # lut[v] = round((cdf[v] - cdf_min) / (N - cdf_min) * 255), cdf_min =
    # cdf at the lowest OCCUPIED bin. For a constant frame (N == cdf_min)
    # cv2 leaves the image unchanged via a guarded division; jnp.where
    # keeps that branch traceable.
    n = jnp.asarray(n, jnp.float32)
    cdf_min = jnp.min(jnp.where(hist > 0, cdf, n + 1.0), axis=1, keepdims=True)
    denom = n - cdf_min
    scale = jnp.where(denom > 0, 255.0 / jnp.maximum(denom, 1.0), 0.0)
    lut = jnp.round((cdf - cdf_min) * scale)
    lut = jnp.where(denom > 0, lut, jnp.arange(256, dtype=jnp.float32)[None])
    return jnp.clip(lut, 0.0, 255.0).astype(jnp.uint8)


_BAND_ROWS = 128   # rows of a band of the counting form's pass over a whole plane


def _bands(plane_u8: jnp.ndarray) -> tuple:
    """A (B, H, W) plane as the kernels' tiles: full-width bands of at most
    ``_BAND_ROWS`` rows, the last filled up with zeros."""
    h = plane_u8.shape[1]
    rows = min(h, _BAND_ROWS)
    gy = -(-h // rows)
    x = jnp.pad(plane_u8, ((0, 0), (0, gy * rows - h), (0, 0)))
    return pk.to_tiles(x, gy, 1), gy, rows


def _equalize_u8_plane(plane_u8: jnp.ndarray, reduce_cdf=None, n_total=None,
                       impl: str = "sort", interpret: bool = False) -> jnp.ndarray:
    """Equalize uint8 planes (B, H, W), vectorized over the batch.

    ``reduce_cdf``/``n_total``: the spatial-sharding hooks — inside a
    shard_map, ``reduce_cdf`` is ``psum over 'space'`` and ``n_total``
    the GLOBAL pixel count, so each shard LUTs its rows against the
    whole-frame statistic."""
    b, h, w = plane_u8.shape
    if impl == "sort":
        flat = plane_u8.reshape(b, h * w).astype(jnp.int32)
        cdf = _plane_cdf(flat)
    else:
        tiles, gy, rows = _bands(plane_u8)
        counts = pk.tile_hist_pallas(tiles, 1, rows * w, "equalize_hist", interpret)
        counts = counts.sum(axis=(1, 2)).at[:, 0].add((h - gy * rows) * w)   # the last band's filler rows
        cdf = jnp.cumsum(counts, axis=1).astype(jnp.float32)
    if reduce_cdf is not None:
        cdf = reduce_cdf(cdf)
    lut = _equalize_lut(cdf, n_total if n_total is not None else h * w)
    if impl == "sort":
        return jnp.take_along_axis(lut, flat, axis=1).reshape(b, h, w)
    table = jnp.broadcast_to(lut.astype(jnp.int32)[:, None, None], (b, gy, 1, 256))
    out = pk.lut_apply_pallas(tiles, table, "equalize_apply", interpret=interpret)
    return pk.from_tiles(out, gy, 1, rows, w)[:, :h]


def _dispatch_planes(x_u8: jnp.ndarray, on_gray: bool, apply_planes):
    """Shared plane dispatch for the histogram family: ``on_gray`` runs
    ``apply_planes`` on the luma and broadcasts (the cv2 golden mode);
    otherwise channels fold into the batch axis so ONE traced chain
    serves all C planes."""
    if on_gray:
        gray = (x_u8 if x_u8.shape[-1] == 1
                else to_uint8(rgb_to_gray(to_float(x_u8))))
        eq = apply_planes(gray[..., 0])[..., None]
        return jnp.broadcast_to(eq, x_u8.shape)
    b, h, w, c = x_u8.shape
    planes = jnp.moveaxis(x_u8, -1, 1).reshape(b * c, h, w)
    return jnp.moveaxis(apply_planes(planes).reshape(b, c, h, w), 1, -1)


@register_filter("equalize")
def equalize(on_gray: bool = False, impl: Optional[str] = None,
             interpret: Optional[bool] = None) -> Filter:
    """Global histogram equalization.

    ``on_gray=False`` (default) equalizes each RGB channel independently
    (the common video look); ``on_gray=True`` reproduces
    ``cv2.equalizeHist`` on the luma and broadcasts it — the golden-test
    mode. ``impl``: "sort" or "pallas" (module docstring), None = the
    backend's measured winner; ``interpret`` as the Pallas filters' (None:
    compiled on a TPU, interpret mode elsewhere).
    """
    impl = _resolve_impl(impl, "equalize")

    def body(batch: jnp.ndarray, reduce_cdf=None, h_total=None) -> jnp.ndarray:
        u8 = batch.dtype == jnp.uint8
        x = to_uint8(batch)
        nt = None if h_total is None else h_total * x.shape[2]
        out = _dispatch_planes(
            x, on_gray, lambda p: _equalize_u8_plane(
                p, reduce_cdf, nt, impl, pk._auto_interpret(interpret)))
        return out if u8 else to_float(out, batch.dtype)

    def fn(batch: jnp.ndarray) -> jnp.ndarray:
        return body(batch)

    def specialize(mesh, batch_shape):
        """Spatial sharding the global-reduction way: each shard computes
        the cdf of its H-slice (counts are additive) and ONE psum over
        'space' makes the whole-frame statistic — no halo, no gather of
        pixels, 256 floats of collective traffic per plane."""
        from jax.sharding import PartitionSpec as P

        axes = dict(zip(mesh.axis_names, mesh.devices.shape))
        d, sp = axes.get("data", 1), axes.get("space", 1)
        b, h = batch_shape[0], batch_shape[1]
        if sp <= 1 or h % sp != 0:
            return None  # engine default: replicate H (correct, just unsharded)
        # H-sharding only needs h % space == 0; an indivisible batch just
        # degrades the batch axis (like ops.style / ops.sr do).
        bspec = "data" if b % d == 0 else None
        spec = P(bspec, "space", None, None)

        def inner(x_shard):
            return body(x_shard,
                        reduce_cdf=lambda cdf: jax.lax.psum(cdf, "space"),
                        h_total=h)

        def sharded_fn(batch, state):
            out = jax.shard_map(
                inner, mesh=mesh,
                in_specs=spec,
                out_specs=spec,
                check_vma=False,
            )(batch)
            return out, state

        return Filter(
            name=f"space(equalize(gray={on_gray}))",
            fn=sharded_fn,
            uint8_ok=True,
            # halo=0: this body OWNS its spatial distribution (the psum);
            # the engine must keep H GSPMD-sharded and must not route it
            # through the stencil halo machinery or replicate H.
            halo=0,
        )

    label = "equalize" if impl == "sort" else "equalize_pallas"
    return stateless(f"{label}(gray={on_gray})", fn, uint8_ok=True, halo=None,
                     specialize=specialize)


# ---------------------------------------------------------------------------
# CLAHE — contrast-limited ADAPTIVE histogram equalization
# ---------------------------------------------------------------------------


def _clahe_luts(hist: jnp.ndarray, tile_area: int,
                clip_abs: int) -> jnp.ndarray:
    """(T, 256) float32 tile histograms → (T, 256) uint8 CLAHE LUTs,
    matching cv2.CLAHE: clip at ``clip_abs``, redistribute the excess
    exactly the way cv2 does (uniform batch + strided residual), then the
    scaled cumulative LUT."""
    # Clip + uniform redistribution.
    excess = jnp.sum(jnp.maximum(hist - clip_abs, 0.0), axis=1, keepdims=True)
    hist = jnp.minimum(hist, float(clip_abs))
    batch_add = jnp.floor(excess / 256.0)
    residual = excess - batch_add * 256.0              # (T, 1), 0..255
    hist = hist + batch_add
    # cv2's residual pass: step = max(256 // residual, 1); bins 0, step,
    # 2*step, ... each get +1 until the residual runs out.
    step = jnp.maximum(jnp.floor(256.0 / jnp.maximum(residual, 1.0)), 1.0)
    idx = jnp.arange(256, dtype=jnp.float32)[None, :]
    gets_one = ((jnp.mod(idx, step) == 0.0)
                & (jnp.floor(idx / step) < residual)
                & (residual > 0.0))
    hist = hist + gets_one.astype(jnp.float32)
    lut = jnp.round(jnp.cumsum(hist, axis=1) * (255.0 / tile_area))
    return jnp.clip(lut, 0.0, 255.0).astype(jnp.uint8)


def clahe_geometry(h: int, w: int, grid: int, clip_limit: float) -> dict:
    """What CLAHE's tile grid makes of an ``h x w`` plane: the padded plane
    (reflect pad right and bottom to a multiple of ``grid``, what cv2
    does), the tile, and cv2's absolute clip level."""
    hp, wp = -(-h // grid) * grid, -(-w // grid) * grid
    th, tw = hp // grid, wp // grid
    return {"hp": hp, "wp": wp, "tile_h": th, "tile_w": tw,
            "clip_abs": max(1, int(clip_limit * th * tw / 256.0))}


def _reflect_to_grid(planes: jnp.ndarray, hp: int, wp: int) -> jnp.ndarray:
    """(N, H, W) planes reflect-padded right and bottom to ``hp x wp``."""
    _, h, w = planes.shape
    if (hp, wp) == (h, w):
        return planes
    return jnp.pad(planes, ((0, 0), (0, hp - h), (0, wp - w)), mode="reflect")


def _corners(size: int, tile: int, grid: int) -> tuple:
    """cv2's interpolation lattice along one axis, as host constants: the
    tile-space coordinate of a pixel center is ``p / tile - 0.5``; the two
    tiles it lies between (clamped) and its fraction of the way. Both
    forms take these float32 values, so they blend with the same weights
    on every backend. Pixel ``p`` lies in interpolation cell ``lo + 1``
    = ``(p + tile // 2) // tile`` of ``grid + 1`` (checked here): the
    cells are whole tiles of the plane shifted by half a tile."""
    f = np.arange(size, dtype=np.float32) / np.float32(tile) - np.float32(0.5)
    lo = np.floor(f).astype(np.int32)
    frac = f - lo.astype(np.float32)
    if not np.array_equal(lo + 1, (np.arange(size) + tile // 2) // tile):
        raise AssertionError(f"interpolation cells of {size} / {tile} are not half-tile shifts")
    return np.clip(lo, 0, grid - 1), np.clip(lo + 1, 0, grid - 1), frac


def _clahe_planes_sort(planes: jnp.ndarray, grid: int, clip_limit: float) -> jnp.ndarray:
    """(N, H, W) uint8 planes → CLAHE'd uint8 planes, the "sort" form:
    tile histograms fold into the batch axis of :func:`_plane_cdf`, the
    interpolation is 4 image-sized gathers from the (grid, grid, 256) LUT
    lattice."""
    n, h, w = planes.shape
    g = clahe_geometry(h, w, grid, clip_limit)
    hp, wp, th, tw = g["hp"], g["wp"], g["tile_h"], g["tile_w"]
    x = _reflect_to_grid(planes, hp, wp)
    u = x.astype(jnp.int32)
    tiles = u.reshape(n, grid, th, grid, tw).transpose(0, 1, 3, 2, 4)
    cdf = _plane_cdf(tiles.reshape(n * grid * grid, th * tw))
    luts = _clahe_luts(jnp.diff(cdf, axis=1, prepend=0.0), th * tw, g["clip_abs"])
    luts = luts.reshape(n, grid, grid, 256)
    ty0, ty1, fy = _corners(hp, th, grid)
    tx0, tx1, fx = _corners(wp, tw, grid)
    bidx = jnp.arange(n)[:, None, None]

    def look(ty, tx):
        # (N, Hp, Wp) gather: LUT of tile (ty[y], tx[x]) at value u.
        return luts[bidx, ty[None, :, None], tx[None, None, :],
                    u].astype(jnp.float32)

    fy_ = jnp.asarray(fy)[None, :, None]
    fx_ = jnp.asarray(fx)[None, None, :]
    out = pk.blend_rounded([(1 - fy_) * (1 - fx_) * look(ty0, tx0),
                            (1 - fy_) * fx_ * look(ty0, tx1),
                            fy_ * (1 - fx_) * look(ty1, tx0),
                            fy_ * fx_ * look(ty1, tx1)],
                           jax.default_backend() == "cpu")
    out = jnp.clip(jnp.round(out), 0.0, 255.0).astype(jnp.uint8)
    return out[:, :h, :w]


def _cell_weights(frac: np.ndarray, tile: int, grid: int, padded: int) -> tuple:
    """An axis's blend weights ``(1 - frac, frac)`` laid out as the
    interpolation cells are: ``(grid + 1, padded)`` float32, zero in the
    half tiles beyond the plane and in a cell's filler."""
    def cells(wgt):
        wgt = np.pad(wgt.astype(np.float32), (tile // 2, tile - tile // 2))
        return np.pad(wgt.reshape(grid + 1, tile), ((0, 0), (0, padded - tile)))
    return cells(np.float32(1) - frac), cells(frac)


def _clahe_planes_pallas(planes: jnp.ndarray, grid: int, clip_limit: float,
                         interpret: bool) -> jnp.ndarray:
    """The "pallas" form of :func:`_clahe_planes_sort`, bit-identical to it.
    ``clahe_hist``: the plane cut into its ``grid x grid`` tiles, each
    padded to whole vregs, counted by ``tile_hist_pallas``. ``clahe_lut``: cv2's clip and redistribution on
    the (N, grid, grid, 256) counts, then for each of the ``(grid + 1)^2``
    interpolation cells its four corner tables packed a byte each.
    ``clahe_apply``: the plane shifted by half a tile, so that the cells
    are whole tiles of it, each mapped and blended by ``lut_apply_pallas``."""
    n, h, w = planes.shape
    g = clahe_geometry(h, w, grid, clip_limit)
    hp, wp, th, tw = g["hp"], g["wp"], g["tile_h"], g["tile_w"]
    rows, lanes = pk.tile_pad(th, tw)
    x = _reflect_to_grid(planes, hp, wp)
    with jax.named_scope("clahe_hist"):
        hist = pk.tile_hist_pallas(pk.to_tiles(x, grid, grid), grid, th * tw,
                                   "clahe_hist", interpret)
    with jax.named_scope("clahe_lut"):
        luts = _clahe_luts(hist.reshape(n * grid * grid, 256).astype(jnp.float32),
                           th * tw, g["clip_abs"]).reshape(n, grid, grid, 256)

        def either_side(t, axis):
            """Cell k of ``grid + 1`` lies between tiles k - 1 and k,
            clamped to the grid: both, as slices (no gather)."""
            return (jnp.concatenate([jax.lax.slice_in_dim(t, 0, 1, axis=axis), t], axis),
                    jnp.concatenate([t, jax.lax.slice_in_dim(t, grid - 1, grid, axis=axis)], axis))

        packed = pk.pack_luts(*(corner for side in either_side(luts, 1)
                                for corner in either_side(side, 2)))
    with jax.named_scope("clahe_apply"):
        _, _, fy = _corners(hp, th, grid)
        _, _, fx = _corners(wp, tw, grid)
        wy = [jnp.broadcast_to(jnp.asarray(a)[:, :, None], (grid + 1, rows, pk._LANE))
              for a in _cell_weights(fy, th, grid, rows)]
        wx = [jnp.asarray(a).reshape(1, (grid + 1) * lanes)
              for a in _cell_weights(fx, tw, grid, lanes)]
        top, left = th // 2, tw // 2
        shifted = jnp.pad(x, ((0, 0), (top, th - top), (left, tw - left)))
        out = pk.lut_apply_pallas(pk.to_tiles(shifted, grid + 1, grid + 1),
                                  packed, "clahe_apply", (*wy, *wx), interpret)
        out = pk.from_tiles(out, grid + 1, grid + 1, th, tw)
        return out[:, top:top + h, left:left + w]


def clahe_plan(shape, clip_limit: float = 2.0, grid: int = 8,
               on_gray: bool = False, interpret: bool = False) -> dict:
    """What the "pallas" form resolves to for an NHWC batch of ``shape``,
    as data (``Filter.kernel_plan`` → ``Engine.kernel_plan`` → the bucket
    row's ``kernel`` block): the step's named ``pallas_call``s in order,
    ``kernel`` the one that takes most of the step, and the tiling both
    walk. A tile of ``tile_h x tile_w`` pixels is walked as ``tile_h_pad x
    tile_w_pad`` (whole vregs); ``clahe_hist`` walks ``grid^2`` of them a
    plane, ``clahe_apply`` ``cells^2`` (the half-tile shift)."""
    b, h, w, c = (int(v) for v in shape)
    g = clahe_geometry(h, w, grid, clip_limit)
    rows, lanes = pk.tile_pad(g["tile_h"], g["tile_w"])
    planes = b * (1 if on_gray else c)
    window = rows * (grid + 1) * lanes          # clahe_apply's uint8 window, in and out each
    return {
        "kernel": "clahe_hist",
        "kernels": ["clahe_hist", "clahe_apply"],
        "impl": "pallas",
        "grid": grid,
        "cells": grid + 1,
        "bins": pk.HIST_BINS,
        "planes": planes,
        "tile_h": g["tile_h"],
        "tile_w": g["tile_w"],
        "tile_h_pad": rows,
        "tile_w_pad": lanes,
        "clip_abs": g["clip_abs"],
        "hist_grid": [planes, grid],
        "apply_grid": [planes, grid + 1],
        "hist_form": "bitplane",
        "hist_ops_per_pixel": pk.hist_ops_per_pixel(rows, lanes),
        # clahe_hist's: a vreg of word counts a bin
        "vmem_scratch_bytes": pk.HIST_BINS * pk._SUBLANE * pk._LANE * 4,
        "vmem_window_bytes": window,
        # None: Mosaic's default scoped-VMEM limit (16 MiB)
        "vmem_limit_bytes": pk._block_vmem_limit(2 * window, interpret),
        "io_dtype": "uint8",
        "compute_dtype": "int32",
    }


@register_filter("clahe")
def clahe(clip_limit: float = 2.0, grid: int = 8, on_gray: bool = False,
          impl: Optional[str] = None, interpret: Optional[bool] = None) -> Filter:
    """Contrast-Limited Adaptive Histogram Equalization — cv2.createCLAHE
    semantics (the standard low-light/contrast video enhancement).

    Where ``equalize`` uses one whole-frame histogram, CLAHE builds a
    ``grid``×``grid`` lattice of tile histograms, clips each at
    ``clip_limit``×(uniform level) to bound noise amplification,
    redistributes the clipped mass, and bilinearly interpolates the four
    neighboring tile LUTs at every pixel.

    ``impl``: "sort" (sort + searchsorted histograms, four image-sized
    gathers) or "pallas" (counted histograms, lane-gather lookups: the
    module docstring), bit-identical; None = the backend's measured winner
    (``MEASURED_DEFAULTS["clahe"]``); ``clahe_pallas`` pins the second.
    Non-divisible geometries reflect-pad right/bottom (what cv2 does) and
    crop. ``on_gray=False`` applies per RGB channel; ``on_gray=True`` is
    the cv2 golden-test mode (single luma plane, broadcast). halo=None:
    tiles are frame-global structure — the engine replicates H rather than
    spatially sharding.
    """
    if grid < 1:
        raise ValueError(f"grid must be >= 1, got {grid}")
    if clip_limit <= 0:
        raise ValueError(f"clip_limit must be > 0, got {clip_limit}")
    impl = _resolve_impl(impl, "clahe")

    def apply_planes(planes: jnp.ndarray) -> jnp.ndarray:
        if impl == "sort":
            return _clahe_planes_sort(planes, grid, clip_limit)
        return _clahe_planes_pallas(planes, grid, clip_limit,
                                    pk._auto_interpret(interpret))

    def fn(batch: jnp.ndarray) -> jnp.ndarray:
        u8 = batch.dtype == jnp.uint8
        x = to_uint8(batch)
        out = _dispatch_planes(x, on_gray, apply_planes)
        return out if u8 else to_float(out, batch.dtype)

    label = "clahe" if impl == "sort" else "clahe_pallas"
    return stateless(
        f"{label}(c={clip_limit},g={grid})", fn, uint8_ok=True, halo=None,
        kernel_plan=None if impl == "sort" else (
            lambda shape: clahe_plan(shape, clip_limit, grid, on_gray,
                                     pk._auto_interpret(interpret))))


@register_filter("clahe_pallas")
def clahe_pallas(clip_limit: float = 2.0, grid: int = 8, on_gray: bool = False,
                 interpret: Optional[bool] = None) -> Filter:
    """``clahe(impl="pallas")`` under a name of its own, so that a
    configuration can name the counted form whatever backend it meets
    (the benchmark's ``clahe_1080p``)."""
    return clahe(clip_limit, grid, on_gray, impl="pallas", interpret=interpret)
