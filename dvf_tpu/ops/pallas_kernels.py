"""Pallas TPU kernels for the hot stencil ops.

Three stencil kernels, each with a jnp golden it must match:

- **bilateral** — XLA already fuses the unrolled shifted-window bilateral
  (:mod:`dvf_tpu.ops.bilateral`) well; this kernel exists for the cases
  where hand control wins: one HBM pass per tile with all (2r+1)² taps,
  the numerator/denominator accumulators, and the exp() range weights held
  in VMEM/registers — no intermediate HBM traffic at 1080p, where the jnp
  version's 25 shifted views can spill.
- **fused sobel+bilateral** — the whole BASELINE configs[2] chain in one
  VMEM residency: gray → Sobel magnitude → bilateral, no HBM round-trip
  for the intermediate edge map. Exploits two identities: the chain's
  bilateral input is grayscale broadcast ×3, so color distance collapses
  to 3·Δ² and all accumulation is single-channel; and Sobel *magnitude*
  commutes with reflect-101 padding (the derivative antisymmetrizes under
  reflection, |·| restores it), so computing Sobel inside the halo'd tile
  reproduces the unfused chain's borders exactly.
- **flow bilinear-warp** (:func:`warp_bounded_pallas`) — backward warp as
  (2R+2)² statically-unrolled hat-weighted shifted-window sums instead of
  the 4 dynamic gathers in :func:`dvf_tpu.ops.flow.bilinear_sample`; TPU
  has no fast vector gather, while bounded-displacement warps are pure VPU
  work. Since PR 51 it walks its tile in register-sized strips
  (:func:`_warp_kernel`; the tiling is :func:`warp_plan`'s, one form for
  the flow step's final warp and its nine inner warps).

Layout choices (see /opt/skills/guides/pallas_guide.md):
- frames are transposed NHWC→NCHW before the kernel so W (1920 at 1080p)
  rides the lane axis; C=3 would waste 125/128 lanes;
- grid = (batch, H tiles); each step DMAs a (C, tile_h + 2r, W + 2r) slab
  from HBM (kept in ANY space) into a VMEM scratch, computes the tile's
  core rows, and writes a (C, tile_h, W) output block;
- tile_h is 8-row aligned (or whole-H): Mosaic requires output blocks
  whose second-to-last dim is a multiple of the f32 sublane tile — see
  :func:`_pick_tile_h`, which pads H when no aligned divisor exists;
- all window shifts are static python-int slices — fully unrolled at trace
  time, no data-dependent control flow; the two kernels that walk their
  tile in strips (the fused Sobel+bilateral, PR 46; the warp, PR 51) loop
  over the strips and unroll a strip's taps;
- accumulation in float32 regardless of I/O dtype.

The jnp implementations are the numerics goldens; tests compare in
interpret mode (CPU) and the benchmark table compares wall time on device.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dvf_tpu.api.filter import Filter, stateless
from dvf_tpu.ops.registry import register_filter


def _auto_interpret(interpret):
    """None → compiled on TPU, interpret mode elsewhere (CPU tests)."""
    if interpret is None:
        return jax.default_backend() not in ("tpu",)
    return interpret


_TILE_TARGET = 32  # rows per program; multiple of the f32 sublane tile (8)
_SUBLANE = 8       # f32 sublane tile: DMA slice rows must be multiples
_LANE = 128        # lane tile: DMA slice cols must be multiples (or full)


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def _slab_rows(th: int, halo2: int) -> int:
    """DMA slab row extent: tile + two-sided halo, rounded up to the
    sublane tile (Mosaic rejects unaligned ``tpu.memref_slice`` extents;
    the spare rows are DMA'd but never read)."""
    return _round_up(th + halo2, _SUBLANE)


def _extra_rows(h: int, h_pad: int, th: int, halo2: int) -> int:
    """Bottom padding beyond the halo so the LAST grid step's slab
    ``[h_pad - th, h_pad - th + slab_rows)`` is in-bounds."""
    return (h_pad - th + _slab_rows(th, halo2)) - (h + halo2)


def _pick_tile_h(h: int, target: int = _TILE_TARGET) -> tuple:
    """``(tile_h, padded_h)`` for the TPU grid over H.

    Mosaic requires an output block's second-to-last dim to be a multiple
    of the 8-row f32 sublane tile — or the whole dimension.  (The round-3
    on-chip A/Bs all died on exactly this: a 15-row tile over H=1080.)
    Preference order: the largest 8-aligned divisor of ``h`` that is
    ≤ ``target`` (no padding); a short image as one whole-H tile (legal at
    any h); else — h > target with no 8-aligned divisor, e.g. 540 = 4·135
    — pad H up to a tile multiple and let the caller slice the pad off.
    Tile choice never affects numerics, only the grid.
    """
    if h <= target:
        return h, h
    for th in range(target - target % 8, 7, -8):
        if h % th == 0:
            return th, h
    th = target - target % 8 or 8
    return th, ((h + th - 1) // th) * th


def _resolve_tile_h(h: int, tile_h: Optional[int],
                    target: int = _TILE_TARGET,
                    compiled: bool = True) -> tuple:
    """Caller-pinned tile (must divide h — the pre-round-4 contract) or
    the auto ``(tile_h, padded_h)`` pick aiming at ``target`` rows.

    A ``compiled`` (non-interpret) pin must also satisfy Mosaic's 8-row
    sublane rule — rejecting it here with a clear message beats the
    opaque lowering error the same pin produced in round 3 (tile 15 over
    H=1080). Interpret mode has no such constraint, so any divisor stays
    legal there."""
    if tile_h is not None:
        if h % tile_h != 0:
            raise ValueError(f"tile_h {tile_h} must divide H {h}")
        if compiled and tile_h != h and tile_h % _SUBLANE != 0:
            raise ValueError(
                f"compiled TPU kernels need tile_h to be a multiple of "
                f"{_SUBLANE} or the whole H; got {tile_h} (H={h})")
        return tile_h, h
    return _pick_tile_h(h, target)


_VMEM_LIMIT_RAISED = 64 * 1024 * 1024
_TAPS_UNDER_DEFAULT_VMEM = 25   # a 5x5 window: the largest served before PR 43


def _stencil_vmem_limit(tile_h: Optional[int], interpret: bool,
                        taps: int) -> Optional[int]:
    """Scoped-VMEM limit for a stencil kernel, None = Mosaic's default
    (16 MiB). The unrolled taps' temporaries grow with the tile and with
    the window: bilateral at a pinned tile 40 over 1080p needs 16.55 MB on
    the v5e (RESOURCE_EXHAUSTED in PR 21's chip run), and the fused
    Sobel+bilateral at d = 9 (81 taps) needed 26.33 MB at the AUTO tile of
    24 rows (Mosaic for a described v5e, PR 43: the unpinned kernel did
    not compile) until PR 46 ran its taps in strips (3.25 MB now:
    tests/test_tpu_compile.py compiles it under the default). So a
    caller-pinned ``tile_h`` (chip_smoke.py's tile pins) or a window over
    5x5 taps gets 64 MiB (the chip has 128 MiB of VMEM),
    which costs nothing where it goes unused; the auto-picked tile at up
    to 25 taps compiles under the default and keeps it."""
    if interpret or (tile_h is None and taps <= _TAPS_UNDER_DEFAULT_VMEM):
        return None
    return _VMEM_LIMIT_RAISED


def _vmem_params(limit: Optional[int]):
    """Compiler params carrying a :func:`_stencil_vmem_limit`."""
    return None if limit is None else pltpu.CompilerParams(
        vmem_limit_bytes=limit)


def _pad_rows(x: jnp.ndarray, extra: int) -> jnp.ndarray:
    """Append ``extra`` edge-value rows to NCHW ``x`` (dim 2) so the grid
    tiles exactly and every DMA slab is in-bounds; the values never reach
    a valid output row (each output row y reads input rows y..y+2r, all
    < h+2r) and the pad is sliced off after the kernel."""
    if extra == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, extra), (0, 0)), mode="edge")


def _pad_cols(x: jnp.ndarray, extra: int) -> jnp.ndarray:
    """Append ``extra`` edge-value cols to NCHW ``x`` (dim 3): the DMA
    slab copies the input's FULL width, so the width itself must be
    lane-aligned — Mosaic rejects ``tpu.memref_slice`` extents that are
    not multiples of the (8, 128) tile (the round-4 on-chip failure mode
    after block alignment was fixed). Valid output col x reads cols
    x..x+2r < w+2r, never the pad."""
    if extra == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, extra)), mode="edge")


def _bilateral_kernel(tile_h: int, r: int, w: int, c: int, sigma_color: float, sigma_space: float):
    d = 2 * r + 1
    inv2sc = 1.0 / (2.0 * sigma_color * sigma_color)
    spatial = [
        [math.exp(-(dy * dy + dx * dx) / (2.0 * sigma_space * sigma_space))
         for dx in range(-r, r + 1)]
        for dy in range(-r, r + 1)
    ]

    slab = _slab_rows(tile_h, 2 * r)

    def kernel(in_ref, out_ref, scratch, sem):
        b = pl.program_id(0)
        i = pl.program_id(1)
        copy = pltpu.make_async_copy(
            in_ref.at[b, :, pl.ds(i * tile_h, slab), :],
            scratch,
            sem,
        )
        copy.start()
        copy.wait()
        tile = scratch[...].astype(jnp.float32)
        center = tile[:, r : r + tile_h, r : r + w]
        num = jnp.zeros((c, tile_h, w), jnp.float32)
        den = jnp.zeros((1, tile_h, w), jnp.float32)
        for dy in range(d):
            for dx in range(d):
                sh = tile[:, dy : dy + tile_h, dx : dx + w]
                diff = sh - center
                dist2 = jnp.sum(diff * diff, axis=0, keepdims=True)
                wgt = spatial[dy][dx] * jnp.exp(-dist2 * inv2sc)
                num = num + wgt * sh
                den = den + wgt
        out_ref[...] = (num / den)[None].astype(out_ref.dtype)

    return kernel


def bilateral_nhwc_pallas(
    batch: jnp.ndarray,
    d: int = 5,
    sigma_color: float = 0.1,
    sigma_space: float = 2.0,
    tile_h: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Pallas bilateral over float NHWC in [0,1]; numerics match
    ops.bilateral.bilateral_nhwc (same reflect borders and weights)."""
    if d % 2 != 1:
        raise ValueError(f"window d must be odd, got {d}")
    r = d // 2
    b, h, w, c = batch.shape
    th, h_pad = _resolve_tile_h(h, tile_h, compiled=not interpret)
    w_al = _round_up(w + 2 * r, _LANE)

    x = jnp.transpose(batch, (0, 3, 1, 2))  # NCHW: W on lanes
    x = jnp.pad(x, ((0, 0), (0, 0), (r, r), (r, r)), mode="reflect")
    x = _pad_rows(x, _extra_rows(h, h_pad, th, 2 * r))
    x = _pad_cols(x, w_al - (w + 2 * r))

    kernel = _bilateral_kernel(th, r, w, c, sigma_color, sigma_space)
    out = pl.pallas_call(
        kernel,
        grid=(b, h_pad // th),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, c, th, w), lambda bb, ii: (bb, 0, ii, 0)),
        out_shape=jax.ShapeDtypeStruct((b, c, h_pad, w), batch.dtype),
        scratch_shapes=[
            pltpu.VMEM((c, _slab_rows(th, 2 * r), w_al), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=_vmem_params(
            _stencil_vmem_limit(tile_h, interpret, d * d)),
        interpret=interpret,
        name="bilateral",
    )(x)
    return jnp.transpose(out[:, :, :h, :], (0, 2, 3, 1))


# ---------------------------------------------------------------------------
# Bounded-displacement bilinear warp (the flow gather, gather-free)
# ---------------------------------------------------------------------------


_WARP_VMEM_BUDGET = 14 * 1024 * 1024   # of Mosaic's default 16 MiB of scoped VMEM
_WARP_STEP_ROWS = 8      # a grid step's fixed cost (slab wait, halo copies) in rows of taps


def _warp_vmem_bytes(th: int, halo: int, c: int, side: int, w_out: int, w_al: int) -> tuple:
    """``(slab scratch, shifted copies, pipelined blocks)`` in bytes, of a
    grid step over a tile of ``th`` rows."""
    slab = th + halo
    return (c * slab * w_al * 4, side * c * slab * w_out * 4,
            2 * (2 + c) * th * w_out * 4)       # flow in, planes out, double-buffered


def _warp_tile(h: int, tile_h: Optional[int], fits) -> tuple:
    """``(tile_h, padded_h)`` of the warp's grid over H; the tile a
    multiple of the sublane tile always (a strip is 8 rows). Unpinned, the
    tile that costs least over the frame among those whose grid step
    ``fits`` the VMEM budget, a grid step counted as its rows plus
    ``_WARP_STEP_ROWS``. At flow_720p's shapes: 720 x 1280 x 3 at +-4 px ->
    48 x 15; 360 x 640 x 5 at +-2 -> 72 x 5; 180 x 320 -> one tile of 184;
    90 x 160 -> one of 96."""
    if tile_h is not None:
        if h % tile_h:
            raise ValueError(f"tile_h {tile_h} must divide H {h}")
        if tile_h % _SUBLANE and tile_h != h:
            raise ValueError(f"the warp's tile_h must be a multiple of "
                             f"{_SUBLANE} or the whole H; got {tile_h} (H={h})")
        th = _round_up(tile_h, _SUBLANE)
        return th, h // tile_h * th
    tiles = [t for t in range(_SUBLANE, _round_up(h, _SUBLANE) + 1, _SUBLANE)
             if t == _SUBLANE or fits(t)]
    th = min(tiles, key=lambda t: (-(-h // t) * (t + _WARP_STEP_ROWS), -t))
    return th, -(-h // th) * th


def warp_plan(shape, max_disp: int = 4, tile_h: Optional[int] = None,
              interpret: bool = False) -> dict:
    """The tiling :func:`warp_bounded_pallas` resolves to for an NHWC image
    of ``shape``, as data: the wrapper builds its ``pallas_call`` from this
    dict, and ``flow_warp`` lists one for each distinct call of its step
    (``ops/flow.py::flow_warp`` -> ``Filter.kernel_plan`` -> the bucket
    row's ``kernel`` block). Everything follows from what the call observes
    in its input: ``planes`` (c), ``max_disp`` (R), H and W."""
    b, h, w, c = (int(v) for v in shape)
    R = int(max_disp)
    if R < 1:
        raise ValueError("max_disp must be >= 1")
    side = 2 * R + 2                    # taps an axis: dy, dx in [-R, R + 1]
    halo = _round_up(side - 1, _SUBLANE)
    w_out, w_al = _round_up(w, _LANE), _round_up(w + side - 1, _LANE)

    def vmem(th):
        return _warp_vmem_bytes(th, halo, c, side, w_out, w_al)

    th, h_pad = _warp_tile(h, tile_h, lambda t: sum(vmem(t)) <= _WARP_VMEM_BUDGET)
    scratch, shifted, blocks = vmem(th)
    unaligned = range(1, min(side, _SUBLANE))
    return {
        "planes": c,
        "max_disp": R,
        "taps": side * side,
        "tile_h": th,
        "h_pad": h_pad,
        "grid": [b, h_pad // th],
        "slab_rows": th + halo,         # rows DMA'd a grid step (tile + halo, 8-aligned)
        "w_aligned": w_al,              # columns DMA'd (W + halo, 128-aligned)
        "w_out": w_out,                 # columns of the flow, the copies and the result
        # rows x lanes of the tile whose taps run at a time: one vreg an array
        "strip": [_SUBLANE, _LANE],
        # the two row offsets of a window taken from its aligned row tiles in
        # registers, not by a load (:func:`_warp_kernel`)
        "rows_in_registers": sorted({unaligned[len(unaligned) // 4],
                                     unaligned[3 * len(unaligned) // 4]}),
        "vmem_scratch_bytes": scratch,
        # the slab's `side` column-shifted copies, a plane a lane tile
        "vmem_shifted_bytes": shifted,
        # None: Mosaic's default scoped-VMEM limit (16 MiB); raised where a
        # pinned tile, or a frame too wide for a tile of 8 rows, outgrows it
        "vmem_limit_bytes": None if interpret or (
            scratch + shifted + blocks <= _WARP_VMEM_BUDGET) else _VMEM_LIMIT_RAISED,
        "compute_dtype": "float32",
    }


def _warp_kernel(tile_h: int, R: int, w_out: int, c: int, rows_in_registers):
    """out(y,x) = sum_dy sum_dx relu(1-|fy-dy|) relu(1-|fx-dx|) img(y+dy, x+dx)
    over dy, dx in [-R, R+1]: exactly bilinear interpolation at the clipped
    flow, because the hat weights are nonzero only at floor(f) and
    floor(f)+1. Every shift is static; no gather anywhere.

    A grid step walks its ``(c, tile_h, w_out)`` tile in register-sized
    strips (8 rows x 128 lanes, a vreg a plane; PR 51). As whole-tile
    expressions the accumulator alone was 60 of the file's 64
    vregs at 16 x 1280 x 3 and every tap went through VMEM: 22.7K of 24.2K
    loads and stores a grid step were spills. Now, once the slab has
    landed, its ``2R+2`` column-shifted copies go to the ``shifted`` scratch
    a row tile at a time (a lane rotate a copy, paid once a grid step, not
    once a tap), each lane tile of each plane an array of its own,
    ``[rows, 128]``: there 8 rows at ANY row offset are one ``vld``. Then
    strip by strip: the flow clipped and the ``2R+2`` wx and wy hats once; column
    shift by column shift ``col = sum_dy wy * rows`` and ``acc += wx * col``
    in registers, every plane at once (the arrays are ``[c, 8, 128]``: c
    vregs each, which Mosaic unrolls; written a plane at a time the body is
    c times the equations, and the serving host took 9 s more to trace and
    lower the step's kernels beside a busy generator: PR 51's chip runs);
    one store. The sum is the product form's, re-associated. The v5e issues
    one unaligned ``vld`` a bundle (aligned ones pair up) and the loop has
    VALU slots to spare, so ``rows_in_registers`` of a window's row offsets
    are merged from its two aligned row tiles by a sublane select and a
    rotate instead (271 -> 233 bundles a strip at c 3, R 4). ``jax.lax``
    ops throughout: the step traces this body ten times, twice over."""
    side = 2 * R + 2
    halo = _round_up(side - 1, _SUBLANE)
    slab = tile_h + halo
    lane_tiles = w_out // _LANE

    def hat(f, d):
        return lax.max(lax.sub(1.0, lax.abs(lax.sub(f, float(d)))), 0.0)

    def kernel(img_ref, flow_ref, out_ref, scratch, shifted, sem):
        b = pl.program_id(0)
        i = pl.program_id(1)
        copy = pltpu.make_async_copy(
            img_ref.at[b, :, pl.ds(i * tile_h, slab), :], scratch, sem)
        copy.start()
        copy.wait()
        w_al = scratch.shape[-1]

        def shift_row_tile(t):
            rows = pl.ds(_aligned(t, _SUBLANE), _SUBLANE)
            x = scratch[:, rows, :]                 # (c, 8, w_al)
            for kx in range(side):
                y = pltpu.roll(x, w_al - kx, 2) if kx else x
                for q in range(lane_tiles):
                    shifted[kx, q, :, rows, :] = y[:, :, q * _LANE:(q + 1) * _LANE]

        _loop(slab // _SUBLANE, shift_row_tile)

        def strip_at(s):
            q = s % lane_tiles
            row0 = _aligned(s // lane_tiles, _SUBLANE)
            rows = pl.ds(row0, _SUBLANE)
            lanes = pl.ds(_aligned(q, _LANE), _LANE)
            planes = (c, _SUBLANE, _LANE)

            def hats(f):
                f = lax.broadcast_in_dim(lax.clamp(-float(R), f, float(R)), planes, (1, 2))
                return [hat(f, k - R) for k in range(side)]

            wx, wy = hats(flow_ref[0, 0, rows, lanes]), hats(flow_ref[0, 1, rows, lanes])
            sublane = lax.broadcasted_iota(jnp.int32, planes, 1)
            wraps = {ky: lax.ge(sublane, ky) for ky in rows_in_registers}
            acc = None
            for kx in range(side):
                win = shifted.at[kx, q, :, pl.ds(row0, _SUBLANE + halo), :]
                tiles = [win[:, pl.ds(0, _SUBLANE), :],
                         win[:, pl.ds(_SUBLANE, _SUBLANE), :]]

                def window_rows(ky):
                    """Rows [ky, ky + 8) of the window, every plane's."""
                    if ky in (0, _SUBLANE):
                        return tiles[ky // _SUBLANE]
                    if ky in wraps:     # row i on sublane (i + ky) % 8, then home
                        return pltpu.roll(lax.select(wraps[ky], *tiles),
                                          _SUBLANE - ky, 1)
                    return win[:, pl.ds(ky, _SUBLANE), :]

                col = None
                for ky in range(side):
                    term = lax.mul(wy[ky], window_rows(ky))
                    col = term if col is None else lax.add(col, term)
                term = lax.mul(wx[kx], col)
                acc = term if acc is None else lax.add(acc, term)
            out_ref[0, :, rows, lanes] = acc.astype(out_ref.dtype)

        _loop(tile_h // _SUBLANE * lane_tiles, strip_at)

    return kernel


def warp_bounded_pallas(
    img: jnp.ndarray,
    flow: jnp.ndarray,
    max_disp: int = 4,
    tile_h: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Backward-warp ``img`` (B,H,W,C) by ``flow`` (B,H,W,2; [...,0]=dx)
    with displacements clipped to ±``max_disp`` px.

    Numerics match :func:`dvf_tpu.ops.flow.warp_by_flow` on the clipped
    flow (border behavior included: edge padding reproduces the golden's
    coordinate clamping for any |f| ≤ max_disp). The (2·max_disp+2)² hat-
    weighted static shifts trade FLOPs for the dynamic gathers TPUs hate —
    worth it while max_disp stays small (Farneback flows at video rates
    are a few px). ``interpret=None`` auto-selects: compiled on TPU,
    interpret mode elsewhere. The tiling is :func:`warp_plan`'s. A ``jit``
    of its own: the flow step calls it ten times at four distinct shapes
    and the Engine traces a step twice, and the kernel's unrolled taps
    (hundreds of loads, each an indexer) are then traced once a shape.
    """
    return _warp_bounded(img, flow, int(max_disp), tile_h,
                         _auto_interpret(interpret))


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _warp_bounded(img, flow, max_disp, tile_h, interpret):
    plan = warp_plan(img.shape, max_disp, tile_h, interpret)
    R, side = plan["max_disp"], 2 * plan["max_disp"] + 2
    b, h, w, c = img.shape
    th, h_pad, w_al, w_out = (plan[k] for k in ("tile_h", "h_pad", "w_aligned", "w_out"))
    slab = plan["slab_rows"]

    # Taps dy, dx in [-R, R+1]: R edge rows over the image and R+1 under
    # it, then the filler that keeps the last slab in bounds and the width
    # lane-aligned (never read for a valid output: ``_pad_rows``).
    x = jnp.transpose(img, (0, 3, 1, 2))                    # (b,c,h,w)
    x = jnp.pad(x, ((0, 0), (0, 0), (R, R + 1), (R, R + 1)), mode="edge")
    x = _pad_rows(x, h_pad - th + slab - (h + side - 1))
    x = _pad_cols(x, w_al - (w + side - 1))
    fl = jnp.transpose(flow, (0, 3, 1, 2))                  # (b,2,h,w)
    fl = _pad_cols(_pad_rows(fl, h_pad - h), w_out - w)

    out = pl.pallas_call(
        _warp_kernel(th, R, w_out, c, plan["rows_in_registers"]),
        grid=tuple(plan["grid"]),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec((1, 2, th, w_out), lambda bb, ii: (bb, 0, ii, 0))],
        out_specs=pl.BlockSpec((1, c, th, w_out), lambda bb, ii: (bb, 0, ii, 0)),
        out_shape=jax.ShapeDtypeStruct((b, c, h_pad, w_out), img.dtype),
        scratch_shapes=[
            pltpu.VMEM((c, slab, w_al), jnp.float32),
            pltpu.VMEM((side, w_out // _LANE, c, slab, _LANE), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=_vmem_params(plan["vmem_limit_bytes"]),
        interpret=interpret,
        name="warp_bounded",
    )(x, fl)
    return jnp.transpose(out[:, :, :h, :w], (0, 2, 3, 1))


# ---------------------------------------------------------------------------
# Fused separable blur (both 1-D passes in one VMEM residency)
# ---------------------------------------------------------------------------


def _sep_blur_kernel(tile_h: int, rh: int, rw: int, w: int, kh_taps, kw_taps):
    slab = _slab_rows(tile_h, 2 * rh)

    def kernel(in_ref, out_ref, scratch, sem):
        b = pl.program_id(0)
        i = pl.program_id(1)
        copy = pltpu.make_async_copy(
            in_ref.at[b, :, pl.ds(i * tile_h, slab), :],
            scratch,
            sem,
        )
        copy.start()
        copy.wait()
        x = scratch[...].astype(jnp.float32)       # (c, th+2rh, w+2rw)
        # H pass on the slab, W extent kept: (c, th, w+2rw).
        acc = kh_taps[0] * x[:, 0:tile_h, :]
        for t in range(1, len(kh_taps)):
            acc = acc + kh_taps[t] * x[:, t : t + tile_h, :]
        # W pass: (c, th, w).
        out = kw_taps[0] * acc[:, :, 0:w]
        for t in range(1, len(kw_taps)):
            out = out + kw_taps[t] * acc[:, :, t : t + w]
        out_ref[...] = out[None].astype(out_ref.dtype)

    return kernel


def sep_blur_nhwc_pallas(
    batch: jnp.ndarray,
    kh,
    kw,
    tile_h: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Separable conv over float NHWC with both 1-D passes fused into one
    VMEM residency per tile — the intermediate (H-blurred) slab never
    touches HBM, unlike the two-pass jnp lowerings in ops.conv. Numerics
    match ``sep_conv2d`` (same reflect-101 borders, same tap order)."""
    import numpy as np

    kh_taps = [float(v) for v in np.asarray(kh)]
    kw_taps = [float(v) for v in np.asarray(kw)]
    rh, rw = len(kh_taps) // 2, len(kw_taps) // 2
    b, h, w, c = batch.shape
    th, h_pad = _resolve_tile_h(h, tile_h, compiled=not interpret)
    w_al = _round_up(w + 2 * rw, _LANE)

    x = jnp.transpose(batch, (0, 3, 1, 2))  # NCHW: W on lanes
    x = jnp.pad(x, ((0, 0), (0, 0), (rh, rh), (rw, rw)), mode="reflect")
    x = _pad_rows(x, _extra_rows(h, h_pad, th, 2 * rh))
    x = _pad_cols(x, w_al - (w + 2 * rw))

    kernel = _sep_blur_kernel(th, rh, rw, w, kh_taps, kw_taps)
    out = pl.pallas_call(
        kernel,
        grid=(b, h_pad // th),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, c, th, w), lambda bb, ii: (bb, 0, ii, 0)),
        out_shape=jax.ShapeDtypeStruct((b, c, h_pad, w), batch.dtype),
        scratch_shapes=[
            pltpu.VMEM((c, _slab_rows(th, 2 * rh), w_al), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=_vmem_params(_stencil_vmem_limit(
            tile_h, interpret, len(kh_taps) + len(kw_taps))),
        interpret=interpret,
        name="sep_blur",
    )(x)
    return jnp.transpose(out[:, :, :h, :], (0, 2, 3, 1))


@register_filter("gaussian_blur_pallas")
def gaussian_blur_pallas(
    ksize: int = 9,
    sigma: float = 0.0,
    tile_h: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Filter:
    """Pallas-backed separable Gaussian (A/B partner of ``gaussian_blur``;
    ops/registry.py MEASURED_DEFAULTS holds the per-backend winner).
    ``interpret=None`` → auto:
    compiled on TPU, interpret mode elsewhere."""
    from dvf_tpu.ops.conv import gaussian_kernel_1d

    kern = gaussian_kernel_1d(ksize, sigma)

    def fn(batch: jnp.ndarray) -> jnp.ndarray:
        return sep_blur_nhwc_pallas(batch, kern, kern, tile_h=tile_h,
                                    interpret=_auto_interpret(interpret))

    return stateless(f"gaussian_blur_pallas(k={ksize},s={sigma})", fn,
                     halo=ksize // 2)


# ---------------------------------------------------------------------------
# Fused Sobel + bilateral (BASELINE configs[2] as ONE kernel)
# ---------------------------------------------------------------------------

_LUMA = (0.299, 0.587, 0.114)  # Rec.601, matches utils.image.rgb_to_gray


_STRIP = (_SUBLANE, 3 * _LANE)   # rows x lanes: three f32 vregs an array


def _strip_shape(interpret: bool) -> Optional[tuple]:
    """``(rows, lanes)`` of the piece of a tile whose taps the fused kernel
    runs at a time, or None for the whole tile at once. Compiled: 8 x 384,
    so center, both accumulators and a tap's temporaries stay in the
    64-vreg file. Interpret mode has no register file: the whole-tile form
    lowers to ONE vectorised XLA loop fusion over the tile, while the
    strips' rolls and selects keep the CPU's fusion scalar (1.6 against
    13.7 frames/s at 1080p, d 5, here on the CPU, PR 46), so CPU users
    keep the whole-tile form. Tests pin the strips in interpret mode by
    patching this function."""
    return None if interpret else _STRIP


def _loop(trips: int, body) -> None:
    """``body(i)`` for i in [0, trips): a ``fori_loop`` (one copy of the body
    whatever the trips), the body itself where there is one trip."""
    if trips == 1:
        body(0)
    elif trips:
        jax.lax.fori_loop(0, trips, lambda i, carry: body(i), None)


def _aligned(i, step: int):
    """``i * step``, stated as a multiple of ``step`` where ``i`` is traced."""
    return i * step if isinstance(i, int) else pl.multiple_of(i * step, step)


def _shifted_shape(tile_h: int, r: int, w: int) -> tuple:
    """Shape of the column-shifted copies of the edge map the strips read:
    ``d`` shifts; the rows every 8-row strip of the tile reaches (the 2r
    under it, in whole sublane tiles); the columns the tile emits."""
    return (2 * r + 1, _round_up(tile_h, _SUBLANE) + _round_up(2 * r, _SUBLANE),
            _round_up(w, _LANE))


def _sobel_bilateral_kernel(tile_h: int, r: int, w: int,
                            sigma_color: float, sigma_space: float,
                            magnitude_scale: float,
                            strip_shape: Optional[tuple] = None):
    """The kernel over ONE float32 plane, the frame's luma: nothing after
    the luma sees a channel, so the slab a grid step DMAs and the block it
    stores are one plane each (``sobel_bilateral_plan``'s ``planes``).

    ``strip_shape`` None: every expression is over the whole
    ``(tile_h, w)`` tile, the form interpret mode runs
    (:func:`_strip_shape`). Compiled, that tile is 45 vregs an array at
    24 x 1920 where the file holds 64: center, num and den alone are 135,
    so every tap went through VMEM (44K spill loads and stores a grid
    step, 32.6K bundles; PR 46 has the count). Given ``(8, lanes)``, a
    grid step works in register-sized pieces instead. Sobel runs a row
    tile (8 slab rows) at a time and stores the edge map ``d`` times,
    shifted left by 0..d-1 columns (the ``shifted`` scratch): a lane shift
    is paid once a grid step, not once a tap. The taps then run strip by
    strip, center / num / den in registers from the first tap to the
    divide, on sublane-aligned loads only (``strip`` has how). Loops, not
    unrolled strips: a live range ends with its strip. On the v5e, 64
    frames of 1080p at d 9: 11.1K bundles a grid step and 27.5 ms a batch
    where the whole-tile form took 71.6 (scripts/stencil_kernel_probe.py,
    chip runs of PR 46)."""
    d = 2 * r + 1
    R = r + 1  # bilateral halo + 1 row/col of Sobel support
    # Range distance on a 3-channel broadcast-gray image is 3·Δ²gray.
    inv2sc = 3.0 / (2.0 * sigma_color * sigma_color)
    # exp(-x·inv2sc) = exp2(x·k2): the strips fold the constants into one
    # multiply (Mosaic's exp is 0 - x, two multiplies and the same vpow2).
    k2 = -inv2sc * math.log2(math.e)
    spatial = [
        [math.exp(-(dy * dy + dx * dx) / (2.0 * sigma_space * sigma_space))
         for dx in range(-r, r + 1)]
        for dy in range(-r, r + 1)
    ]

    slab = _slab_rows(tile_h, 2 * R)

    def whole_tile(scratch, out_ref):
        gray = scratch[...].astype(jnp.float32)   # (slab, w_al)
        # Sobel (ksize=3, conv taps [1,2,1]⊗[-1,0,1]) on the full slab:
        # valid region shrinks by 1 each side → (th+2r, w+2r).
        sx = gray[:-2, :] + 2.0 * gray[1:-1, :] + gray[2:, :]   # smooth V
        gx = sx[:, 2:] - sx[:, :-2]                              # deriv H
        sy = gray[:, :-2] + 2.0 * gray[:, 1:-1] + gray[:, 2:]    # smooth H
        gy = sy[2:, :] - sy[:-2, :]                              # deriv V
        mag = jnp.clip(jnp.sqrt(gx * gx + gy * gy) * magnitude_scale, 0.0, 1.0)
        # Bilateral on the single-channel edge map.
        center = mag[r: r + tile_h, r: r + w]
        num = jnp.zeros((tile_h, w), jnp.float32)
        den = jnp.zeros((tile_h, w), jnp.float32)
        for dy in range(d):
            for dx in range(d):
                sh = mag[dy: dy + tile_h, dx: dx + w]
                diff = sh - center
                wgt = spatial[dy][dx] * jnp.exp(-(diff * diff) * inv2sc)
                num = num + wgt * sh
                den = den + wgt
        out_ref[0] = (num / den).astype(out_ref.dtype)

    def in_strips(scratch, shifted, out_ref):
        strip_rows, strip_lanes = strip_shape
        _, rows_sh, w_sh = shifted.shape

        def left(x, k):
            """``x[:, j + k]`` at column j (the last k columns wrap)."""
            return pltpu.roll(x, x.shape[1] - k, 1)

        def sobel_tile(t):
            # Edge-map rows [8t, 8t+8) from slab rows [8t, 8t+10), in the
            # whole-tile form's order of additions. The slab's last row
            # tile has none under it: its own rows stand in, and the map's
            # rows that read them (>= tile_h + 2r) are never used.
            y0 = _aligned(t, _SUBLANE)
            y1 = pl.multiple_of(jnp.minimum(y0 + _SUBLANE, slab - _SUBLANE),
                                _SUBLANE)
            win = jnp.concatenate([scratch[pl.ds(y0, _SUBLANE), :],
                                   scratch[pl.ds(y1, _SUBLANE), :]], axis=0)
            g0 = win[:_SUBLANE]
            g1 = pltpu.roll(win, 2 * _SUBLANE - 1, 0)[:_SUBLANE]
            g2 = pltpu.roll(win, 2 * _SUBLANE - 2, 0)[:_SUBLANE]
            sx = g0 + 2.0 * g1 + g2                              # smooth V
            gx = left(sx, 2) - sx                                # deriv H
            sy0 = g0 + 2.0 * left(g0, 1) + left(g0, 2)           # smooth H
            sy2 = g2 + 2.0 * left(g2, 1) + left(g2, 2)
            gy = sy2 - sy0                                       # deriv V
            mag = jnp.clip(jnp.sqrt(gx * gx + gy * gy) * magnitude_scale,
                           0.0, 1.0)
            for dx in range(d):
                shifted[dx, pl.ds(y0, _SUBLANE), :] = \
                    (left(mag, dx) if dx else mag)[:, :w_sh]

        _loop(min(rows_sh, slab) // _SUBLANE, sobel_tile)

        def strip(row0, n_rows, c0, n_cols):
            """Output rows [row0, row0 + n_rows) x columns [c0, c0 + n_cols)
            of the tile: all d*d taps with the accumulators in registers.
            ``row0`` and ``c0`` are tile-aligned and may be traced; the
            extents are static.

            A tap's window, map rows [row0 + dy, +8), lies across two
            sublane tiles unless dy is a multiple of 8. It is not moved:
            the two tiles are merged by a sublane select (row i of the
            window lands on sublane (i + dy) mod 8), the center is rolled
            ONCE a dy to meet it, the d taps of that dy accumulate in the
            rolled frame, and their two sums are rolled back once."""
            # jax.lax, not jnp, from here to the store: a jnp operator traces
            # through a jit of its own, and with 81 taps of them the step
            # took 2.0 s to trace on the serving host where the whole-tile
            # form takes 0.4, twice a set-up (the Engine's eval_shape, then
            # its jit; chip runs of PR 46). The same primitives reach Mosaic.
            lanes = pl.ds(c0, _round_up(n_cols, _LANE))
            sublane = lax.broadcasted_iota(
                jnp.int32, (strip_rows, lanes.size), 0)
            tiles = [pl.ds(row0 + y, strip_rows)
                     for y in range(0, 2 * r + strip_rows, strip_rows)]
            wraps = {off: lax.ge(sublane, off) for off in range(1, strip_rows)}

            @functools.cache
            def rows(dx, tile):
                # One load a (copy, row tile) in the trace; the compiler
                # reloads where it likes (the schedule is the same as with
                # a load a tap, and the trace a third shorter).
                return shifted[dx, tiles[tile], lanes]

            def merged(dx, dy):
                """Map rows [row0 + dy, +8) at column shift dx, row i of
                them on sublane (i + dy) mod 8."""
                tile, off = divmod(dy, strip_rows)
                if not off:
                    return rows(dx, tile)
                return lax.select(wraps[off], rows(dx, tile), rows(dx, tile + 1))

            def to_frame(x, dy):
                """Row i of ``x`` to sublane (i + dy) mod 8."""
                return pltpu.roll(x, dy % strip_rows, 0) if dy % strip_rows else x

            center = to_frame(merged(r, r), -r)
            num = den = jnp.zeros_like(center)
            for dy in range(d):
                center_dy = to_frame(center, dy)
                num_dy = den_dy = jnp.zeros_like(center)
                for dx in range(d):
                    sh = merged(dx, dy)
                    diff = lax.sub(sh, center_dy)
                    wgt = lax.mul(lax.exp2(lax.mul(lax.mul(diff, diff), k2)),
                                  spatial[dy][dx])
                    num_dy = lax.add(num_dy, lax.mul(wgt, sh))
                    den_dy = lax.add(den_dy, wgt)
                num = lax.add(num, to_frame(num_dy, -dy))
                den = lax.add(den, to_frame(den_dy, -dy))
            out_ref[0, pl.ds(row0, n_rows), pl.ds(c0, n_cols)] = \
                lax.div(num, den)[:n_rows, :n_cols].astype(out_ref.dtype)

        def row_strip(row0, n_rows):
            _loop(w // strip_lanes, lambda c: strip(
                row0, n_rows, _aligned(c, strip_lanes), strip_lanes))
            if w % strip_lanes:
                strip(row0, n_rows, w - w % strip_lanes, w % strip_lanes)

        _loop(tile_h // strip_rows,
              lambda s: row_strip(_aligned(s, strip_rows), strip_rows))
        if tile_h % strip_rows:
            row_strip(tile_h - tile_h % strip_rows, tile_h % strip_rows)

    def kernel(in_ref, out_ref, scratch, *rest):
        *shifted, sem = rest        # the copies' scratch, where there are strips
        b = pl.program_id(0)
        i = pl.program_id(1)
        copy = pltpu.make_async_copy(
            in_ref.at[b, pl.ds(i * tile_h, slab), :],
            scratch,
            sem,
        )
        copy.start()
        copy.wait()
        if strip_shape is None:
            whole_tile(scratch, out_ref)
        else:
            in_strips(scratch, *shifted, out_ref)

    return kernel


def sobel_bilateral_plan(shape, d: int = 5, tile_h: Optional[int] = None,
                         interpret: bool = False) -> dict:
    """The tiling :func:`sobel_bilateral_nhwc_pallas` resolves to for an
    NHWC batch of ``shape``, as data. The kernel's wrapper takes its own
    numbers from this dict, so what a compiled step states about its
    kernel (``Filter.kernel_plan`` → ``Engine.kernel_plan`` → the bucket
    row's ``kernel`` block) is what ran, not a second copy of the
    arithmetic. ``planes`` is how many float32 planes a grid step DMAs and
    how many it stores: 1, the luma in and the edge map out, whatever the
    frame's channel count."""
    if d % 2 != 1:
        raise ValueError(f"window d must be odd, got {d}")
    b, h, w, _ = (int(v) for v in shape)
    R = d // 2 + 1  # bilateral halo + 1 row/col of Sobel support
    th, h_pad = _resolve_tile_h(h, tile_h, compiled=not interpret)
    slab, w_al = _slab_rows(th, 2 * R), _round_up(w + 2 * R, _LANE)
    strip = _strip_shape(interpret)
    return {
        "kernel": "sobel_bilateral",   # the pallas_call's name in a trace
        "impl": "pallas",
        "taps": d * d,
        "planes": 1,                   # planes DMA'd, and stored, a grid step
        "tile_h": th,
        "h_pad": h_pad,
        "grid": [b, h_pad // th],
        "slab_rows": slab,             # rows DMA'd a grid step (tile + halo, 8-aligned)
        "w_aligned": w_al,             # columns DMA'd (W + halo, 128-aligned)
        "vmem_scratch_bytes": slab * w_al * 4,
        # rows x lanes of the tile whose taps run at a time and the d
        # column-shifted copies of the edge map they read (PR 46; None and
        # 0 in interpret mode: the whole tile at once, no copies)
        "strip": list(strip) if strip else None,
        "vmem_shifted_bytes": math.prod(_shifted_shape(th, d // 2, w)) * 4 if strip else 0,
        # None: Mosaic's default scoped-VMEM limit (16 MiB)
        "vmem_limit_bytes": _stencil_vmem_limit(tile_h, interpret, d * d),
        "compute_dtype": "float32",
    }


def _reflect_fill(x: jnp.ndarray, axis: int, R: int, fill: int) -> jnp.ndarray:
    """``[reflected strip, x, reflected strip, zeros]`` along ``axis`` as
    ONE concatenate: the reflect-101 halo of ``R`` and ``fill`` filler
    entries behind it. The filler keeps the grid's last slab in bounds and
    the slab's width lane-aligned and never reaches a valid output
    (``_pad_rows`` has the argument), so its value is free."""
    n = x.shape[axis]
    if n <= R:
        raise ValueError(f"a reflected halo of {R} needs more than {R} "
                         f"entries along axis {axis}, got {n}")
    parts = [jnp.flip(jax.lax.slice_in_dim(x, 1, R + 1, axis=axis), axis), x,
             jnp.flip(jax.lax.slice_in_dim(x, n - 1 - R, n - 1, axis=axis), axis)]
    if fill:
        shape = x.shape[:axis] + (fill,) + x.shape[axis + 1:]
        parts.append(jnp.zeros(shape, x.dtype))
    return jnp.concatenate(parts, axis=axis)


def sobel_bilateral_nhwc_pallas(
    batch: jnp.ndarray,
    d: int = 5,
    sigma_color: float = 0.1,
    sigma_space: float = 2.0,
    magnitude_scale: float = 1.0,
    tile_h: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused Sobel→bilateral over float NHWC in [0,1]; numerics match
    FilterChain(sobel, bilateral) — ops.chains.sobel_bilateral.

    The stencil's data is ONE plane from the first pass to the last (PR
    44). ``stencil_prep`` takes the Rec.601 luma of the NHWC batch (luma is
    pointwise, so it commutes with every pad) and pads that plane, an axis
    a concatenate, to ``[b, h_pad + halo, w_aligned]``; ``stencil_kernel``
    DMAs one-plane slabs and stores the filtered edge map once (compiled,
    strip by strip of ``plan["strip"]``, the taps' accumulators in
    registers: PR 46, :func:`_sobel_bilateral_kernel`);
    ``stencil_finish`` slices the map and broadcasts it to the frame's
    channels, so behind it the Engine rounds one plane, not three equal
    ones. The ``jax.named_scope``s put each part into a compiled step's
    ``op_name``s (scripts/style_step_probe.py --model stencil)."""
    plan = sobel_bilateral_plan(batch.shape, d, tile_h, interpret)
    r = d // 2
    R = r + 1
    b, h, w, c = batch.shape
    th, h_pad, w_al = plan["tile_h"], plan["h_pad"], plan["w_aligned"]

    with jax.named_scope("stencil_prep"):
        # A reduction, not three channel slices: XLA then reads the uint8
        # frame straight into the one float32 plane (sliced, it first
        # writes all three).
        x = (batch * jnp.asarray(_LUMA, batch.dtype)).sum(-1)
        x = _reflect_fill(x, 1, R, _extra_rows(h, h_pad, th, 2 * R))
        x = _reflect_fill(x, 2, R, w_al - (w + 2 * R))

    kernel = _sobel_bilateral_kernel(th, r, w, sigma_color, sigma_space,
                                     magnitude_scale, plan["strip"])
    with jax.named_scope("stencil_kernel"):
        out = pl.pallas_call(
            kernel,
            grid=tuple(plan["grid"]),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, th, w), lambda bb, ii: (bb, ii, 0)),
            out_shape=jax.ShapeDtypeStruct((b, h_pad, w), batch.dtype),
            scratch_shapes=[
                pltpu.VMEM((plan["slab_rows"], w_al), jnp.float32),
                *([pltpu.VMEM(_shifted_shape(th, r, w), jnp.float32)]
                  if plan["strip"] else []),
                pltpu.SemaphoreType.DMA,
            ],
            compiler_params=_vmem_params(plan["vmem_limit_bytes"]),
            interpret=interpret,
            name=plan["kernel"],
        )(x)
    with jax.named_scope("stencil_finish"):
        return jax.lax.broadcast_in_dim(out[:, :h], (b, h, w, c), (0, 1, 2))


@register_filter("sobel_bilateral_pallas")
def sobel_bilateral_pallas(
    d: int = 5,
    sigma_color: float = 0.1,
    sigma_space: float = 2.0,
    magnitude_scale: float = 1.0,
    tile_h: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Filter:
    """Fused Pallas Sobel+bilateral chain (configs[2] in one kernel).
    ``interpret=None`` → auto: compiled on TPU, interpret mode elsewhere."""

    def fn(batch: jnp.ndarray) -> jnp.ndarray:
        return sobel_bilateral_nhwc_pallas(
            batch, d=d, sigma_color=sigma_color, sigma_space=sigma_space,
            magnitude_scale=magnitude_scale, tile_h=tile_h,
            interpret=_auto_interpret(interpret),
        )

    return stateless(
        f"sobel_bilateral_pallas(d={d})",
        fn,
        halo=d // 2 + 1,
        kernel_plan=lambda shape: sobel_bilateral_plan(
            shape, d, tile_h, _auto_interpret(interpret)),
    )


@register_filter("bilateral_pallas")
def bilateral_pallas(
    d: int = 5,
    sigma_color: float = 0.1,
    sigma_space: float = 2.0,
    tile_h: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Filter:
    """Pallas-backed bilateral. ``interpret=None`` → auto: compiled on TPU,
    interpret mode elsewhere (CPU tests)."""

    def fn(batch: jnp.ndarray) -> jnp.ndarray:
        return bilateral_nhwc_pallas(
            batch, d=d, sigma_color=sigma_color, sigma_space=sigma_space,
            tile_h=tile_h, interpret=_auto_interpret(interpret),
        )

    return stateless(
        f"bilateral_pallas(d={d},sc={sigma_color},ss={sigma_space})",
        fn,
        halo=d // 2,
    )


# ---------------------------------------------------------------------------
# Temporal-delta change detection (PR 7): per-tile max-abs-diff reduction
# ---------------------------------------------------------------------------


def tile_maxdiff_ref(a: jnp.ndarray, b: jnp.ndarray,
                     tile: int = 32) -> jnp.ndarray:
    """jnp golden: per-tile max |a − b| of two uint8 NHWC batches.

    ``(B, H, W, C) × (B, H, W, C) → (B, ⌈H/tile⌉, ⌈W/tile⌉) uint8`` —
    the device half of the temporal-delta wire (transport.codec
    .DeltaCodec): a tile whose reduction exceeds ``delta_threshold`` is
    re-encoded, the rest composite from the decoder's cache. Pure VPU
    arithmetic (max − min keeps everything uint8; no float cast), cheap
    enough to ride as an appended stage after any filter program.
    Unaligned H/W are zero-padded — a zero diff can never mark a tile
    dirty, so padding is semantically invisible.
    """
    if a.ndim == 3:
        return tile_maxdiff_ref(a[None], b[None], tile)[0]
    bsz, h, w, c = a.shape
    d = jnp.maximum(a, b) - jnp.minimum(a, b)
    nty, ntx = -(-h // tile), -(-w // tile)
    ph, pw = nty * tile - h, ntx * tile - w
    if ph or pw:
        d = jnp.pad(d, ((0, 0), (0, ph), (0, pw), (0, 0)))
    return d.reshape(bsz, nty, tile, ntx, tile, c).max(axis=(2, 4, 5))


def _tile_maxdiff_kernel(tile: int, row_px: int, ntx: int):
    """One grid step reduces a (tile, W·C) slab pair to its (ntx,) tile
    row. W·C rides the lane axis (channel-fastest NHWC layout means tile
    j's pixels are the CONTIGUOUS lane range [j·tile·C, (j+1)·tile·C) —
    no transpose needed, unlike the stencil kernels above). The per-tile
    segmentation is a static unroll over ntx: ~tens of segments, each a
    single VPU max-reduce."""

    def kernel(a_ref, b_ref, out_ref):
        a = a_ref[0].astype(jnp.int32)
        b = b_ref[0].astype(jnp.int32)
        d = jnp.maximum(a, b) - jnp.minimum(a, b)   # (tile, row_px)
        cols = jnp.max(d, axis=0)                   # (row_px,)
        seg = row_px // ntx
        vals = [jnp.max(cols[j * seg: (j + 1) * seg]) for j in range(ntx)]
        out_ref[0, 0, :] = jnp.stack(vals).astype(jnp.uint8)

    return kernel


def tile_maxdiff_pallas(a: jnp.ndarray, b: jnp.ndarray, tile: int = 32,
                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """Pallas tile_maxdiff: one pass per (batch row, tile row) pair, the
    whole reduction held in VMEM/registers. Falls back to the jnp golden
    when the geometry doesn't tile exactly (edge tiles). Interpret mode
    only: it does not lower through Mosaic (``TILE_MAXDIFF_PALLAS_ON_TPU``
    says why), so on a TPU call :func:`tile_maxdiff`.
    """
    interpret = _auto_interpret(interpret)
    squeeze = a.ndim == 3
    if squeeze:
        a, b = a[None], b[None]
    bsz, h, w, c = a.shape
    if h % tile or w % tile or h % _SUBLANE:
        out = tile_maxdiff_ref(a, b, tile)
        return out[0] if squeeze else out
    nty, ntx = h // tile, w // tile
    a3 = a.reshape(bsz, h, w * c)
    b3 = b.reshape(bsz, h, w * c)
    out = pl.pallas_call(
        _tile_maxdiff_kernel(tile, w * c, ntx),
        grid=(bsz, nty),
        in_specs=[pl.BlockSpec((1, tile, w * c), lambda bb, ii: (bb, ii, 0)),
                  pl.BlockSpec((1, tile, w * c), lambda bb, ii: (bb, ii, 0))],
        out_specs=pl.BlockSpec((1, 1, ntx), lambda bb, ii: (bb, ii, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, nty, ntx), jnp.uint8),
        interpret=interpret,
    )(a3, b3)
    return out[0] if squeeze else out


# Whether the dispatcher compiles the Pallas kernel on a TPU. It does not:
# on the v5e (PR 21's chip run, jax 0.9.0) the kernel does not lower — its
# (1, 1, ntx) output block is neither a multiple of the (8, 128) tile nor
# the whole axis, the body stacks scalars into a vector and slices lanes
# at 96-lane offsets, and a uint8 input block wants 32-row tiles that 1080
# rows do not divide. The golden loses nothing to it: XLA fuses |a - b|
# into the tile reduce and reads each frame once. The kernel stays for
# interpret mode (tier-1 pins its arithmetic) until a cell shows a TPU
# kernel would win (ROADMAP D4).
TILE_MAXDIFF_PALLAS_ON_TPU = False


def tile_maxdiff(a: jnp.ndarray, b: jnp.ndarray, tile: int = 32,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """Dispatch: the jnp golden on unaligned geometries and (see
    ``TILE_MAXDIFF_PALLAS_ON_TPU``) on the TPU; the Pallas kernel in
    interpret mode on aligned ones."""
    h, w = a.shape[-3], a.shape[-2]
    aligned = h % tile == 0 and w % tile == 0 and h % _SUBLANE == 0
    if aligned and (_auto_interpret(interpret)
                    or TILE_MAXDIFF_PALLAS_ON_TPU):
        return tile_maxdiff_pallas(a, b, tile, interpret=interpret)
    return tile_maxdiff_ref(a, b, tile)


# ---------------------------------------------------------------------------
# JPEG forward DCT + quantization (PR 16): the transform half of the host
# codec, on device — NativeJpegCodec.encode_coefficients then does entropy
# coding and nothing else.
# ---------------------------------------------------------------------------

# Annex-K base tables (the ones libjpeg scales in jpeg_set_quality).
_JPEG_LUMA_BASE = (
    (16, 11, 10, 16, 24, 40, 51, 61),
    (12, 12, 14, 19, 26, 58, 60, 55),
    (14, 13, 16, 24, 40, 57, 69, 56),
    (14, 17, 22, 29, 51, 87, 80, 62),
    (18, 22, 37, 56, 68, 109, 103, 77),
    (24, 35, 55, 64, 81, 104, 113, 92),
    (49, 64, 78, 87, 103, 121, 120, 101),
    (72, 92, 95, 98, 112, 100, 103, 99),
)
_JPEG_CHROMA_BASE = (
    (17, 18, 24, 47, 99, 99, 99, 99),
    (18, 21, 26, 66, 99, 99, 99, 99),
    (24, 26, 56, 99, 99, 99, 99, 99),
    (47, 66, 99, 99, 99, 99, 99, 99),
    (99, 99, 99, 99, 99, 99, 99, 99),
    (99, 99, 99, 99, 99, 99, 99, 99),
    (99, 99, 99, 99, 99, 99, 99, 99),
    (99, 99, 99, 99, 99, 99, 99, 99),
)


def jpeg_quant_table(quality: int, chroma: bool = False):
    """The (8, 8) quantization table ``jpeg_set_quality(quality,
    force_baseline=TRUE)`` installs, reproduced exactly (IJG scaling of
    the Annex-K base tables). Device-side quantization MUST divide by
    these values so the native shim's entropy-only encode — which tells
    the decoder to multiply by the same tables — reconstructs correctly.
    Returns int32 numpy, natural (row-major) order."""
    import numpy as np

    q = min(100, max(1, int(quality)))
    scale = 5000 // q if q < 50 else 200 - 2 * q
    base = np.asarray(_JPEG_CHROMA_BASE if chroma else _JPEG_LUMA_BASE,
                      dtype=np.int64)
    table = (base * scale + 50) // 100
    return np.clip(table, 1, 255).astype(np.int32)


def _dct8_matrix():
    """D[u, x] = C(u)/2 · cos((2x+1)uπ/16) — the orthonormal forward
    8-point DCT-II so that coef = D · block · Dᵀ matches JPEG's
    definition (float64 build, float32 constants)."""
    import numpy as np

    d = np.zeros((8, 8), np.float64)
    for u in range(8):
        cu = (1.0 / math.sqrt(2.0)) if u == 0 else 1.0
        for x in range(8):
            d[u, x] = 0.5 * cu * math.cos((2 * x + 1) * u * math.pi / 16.0)
    return d.astype(np.float32)


_DCT8 = _dct8_matrix()


def _qrecip_lanes(qtable, nbx: int):
    """Quantizer reciprocals laid out for the interleaved slab: lane
    ``u·nbx + j`` holds 1/qtable[·, u] (u = horizontal frequency, j =
    block index) — ``jnp.repeat`` along the frequency axis."""
    import numpy as np

    recip = (1.0 / np.asarray(qtable, np.float64)).astype(np.float32)
    return np.repeat(recip, nbx, axis=1)  # (8, 8*nbx)


@functools.partial(jax.jit, static_argnums=(1,))
def _dct8x8_quant_slab_jit(x, nbx, qrecip):
    """The golden's execution of the slab math. Jitted on purpose: eager
    per-op dispatch compiles each multiply-add as its own XLA program and
    never forms FMAs, while the Pallas interpreter runs the kernel body
    as one fused program (which does) — a 1-ulp difference that flips
    round() on coefficient-boundary values. One fused program on both
    sides restores bit-identity (pinned by tests/test_delta_wire.py in
    interpret mode and by chip_smoke.py on the TPU)."""
    return _dct8x8_quant_slab(x, nbx, qrecip)


def _dct8x8_quant_slab(x: jnp.ndarray, nbx: int,
                       qrecip: jnp.ndarray) -> jnp.ndarray:
    """The golden's arithmetic; the Pallas kernel
    (:func:`_dct8x8_quant_kernel`) performs the same products and sums in
    the same order on a layout Mosaic can lower.

    ``x`` is a (…, 8, 8·nbx) float32 slab of 8-pixel-tall block rows in
    INTERLEAVED lane order (lane = x_in_block · nbx + block_idx): every
    per-block slice is then a contiguous lane chunk, which is the whole
    trick — no strided lane access, no in-kernel reshape. Returns the
    rounded quantized coefficients as float32, same layout with lane =
    u_horiz · nbx + block_idx (caller casts to int16)."""
    # Each product passes through an optimization barrier before the
    # add: XLA's FMA contraction (fusing a*b+c into one fused
    # multiply-add with unrounded product) is a per-fusion-context
    # choice, so the golden and the kernel could round 1 ulp apart —
    # enough to flip round() on quotients that land exactly on a ±.5
    # quantization boundary (common at high quality, where divisors are
    # 1–2). Barring contraction pins both programs to the identical
    # IEEE mul-then-add sequence; the barrier is a compile-time marker,
    # not a runtime op.
    nofma = jax.lax.optimization_barrier
    rows = [x[..., y, :] - 128.0 for y in range(8)]  # JPEG level shift
    vert = []
    for u in range(8):
        acc = nofma(float(_DCT8[u, 0]) * rows[0])
        for y in range(1, 8):
            acc = acc + nofma(float(_DCT8[u, y]) * rows[y])
        vert.append(acc)
    v = jnp.stack(vert, axis=-2)                      # (…, 8, 8·nbx)
    chunks = [v[..., :, k * nbx: (k + 1) * nbx] for k in range(8)]
    horiz = []
    for u in range(8):
        acc = nofma(float(_DCT8[u, 0]) * chunks[0])
        for k in range(1, 8):
            acc = acc + nofma(float(_DCT8[u, k]) * chunks[k])
        horiz.append(acc)
    t = jnp.concatenate(horiz, axis=-1)               # (…, 8, 8·nbx)
    return jnp.round(t * qrecip)


def _to_slab(plane: jnp.ndarray, nby: int, nbx: int) -> jnp.ndarray:
    """(B, H, W) → (B, nby, 8, 8·nbx) float32, interleaved lane order."""
    b = plane.shape[0]
    x = plane.astype(jnp.float32)
    return (x.reshape(b, nby, 8, nbx, 8).transpose(0, 1, 2, 4, 3)
            .reshape(b, nby, 8, 8 * nbx))


def _from_slab(q: jnp.ndarray, nby: int, nbx: int) -> jnp.ndarray:
    """(B, nby, 8, 8·nbx) quantized slab → (B, nby, nbx, 8, 8) int16
    coefficient blocks in natural (row-major frequency) order — the
    layout ``dvf_jpeg_encode_coefficients`` consumes."""
    b = q.shape[0]
    return (q.reshape(b, nby, 8, 8, nbx).transpose(0, 1, 4, 2, 3)
            .astype(jnp.int16))


def dct8x8_quant_ref(plane: jnp.ndarray, qtable) -> jnp.ndarray:
    """jnp golden: per-8×8-block forward DCT + quantization of a sample
    plane. ``(B, H, W) uint8 → (B, ⌈H/8⌉, ⌈W/8⌉, 8, 8) int16`` quantized
    coefficients (natural order, level-shifted by −128, divided by
    ``qtable`` with round-half-even). Unaligned H/W are edge-padded to
    the block grid first — libjpeg's own edge replication. Bit-identity
    with libjpeg's integer DCT is NOT claimed (it uses a scaled-integer
    AAN transform); the pinned equivalence is decode tolerance, see
    tests/test_delta_wire.py."""
    squeeze = plane.ndim == 2
    if squeeze:
        plane = plane[None]
    b, h, w = plane.shape
    ph, pw = (-h) % 8, (-w) % 8
    if ph or pw:
        plane = jnp.pad(plane, ((0, 0), (0, ph), (0, pw)), mode="edge")
        h, w = h + ph, w + pw
    nby, nbx = h // 8, w // 8
    qrecip = jnp.asarray(_qrecip_lanes(qtable, nbx))
    out = _from_slab(_dct8x8_quant_slab_jit(_to_slab(plane, nby, nbx),
                                            nbx, qrecip), nby, nbx)
    return out[0] if squeeze else out


def _dct8x8_quant_kernel(nofma):
    """One grid step transforms one block row, laid out
    ``[y, x_in_block, block]`` (see :func:`dct8x8_quant_pallas`): every
    operand is an aligned ``(8, nbx)`` tile (x-in-block on sublanes, block
    index on lanes), a ``(8, 1)`` / ``(1, nbx)`` broadcast of one, or a
    Python scalar — no lane slicing, concatenation, stacking or reshape in
    the body, which is what Mosaic lowers. The per-element arithmetic is
    :func:`_dct8x8_quant_slab`'s, product by product and in its order, so
    the two agree bit for bit wherever neither compiler contracts a
    multiply-add. ``nofma`` guards each product in interpret mode only:
    Mosaic has no lowering for ``optimization_barrier`` (PR 21's chip
    run), and XLA:CPU is the compiler that contracts."""

    def kernel(x_ref, d_ref, q_ref, out_ref):
        rows = [x_ref[0, 0, y] - 128.0 for y in range(8)]  # JPEG level shift
        for u in range(8):          # vertical frequency
            vert = nofma(float(_DCT8[u, 0]) * rows[0])
            for y in range(1, 8):
                vert = vert + nofma(float(_DCT8[u, y]) * rows[y])
            # Horizontal pass: x-in-block is the sublane axis, so mixing it
            # is an outer-product accumulate, d_ref[k] holding column k of
            # the DCT matrix as an (8, 1) vector over horizontal frequency.
            t = nofma(d_ref[0] * vert[0:1, :])
            for k in range(1, 8):
                t = t + nofma(d_ref[k] * vert[k:k + 1, :])
            out_ref[0, 0, u] = jnp.round(t * q_ref[u])

    return kernel


def dct8x8_quant_pallas(plane: jnp.ndarray, qtable,
                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """Pallas DCT+quant: grid = (batch, block rows); each step transforms
    one (8, W) block row entirely in VMEM/registers. The plane arrives as
    ``(B, nby, 8, 8, nbx)`` = ``[.., y, x_in_block, block]`` (one XLA
    transpose outside the kernel) and the coefficients leave as
    ``[.., v_freq, h_freq, block]``, so both DCT passes are aligned
    whole-tile VPU work. Requires H and W to be block multiples (the
    dispatcher sends everything else to the golden)."""
    interpret = _auto_interpret(interpret)
    squeeze = plane.ndim == 2
    if squeeze:
        plane = plane[None]
    b, h, w = plane.shape
    if h % 8 or w % 8:
        raise ValueError(f"dct8x8_quant_pallas needs H, W multiples of 8; "
                         f"got {h}x{w}")
    nby, nbx = h // 8, w // 8
    import numpy as np

    x = (plane.astype(jnp.float32).reshape(b, nby, 8, nbx, 8)
         .transpose(0, 1, 2, 4, 3))                    # [b, by, y, x, bx]
    dcols = jnp.asarray(_DCT8.T[:, :, None])           # [k][h_freq, 0]
    qrecip = jnp.asarray((1.0 / np.asarray(qtable, np.float64))
                         .astype(np.float32)[:, :, None])  # [v][h, 0]
    block = pl.BlockSpec((1, 1, 8, 8, nbx), lambda bb, ii: (bb, ii, 0, 0, 0))
    table = pl.BlockSpec((8, 8, 1), lambda bb, ii: (0, 0, 0))
    out = pl.pallas_call(
        _dct8x8_quant_kernel(jax.lax.optimization_barrier if interpret
                             else (lambda v: v)),
        grid=(b, nby),
        in_specs=[block, table, table],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((b, nby, 8, 8, nbx), jnp.float32),
        interpret=interpret,
    )(x, dcols, qrecip)
    out = out.transpose(0, 1, 4, 2, 3).astype(jnp.int16)  # (b,by,bx,8,8)
    return out[0] if squeeze else out


def dct8x8_quant(plane: jnp.ndarray, qtable,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """Dispatch: the Pallas kernel on block-aligned planes (compiled on
    TPU, interpret elsewhere), the jnp golden (which edge-pads) for
    unaligned geometries."""
    h, w = plane.shape[-2], plane.shape[-1]
    if h % 8 == 0 and w % 8 == 0:
        return dct8x8_quant_pallas(plane, qtable, interpret=interpret)
    return dct8x8_quant_ref(plane, qtable)


# ---------------------------------------------------------------------------
# The histogram family (PR 49): tile histograms by counting on the VPU (since
# PR 50 bit planes and the population count), and the table lookup as a lane
# gather (ops/histogram.py: clahe, equalize)
# ---------------------------------------------------------------------------

HIST_BINS = 256


def tile_pad(th: int, tw: int) -> tuple:
    """``(rows, lanes)`` of the whole (8, 128) vregs a ``th x tw`` tile fills."""
    return _round_up(th, _SUBLANE), _round_up(tw, _LANE)


def to_tiles(x: jnp.ndarray, gy: int, gx: int) -> jnp.ndarray:
    """``(N, gy*th, gx*tw)`` → ``(N, gy, TH, gx*TW)``: each of the ``gy x
    gx`` tiles padded to whole vregs with zeros (bottom and right), a row
    of tiles side by side on the lane axis, so that tile ``(ty, tx)`` is
    the aligned window ``[ty, :, tx*TW:(tx+1)*TW]``. Columns first, as
    slices side by side, then rows: the lane axis stays the minor one all
    the way (a ``(.., gx, tw)`` intermediate would put ``gx`` on the
    sublanes, padded fourfold for bytes)."""
    n, hp, wp = x.shape
    th, tw = hp // gy, wp // gx
    rows, lanes = tile_pad(th, tw)
    if lanes != tw:
        x = jnp.concatenate([jnp.pad(x[:, :, tx * tw:(tx + 1) * tw],
                                     ((0, 0), (0, 0), (0, lanes - tw)))
                             for tx in range(gx)], axis=2)
    return jnp.pad(x.reshape(n, gy, th, gx * lanes),
                   ((0, 0), (0, 0), (0, rows - th), (0, 0)))


def from_tiles(t: jnp.ndarray, gy: int, gx: int, th: int, tw: int) -> jnp.ndarray:
    """The inverse of :func:`to_tiles`, the filler dropped."""
    n, _, _, width = t.shape
    lanes = width // gx
    x = t[:, :, :th].reshape(n, gy * th, width)
    if lanes == tw:
        return x
    return jnp.concatenate([x[:, :, tx * lanes:tx * lanes + tw] for tx in range(gx)],
                           axis=2)


def _block_vmem_limit(block_bytes: int, interpret: bool) -> Optional[int]:
    """Scoped-VMEM limit for a kernel whose windows Pallas double-buffers:
    None (Mosaic's default 16 MiB) while two copies of every window and as
    much again of temporaries fit under 12 MiB, else the stencils' raised
    limit (CLAHE at 1080p, grid 8, holds 0.6 MB of uint8 windows a grid
    step; an 8K frame would pass the default)."""
    if interpret or 4 * block_bytes <= 12 * 1024 * 1024:
        return None
    return _VMEM_LIMIT_RAISED


_WORD_BITS = 32     # pixels a word of a bit plane: the row tiles one group of a strip holds


def _hist_groups(rows: int) -> list:
    """``(first row tile, row tiles)`` of the groups a strip of ``rows``
    rows is walked in: at most a word's 32 row tiles each."""
    row_tiles = rows // _SUBLANE
    return [(r0, min(_WORD_BITS, row_tiles - r0)) for r0 in range(0, row_tiles, _WORD_BITS)]


def _word_of(n: int) -> int:
    """The int32 word with the bit of every row tile of a group of ``n``
    set: row tile ``r`` sits at bit ``8 (r % 4) + r // 4`` (four row tiles
    a byte lane of the packed word, :func:`_bit_planes`)."""
    word = sum(1 << (8 * (r % 4) + r // 4) for r in range(n))
    return word - (1 << 32) if word >> 31 else word


def _bit_planes(t: jnp.ndarray) -> jnp.ndarray:
    """``(n, 8, 128)`` int32 pixels (0..255), ``n <= 32`` row tiles of one
    lane tile → ``(8, 8, 128)`` int32: plane ``k``'s word at a (sublane,
    lane) holds bit ``k`` of the ``n`` pixels there, row tile ``r`` at bit
    ``8 (r % 4) + r // 4``. Four row tiles are first packed a byte each
    into a word, so that one shift and mask then moves a bit of all four;
    the bits are disjoint, so the sums are ORs (and bit 31, the sign, is a
    pixel like any other)."""
    n = t.shape[0]
    quads = -(-n // 4)
    if 4 * quads != n:
        t = jnp.concatenate([t, jnp.zeros((4 * quads - n, *t.shape[1:]), jnp.int32)], axis=0)
    t = t.reshape(quads, 4, *t.shape[1:])
    packed = jnp.sum(lax.shift_left(t, 8 * lax.broadcasted_iota(jnp.int32, t.shape, 1)), axis=1)
    shape = (8, *packed.shape)
    bits = lax.shift_right_logical(jnp.broadcast_to(packed[None], shape),
                                   lax.broadcasted_iota(jnp.int32, shape, 0)) & 0x01010101
    return jnp.sum(lax.shift_left(bits, lax.broadcasted_iota(jnp.int32, shape, 1)), axis=1)


def hist_ops_per_pixel(rows: int, lanes: int) -> float:
    """Vector operations :func:`_tile_hist_kernel`'s expressions state for a
    ``rows x lanes`` tile, a vreg each, over the tile's vregs of pixels
    (what the compare form's 768 counted). A group of ``n`` row tiles of a
    strip: widen ``n``, pack ``7 q`` (``q`` quads of row tiles), the planes
    ``8 (4 q - 1)``, both nibbles' masks 68, then AND, count and add for
    each of 256 bins, whatever ``n``: a fuller word is a cheaper pixel. A
    tile: 7 adds for each of the 32 vregs of the sublane sum, a lane sum
    and a select for each."""
    def group(n):
        quads = -(-n // 4)
        return n + 7 * quads + 8 * (4 * quads - 1) + 68 + 3 * HIST_BINS

    strips = lanes // _LANE
    ops = strips * sum(group(n) for _, n in _hist_groups(rows))
    ops += (_SUBLANE - 1 + 2) * HIST_BINS // _SUBLANE
    return round(ops / (rows // _SUBLANE * strips), 1)


def _nibble_masks(root: jnp.ndarray, planes) -> jnp.ndarray:
    """``(16, 8, 128)``: entry ``v`` has the bits of the pixels of ``root``
    whose four ``planes`` (most significant first) spell ``v``."""
    masks = root[None]
    for plane in planes:
        masks = jnp.stack([masks & ~plane, masks & plane], axis=1).reshape(-1, *plane.shape)
    return masks


def _tile_hist_kernel(rows: int, lanes: int, gx: int):
    """One grid step counts the ``gx`` tiles of one row of tiles, exactly,
    by POPULATION COUNT over bit planes (PR 50; the compare, select and add
    a pixel a bin that stood here cost 768 VPU operations a pixel). A tile
    is walked a lane tile (a strip of 128 lanes) at a time, and a strip in
    groups of at most 32 row tiles: the group's pixels are cut into their 8
    bit planes, 32 pixels a word (:func:`_bit_planes`); the planes and
    their complements are ANDed into 16 masks of the high nibble and 16 of
    the low (:func:`_nibble_masks`); bin ``16 h + l``'s pixels are ``high[h]
    & low[l]``, counted by ``lax.population_count`` (``vpcnt``, an
    instruction a vreg) and added into row ``bin`` of a (256 x 8, 128)
    scratch. Once a tile the scratch's sublanes are summed (8 strided
    loads, a sublane of every bin each), then its lanes, into lane ``tx``
    of the (256, 128) result. What a pixel costs is the plan's
    ``hist_ops_per_pixel`` (:func:`hist_ops_per_pixel`). Whole-array
    expressions and loops over tiles and strips: the body is traced once
    (``setup_s``: the Engine traces a step twice). A tile's first strip
    SETS the scratch, outside the loop over the others, which add to it:
    by the compiler's schedule for a described v5e
    (``scripts/stencil_kernel_probe.py --kernel clahe_hist``) CLAHE's
    1080p tile, two strips, is then straight-line code, 6,252 bundles a
    grid step (the loop's one trip is flattened, unrolled or not), where
    zeroing the scratch and looping over both strips reads 10,660 (the
    scratch's 256 vregs go through VMEM a group: ``vst`` 6,402 for
    2,890); ``equalize``'s band of 15 strips reads the same either way
    (5,644 / 5,622)."""
    strips, groups = lanes // _LANE, _hist_groups(rows)

    def kernel(x_ref, out_ref, acc_ref):
        lane = lax.broadcasted_iota(jnp.int32, (HIST_BINS, _LANE), 1)

        def strip(i, first):
            c0 = pl.multiple_of(i * _LANE, _LANE)
            for g, (r0, n) in enumerate(groups):
                t = x_ref[0, 0, pl.ds(r0 * _SUBLANE, n * _SUBLANE), pl.ds(c0, _LANE)]
                p = _bit_planes(t.astype(jnp.int32).reshape(n, _SUBLANE, _LANE))
                high = _nibble_masks(jnp.full(p.shape[1:], _word_of(n), jnp.int32), [p[k] for k in (7, 6, 5, 4)])
                low = _nibble_masks(jnp.full(p.shape[1:], -1, jnp.int32), [p[k] for k in (3, 2, 1, 0)])
                counts = lax.population_count(high[:, None] & low[None]).reshape(acc_ref.shape)
                acc_ref[...] = counts if first and g == 0 else acc_ref[...] + counts

        def tile(tx, carry):
            def later_strip(s, carry):
                strip(tx * strips + s, False)
                return carry

            strip(tx * strips, True)
            if strips > 1:
                lax.fori_loop(1, strips, later_strip, 0)
            by_lane = acc_ref[pl.ds(0, HIST_BINS, stride=_SUBLANE), :]
            for sub in range(1, _SUBLANE):
                by_lane = by_lane + acc_ref[pl.ds(sub, HIST_BINS, stride=_SUBLANE), :]
            total = jnp.sum(by_lane, axis=1, keepdims=True)
            out_ref[0, 0] = jnp.where(lane == tx, total, out_ref[0, 0])
            return carry

        out_ref[0, 0] = jnp.zeros((HIST_BINS, _LANE), jnp.int32)
        lax.fori_loop(0, gx, tile, 0)

    return kernel


def tile_hist_pallas(tiles: jnp.ndarray, gx: int, pixels: int, name: str,
                     interpret: bool = False) -> jnp.ndarray:
    """``(N, gy, TH, gx*TW)`` uint8 tiles (:func:`to_tiles`) of ``pixels``
    pixels each → ``(N, gy, gx, 256)`` int32 counts: how many pixels of
    tile ``(ty, tx)`` hold each value. The filler is zeros, counted with
    the tile and taken off bin 0 again (``TH*TW - pixels`` a tile). Exact
    integer counting, so any sum of tiles is the histogram of their union."""
    n, gy, rows, width = tiles.shape
    lanes = width // gx
    if gx > _LANE:
        raise ValueError(f"at most {_LANE} tiles a row, got {gx}")
    out = pl.pallas_call(
        _tile_hist_kernel(rows, lanes, gx),
        grid=(n, gy),
        in_specs=[pl.BlockSpec((1, 1, rows, width), lambda b, t: (b, t, 0, 0))],
        out_specs=pl.BlockSpec((1, 1, HIST_BINS, _LANE), lambda b, t: (b, t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, gy, HIST_BINS, _LANE), jnp.int32),
        scratch_shapes=[pltpu.VMEM((HIST_BINS * _SUBLANE, _LANE), jnp.int32)],
        compiler_params=_vmem_params(_block_vmem_limit(rows * width, interpret)),
        interpret=interpret,
        name=name,
    )(tiles)
    counts = jnp.swapaxes(out[..., :gx], 2, 3)
    return counts.at[..., 0].add(pixels - rows * lanes)


def pack_luts(a, b, c, d) -> jnp.ndarray:
    """Four uint8 tables → one int32 table, a byte each (``a`` lowest): one
    lookup then fetches all four (:func:`lut_apply_pallas`)."""
    a, b, c, d = (t.astype(jnp.int32) for t in (a, b, c, d))
    return a | (b << 8) | (c << 16) | (d << 24)


def blend_rounded(terms, as_xla_cpu_code: bool) -> jnp.ndarray:
    """``((t0 + t1) + t2) + t3`` of four float32 products, each product
    rounded before it is added. The VPU has no fused multiply-add, but
    XLA's CPU code contracts ``a * b + c`` into one wherever both land in
    one fusion (15,322 of 65,536 blends differ in the last bit, 9 of 2880
    pixels a step, here on the CPU, PR 49; an ``optimization_barrier`` did
    not stop it). ``as_xla_cpu_code``: give every product a second use (a
    comparison with itself, never false), which keeps it rounded there, so
    that every form of a filter blends to the same bits on every backend,
    jitted or not."""
    val = ((terms[0] + terms[1]) + terms[2]) + terms[3]
    if as_xla_cpu_code:
        val = jnp.where(functools.reduce(jnp.logical_and, [t == t for t in terms]),
                        val, 0.0)
    return val


def _lut_apply_kernel(rows: int, lanes: int, gx: int, blend: bool,
                      interpret: bool):
    """One grid step maps the ``gx`` cells of one row of cells through
    their own 256-entry tables. A table's two halves are two vregs (128
    lanes each, every sublane alike); a pixel's entry is a lane gather from
    each by its low seven bits and a select on the eighth: no compare a
    bin. ``blend``: the entry is four byte tables (:func:`pack_luts`) and
    the result their bilinear blend in float32, ``(wy0 wx0) a + (wy0 wx1) b
    + (wy1 wx0) c + (wy1 wx1) d`` in that order, rounded half to even and
    clipped (what ops/histogram.py's gather form computes, operation for
    operation); else the entry is the result. ``interpret``: the kernel's
    body then runs as XLA's CPU code (:func:`blend_rounded`)."""

    def kernel(x_ref, lut_ref, *rest):
        *weights, out_ref = rest
        for cx in range(gx):
            table = [jnp.broadcast_to(
                lut_ref[0, 0, pl.ds(cx, 1), pl.ds(half * _LANE, _LANE)],
                (rows, _LANE)) for half in (0, 1)]
            for c in range(lanes // _LANE):
                cols = pl.ds(cx * lanes + c * _LANE, _LANE)
                x = x_ref[0, 0, :, cols].astype(jnp.int32)
                low = x & (_LANE - 1)
                entry = jnp.where(x >= _LANE,
                                  jnp.take_along_axis(table[1], low, axis=1),
                                  jnp.take_along_axis(table[0], low, axis=1))
                if blend:
                    wy0, wy1, wx0, wx1 = weights
                    y0, y1 = wy0[0], wy1[0]
                    x0 = jnp.broadcast_to(wx0[:, cols], (rows, _LANE))
                    x1 = jnp.broadcast_to(wx1[:, cols], (rows, _LANE))
                    a, b, c_, d = (((entry >> s) & 255).astype(jnp.float32)
                                   for s in (0, 8, 16, 24))
                    val = blend_rounded([(y0 * x0) * a, (y0 * x1) * b,
                                         (y1 * x0) * c_, (y1 * x1) * d], interpret)
                    entry = jnp.clip(jnp.round(val), 0.0, 255.0).astype(jnp.int32)
                out_ref[0, 0, :, cols] = entry.astype(out_ref.dtype)

    return kernel


def lut_apply_pallas(cells: jnp.ndarray, luts: jnp.ndarray, name: str,
                     weights=None, interpret: bool = False) -> jnp.ndarray:
    """``(N, gy, CH, gx*CW)`` uint8 cells (:func:`to_tiles`) through ``(N,
    gy, gx, 256)`` int32 tables, a cell its own → uint8 of the cells' shape
    (the low byte of a table's entry, or of the blend). ``weights`` =
    ``(wy0, wy1, wx0, wx1)`` turns the lookup into the four-table blend of
    :func:`_lut_apply_kernel`: ``wy*`` float32 ``(gy, CH, 128)`` (a row's
    weight on every lane), ``wx*`` float32 ``(1, gx*CW)`` (a column's
    weight, laid out as the cells are)."""
    n, gy, rows, width = cells.shape
    gx = luts.shape[2]
    lanes = width // gx
    cell_spec = pl.BlockSpec((1, 1, rows, width), lambda b, t: (b, t, 0, 0))
    in_specs = [cell_spec,
                pl.BlockSpec((1, 1, gx, HIST_BINS), lambda b, t: (b, t, 0, 0))]
    operands = [cells, luts]
    if weights is not None:
        in_specs += [pl.BlockSpec((1, rows, _LANE), lambda b, t: (t, 0, 0))] * 2
        in_specs += [pl.BlockSpec((1, width), lambda b, t: (0, 0))] * 2
        operands += list(weights)
    return pl.pallas_call(
        _lut_apply_kernel(rows, lanes, gx, weights is not None, interpret),
        grid=(n, gy),
        in_specs=in_specs,
        out_specs=cell_spec,
        out_shape=jax.ShapeDtypeStruct(cells.shape, jnp.uint8),
        compiler_params=_vmem_params(
            _block_vmem_limit(2 * rows * width, interpret)),
        interpret=interpret,
        name=name,
    )(*operands)
