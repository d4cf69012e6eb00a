"""Pixels the CLAHE kernels walk beyond the frame: ``grid^2 x tile_h_pad x tile_w_pad`` over ``H x W``, less one, in percent.

From the bucket row's ``kernel`` block (the tiling the compiled step
resolved to: dvf_tpu/ops/histogram.py ``clahe_plan`` ->
``Engine.kernel_plan`` -> ``_Bucket.stats_row``): a 135 x 240 tile is
neither a multiple of 8 sublanes nor of 128 lanes, so ``clahe_hist`` walks
it as 136 x 256 whole vregs: 7.46 at 1080 x 1920, grid 8. The ``[layer]``
line also gives ``clahe_apply``'s walk, ``cells^2`` such tiles (the
interpolation cells are the tiles of the plane shifted by half a tile:
36.0 at the same shape). A guard, as stencil_slab_overread_pct: it moves
when the grid, the geometry or the padding does. None where no bucket row
lists ``kernels`` (a filter of XLA's own ops; any commit before PR 49)."""
from chipbench import spec


def read(ctx):
    block = spec.load_module("layer_metrics/clahe_hist_roofline.py").kernel_block(ctx)
    if block is None:
        return None
    height, width, _ = ctx["cell"].frame_shape
    tile = block["tile_h_pad"] * block["tile_w_pad"]
    walked = lambda side: 100.0 * (side ** 2 * tile / (height * width) - 1.0)
    ctx["log"](f"[layer] clahe_tile_overwork_pct: kernels {block['kernels']} ({block['impl']}, "
               f"{block['planes']} planes, {block['bins']} bins, clip {block['clip_abs']}): tile "
               f"{block['tile_h']} x {block['tile_w']} walked as {block['tile_h_pad']} x "
               f"{block['tile_w_pad']}; clahe_hist {block['grid']}^2 tiles a plane, clahe_apply "
               f"{block['cells']}^2 cells ({walked(block['cells']):.2f}% beyond the frame); "
               f"{block['vmem_window_bytes']} bytes a window")
    return walked(block["grid"])
