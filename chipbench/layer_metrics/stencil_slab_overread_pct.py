"""Bytes the stencil kernel DMAs beyond what it emits: ``slab_rows x w_aligned`` over ``tile_h x W``, less one, in percent.

From the bucket row's ``kernel`` block (the tiling the compiled step
resolved to: dvf_tpu/ops/pallas_kernels.py ``sobel_bilateral_plan`` ->
``Engine.kernel_plan`` -> ``_Bucket.stats_row``): a grid step copies a slab
of tile + halo rows, rounded up to 8, of W + halo columns, rounded up to
128, for the tile_h x W it writes. 77.8 at tile 24, d = 9, W = 1920
(40 x 2048 for 24 x 1920). A guard, as state_table_rows_pct: it moves when
the tile pick, the window or the alignment does. None where no bucket row
states a kernel (a filter of XLA's own ops; any commit before PR 43)."""
from chipbench import spec


def read(ctx):
    block = spec.load_module("layer_metrics/stencil_kernel_roofline.py").kernel_block(ctx)
    if block is None:
        return None
    width = ctx["cell"].frame_shape[1]
    moved = block["slab_rows"] * block["w_aligned"]
    emitted = block["tile_h"] * width
    ctx["log"](f"[layer] stencil_slab_overread_pct: kernel {block['kernel']} ({block['impl']}, "
               f"{block['taps']} taps, {block['compute_dtype']}): tile {block['tile_h']} of "
               f"h_pad {block['h_pad']}, grid {block['grid']}, slab {block['slab_rows']} x "
               f"{block['w_aligned']} for {block['tile_h']} x {width}, "
               f"{block['vmem_scratch_bytes']} bytes of VMEM scratch")
    return 100.0 * (moved / emitted - 1.0)
