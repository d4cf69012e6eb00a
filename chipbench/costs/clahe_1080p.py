"""Operations and bytes one padded batch of CLAHE needs, from the
algorithm's own counts and the configuration's geometry, so the yardstick
reads the same work whatever implements it. Counted LOW wherever there is
a choice (as costs/flow_720p.py and costs/sobel_bilateral_1080p.py do), so
that a share of a roofline cannot pass 100% by counting.

Per pixel of a plane (a frame has ``channels`` planes):
  histogram   one increment of its tile's bin                       =  1
  lookup      four table reads (the tiles either side, each way)    =  4
  blend       4 weight products, 4 multiplies, 3 adds               = 11
  the result  round, clip, cast                                     =  3
Per tile: clip, redistribution, cumulative sum and scale over 256 bins,
about 10 operations a bin, 64 tiles a plane: under 0.1 a pixel, left out.

That is what the ALGORITHM needs: a scatter-add histogram is one
operation a pixel. The shipped kernel counts with 768 VPU operations a
pixel (256 bins x compare, select, add) because the chip has no vector
scatter; those are the implementation's, and are left out.

``cost`` is the whole step's: the bytes that must cross HBM whatever the
fusion does are the uint8 frame in and the uint8 frame out. The shipped
form runs no contraction on the MXU, so no matrix flops are counted.

``kernel_cost(config, batch, kernel)`` is one named kernel's
(``clahe_hist`` / ``clahe_apply``, the names of ops/histogram.py's
``clahe_plan``): uint8 planes read once (and, for ``clahe_apply``,
written once), the tables written (``clahe_hist``: 64 x 256 counts of 4
bytes a plane) or read (``clahe_apply``: 81 cells x 256 packed words);
the int32 the kernels really move, the padding of a 135 x 240 tile to 136
x 256 and the half tiles ``clahe_apply`` walks beyond the plane are the
implementation's and are left out.

The v5e's published peaks (peaks.json) are the HBM's and the MXU's bf16
rate; this work runs on the VPU and its lane-gather unit, which have no
published peak there, so a share reads against the bytes bound and says
how far a kernel is from being bound by its bytes.
"""

HIST_PER_PIXEL = 1.0
APPLY_PER_PIXEL = 4.0 + 11.0 + 3.0
BINS = 256


def _planes(config, batch_size):
    g = config["geometry"]
    return float(g["height"] * g["width"]), float(batch_size * g["channels"])


def _table_bytes(config, planes, tables_a_side):
    return planes * tables_a_side ** 2 * BINS * 4.0


def cost(config, batch_size):
    pixels, planes = _planes(config, batch_size)
    return {"flops": (HIST_PER_PIXEL + APPLY_PER_PIXEL) * pixels * planes,
            "bytes": 2.0 * pixels * planes}


def kernel_cost(config, batch_size, kernel):
    pixels, planes = _planes(config, batch_size)
    grid = int(config["filter"]["kwargs"]["grid"])
    if kernel == "clahe_hist":
        return {"flops": HIST_PER_PIXEL * pixels * planes,
                "bytes": pixels * planes + _table_bytes(config, planes, grid)}
    if kernel == "clahe_apply":
        return {"flops": APPLY_PER_PIXEL * pixels * planes,
                "bytes": 2.0 * pixels * planes + _table_bytes(config, planes, grid + 1)}
    raise KeyError(f"no cost for kernel {kernel!r}")
