"""Operations and bytes one padded batch of the Sobel -> bilateral chain
needs, from the algorithm's own counts and from the configuration's own
``d`` and geometry, so the yardstick reads the same work whatever
implements it. Counted low wherever there is a choice (as
costs/flow_720p.py does), so a share of the roofline cannot pass 100% by
counting.

Per pixel of a frame:
  luma          3 multiplies + 2 adds                                   =  5
  two 3x3 Sobels, separable: a [1, 2, 1] pass (3) and a difference (1)
                each way                                                =  8
  magnitude     2 squares, add, sqrt, scale, clip                       =  6
  a tap         difference, square, scale, exp, spatial weight, its two
                accumulations (multiply-add, add)                       =  8
                times d^2 taps
  the quotient  1 divide; the output's scale, round and cast            =  4

``cost`` is the whole step's: the bytes that must cross HBM whatever the
fusion does are the uint8 frame in and the uint8 frame out; the float32
planes, the NCHW round trip, the padding and every intermediate are the
implementation's and are left out.

``kernel_cost`` is the fused stencil kernel alone: what it must move
whatever implements it is three float32 planes in and ONE float32 edge map
out (the three channels of its result are equal); the slab's halo rows,
the columns padded to the lane tile and the threefold write of that one
map are the implementation's and are left out. Its operations are the
step's less the uint8 conversions.

The v5e's published peaks (peaks.json) are the HBM's and the MXU's bf16
rate; this work runs on the VPU, which has no published peak there, so a
share of either reads a few percent by construction.
"""

PER_PIXEL_FIXED = 5.0 + 8.0 + 6.0      # luma, Sobels, magnitude
PER_TAP = 8.0
PER_PIXEL_OUT = 1.0                    # the quotient
PER_PIXEL_U8 = 3.0                     # scale, round, cast of the result


def _pixels(config, batch_size):
    g = config["geometry"]
    return float(g["height"] * g["width"]) * batch_size, g["channels"]


def _taps(config):
    d = int(config["filter"]["kwargs"]["d"])
    return float(d * d)


def cost(config, batch_size):
    pixels, channels = _pixels(config, batch_size)
    per_pixel = PER_PIXEL_FIXED + PER_TAP * _taps(config) + PER_PIXEL_OUT + PER_PIXEL_U8
    return {"flops": per_pixel * pixels,
            "bytes": 2.0 * channels * pixels}


def kernel_cost(config, batch_size):
    pixels, channels = _pixels(config, batch_size)
    per_pixel = PER_PIXEL_FIXED + PER_TAP * _taps(config) + PER_PIXEL_OUT
    return {"flops": per_pixel * pixels,
            "bytes": 4.0 * (channels + 1) * pixels}
