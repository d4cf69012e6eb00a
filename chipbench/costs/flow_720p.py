"""Operations and bytes one padded batch of the Farneback flow warp needs,
from the algorithm's own counts (XLA's ``bytes accessed`` reads 1.6e13 for
this program: gathers and resizes counted as dense traffic). Counted low
wherever there is a choice, so a share of the roofline cannot pass 100% by
counting:

- each frame is expanded once (a frame that is both a pair's current and
  the next pair's previous frame is not counted twice);
- convolutions in their separable form, 2 operations a tap;
- the warps as 4-tap bilinear samples, whatever taps a kernel spends;
- bytes: only what must cross HBM whatever the fusion does, the uint8
  frames in and out. The float32 pyramid, the polynomial stacks, the
  session table's previous frames and every intermediate are the
  implementation's, and are left out.

Per estimation-grid pixel (H/flow_scale x W/flow_scale, summed over the
pyramid: 1 + 1/4 + 1/16 ... of it):
  expansion    3 vertical + 6 horizontal passes of 11 taps, 5 x 6 solve  = 258
  an iteration 5-channel bilinear sample (40), the update's algebra (45),
               two win_size-tap passes over 5 channels (20 * win_size),
               the 2 x 2 solve (20)
Per full-resolution pixel: gray (5), the flow's bilinear upsampling (16),
the 3-channel bilinear warp (24), the output's scale, round and cast (9).
"""


def _grid_pixels(config):
    g, kw = config["geometry"], config["filter"]["kwargs"]
    eh, ew = g["height"] // kw["flow_scale"], g["width"] // kw["flow_scale"]
    return sum(max(8, round(eh * 0.5 ** lv)) * max(8, round(ew * 0.5 ** lv))
               for lv in range(kw["levels"]))


def cost(config, batch_size):
    g, kw = config["geometry"], config["filter"]["kwargs"]
    full = g["height"] * g["width"]
    per_grid_pixel = 258.0 + kw["n_iters"] * (40.0 + 45.0 + 20.0 * kw["win_size"] + 20.0)
    flops = per_grid_pixel * _grid_pixels(config) + (5.0 + 16.0 + 24.0 + 9.0) * full
    return {"flops": flops * batch_size,
            "bytes": 2.0 * full * g["channels"] * batch_size}


def warp_cost(config, batch_size):
    """The final bounded warp alone (the Pallas kernel): it must read the
    previous frame (3 float32 channels) and the flow (2) and write the
    warped frame (3); a bilinear sample is 4 taps of 2 operations a
    channel plus 6 for the two weights. The kernel's padding, transposes
    and its (2 max_disp + 2)^2 hat taps are its own and are left out."""
    g = config["geometry"]
    full = g["height"] * g["width"] * batch_size
    return {"flops": (8.0 * g["channels"] + 6.0) * full,
            "bytes": 4.0 * (2 * g["channels"] + 2) * full}
