"""Operations and bytes one padded batch of the style net needs, by the
whole-program bound: the convolutions' multiply-adds (2 per tap), and the
bytes that must cross HBM whatever the fusion does: the uint8 frames in,
the uint8 frames out, the float32 weights once. Activation traffic between
layers is the implementation's, not the algorithm's, and is left out, so
the share cannot be pushed past 100% by a better fusion. (Arithmetic taken
from dvf_tpu/models/analysis.py and benchmarks/NEURAL_ROOFLINE.md: 283.47
GFLOP a frame at 720p, c=32, n=5.)"""


def cost(config, batch_size):
    g = config["geometry"]
    h, w = g["height"], g["width"]
    kw = config["filter"]["kwargs"]
    c, n = kw["base_channels"], kw["n_residual"]
    # (k, cin, cout, out_h, out_w)
    convs = [(9, 3, c, h, w), (3, c, 2 * c, h // 2, w // 2),
             (3, 2 * c, 4 * c, h // 4, w // 4)]
    convs += [(3, 4 * c, 4 * c, h // 4, w // 4)] * (2 * n)
    convs += [(3, 4 * c, 2 * c, h // 2, w // 2), (3, 2 * c, c, h, w),
              (9, c, 3, h, w)]
    flops = sum(2.0 * k * k * cin * cout * oh * ow for k, cin, cout, oh, ow in convs)
    weights = sum(4.0 * (k * k * cin * cout + cout) for k, cin, cout, _, _ in convs)
    frame = h * w * g["channels"]
    return {"flops": flops * batch_size,
            "bytes": 2.0 * frame * batch_size + weights}
