"""Operations and bytes one padded batch of the sub-pixel CNN needs, by the
whole-program bound: the three convolutions' multiply-adds (2 per tap), all
at the INPUT geometry (the shuffle is a rearrangement and has none), and
the bytes that must cross HBM whatever the fusion does: the uint8 frames
in, the uint8 frames out at the OUTPUT geometry (scale^2 bytes for each
byte in), the float32 weights once. Activation traffic between layers,
the float32 tensor the shuffle reads and writes included, is the
implementation's and not the algorithm's, and is left out
(costs/style_720p.py argues the same), so the share cannot be pushed past
100% by a better fusion. (Arithmetic as dvf_tpu/models/analysis.py
espcn_layer_costs: 27.67 GFLOP a frame at 540 x 960, scale 2.)"""


def cost(config, batch_size):
    g = config["geometry"]
    h, w, c = g["height"], g["width"], g["channels"]
    r = int(config["filter"]["kwargs"]["scale"])
    convs = [(5, c, 64), (3, 64, 32), (3, 32, c * r * r)]       # (k, cin, cout)
    flops = sum(2.0 * k * k * cin * cout * h * w for k, cin, cout in convs)
    weights = sum(4.0 * (k * k * cin * cout + cout) for k, cin, cout in convs)
    frame_in = h * w * c
    frame_out = (h * r) * (w * r) * c
    return {"flops": flops * batch_size,
            "bytes": float(frame_in + frame_out) * batch_size + weights}
