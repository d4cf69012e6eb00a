"""Operations and bytes one padded batch of the streamed FastDVDnet needs, by
the whole-program bound. Operations: **two** DenBlocks a frame (one new
stage-1 result, one stage-2 block; the other two stage-1 results of the
window are kept from earlier frames), each 159,048 multiply-adds a
full-resolution pixel by the table below, 2 operations a multiply-add. An
implementation that recomputes the window (four blocks a frame) does twice
this work and reads at half its share. The grouped first convolution is
counted grouped (3,240 a pixel, not the 9,720 of a dense block-diagonal
kernel): zero taps are the implementation's.

Bytes: what must cross HBM whatever the fusion does: the uint8 frames in,
the uint8 frames out, the float32 weights and norm terms once, and the
session table's rows: each session in the batch has its four kept planes
read once and written once a batch, float32 as the configuration states
(``compute_dtype``). Activation traffic between layers is the implementation's, not
the algorithm's, and is left out, so the share cannot be pushed past 100% by
a better fusion. (329.8 GFLOP a 540 x 960 frame.)"""

# (cin of a 3 x 3 kernel's column, cout, pixels of the full resolution a result pixel stands for)
DENBLOCK_CONVS = (
    [(4, 90, 1), (90, 32, 1)]                                   # input block: 12 -> 90 in three groups, 90 -> 32
    + [(32, 64, 4), (64, 64, 4), (64, 64, 4)]                   # down 0: stride 2, CvBlock(64)
    + [(64, 128, 16), (128, 128, 16), (128, 128, 16)]           # down 1
    + [(128, 128, 16), (128, 128, 16), (128, 256, 16)]          # up 2: CvBlock(128), conv -> 4 x 64, shuffle
    + [(64, 64, 4), (64, 64, 4), (64, 128, 4)]                  # up 1: CvBlock(64), conv -> 4 x 32, shuffle
    + [(32, 32, 1), (32, 3, 1)]                                 # output block
)
NORMS = (90, 32, 64, 64, 64, 128, 128, 128, 128, 128, 64, 64, 32)      # channels of each batch norm
DENBLOCKS_PER_FRAME = 2


def denblock_macs_per_pixel():
    return sum(9.0 * cin * cout / share for cin, cout, share in DENBLOCK_CONVS)


def cost(config, batch_size):
    g = config["geometry"]
    pixels = g["height"] * g["width"]
    flops = 2.0 * DENBLOCKS_PER_FRAME * denblock_macs_per_pixel() * pixels
    weights = 2 * 4.0 * (sum(9 * cin * cout for cin, cout, _ in DENBLOCK_CONVS) + 4 * sum(NORMS))
    frame = pixels * g["channels"]
    sessions = min(batch_size, config["serve"]["max_sessions"])
    table = 2.0 * sessions * 4 * frame * 4.0      # four float32 planes a session
    return {"flops": flops * batch_size,
            "bytes": 2.0 * frame * batch_size + weights + table}
