"""Host ms per batch the collect thread spent waiting for D2H and copying into slabs."""
from chipbench.layerlib import window_mean


def read(ctx):
    return window_mean(ctx, "egress", ("d2h_wait_ms", "copy_ms"))
