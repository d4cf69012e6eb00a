"""Share of the window's batches bound after at least one tick's hold behind the device's backlog."""
from chipbench.layerlib import hold_window


def read(ctx):
    win = hold_window(ctx)
    if win is None or win["held"] <= 0:       # a held batch is one of the block's batches
        return None
    return 100.0 * win["held"] / win["batches"]
