"""Valid rows as a share of the batch rows the device computed, over the window."""
from chipbench.layerlib import batch_fill_pct


def read(ctx):
    return batch_fill_pct(ctx)
