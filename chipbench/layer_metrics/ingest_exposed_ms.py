"""Host ms per batch the dispatch thread spent staging frames and waiting for H2D."""
from chipbench.layerlib import window_mean


def read(ctx):
    return window_mean(ctx, "ingest", ("stage_ms", "h2d_wait_ms"))
