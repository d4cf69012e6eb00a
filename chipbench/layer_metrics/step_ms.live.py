"""Device time of the Engine step program per batch, from the trace."""
from chipbench.layerlib import trace_value


def read(ctx):
    return trace_value(ctx, "step_ms")
