"""Share of the traced window in which no operation ran on the fullest device."""
from chipbench.layerlib import trace_value


def read(ctx):
    return trace_value(ctx, "idle_pct")
