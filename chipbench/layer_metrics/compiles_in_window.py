"""XLA backend compilations (or cache loads) inside the measured window."""
from chipbench import stagelib


def read(ctx):
    return stagelib.compiles_in_window(ctx, "compiles_in_window")
