"""Share of the window the collect thread spent moving bytes (D2H + route); near 100 it paces."""
from chipbench import stagelib


def read(ctx):
    return stagelib.collect_thread_pct(ctx, "collect_thread_pct")
