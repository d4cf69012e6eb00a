"""Host ms per batch inside the engine's submit call: the jitted step's dispatch on the dispatch thread (row map included)."""
from chipbench import dispatchlib


def read(ctx):
    return dispatchlib.split_ms(ctx, "step dispatch")
