"""Host ms per batch starting the way back on the dispatch thread: the egress pack's dispatch and a ``copy_to_host_async`` a valid row."""
from chipbench import dispatchlib


def read(ctx):
    return dispatchlib.prefetch_start_ms(ctx, "prefetch_start_ms")
