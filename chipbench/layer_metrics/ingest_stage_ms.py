"""Host ms per batch copying frames (and padding rows) into the staging slabs: the copy alone, no wait for the link."""
from chipbench import dispatchlib


def read(ctx):
    return dispatchlib.split_ms(ctx, "stage")
