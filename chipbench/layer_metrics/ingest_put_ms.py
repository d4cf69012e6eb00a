"""Host ms per batch inside the ``device_put`` calls: a synchronous host-side relayout shows here, an asynchronous one in the wait (``ingest_exposed_ms``)."""
from chipbench import dispatchlib


def read(ctx):
    return dispatchlib.split_ms(ctx, "put")
