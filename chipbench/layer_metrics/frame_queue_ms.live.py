"""Mean ms per delivered frame in the session's ingress queue and the bucket's pending set."""
from chipbench import stagelib


def read(ctx):
    value = stagelib.per_frame_ms(ctx, "frame_queue_ms.live", ("queue_ingress", "queue_bucket"))
    stagelib.transit_closure(ctx, "frame_queue_ms.live")
    return value
