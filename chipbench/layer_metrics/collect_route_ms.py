"""Mean ms per batch the collect thread spent in router.route (row copies, reorder, delivery)."""
from chipbench import stagelib


def read(ctx):
    return stagelib.per_batch_ms(ctx, "collect_route_ms", "route")
