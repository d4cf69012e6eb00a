"""The step program's share of its roofline: max(bytes / HBM peak, FLOPs / bf16 peak) / step."""
from chipbench.reduce import roofline_pct


def read(ctx):
    if ctx["trace"] is None or ctx["trace"]["step_ms"] is None:
        return None
    cost = ctx["cell"].cost(ctx["cell"].config, ctx["cell"].batch_size)
    pct, binds = roofline_pct(cost, ctx["peak"], ctx["trace"]["step_ms"])
    ctx["log"](f"[layer] step_roofline: the {binds} bound binds")
    return pct
