"""How late the generator submitted, against each frame's due time (open loop)."""
import statistics


def read(ctx):
    late = ctx["rec"].late_ms
    return statistics.quantiles(late, n=20)[-1] if len(late) >= 20 else None
