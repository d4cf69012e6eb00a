"""MB (1e6 bytes) a second landed on the host over the window: the D2H rate the cell sustains, between the two counter reads' own clock."""
from chipbench import spec, stagelib


def read(ctx):
    win = spec.load_module("layer_metrics/egress_bytes_share_pct.py").bytes_window(ctx)
    stages = stagelib.window(ctx)
    if win is None or stages is None or stages["wall_ms"] <= 0 or win["landed"] <= 0:
        return None
    ctx["log"](f"[layer] egress_landed_mb_s: {win['landed']} bytes in "
               f"{stages['wall_ms']:.1f} ms between the counter reads")
    return win["landed"] / 1e6 / (stages["wall_ms"] / 1e3)
