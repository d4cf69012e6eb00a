"""Mean ms a held batch's frames waited in ``pending`` for the device before the dispatch thread bound them."""
from chipbench.layerlib import hold_window


def read(ctx):
    win = hold_window(ctx)
    if win is None or win["held"] <= 0:
        return None
    ctx["log"](f"[layer] hold_ms.live: {win['held']} of {win['batches']} batches bound after a "
               f"hold, {win['hold_ms']:.1f} ms held in all")
    return win["hold_ms"] / win["held"]
