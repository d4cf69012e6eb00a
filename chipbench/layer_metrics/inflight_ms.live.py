"""Mean ms per delivered frame from the permit to the device result being ready."""
from chipbench import stagelib


def read(ctx):
    return stagelib.per_frame_ms(ctx, "inflight_ms.live",
                                 ("assemble_h2d", "inflight_wait", "device"))
