"""Mean ms per delivered frame from the device result being ready to the session's out queue."""
from chipbench import stagelib


def read(ctx):
    return stagelib.per_frame_ms(ctx, "egress_path_ms.live", ("d2h", "deliver"))
