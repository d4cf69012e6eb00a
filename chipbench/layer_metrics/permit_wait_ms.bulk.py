"""Mean ms per batch between the batch being chosen (frozen) and its in-flight permit."""
from chipbench import stagelib


def read(ctx):
    return stagelib.per_batch_ms(ctx, "permit_wait_ms.bulk", "permit_wait")
