"""MiB of device memory the session-state tables hold at the window's close."""
from chipbench.layerlib import _bucket_pairs


def read(ctx):
    held = [row["state"]["bytes"] for _, row in _bucket_pairs(ctx) if "state" in row]
    return sum(held) / 2.0 ** 20 if held else None
