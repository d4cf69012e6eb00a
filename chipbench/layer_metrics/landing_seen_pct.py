"""Share of the window's batches whose landing the collect thread saw (the bytes were not yet on the chip when it looked): the guard of device_landing_pct."""
from chipbench import linklib


def read(ctx):
    return linklib.landing_seen_pct(ctx, "landing_seen_pct")
