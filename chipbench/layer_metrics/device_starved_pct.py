"""Share of the window in which the chip had nothing of the frontend's to run, from the program's own stamps, by the dispatch thread's state; the device trace's idle share is logged beside it."""
from chipbench import dispatchlib


def read(ctx):
    return dispatchlib.device_starved_pct(ctx, "device_starved_pct")
