"""Share of the window in which the chip held a dispatched step of the frontend's whose bytes were still on the link (the starved ledger's landings that the collect thread saw); its line sets device_starved_pct + device_landing_pct beside the device trace's idle share."""
from chipbench import linklib


def read(ctx):
    return linklib.device_landing_pct(ctx, "device_landing_pct")
