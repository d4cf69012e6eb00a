"""us inside FleetFrontend.submit and poll (entry to return) a delivered frame, from the front door's own cumulative clocks; the calls a delivery are logged beside it."""
from chipbench import fleetlib


def read(ctx):
    return fleetlib.fleet_door_us(ctx, "fleet_door_us")
