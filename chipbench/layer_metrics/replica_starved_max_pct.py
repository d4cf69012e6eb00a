"""The starved share (device_starved_pct's ledger) of the replica that was starved most; device_starved_pct is the mean of the replicas."""
from chipbench import fleetlib


def read(ctx):
    return fleetlib.replica_starved_max_pct(ctx, "replica_starved_max_pct")
