"""Session-state rows restarted inside the window, whatever the cause (admission, rebuild, migration): a measured window restarts none."""
from chipbench import spec

state_delta = spec.load_module("layer_metrics/state_table_rows_pct.py").state_delta


def read(ctx):
    return state_delta(ctx, lambda st: sum(st["resets_total"].values()))
