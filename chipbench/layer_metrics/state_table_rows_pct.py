"""Share of the window's valid rows whose predecessor came from the session-state table (the rest followed a row of their own batch)."""
from chipbench.layerlib import _bucket_pairs


def state_delta(ctx, pick):
    """after - before of ``pick(state block)``, summed over the buckets
    that report a ``state`` block; None where the window was not watched
    or no bucket has one (a program without per-session state)."""
    deltas = [pick(row["state"]) - (pick(prev["state"]) if prev and "state" in prev else 0)
              for prev, row in _bucket_pairs(ctx) if "state" in row]
    return sum(deltas) if deltas else None


def read(ctx):
    table = state_delta(ctx, lambda st: st["table_rows_total"])
    chain = state_delta(ctx, lambda st: st["chain_rows_total"])
    if table is None or table + chain <= 0:
        return None
    return 100.0 * table / (table + chain)
