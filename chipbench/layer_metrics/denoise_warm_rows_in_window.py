"""Rows served inside the window with a temporal window not yet full: fewer predecessors than the filter reads (four for the five-frame denoiser).

The window's delta of the bucket rows' ``state.warm_rows_total``
(serve/server.py::_Bucket.note_state_rows, counted on the dispatch thread
where ``fresh_rows_total`` is, from each session's frames that reached the
device since its row last restarted). A measured window serves none: every
session opened in the ramp and is ten frames in. A restart under load (an
engine rebuild, a migration) shows here as up to four rows a session, beside
``state_resets_in_window``. None where the window was not watched or the
program states no such counter (any commit before PR 52)."""
from chipbench.layerlib import _bucket_pairs


def state_delta(ctx, pick):
    """after - before of ``pick(state block)``, summed over the buckets
    that report a ``state`` block; ``pick`` returns None where the block
    lacks what it reads. None where the window was not watched, no bucket
    has such a block, or one lacks the field. (state_table_rows_pct.py's,
    for fields an older program does not state.)"""
    deltas = []
    for prev, row in _bucket_pairs(ctx):
        if "state" not in row:
            continue
        after = pick(row["state"])
        before = pick(prev["state"]) if prev and "state" in prev else 0
        if after is None or before is None:
            return None
        deltas.append(after - before)
    return sum(deltas) if deltas else None


def read(ctx):
    return state_delta(ctx, lambda st: st.get("warm_rows_total"))
