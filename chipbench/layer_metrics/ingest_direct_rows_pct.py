"""Share of the window's frames that went up as rows from the client's own array (the row path: a put a frame, no host copy) and not through a staging slab: 100 where every batch is eligible (uint8 frames, C-contiguous, one shard on one device), 0 on a program or a lane without the path."""
from chipbench.layerlib import _bucket_pairs


def rows_window(ctx):
    """Window deltas of the ``ingest`` block's cumulative
    ``rows_direct_total`` / ``rows_staged_total`` (and the batches of
    each), summed over buckets and replicas: {"direct", "staged",
    "direct_batches", "staged_batches"}. None where the window was not
    watched or no bucket reports the counters (every commit before the
    row path)."""
    keys = {"direct": "rows_direct_total", "staged": "rows_staged_total",
            "direct_batches": "direct_batches", "staged_batches": "staged_batches"}
    out = None
    for prev, row in _bucket_pairs(ctx):
        block = row.get("ingest", {})
        if any(k not in block for k in keys.values()):
            continue
        was = (prev or {}).get("ingest", {})
        out = out or dict.fromkeys(keys, 0)
        for name, k in keys.items():
            out[name] += block[k] - was.get(k, 0)
    return out


def read(ctx):
    win = rows_window(ctx)
    if win is None or win["direct"] + win["staged"] <= 0:
        return None
    ctx["log"](f"[layer] ingest_direct_rows_pct: {win['direct']} frames in {win['direct_batches']} batches went up "
               f"as rows, {win['staged']} in {win['staged_batches']} through a slab, in the window")
    return 100.0 * win["direct"] / (win["direct"] + win["staged"])
