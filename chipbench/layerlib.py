"""What the per-layer readers share: window deltas of the program's
cumulative counters. A reader is ``read(ctx) -> number or None``; ``ctx``
holds the cell, the generator's record, the program's counters at the
window's open and close (``before``/``after``), the reduced trace (or
None) and the device's peaks."""


def _bucket_pairs(ctx):
    """(before_row or None, after_row) per bucket, matched by position
    within its signature (one bucket per replica)."""
    before, seen, out = {}, {}, []
    if ctx["before"] is None or ctx["after"] is None:     # the window was not watched
        return out
    for row in ctx["before"]["buckets"]:
        before.setdefault(row["signature"], []).append(row)
    for row in ctx["after"]["buckets"]:
        i = seen.get(row["signature"], 0)
        seen[row["signature"]] = i + 1
        prev = before.get(row["signature"], [])
        out.append((prev[i] if i < len(prev) else None, row))
    return out


def window_mean(ctx, side, fields):
    """Mean per batch, over the window's batches and every replica, of the
    sum of ``fields`` in the bucket's ``side`` block ("ingest"/"egress").
    The program reports lifetime means; times batches gives totals."""
    total_ms, batches = 0.0, 0
    for prev, row in _bucket_pairs(ctx):
        if side not in row:
            continue
        n1 = row[side]["batches"]
        t1 = sum(row[side][f] for f in fields) * max(1, n1)
        n0 = t0 = 0
        if prev is not None and side in prev:
            n0 = prev[side]["batches"]
            t0 = sum(prev[side][f] for f in fields) * max(1, n0)
        total_ms += t1 - t0
        batches += n1 - n0
    return total_ms / batches if batches > 0 else None


def batch_fill_pct(ctx):
    """Valid rows over batch rows, from the window's routed frames and
    batches (exact counts; the program's mean_valid_rows is a moving
    average of the same thing)."""
    rows = batches = size = 0
    for prev, row in _bucket_pairs(ctx):
        rows += row["routed_frames_total"] - (prev["routed_frames_total"] if prev else 0)
        batches += row["batches"] - (prev["batches"] if prev else 0)
        size = row["batch_size"]
    return 100.0 * rows / (batches * size) if batches > 0 else None


def hold_window(ctx):
    """Window deltas of the bucket rows' ``hold`` block ("a short batch
    waits for the device", serve/server.py::_Bucket), summed over buckets
    and replicas: {"batches", "held", "hold_ms"}. ``batches`` is the
    block's own short + full count, taken where ``held`` is (at the
    submit, on the dispatch thread; the row's ``batches`` counts on the
    collect thread, a batch in flight later). None where the window was
    not watched or no bucket reports the block."""
    out = None
    for prev, row in _bucket_pairs(ctx):
        if "hold" not in row:
            continue
        was = (prev or {}).get("hold", {})
        out = out or {"batches": 0, "held": 0, "hold_ms": 0.0}
        out["batches"] += sum(row["hold"][k] - was.get(k, 0)
                              for k in ("short_batches_total", "full_batches_total"))
        out["held"] += row["hold"]["held_batches_total"] - was.get("held_batches_total", 0)
        out["hold_ms"] += row["hold"]["hold_ms_total"] - was.get("hold_ms_total", 0.0)
    return out


def trace_value(ctx, key):
    return ctx["trace"][key] if ctx["trace"] is not None else None
