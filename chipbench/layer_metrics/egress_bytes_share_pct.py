"""Share of the window's bytes over the link that left the chip: landed bytes over staged + landed (80 where every row left at twice its height and width, 50 where results have their input's geometry)."""
from chipbench.layerlib import _bucket_pairs


def bytes_window(ctx):
    """Window deltas of the ``ingest`` / ``egress`` blocks' cumulative
    ``bytes_total``, summed over buckets and replicas: {"staged",
    "landed"}. None where the window was not watched or no bucket reports
    the counter on both sides (every commit before the byte counters)."""
    out = None
    for prev, row in _bucket_pairs(ctx):
        sides = [row.get(side, {}) for side in ("ingest", "egress")]
        if any("bytes_total" not in s for s in sides):
            continue
        was = [(prev or {}).get(side, {}).get("bytes_total", 0) for side in ("ingest", "egress")]
        out = out or {"staged": 0, "landed": 0}
        out["staged"] += sides[0]["bytes_total"] - was[0]
        out["landed"] += sides[1]["bytes_total"] - was[1]
    return out


def read(ctx):
    win = bytes_window(ctx)
    if win is None or win["staged"] + win["landed"] <= 0:
        return None
    ctx["log"](f"[layer] egress_bytes_share_pct: {win['staged']} bytes staged to the device, "
               f"{win['landed']} landed on the host in the window")
    return 100.0 * win["landed"] / (win["staged"] + win["landed"])
