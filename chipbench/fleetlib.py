"""What the readers of the fleet front door share (PR 45).

Since PR 45 ``FleetFrontend`` keeps a ``door`` block per replica
(``dvf_tpu/fleet/stats.py::DoorStats``): cumulative ``submit_calls_total``,
``submit_us_total``, ``poll_calls_total``, ``poll_us_total`` and
``deliveries_total``, each call clocked from its entry to its return and
booked under the replica its session is bound to. ``FleetFrontend.stats()
["door"]`` has the fleet's total and the per-replica blocks; a replica's
own block also rides every bucket row of its ``stats_full()["stats"]``
(with ``"replica": <rid>``), which is where these readers find it: the
frontends file passes bucket rows on and nothing else of the fleet's. A
reader takes the window delta between the counter reads at the window's
open and close. A program without the block (every commit before PR 45,
and every ``serve`` cell) gives ``None`` and the line leaves the metric out.
"""

from chipbench.dispatchlib import STARVED
from chipbench.layerlib import _bucket_pairs

DOOR_KEYS = ("submit_calls_total", "submit_us_total", "poll_calls_total", "poll_us_total",
             "deliveries_total")


def door_window(ctx):
    """{replica id: {key: delta}} over the window, or None. A replica's block
    is on each of its bucket rows: one is taken a replica, the same on both
    sides of the delta."""
    after, before = {}, {}
    for prev, row in _replica_pairs(ctx):
        blk = row.get("door")
        if not blk or blk.get("replica") in after:
            continue
        after[blk["replica"]] = blk
        before[blk["replica"]] = (prev or {}).get("door") or {}
    if not after:
        return None
    return {rid: {k: blk[k] - before[rid].get(k, 0) for k in DOOR_KEYS}
            for rid, blk in after.items()}


def fleet_door_us(ctx, metric):
    win = door_window(ctx)
    if win is None:
        return None
    tot = {k: sum(r[k] for r in win.values()) for k in DOOR_KEYS}
    if tot["deliveries_total"] <= 0:
        return None
    n = tot["deliveries_total"]
    ctx["log"](f"[layer] {metric}: {n} deliveries through {len(win)} replicas' doors; a delivery: "
               f"{tot['submit_calls_total'] / n:.2f} submits of "
               f"{tot['submit_us_total'] / max(1, tot['submit_calls_total']):.1f} us + "
               f"{tot['poll_calls_total'] / n:.2f} polls of "
               f"{tot['poll_us_total'] / max(1, tot['poll_calls_total']):.1f} us; by replica (us a "
               f"delivery): " + ", ".join(
                   f"{rid} {(r['submit_us_total'] + r['poll_us_total']) / max(1, r['deliveries_total']):.1f}"
                   for rid, r in sorted(win.items())))
    return (tot["submit_us_total"] + tot["poll_us_total"]) / n


def _replica_pairs(ctx):
    """(before_row or None, after_row) per bucket row, matched by the
    replica its ``door`` block names and its signature, where the rows carry
    the block: ``layerlib._bucket_pairs`` matches by position, which shifts
    when a replica that had finished no batch at the window's open (its row
    is not passed yet) is not the last. Without the block: by position."""
    pairs = _bucket_pairs(ctx)
    if not pairs or not all("door" in row for _, row in pairs):
        return pairs
    before = {(r["door"]["replica"], r["signature"]): r
              for r in ctx["before"]["buckets"] if "door" in r}
    return [(before.get((row["door"]["replica"], row["signature"])), row) for _, row in pairs]


def replica_starved_max_pct(ctx, metric):
    """Each replica's own starved ms over its own wall between the two
    counter reads (its ``stages`` block's clock); the largest. A replica
    with no row at the window's open (no batch finished yet) has no first
    read to take a delta from and is left out, and the line says so."""
    shares, late = [], 0
    for prev, row in _replica_pairs(ctx):
        if "starved" not in row or "stages" not in row:
            continue
        if prev is None or "starved" not in prev or "stages" not in prev:
            late += 1
            continue
        wall_ms = (row["stages"]["t"] - prev["stages"]["t"]) * 1e3
        if wall_ms <= 0:
            continue
        ms = sum(row["starved"][s + "_ms_total"] - prev["starved"].get(s + "_ms_total", 0.0)
                 for s in STARVED)
        shares.append(100.0 * ms / wall_ms)
    if not shares:
        return None
    ctx["log"](f"[layer] {metric}: starved share by replica, in the rows' order: "
               + ", ".join(f"{s:.3f}%" for s in shares)
               + (f"; {late} replica(s) had finished no batch when the window opened and are left out"
                  if late else ""))
    return max(shares)
