"""What the readers of a batch's landing share (PR 54).

Since PR 54 the serve path stamps where a batch's H2D lands (``BatchStamps.
t_landed``: the collect thread waits on the batch's last device frame before it
waits for the step, and stamps only where it saw the landing) and the bucket
row's ``starved`` block (``dvf_tpu/obs/metrics.py::StarvedStats``) books it:
``landing_ms_total`` (the chip held a dispatched step whose bytes were still on
the link, and nothing else of the frontend's), ``landing_unseen_ms_total`` (an
upper bound for the batches whose bytes were there before the thread looked;
added in nowhere), ``landed_seen_total`` / ``landed_unseen_total`` (batches). A
reader takes the window delta between the counter reads at the window's open
and close, summed over buckets and replicas, over the denominator
``device_starved_pct`` uses. A program without the keys (every commit before
PR 54), a window that was not watched and a window with no batch read ``None``,
with the reason on the ``[layer]`` line, and raise nothing.
"""

from chipbench import dispatchlib, stagelib
from chipbench.fleetlib import _replica_pairs

KEYS = {"landing_ms": "landing_ms_total", "unseen_ms": "landing_unseen_ms_total",
        "seen": "landed_seen_total", "unseen": "landed_unseen_total"}


def _read(ctx):
    """(window or None, the reason it is None)."""
    if ctx.get("before") is None or ctx.get("after") is None:
        return None, "the window's counters were not read"
    rows = [(p, a) for p, a in _replica_pairs(ctx)
            if all(k in a.get("starved", {}) for k in KEYS.values())]
    if not rows:
        return None, "no bucket row carries the landing counters (a program before PR 54)"
    stages = stagelib.window(ctx)
    if stages is None or stages["wall_ms"] <= 0:
        return None, "no batch ran between the two counter reads"
    win = {"wall_ms": stages["wall_ms"], "replicas": stages["replicas"], "by_replica": [],
           "late": 0, **dict.fromkeys(KEYS, 0)}
    for prev, row in rows:
        blk, was = row["starved"], (prev or {}).get("starved", {})
        delta = {name: blk[key] - was.get(key, 0) for name, key in KEYS.items()}
        for name, value in delta.items():
            win[name] += value
        if prev is None or "stages" not in prev or "stages" not in row:
            win["late"] += 1            # no first read of this replica: no wall of its own
            continue
        delta["wall_ms"] = (row["stages"]["t"] - prev["stages"]["t"]) * 1e3
        if delta["wall_ms"] > 0:
            win["by_replica"].append(delta)
    return win, None


def window(ctx, metric):
    """The window's deltas: the keys of ``KEYS`` summed over buckets and
    replicas, ``wall_ms`` and ``replicas`` (``stagelib.window``'s, the
    denominator of ``device_starved_pct``), ``by_replica`` (each replica's own
    deltas and its own ``wall_ms`` between its two reads), ``late`` (replicas
    with no row at the window's open). Or None, the reason logged under
    ``metric``."""
    if "link_window" not in ctx:
        ctx["link_window"] = _read(ctx)
    win, why = ctx["link_window"]
    if win is None:
        ctx["log"](f"[layer] {metric}: None: {why}")
    return win


def _shares(win, pick):
    return ", ".join(f"{pick(r):.3f}" for r in win["by_replica"]) + (
        f" ({win['late']} replica(s) had no row when the window opened and are left out)"
        if win["late"] else "")


def device_landing_pct(ctx, metric):
    """Share of the time between the two counter reads, per replica, in which
    the chip held a dispatched step of the frontend's whose bytes were still
    on the link (seen landings only). The line sets it beside
    ``device_starved_pct`` and, in a traced run, the trace's idle share."""
    win = window(ctx, metric)
    if win is None:
        return None
    wall = win["wall_ms"] * win["replicas"]
    value = 100.0 * win["landing_ms"] / wall
    line = (f"[layer] {metric}: {win['landing_ms']:.1f} ms in {win['seen']} landings seen, of "
            f"{win['wall_ms']:.1f} ms between the counter reads on {win['replicas']} replica(s); "
            f"{win['unseen']} landings not seen (the bytes were there before the collect thread "
            f"looked), at most {100.0 * win['unseen_ms'] / wall:.3f}% more, added in nowhere; "
            f"by replica, %: {_shares(win, lambda r: 100.0 * r['landing_ms'] / r['wall_ms'])}")
    disp = dispatchlib.window(ctx)
    if disp is not None:
        starved = 100.0 * sum(disp["starved"].values()) / wall
        line += (f"; device_starved_pct {starved:.3f} + device_landing_pct {value:.3f} = "
                 f"{starved + value:.3f}")
        traced = ctx["trace"]["idle_pct"] if ctx.get("trace") is not None else None
        line += (f" beside the device trace's device_idle_pct {traced:.3f}: "
                 f"{traced - starved - value:.3f} points the program does not explain (the step's "
                 f"own dispatch-to-start, landings not seen)" if traced is not None
                 else "; not traced: no device_idle_pct beside it")
    ctx["log"](line)
    return value


def landing_seen_pct(ctx, metric):
    """Share of the window's batches with a landing probe whose landing the
    collect thread saw: how far ``device_landing_pct`` can be trusted here."""
    win = window(ctx, metric)
    if win is None:
        return None
    n = win["seen"] + win["unseen"]
    if n <= 0:
        ctx["log"](f"[layer] {metric}: None: no batch of the window had a landing probe (the "
                   f"slab and monolithic paths)")
        return None
    ctx["log"](f"[layer] {metric}: {win['seen']} landings seen, {win['unseen']} not, over "
               f"{win['replicas']} replica(s); by replica, %: "
               + _shares(win, lambda r: 100.0 * r["seen"] / (r["seen"] + r["unseen"])
                         if r["seen"] + r["unseen"] else float("nan")))
    return 100.0 * win["seen"] / n
