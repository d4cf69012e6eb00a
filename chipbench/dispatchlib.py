"""What the readers of the dispatch thread's own clocks share (PR 40).

Since PR 40 every bucket row of ``ServeFrontend.stats()`` says what the
dispatch thread did from inside: ``stages.prefetch`` (a thread state beside
``route``: submit returned -> ``lane.prefetch`` returned), five cumulative
clocks on the ``ingest`` block, each taken inside the call that does the
work (``stage_ms_total``, ``h2d_put_ms_total``, ``h2d_wait_ms_total``,
``join_ms_total``, ``step_dispatch_ms_total``), ``prefetch_ms_total`` /
``prefetch_rows_total`` on the ``egress`` block, and a ``starved`` block
(``dvf_tpu/obs/metrics.py::StarvedStats``): the ms in which the chip had
nothing of this frontend's to run, by what the dispatch thread was doing.
A reader takes the window delta between the counter reads at the window's
open and close, summed over buckets and replicas. A program without them
(every commit before PR 40) gives ``None`` and the line leaves the metric out.
"""

from chipbench import stagelib
from chipbench.layerlib import _bucket_pairs

# the parts of assemble_h2d, as the [layer] line names them -> the ingest block's key
SPLIT = (("stage", "stage_ms_total"), ("put", "h2d_put_ms_total"),
         ("h2d wait", "h2d_wait_ms_total"), ("join", "join_ms_total"),
         ("step dispatch", "step_dispatch_ms_total"))
STARVED = ("idle", "hold", "permit_wait", "assemble_h2d")


def _has_clocks(row):
    return ("prefetch" in row.get("stages", {}) and "starved" in row
            and "step_dispatch_ms_total" in row.get("ingest", {})
            and "prefetch_ms_total" in row.get("egress", {}))


def window(ctx):
    """The window's deltas, or None: the window was not watched, no batch
    ran, or no bucket row carries the clocks. ``batches`` is the ingest
    block's own count (taken in ``finish``, where the five clocks are
    recorded), ``prefetched`` the ``prefetch`` cell's (both the dispatch
    thread's; the row's ``batches`` counts on the collect thread, a batch
    in flight later)."""
    if "dispatch_window" in ctx:
        return ctx["dispatch_window"]
    out, stages = None, stagelib.window(ctx)
    rows = [(p or {}, a) for p, a in _bucket_pairs(ctx) if _has_clocks(a)]
    if rows and stages is not None and stages["wall_ms"] > 0:
        out = {"wall_ms": stages["wall_ms"], "replicas": stages["replicas"],
               "assemble_h2d_ms": stages["components"]["assemble_h2d"]["batch_ms"],
               "assembled": stages["components"]["assemble_h2d"]["batches"],
               "batches": 0, "split": dict.fromkeys((name for name, _ in SPLIT), 0.0),
               "prefetched": 0, "prefetch_ms": 0.0, "start_ms": 0.0, "rows_started": 0,
               "starved": dict.fromkeys(STARVED, 0.0), "gaps": 0, "max_gap_ms": 0.0}
        for prev, row in rows:
            ing, was = row["ingest"], prev.get("ingest", {})
            out["batches"] += ing["batches"] - was.get("batches", 0)
            for name, key in SPLIT:
                out["split"][name] += ing[key] - was.get(key, 0.0)
            cell, was = row["stages"]["prefetch"], prev.get("stages", {}).get("prefetch", {})
            out["prefetched"] += cell["batches"] - was.get("batches", 0)
            out["prefetch_ms"] += cell["batch_ms_total"] - was.get("batch_ms_total", 0.0)
            eg, was = row["egress"], prev.get("egress", {})
            out["start_ms"] += eg["prefetch_ms_total"] - was.get("prefetch_ms_total", 0.0)
            out["rows_started"] += eg["prefetch_rows_total"] - was.get("prefetch_rows_total", 0)
            st, was = row["starved"], prev.get("starved", {})
            for state in STARVED:
                key = state + "_ms_total"
                out["starved"][state] += st[key] - was.get(key, 0.0)
            out["gaps"] += st["gaps_total"] - was.get("gaps_total", 0)
            out["max_gap_ms"] = max(out["max_gap_ms"], st["max_gap_ms"])    # the lifetime's
        if out["batches"] <= 0 or out["prefetched"] <= 0:
            out = None
    ctx["dispatch_window"] = out
    return out


def split_ms(ctx, name):
    """Mean ms a batch of one of assemble_h2d's parts."""
    win = window(ctx)
    return None if win is None else win["split"][name] / win["batches"]


def dispatch_thread_pct(ctx, metric):
    """assemble_h2d + prefetch batch totals over the wall time between the
    two counter reads, per dispatch thread: the mirror of
    ``stagelib.collect_thread_pct``. The [layer] line splits a batch."""
    win = window(ctx)
    if win is None:
        return None
    n = win["batches"]
    asm = win["assemble_h2d_ms"] / max(1, win["assembled"])
    parts = {name: ms / n for name, ms in win["split"].items()}
    ctx["log"](f"[layer] {metric}: assemble_h2d {win['assemble_h2d_ms']:.1f} ms + prefetch "
               f"{win['prefetch_ms']:.1f} ms of {win['wall_ms']:.1f} ms between the counter "
               f"reads, {win['replicas']} dispatch thread(s); a batch ({n} of them): "
               + " + ".join(f"{name} {ms:.3f}" for name, ms in parts.items())
               + f" + unattributed {asm - sum(parts.values()):.3f} = assemble_h2d {asm:.3f} ms; "
               f"prefetch {win['prefetch_ms'] / win['prefetched']:.3f} ms")
    busy = win["assemble_h2d_ms"] + win["prefetch_ms"]
    return 100.0 * busy / (win["wall_ms"] * win["replicas"])


def prefetch_start_ms(ctx, metric):
    """ms a batch inside the fetcher's ``prefetch``: the pack's dispatch and
    the start of each row's transfer."""
    win = window(ctx)
    if win is None:
        return None
    ctx["log"](f"[layer] {metric}: {win['rows_started'] / win['prefetched']:.2f} transfers "
               f"started a batch over {win['prefetched']} batches; the thread's prefetch state "
               f"(the lane's call, stamp to stamp) {win['prefetch_ms'] / win['prefetched']:.3f} ms")
    return win["start_ms"] / win["prefetched"]


def device_starved_pct(ctx, metric):
    """Share of the time between the two counter reads in which the chip
    had nothing of the frontend's to run (per replica), by what the
    dispatch thread was doing; beside it the traced idle share, where the
    run was traced (that one is of the traced window, the run's last
    seconds, and also holds an H2D that lands after the submit)."""
    win = window(ctx)
    if win is None:
        return None
    wall = win["wall_ms"] * win["replicas"]
    total = sum(win["starved"].values())
    traced = ctx["trace"]["idle_pct"] if ctx.get("trace") is not None else None
    ctx["log"](f"[layer] {metric}: {total:.1f} ms in {win['gaps']} gaps of {win['wall_ms']:.1f} ms "
               f"between the counter reads (longest of the run {win['max_gap_ms']:.1f} ms): "
               + ", ".join(f"{state} {100.0 * ms / wall:.3f}%"
                           for state, ms in win["starved"].items())
               + (f"; the device trace's device_idle_pct {traced:.3f}" if traced is not None
                  else "; not traced: no device_idle_pct beside it"))
    return 100.0 * total / wall
