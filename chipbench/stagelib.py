"""What the readers of the program's stage counters share.

Every bucket row of ``ServeFrontend.stats()`` carries a ``stages`` block
(``dvf_tpu/obs/metrics.py::StageStats``): per component of a frame's way
through the service a cumulative frame count, frame-weighted and per-batch
millisecond totals and a histogram on fixed log-spaced edges, all read off
one set of wall-clock stamps per batch. A reader takes the window delta of
that block between the counter reads at the window's open and close
(``ctx["before"]`` / ``ctx["after"]``), summed over buckets. A program
without the block (any commit before the counters) gives ``None`` and the
result line leaves the metric out.
"""

from chipbench.layerlib import _bucket_pairs

FRAME_COMPONENTS = ("queue_ingress", "queue_bucket", "permit_wait", "assemble_h2d",
                    "inflight_wait", "device", "d2h", "deliver")


def _dense(pairs, bins):
    out = [0] * bins
    for i, n in pairs:
        out[i] += n
    return out


def _cell_delta(after, before, bins):
    """after − before of one component's cell; ``before`` may be None."""
    before = before or {}
    hist = _dense(after["hist"], bins)
    for i, n in before.get("hist", []):
        hist[i] -= n
    return {"ms": after.get("ms_total", 0.0) - before.get("ms_total", 0.0),
            "batches": after.get("batches", 0) - before.get("batches", 0),
            "batch_ms": after.get("batch_ms_total", 0.0) - before.get("batch_ms_total", 0.0),
            "hist": hist}


def _add(total, part):
    if total is None:
        return part
    return {"ms": total["ms"] + part["ms"], "batches": total["batches"] + part["batches"],
            "batch_ms": total["batch_ms"] + part["batch_ms"],
            "hist": [a + b for a, b in zip(total["hist"], part["hist"])]}


def window(ctx):
    """The window's delta over every bucket, or None: the window was not
    watched, the program has no ``stages`` block, or no batch ran."""
    if "stage_window" in ctx:
        return ctx["stage_window"]
    out = None
    pairs = [(p, a) for p, a in _bucket_pairs(ctx) if "stages" in a]
    if pairs:
        first = pairs[0][1]["stages"]
        out = {"lo_ms": first["hist_lo_ms"], "per_decade": first["hist_bins_per_decade"],
               "bins": first["hist_bins"], "delivered": 0, "latency_ms": 0.0, "wall_ms": 0.0,
               "components": {c: None for c in FRAME_COMPONENTS}, "route": None}
        per_signature = {}
        for prev, row in pairs:
            a, b = row["stages"], (prev or {}).get("stages")
            per_signature[row["signature"]] = per_signature.get(row["signature"], 0) + 1
            out["delivered"] += a["delivered"] - (b["delivered"] if b else 0)
            out["latency_ms"] += a["latency_ms_total"] - (b["latency_ms_total"] if b else 0.0)
            if b:                       # the two reads' own clock: the window they span
                out["wall_ms"] = max(out["wall_ms"], (a["t"] - b["t"]) * 1e3)
            for c in FRAME_COMPONENTS:
                out["components"][c] = _add(out["components"][c], _cell_delta(
                    a["components"][c], b["components"][c] if b else None, out["bins"]))
            out["route"] = _add(out["route"], _cell_delta(
                a["route"], b["route"] if b else None, out["bins"]))
        # one bucket per replica and signature: rows of one signature are replicas,
        # each with a collect thread of its own
        out["replicas"] = max(per_signature.values())
        if out["route"]["batches"] <= 0:
            out = None
    ctx["stage_window"] = out
    return out


def quantile(win, hist, q):
    """The q-quantile (0..1) of a histogram delta, in ms, interpolated on
    the log scale inside its bin; None for an empty histogram."""
    total = sum(hist)
    if total <= 0:
        return None
    want, seen = q * total, 0.0
    for i, n in enumerate(hist):
        if n <= 0:
            continue
        if seen + n >= want:
            frac = (want - seen) / n
            if i == 0:                                   # [0, lo)
                return win["lo_ms"] * frac
            lo = win["lo_ms"] * 10.0 ** ((i - 1) / win["per_decade"])
            if i == win["bins"] - 1:                     # [100 s, inf)
                return lo
            return lo * 10.0 ** (frac / win["per_decade"])
        seen += n
    return None


def describe(ctx, metric, win, names, what="frames"):
    """One [layer] line per component: p50, p95 and max from the histogram
    delta (max = the upper edge of the highest occupied bin)."""
    for name in names:
        cell = win["route"] if name == "route" else win["components"][name]
        hist = cell["hist"]
        top = max((i for i, n in enumerate(hist) if n > 0), default=None)
        if top is None:
            continue
        upper = (win["lo_ms"] * 10.0 ** (top / win["per_decade"])
                 if top < win["bins"] - 1 else float("inf"))
        ctx["log"](f"[layer] {metric}: {name} p50 {quantile(win, hist, 0.5):.3f} ms, p95 "
                   f"{quantile(win, hist, 0.95):.3f} ms, max under {upper:.3f} ms over "
                   f"{sum(hist)} {what}"
                   + (f"; {cell['batches']} batches, {cell['batch_ms']:.1f} ms in them"
                      if cell["batches"] else ""))


def per_frame_ms(ctx, metric, names):
    """Mean per delivered frame of the sum of the named components."""
    win = window(ctx)
    if win is None:
        return None
    describe(ctx, metric, win, names)
    if win["delivered"] <= 0:
        return 0.0
    return sum(win["components"][c]["ms"] for c in names) / win["delivered"]


def per_batch_ms(ctx, metric, name):
    """Mean per batch of one batch-level component or of ``route``."""
    win = window(ctx)
    if win is None:
        return None
    describe(ctx, metric, win, [name], "batches" if name == "route" else "frames")
    cell = win["route"] if name == "route" else win["components"][name]
    return cell["batch_ms"] / cell["batches"] if cell["batches"] > 0 else 0.0


def collect_thread_pct(ctx, metric):
    """d2h + route batch totals over the wall time between the two counter
    reads, per collect thread."""
    win = window(ctx)
    if win is None or win["wall_ms"] <= 0:
        return None
    busy = win["components"]["d2h"]["batch_ms"] + win["route"]["batch_ms"]
    ctx["log"](f"[layer] {metric}: d2h {win['components']['d2h']['batch_ms']:.1f} ms + route "
               f"{win['route']['batch_ms']:.1f} ms of {win['wall_ms']:.1f} ms between the counter "
               f"reads, {win['replicas']} collect thread(s); device wait "
               f"{win['components']['device']['batch_ms']:.1f} ms")
    return 100.0 * busy / (win["wall_ms"] * win["replicas"])


def compiles_in_window(ctx, metric):
    """Delta of the process-wide ``xla_compiles_total`` (the same number on
    every row: the maximum over rows, not their sum)."""
    if ctx["before"] is None or ctx["after"] is None:
        return None
    a = [r for r in ctx["after"]["buckets"] if "xla_compiles_total" in r]
    b = [r for r in ctx["before"]["buckets"] if "xla_compiles_total" in r]
    if not a or not b:
        return None
    n = max(r["xla_compiles_total"] for r in a) - max(r["xla_compiles_total"] for r in b)
    s = max(r["xla_compile_s_total"] for r in a) - max(r["xla_compile_s_total"] for r in b)
    ctx["log"](f"[layer] {metric}: {n} XLA backend compilations (or cache loads) in the "
               f"window, {s:.3f} s in them")
    return float(n)


def transit_closure(ctx, metric):
    """The live cell's transit from inside: the four components against the
    bucket's own mean delivered latency, and against the generator's
    transit percentile."""
    win = window(ctx)
    if win is None or win["delivered"] <= 0:
        return
    comp, n = win["components"], win["delivered"]
    parts = {"frame_queue_ms": comp["queue_ingress"]["ms"] + comp["queue_bucket"]["ms"],
             "permit_wait (per frame)": comp["permit_wait"]["ms"],
             "inflight_ms": comp["assemble_h2d"]["ms"] + comp["inflight_wait"]["ms"]
             + comp["device"]["ms"],
             "egress_path_ms": comp["d2h"]["ms"] + comp["deliver"]["ms"]}
    total, latency = sum(parts.values()) / n, win["latency_ms"] / n
    ctx["log"](f"[layer] {metric}: closure over {n} delivered frames: "
               + " + ".join(f"{k} {v / n:.3f}" for k, v in parts.items())
               + f" = {total:.3f} ms against the bucket's mean delivered latency {latency:.3f} ms "
               f"({100.0 * (total - latency) / latency if latency else 0.0:+.4f}%)")
    transit = sorted((t - due) * 1e3 for due, t in ctx["rec"].transit)
    if transit:
        p50 = transit[len(transit) // 2]
        ctx["log"](f"[layer] {metric}: the generator's transit p50 {p50:.3f} ms is {p50 - latency:+.3f} "
                   f"ms from that mean: due → submit (the generator's lateness) is inside "
                   f"queue_ingress, the wait in the session's out queue until the next poll is not")
