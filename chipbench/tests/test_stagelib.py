"""The readers of the program's stage counters, on rows made by hand.

Run from the repository's root, on the CPU:

    python3 -m pytest chipbench/tests/test_stagelib.py -q

A ``before`` and an ``after`` set of bucket rows, as ``frontends.counters()``
passes them, with known numbers between them: every reader returns the
window's delta and not the lifetime's; zero counts read 0.0; a program
without the ``stages`` block (every commit before the counters) reads None
and raises nothing; the histogram delta gives the window's percentiles.
"""

import pytest

from chipbench import spec, stagelib

LO, PER_DECADE, BINS = 0.1, 16, 98
NAMES = stagelib.FRAME_COMPONENTS
BATCH_LEVEL = ("permit_wait", "assemble_h2d", "inflight_wait", "device", "d2h")


def bin_of(ms):
    import math
    if ms < LO:
        return 0
    return min(BINS - 1, int(math.log10(ms / LO) * PER_DECADE) + 1)


def stages(t, frames, batches, per_frame_ms, route_ms):
    """A cumulative block: ``frames`` delivered in ``batches`` batches, every
    frame spending per_frame_ms[c] in component c, every batch route_ms in route."""
    comps = {}
    for c in NAMES:
        cell = {"frames": frames, "ms_total": frames * per_frame_ms[c],
                "max_ms": per_frame_ms[c], "hist": [[bin_of(per_frame_ms[c]), frames]]}
        if c in BATCH_LEVEL:
            cell.update(batches=batches, batch_ms_total=batches * per_frame_ms[c])
        comps[c] = cell
    return {"t": t, "delivered": frames,
            "latency_ms_total": frames * sum(per_frame_ms.values()),
            "hist_lo_ms": LO, "hist_bins_per_decade": PER_DECADE, "hist_bins": BINS,
            "components": comps,
            "route": {"max_ms": route_ms, "hist": [[bin_of(route_ms), batches]],
                      "batches": batches, "batch_ms_total": batches * route_ms}}


def merged(a, b):
    """Two cumulative blocks of different per-frame costs, laid end to end."""
    out = dict(b, delivered=a["delivered"] + b["delivered"],
               latency_ms_total=a["latency_ms_total"] + b["latency_ms_total"])
    out["components"] = {}
    for c in NAMES:
        x, y = a["components"][c], b["components"][c]
        cell = {"frames": x["frames"] + y["frames"], "ms_total": x["ms_total"] + y["ms_total"],
                "max_ms": max(x["max_ms"], y["max_ms"]), "hist": x["hist"] + y["hist"]}
        if "batches" in x:
            cell.update(batches=x["batches"] + y["batches"],
                        batch_ms_total=x["batch_ms_total"] + y["batch_ms_total"])
        out["components"][c] = cell
    x, y = a["route"], b["route"]
    out["route"] = {"max_ms": max(x["max_ms"], y["max_ms"]), "hist": x["hist"] + y["hist"],
                    "batches": x["batches"] + y["batches"],
                    "batch_ms_total": x["batch_ms_total"] + y["batch_ms_total"]}
    return out


WARM = dict(queue_ingress=50.0, queue_bucket=50.0, permit_wait=50.0, assemble_h2d=50.0,
            inflight_wait=50.0, device=50.0, d2h=50.0, deliver=50.0)
WINDOW = dict(queue_ingress=3.0, queue_bucket=7.0, permit_wait=40.0, assemble_h2d=6.0,
              inflight_wait=800.0, device=250.0, d2h=150.0, deliver=2.5)


def make_ctx(with_stages=True, window_batches=10, compiles=(4, 4)):
    before = stages(100.0, 160, 10, WARM, 50.0)
    after = merged(before, stages(140.0, 16 * window_batches, window_batches, WINDOW, 12.0))
    rows = []
    for blk, n in ((before, compiles[0]), (after, compiles[1])):
        row = {"signature": "sig", "batches": blk["route"]["batches"]}
        if with_stages:
            row.update(stages=blk, xla_compiles_total=n, xla_compile_s_total=0.5 * n)
        rows.append(row)
    logs = []

    class Rec:
        transit = [(0.0, 1.3), (0.0, 1.26), (0.0, 1.28)]

    return {"before": {"buckets": [rows[0]]}, "after": {"buckets": [rows[1]]},
            "rec": Rec(), "log": logs.append, "logs": logs}


def reader(name):
    return spec.load_module(f"layer_metrics/{name}.py").read


@pytest.mark.parametrize("name,want", [
    ("frame_queue_ms.live", 10.0),
    ("permit_wait_ms.live", 40.0),
    ("permit_wait_ms.bulk", 40.0),
    ("inflight_ms.live", 1056.0),
    ("egress_path_ms.live", 152.5),
    ("collect_route_ms", 12.0),
    ("collect_thread_pct", 100.0 * 10 * (150.0 + 12.0) / 40_000.0),
    ("compiles_in_window", 0.0),
])
def test_reader_returns_the_windows_delta(name, want):
    ctx = make_ctx()
    assert reader(name)(ctx) == pytest.approx(want)
    assert any(line.startswith(f"[layer] {name}:") for line in ctx["logs"])


def test_live_closure_is_logged_and_exact():
    ctx = make_ctx()
    reader("frame_queue_ms.live")(ctx)
    closure = [line for line in ctx["logs"] if "closure over 160 delivered frames" in line]
    assert closure and "(+0.0000%)" in closure[0]
    assert "1258.500 ms" in closure[0]                       # the window's mean, not the lifetime's
    assert any("transit p50 1280.000 ms" in line for line in ctx["logs"])


def test_percentiles_come_from_the_histogram_delta():
    ctx = make_ctx()
    win = stagelib.window(ctx)
    hist = win["components"]["inflight_wait"]["hist"]
    assert sum(hist) == 160                                   # the warm-up's 160 at 50 ms are gone
    ratio = 10.0 ** (1.0 / PER_DECADE)
    for q in (0.5, 0.95):
        assert 800.0 / ratio <= stagelib.quantile(win, hist, q) <= 800.0 * ratio
    assert stagelib.quantile(win, [0] * BINS, 0.5) is None


@pytest.mark.parametrize("name", [
    "frame_queue_ms.live", "permit_wait_ms.live", "permit_wait_ms.bulk", "inflight_ms.live",
    "egress_path_ms.live", "collect_route_ms", "collect_thread_pct", "compiles_in_window"])
def test_a_program_without_the_counters_reads_none(name):
    assert reader(name)(make_ctx(with_stages=False)) is None   # any commit before the counters
    unwatched = dict(make_ctx(), before=None, after=None)
    assert reader(name)(unwatched) is None


def test_counts_of_zero_and_an_idle_window():
    ctx = make_ctx(compiles=(4, 6))
    assert reader("compiles_in_window")(ctx) == 2.0
    idle = make_ctx(window_batches=0)                          # no batch ran in the window
    assert reader("collect_route_ms")(idle) is None
    assert reader("compiles_in_window")(idle) == 0.0
    quiet = make_ctx()                                         # batches, none of them waited
    for blk in (quiet["before"], quiet["after"]):
        cell = blk["buckets"][0]["stages"]["components"]["permit_wait"]
        cell.update(ms_total=0.0, batch_ms_total=0.0, hist=[[0, cell["frames"]]])
    assert reader("permit_wait_ms.bulk")(quiet) == 0.0


def test_replicas_share_the_wall_time():
    """Two rows of one signature are two replicas, each with a collect thread."""
    ctx = make_ctx()
    for side in ("before", "after"):
        ctx[side]["buckets"] = ctx[side]["buckets"] * 2
    assert reader("collect_thread_pct")(ctx) == pytest.approx(
        100.0 * 2 * 10 * 162.0 / (40_000.0 * 2))
    assert reader("collect_route_ms")(ctx) == pytest.approx(12.0)
