"""The six readers of the dispatch thread's own clocks (PR 40), on rows
made by hand and through a toy run.

    python3 -m pytest chipbench/tests/test_dispatch_readers.py -q

``before``/``after`` are bucket rows as ``frontends.counters()`` passes
them. Every reader returns the window's delta and not the lifetime's; a
program without the clocks (every commit before PR 40: a parent-shaped
row), a window that was not watched and a window with no batch read None
and raise nothing.
"""

import pytest

from chipbench import dispatchlib, spec
from chipbench.tests.test_stagelib import WARM, WINDOW, merged, stages

NAMES = ("dispatch_thread_pct", "ingest_stage_ms", "ingest_put_ms", "step_dispatch_ms",
         "prefetch_start_ms", "device_starved_pct")

# one batch of the window, ms: the five clocks leave 0.4 of assemble_h2d's 6.0
SPLIT = {"stage_ms_total": 2.5, "h2d_put_ms_total": 1.5, "h2d_wait_ms_total": 0.75,
         "join_ms_total": 0.25, "step_dispatch_ms_total": 0.6}
PREFETCH_MS, START_MS, ROWS = 2.0, 1.75, 16
STARVED = {"idle_ms_total": 1.0, "hold_ms_total": 0.5, "permit_wait_ms_total": 0.25,
           "assemble_h2d_ms_total": 4.0}


def row(t, batches, window_batches=0, with_clocks=True, signature="sig"):
    """A cumulative bucket row: ``batches`` warm ones at 50 ms a part, then
    ``window_batches`` at the numbers above."""
    blk = stages(t, 16 * batches, batches, WARM, 50.0)
    if window_batches:
        blk = merged(blk, stages(t, 16 * window_batches, window_batches, WINDOW, 12.0))
    n, w = batches + window_batches, window_batches
    out = {"signature": signature, "batches": n, "stages": blk,
           "ingest": {"batches": n, "stage_ms": 0.0, "h2d_wait_ms": 0.0},
           "egress": {"batches": n}}
    if with_clocks:
        blk["prefetch"] = {"max_ms": 50.0, "hist": [], "batches": n,
                           "batch_ms_total": 50.0 * batches + PREFETCH_MS * w}
        out["ingest"].update({k: 50.0 * batches + v * w for k, v in SPLIT.items()})
        out["egress"].update(prefetch_ms_total=50.0 * batches + START_MS * w,
                             prefetch_rows_total=16 * batches + ROWS * w)
        out["starved"] = {**{k: 50.0 * batches + v * w for k, v in STARVED.items()},
                          "gaps_total": n, "max_gap_ms": 80.0}
    return out


def make_ctx(before, after, trace=None):
    logs = []
    ctx = {"before": None if before is None else {"buckets": before},
           "after": None if after is None else {"buckets": after},
           "log": logs.append, "logs": logs}
    if trace is not None:
        ctx["trace"] = trace
    return ctx


def reader(name):
    return spec.load_module(f"layer_metrics/{name}.py").read


def test_readers_return_the_windows_delta():
    ctx = make_ctx([row(100.0, 10)], [row(140.0, 10, 1000)], trace={"idle_pct": 86.0})
    wall = 40_000.0
    assert reader("dispatch_thread_pct")(ctx) == pytest.approx(100.0 * 1000 * (6.0 + 2.0) / wall)
    assert reader("ingest_stage_ms")(ctx) == pytest.approx(2.5)
    assert reader("ingest_put_ms")(ctx) == pytest.approx(1.5)
    assert reader("step_dispatch_ms")(ctx) == pytest.approx(0.6)
    assert reader("prefetch_start_ms")(ctx) == pytest.approx(1.75)
    assert reader("device_starved_pct")(ctx) == pytest.approx(100.0 * 1000 * 5.75 / wall)
    (line,) = [m for m in ctx["logs"] if m.startswith("[layer] dispatch_thread_pct")]
    assert ("a batch (1000 of them): stage 2.500 + put 1.500 + h2d wait 0.750 + join 0.250 + "
            "step dispatch 0.600 + unattributed 0.400 = assemble_h2d 6.000 ms; "
            "prefetch 2.000 ms") in line
    (line,) = [m for m in ctx["logs"] if m.startswith("[layer] device_starved_pct")]
    assert "in 1000 gaps" in line and "assemble_h2d 10.000%" in line and "hold 1.250%" in line
    assert "device_idle_pct 86.000" in line
    (line,) = [m for m in ctx["logs"] if m.startswith("[layer] prefetch_start_ms")]
    assert "16.00 transfers started a batch over 1000 batches" in line


def test_an_untraced_window_says_so():
    ctx = make_ctx([row(100.0, 10)], [row(140.0, 10, 100)])
    assert reader("device_starved_pct")(ctx) == pytest.approx(100.0 * 100 * 5.75 / 40_000.0)
    assert any("not traced" in m for m in ctx["logs"])


def test_two_replicas_share_the_wall():
    """Rows of one signature are replicas, each with a dispatch thread and
    a chip of its own: the shares are per thread, the ms per batch."""
    ctx = make_ctx([row(100.0, 10), row(100.0, 10)],
                   [row(140.0, 10, 1000), row(140.0, 10, 500)])
    assert dispatchlib.window(ctx)["replicas"] == 2
    assert reader("dispatch_thread_pct")(ctx) == pytest.approx(100.0 * 1500 * 8.0 / 80_000.0)
    assert reader("device_starved_pct")(ctx) == pytest.approx(100.0 * 1500 * 5.75 / 80_000.0)
    assert reader("ingest_stage_ms")(ctx) == pytest.approx(2.5)


def test_a_batch_between_finish_and_the_stamps_moves_the_split_little():
    """The ingest block counts in ``finish``, the ``assemble_h2d`` cell once
    the prefetch returned: a read between the two sees one batch more on
    the block. Each side is divided by its own count."""
    after = row(140.0, 10, 1000)
    after["ingest"]["batches"] += 1
    for k, v in SPLIT.items():
        after["ingest"][k] += v
    ctx = make_ctx([row(100.0, 10)], [after])
    assert reader("ingest_stage_ms")(ctx) == pytest.approx(2.5)
    assert dispatchlib.window(ctx)["assembled"] == 1000


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("case", ["parent_shaped", "unwatched", "no_batches",
                                  "bucket_without_before"])
def test_nothing_to_read_is_none(name, case):
    ctx = {
        # every commit before PR 40: stages, ingest and egress, none of the clocks
        "parent_shaped": make_ctx([row(100.0, 10, with_clocks=False)],
                                  [row(140.0, 10, 1000, with_clocks=False)]),
        "unwatched": make_ctx(None, None),
        "no_batches": make_ctx([row(100.0, 10)], [row(140.0, 10)]),
        # no second read's clock to take the wall from
        "bucket_without_before": make_ctx([], [row(140.0, 10, 1000)]),
    }[case]
    assert reader(name)(ctx) is None


# -- through the frontend, at toy size on the CPU ---------------------------

def test_a_toy_run_reports_all_six_and_the_split_closes():
    from chipbench.tests.test_hold_readers import _traced_toy_run

    result, before, after = _traced_toy_run("invert_1080p.bulk")
    assert set(NAMES) <= set(result["metrics"])
    (b,), (a,) = before["buckets"], after["buckets"]
    n = a["ingest"]["batches"] - b["ingest"]["batches"]
    assert n > 0
    assert result["metrics"]["ingest_stage_ms"]["value"] == pytest.approx(
        (a["ingest"]["stage_ms_total"] - b["ingest"]["stage_ms_total"]) / n)
    assert result["metrics"]["step_dispatch_ms"]["value"] == pytest.approx(
        (a["ingest"]["step_dispatch_ms_total"] - b["ingest"]["step_dispatch_ms_total"]) / n)
    done = a["stages"]["prefetch"]["batches"] - b["stages"]["prefetch"]["batches"]
    assert result["metrics"]["prefetch_start_ms"]["value"] == pytest.approx(
        (a["egress"]["prefetch_ms_total"] - b["egress"]["prefetch_ms_total"]) / done)
    wall_ms = (a["stages"]["t"] - b["stages"]["t"]) * 1e3
    starved = sum(a["starved"][k] - b["starved"][k] for k in STARVED)
    assert result["metrics"]["device_starved_pct"]["value"] == pytest.approx(
        100.0 * starved / wall_ms)
    assert 0.0 <= result["metrics"]["device_starved_pct"]["value"] <= 100.0
    assert 0.0 < result["metrics"]["dispatch_thread_pct"]["value"] <= 100.0
    # the five clocks are parts of assemble_h2d: they do not sum past it
    # (a read may fall between a batch's finish and its stamps: one batch of room)
    asm = (a["stages"]["components"]["assemble_h2d"]["batch_ms_total"]
           - b["stages"]["components"]["assemble_h2d"]["batch_ms_total"])
    parts = sum(a["ingest"][k] - b["ingest"][k] for k in SPLIT)
    assert parts <= asm * (1.0 + 1.0 / n) + 1.0
