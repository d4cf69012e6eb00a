"""The two readers of the bucket row's ``hold`` block, and the rule the
live cell's bounds were set by, on numbers made by hand.

    python3 -m pytest chipbench/tests/test_hold_readers.py -q

``before``/``after`` are bucket rows as ``frontends.counters()`` passes
them. The readers return the window's delta and not the lifetime's; a
program without the block (every commit before PR 30), a window that was
not watched, and a window with no held batch read None.
"""

import pytest

from chipbench import layerlib, spec, spread


def row(short, full, held, hold_ms, batches=None, with_hold=True):
    out = {"signature": "sig", "batches": short + full if batches is None else batches}
    if with_hold:
        out["hold"] = {"short_batches_total": short, "full_batches_total": full,
                       "held_batches_total": held, "hold_ms_total": hold_ms}
    return out


def make_ctx(before, after):
    logs = []
    return {"before": None if before is None else {"buckets": before},
            "after": None if after is None else {"buckets": after},
            "log": logs.append, "logs": logs}


def reader(name):
    return spec.load_module(f"layer_metrics/{name}.py").read


WARM = row(short=90, full=10, held=50, hold_ms=9000.0)          # ramp: half held, 180 ms each
AFTER = row(short=505, full=15, held=449, hold_ms=9000.0 + 399 * 64.0)


def test_readers_return_the_windows_delta():
    ctx = make_ctx([WARM], [AFTER])
    assert reader("held_batches_pct.live")(ctx) == pytest.approx(100.0 * 399 / 420)
    assert reader("hold_ms.live")(ctx) == pytest.approx(64.0)
    assert any(line.startswith("[layer] hold_ms.live: 399 of 420") for line in ctx["logs"])


def test_the_share_is_over_the_blocks_own_batches():
    """The row's ``batches`` counts on the collect thread, a batch in
    flight behind the dispatch thread's: the share's base is short + full."""
    ctx = make_ctx([WARM], [dict(AFTER, batches=AFTER["batches"] - 1)])
    assert reader("held_batches_pct.live")(ctx) == pytest.approx(100.0 * 399 / 420)


def test_two_replicas_add_up():
    other = row(short=200, full=0, held=100, hold_ms=5000.0)
    other_after = row(short=300, full=0, held=121, hold_ms=5000.0 + 21 * 10.0)
    ctx = make_ctx([WARM, other], [AFTER, other_after])
    assert layerlib.hold_window(ctx) == {"batches": 520, "held": 420,
                                         "hold_ms": pytest.approx(399 * 64.0 + 210.0)}
    assert reader("held_batches_pct.live")(ctx) == pytest.approx(100.0 * 420 / 520)
    assert reader("hold_ms.live")(ctx) == pytest.approx((399 * 64.0 + 210.0) / 420)


def test_a_bucket_that_opened_inside_the_window_counts_whole():
    ctx = make_ctx([], [AFTER])
    assert reader("held_batches_pct.live")(ctx) == pytest.approx(100.0 * 449 / 520)


@pytest.mark.parametrize("name", ["held_batches_pct.live", "hold_ms.live"])
@pytest.mark.parametrize("case", ["no_hold_block", "unwatched", "none_held", "no_batches"])
def test_nothing_to_read_is_none(name, case):
    ctx = {
        "no_hold_block": make_ctx([row(90, 10, 0, 0.0, with_hold=False)],
                                  [row(505, 15, 0, 0.0, with_hold=False)]),
        "unwatched": make_ctx(None, None),
        # a bulk cell: every batch full, none held
        "none_held": make_ctx([row(0, 100, 0, 0.0)], [row(0, 573, 0, 0.0)]),
        "no_batches": make_ctx([WARM], [WARM]),
    }[case]
    assert reader(name)(ctx) is None


# -- the rule (PERF.md section 2) ------------------------------------------

def test_trimmed_range_leaves_out_the_farthest_run_where_that_narrows():
    assert spread.trimmed_range([10.0, 10.2, 10.1, 10.3, 10.4, 14.0]) == pytest.approx(0.4)
    assert spread.trimmed_range([10.0, 10.2, 10.1, 10.3, 10.4, 6.0]) == pytest.approx(0.4)
    assert spread.trimmed_range([5.0, 5.0, 5.0]) == 0.0
    assert spread.trimmed_range([1.0, 2.0]) == 1.0


def test_quartile_spread_is_pythons_exclusive_quartiles():
    assert spread.quartile_spread([1.0, 2.0, 3.0, 4.0, 5.0, 6.0]) == pytest.approx(3.5)


@pytest.mark.parametrize("spreads,want", [
    ([0.0134, 0.0131, 0.0124], 0.045),       # 3 x 1.34% = 4.02% -> the next 0.005
    ([0.0052, 0.0100], 0.03),                # exactly on a step stays there
    ([0.0130], 0.04),
    ([0.0300], 0.06),                        # capped
])
def test_bound_from(spreads, want):
    assert spread.bound_from(spreads) == pytest.approx(want)


# -- through the frontend, at toy size on the CPU ---------------------------

def _traced_toy_run(workload):
    """(result line, the counters the window's watch read at t0 and t1)."""
    import os
    import threading
    import time

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from chipbench import run

    reads = []

    def record_counters(front):
        sound = front.counters

        def counters():
            got = sound()
            if threading.current_thread().name == "chipbench-window-watch":
                reads.append(got)
            return got

        front.counters = counters

    cell = spec.Cell(workload, toy=True)
    result = run.run_cell(cell, seed=2147483803, seconds=3.0, trace=True, require_tpu=False,
                          t_start=time.time(), front_hook=record_counters,
                          log=lambda msg: None)
    assert result["correct"] and len(reads) == 2          # the watch's reads at t0 and t1
    return (result, *reads)


def test_live_run_reads_what_the_hold_block_counted():
    result, before, after = _traced_toy_run("style_720p.live")
    (b,), (a,) = before["buckets"], after["buckets"]
    delta = {k: a["hold"][k] - b["hold"][k] for k in a["hold"]}
    bound = delta["short_batches_total"] + delta["full_batches_total"]
    assert bound > 0 and delta["held_batches_total"] > 0  # the toy step outlasts a tick
    assert result["metrics"]["held_batches_pct.live"]["value"] == pytest.approx(
        100.0 * delta["held_batches_total"] / bound)
    assert result["metrics"]["hold_ms.live"]["value"] == pytest.approx(
        delta["hold_ms_total"] / delta["held_batches_total"])


def test_bulk_run_holds_nothing_and_reports_neither():
    result, before, after = _traced_toy_run("invert_1080p.bulk")
    assert "held_batches_pct.live" not in result["metrics"]
    assert "hold_ms.live" not in result["metrics"]
    ctx = {"before": before, "after": after, "log": lambda msg: None}
    win = layerlib.hold_window(ctx)
    assert win["batches"] > 0
    # a closed loop keeps a full batch pending: at most a straggler is held
    share = reader("held_batches_pct.live")(ctx)
    assert share is None or share < 50.0
