"""The two readers of a batch's landing (PR 54), on rows made by hand and
through a toy run.

    python3 -m pytest chipbench/tests/test_link_readers.py -q

``before``/``after`` are bucket rows as ``frontends.counters()`` passes them.
Every reader returns the window's delta and not the lifetime's; a program
without the keys (every commit before PR 54: a parent-shaped row), a window
that was not watched and a window with no batch read None, say why on their
``[layer]`` line, and raise nothing.
"""

import pytest

from chipbench import linklib
from chipbench.tests.test_dispatch_readers import STARVED, make_ctx, reader, row
from chipbench.tests.test_fleet_readers import fleet_row

NAMES = ("device_landing_pct", "landing_seen_pct")

# one batch of the window: a landing seen 10 ms after the chip ran out; an
# unseen one bounded at 4 ms
LANDING_MS, UNSEEN_MS = 10.0, 4.0


def link_row(t, batches, seen=0, unseen=0, rid=None):
    """``row`` (a replica's, with ``rid``) with the landing counters:
    ``batches`` warm ones, each seen at 50 ms a part, then ``seen`` +
    ``unseen`` window batches."""
    out = (row(t, batches, seen + unseen) if rid is None
           else fleet_row(rid, t, batches, seen + unseen))
    out["starved"].update(
        landing_ms_total=50.0 * batches + LANDING_MS * seen,
        landing_unseen_ms_total=UNSEEN_MS * unseen,
        landed_seen_total=batches + seen, landed_unseen_total=unseen)
    return out


def test_readers_return_the_windows_delta():
    ctx = make_ctx([link_row(100.0, 10)], [link_row(140.0, 10, seen=800, unseen=200)],
                   trace={"idle_pct": 86.0})
    wall = 40_000.0
    landing = 100.0 * 800 * LANDING_MS / wall
    assert reader("device_landing_pct")(ctx) == pytest.approx(landing)
    assert reader("landing_seen_pct")(ctx) == pytest.approx(80.0)
    (line,) = [m for m in ctx["logs"] if m.startswith("[layer] device_landing_pct")]
    starved = 100.0 * 1000 * sum(STARVED.values()) / wall
    assert "in 800 landings seen" in line and "200 landings not seen" in line
    assert f"at most {100.0 * 200 * UNSEEN_MS / wall:.3f}% more" in line
    # the two shares and the trace's idle share on one line, and what is left
    assert (f"device_starved_pct {starved:.3f} + device_landing_pct {landing:.3f} = "
            f"{starved + landing:.3f} beside the device trace's device_idle_pct 86.000: "
            f"{86.0 - starved - landing:.3f} points") in line
    (line,) = [m for m in ctx["logs"] if m.startswith("[layer] landing_seen_pct")]
    assert "800 landings seen, 200 not" in line


def test_an_untraced_window_says_so():
    ctx = make_ctx([link_row(100.0, 10)], [link_row(140.0, 10, seen=100)])
    assert reader("device_landing_pct")(ctx) == pytest.approx(100.0 * 100 * LANDING_MS / 40_000.0)
    assert any("not traced" in m for m in ctx["logs"])


def test_fleet_rows_are_summed_and_shown_by_replica():
    """Four replicas' rows: the share is per replica (the denominator
    ``device_starved_pct`` uses), and the line shows each replica's own."""
    seen = (400, 200, 100, 100)
    before = [link_row(100.0, 10, rid=f"r{i}") for i in range(4)]
    after = [link_row(140.0, 10, seen=n, unseen=100, rid=f"r{i}")
             for i, n in enumerate(seen)]
    ctx = make_ctx(before, after)
    assert linklib.window(ctx, "x")["replicas"] == 4
    assert reader("device_landing_pct")(ctx) == pytest.approx(
        100.0 * 800 * LANDING_MS / (4 * 40_000.0))
    assert reader("landing_seen_pct")(ctx) == pytest.approx(100.0 * 800 / 1200)
    (line,) = [m for m in ctx["logs"] if m.startswith("[layer] device_landing_pct")]
    assert "by replica, %: 10.000, 5.000, 2.500, 2.500" in line
    (line,) = [m for m in ctx["logs"] if m.startswith("[layer] landing_seen_pct")]
    assert "by replica, %: 80.000, 66.667, 50.000, 50.000" in line


def test_a_replica_with_no_first_read_is_summed_and_left_out_of_the_list():
    before = [link_row(100.0, 10, rid="r0")]
    after = [link_row(140.0, 10, seen=100, rid="r0"),
             link_row(140.0, 0, seen=50, rid="r1")]
    ctx = make_ctx(before, after)
    assert reader("landing_seen_pct")(ctx) == pytest.approx(100.0)
    (line,) = [m for m in ctx["logs"] if m.startswith("[layer] landing_seen_pct")]
    assert "1 replica(s) had no row when the window opened" in line


def test_no_landing_seen_reads_a_zero_share_and_a_zero_guard():
    """A device-paced cell: every batch's bytes land under the step before."""
    ctx = make_ctx([link_row(100.0, 10)], [link_row(140.0, 10, unseen=500)])
    assert reader("device_landing_pct")(ctx) == 0.0
    assert reader("landing_seen_pct")(ctx) == 0.0
    (line,) = [m for m in ctx["logs"] if m.startswith("[layer] device_landing_pct")]
    assert "0.0 ms in 0 landings seen" in line and "500 landings not seen" in line


def test_no_probe_in_the_window_has_no_guard_to_report():
    """The slab and monolithic paths: the keys are there, nothing was counted."""
    ctx = make_ctx([link_row(100.0, 10)], [link_row(140.0, 10)])
    ctx["after"]["buckets"][0]["batches"] += 5
    ctx["after"]["buckets"][0]["stages"]["route"]["batches"] += 5
    assert reader("device_landing_pct")(ctx) == 0.0
    assert reader("landing_seen_pct")(ctx) is None
    assert any("no batch of the window had a landing probe" in m for m in ctx["logs"])


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("case,why", [
    ("parent_shaped", "a program before PR 54"),
    ("unwatched", "were not read"),
    ("no_batches", "no batch ran"),
    ("bucket_without_before", "no batch ran"),
])
def test_nothing_to_read_is_none_and_says_why(name, case, why):
    ctx = {
        # every commit before PR 54: the starved block without the landing keys
        "parent_shaped": make_ctx([row(100.0, 10)], [row(140.0, 10, 1000)]),
        "unwatched": make_ctx(None, None),
        "no_batches": make_ctx([link_row(100.0, 10)], [link_row(140.0, 10)]),
        # no second read's clock to take the wall from
        "bucket_without_before": make_ctx([], [link_row(140.0, 10, seen=1000)]),
    }[case]
    assert reader(name)(ctx) is None
    (line,) = [m for m in ctx["logs"] if m.startswith(f"[layer] {name}: None")]
    assert why in line


# -- through the frontend, at toy size on the CPU ---------------------------

def test_a_toy_run_reports_the_share_and_closes_on_the_rows():
    """The toy batches go up monolithic on the CPU (no probe): the share is
    the rows' delta, 0, and the guard, which needs a probe, says why it is None."""
    from chipbench.tests.test_hold_readers import _traced_toy_run

    result, before, after = _traced_toy_run("invert_1080p.bulk")
    (b,), (a,) = before["buckets"], after["buckets"]
    assert all(key in a["starved"] for key in linklib.KEYS.values())
    wall_ms = (a["stages"]["t"] - b["stages"]["t"]) * 1e3
    want = 100.0 * (a["starved"]["landing_ms_total"] - b["starved"]["landing_ms_total"]) / wall_ms
    assert result["metrics"]["device_landing_pct"]["value"] == pytest.approx(want)
    probed = sum(a["starved"][k] - b["starved"][k]
                 for k in ("landed_seen_total", "landed_unseen_total"))
    assert ("landing_seen_pct" in result["metrics"]) == (probed > 0)
