"""The reader of the ``ingest`` block's row-path counters
(``rows_direct_total`` / ``rows_staged_total``, PR 47), on rows made by hand.

    python3 -m pytest chipbench/tests/test_ingest_rows_reader.py -q

``before``/``after`` are bucket rows as ``frontends.counters()`` passes
them. The reader returns the window's delta and not the lifetime's; a
program without the counters (every commit before PR 47) and a window that
was not watched read None.
"""

import pytest

from chipbench import spec

BATCH = 32


def row(direct, staged, counters=True, signature="sig"):
    """A bucket row after ``direct`` batches as rows and ``staged`` through slabs."""
    ingest = {"batches": direct + staged}
    if counters:
        ingest.update(rows_direct_total=direct * BATCH, rows_staged_total=staged * BATCH,
                      direct_batches=direct, staged_batches=staged)
    return {"signature": signature, "batches": direct + staged, "ingest": ingest}


def make_ctx(before, after):
    logs = []
    return {"before": None if before is None else {"buckets": before},
            "after": None if after is None else {"buckets": after},
            "log": logs.append, "logs": logs}


def read(ctx):
    return spec.load_module("layer_metrics/ingest_direct_rows_pct.py").read(ctx)


@pytest.mark.parametrize("before,after,want", [
    ((10, 0), (1010, 0), 100.0),        # every batch eligible: every batch rows
    ((4, 6), (4, 526), 0.0),            # a lane degraded to the slabs: its rows lie before the window
    ((4, 0), (304, 100), 75.0),         # a mix
    ((0, 50), (100, 50), 100.0),        # the window's delta, not the lifetime's
])
def test_share_of_the_windows_frames(before, after, want):
    ctx = make_ctx([row(*before)], [row(*after)])
    assert read(ctx) == pytest.approx(want)
    assert any(line.startswith("[layer] ingest_direct_rows_pct:") for line in ctx["logs"])


def test_replicas_sum_and_a_bucket_born_in_the_window_counts_whole():
    ctx = make_ctx([row(10, 10)], [row(20, 10), row(0, 10)])
    assert read(ctx) == pytest.approx(50.0)


@pytest.mark.parametrize("ctx", [
    make_ctx([row(1, 1, counters=False)], [row(5, 5, counters=False)]),   # a program before the row path
    make_ctx(None, None),                                                  # a window nobody watched
    make_ctx([row(3, 3)], [row(3, 3)]),                                    # no batch in the window
])
def test_nothing_to_read_is_none_and_does_not_raise(ctx):
    assert read(ctx) is None
