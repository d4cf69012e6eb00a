"""The two readers of the ``ingest`` / ``egress`` blocks' byte counters
(``bytes_total``, PR 37), on rows made by hand and through one toy run of
the cell that lists them.

    python3 -m pytest chipbench/tests/test_sr_readers.py -q

``before``/``after`` are bucket rows as ``frontends.counters()`` passes
them. The readers return the window's delta and not the lifetime's; a
program without the counters (every commit before PR 37) and a window that
was not watched read None.
"""

import pytest

from chipbench import spec

IN_ROW, OUT_ROW = 540 * 960 * 3, 1080 * 1920 * 3          # bytes a frame, each way
BATCH = 16


def row(batches, t, in_row=IN_ROW, out_row=OUT_ROW, with_bytes=True, signature="sig"):
    """A bucket row after ``batches`` whole batches, read at clock ``t`` s."""
    ingest, egress = {"batches": batches}, {"batches": batches}
    if with_bytes:
        ingest["bytes_total"] = batches * BATCH * in_row
        egress["bytes_total"] = batches * BATCH * out_row
    cell = {"ms_total": 0.0, "batches": batches, "batch_ms_total": 0.0, "hist": []}
    stages = {"t": t, "delivered": batches * BATCH, "latency_ms_total": 0.0,
              "hist_lo_ms": 0.1, "hist_bins_per_decade": 16, "hist_bins": 98,
              "components": {c: dict(cell) for c in (
                  "queue_ingress", "queue_bucket", "permit_wait", "assemble_h2d",
                  "inflight_wait", "device", "d2h", "deliver")},
              "route": dict(cell)}
    return {"signature": signature, "batches": batches, "ingest": ingest, "egress": egress,
            "stages": stages}


def make_ctx(before, after):
    logs = []
    return {"before": None if before is None else {"buckets": before},
            "after": None if after is None else {"buckets": after},
            "log": logs.append, "logs": logs}


def reader(name):
    return spec.load_module(f"layer_metrics/{name}.py").read


def test_an_upscaling_window_reads_80_and_its_rate():
    ctx = make_ctx([row(100, t=50.0)], [row(1000, t=90.0)])
    assert reader("egress_bytes_share_pct")(ctx) == pytest.approx(80.0)
    # 900 batches of 16 1080p frames in 40 s between the two reads
    assert reader("egress_landed_mb_s")(ctx) == pytest.approx(900 * BATCH * OUT_ROW / 1e6 / 40.0)
    assert any(line.startswith("[layer] egress_bytes_share_pct:") for line in ctx["logs"])


def test_results_of_the_inputs_geometry_read_50():
    ctx = make_ctx([row(10, 0.0, out_row=IN_ROW)], [row(30, 4.0, out_row=IN_ROW)])
    assert reader("egress_bytes_share_pct")(ctx) == pytest.approx(50.0)


def test_readers_return_the_windows_delta_not_the_lifetimes():
    """A warm-up at another geometry before the window moves nothing."""
    warm = row(100, 10.0, out_row=IN_ROW)                    # lifetime share so far: 50
    after = row(100, 50.0, out_row=IN_ROW)
    after["ingest"]["bytes_total"] += 200 * BATCH * IN_ROW
    after["egress"]["bytes_total"] += 200 * BATCH * OUT_ROW
    after["stages"]["route"]["batches"] += 200
    ctx = make_ctx([warm], [after])
    assert reader("egress_bytes_share_pct")(ctx) == pytest.approx(80.0)
    assert reader("egress_landed_mb_s")(ctx) == pytest.approx(200 * BATCH * OUT_ROW / 1e6 / 40.0)


def test_a_batch_still_in_flight_moves_the_share_by_little():
    """The ingest block counts at the submit, the egress block at the
    fetch, a batch or more later: the share is off by that batch."""
    after = row(1000, 90.0)
    after["ingest"]["bytes_total"] += 4 * BATCH * IN_ROW     # four more staged than landed
    ctx = make_ctx([row(100, 50.0)], [after])
    assert reader("egress_bytes_share_pct")(ctx) == pytest.approx(80.0, abs=0.1)


def test_two_replicas_add_up():
    ctx = make_ctx([row(100, 50.0), row(50, 50.0)], [row(500, 90.0), row(450, 90.0)])
    assert reader("egress_bytes_share_pct")(ctx) == pytest.approx(80.0)
    assert reader("egress_landed_mb_s")(ctx) == pytest.approx(800 * BATCH * OUT_ROW / 1e6 / 40.0)


def test_a_bucket_that_opened_inside_the_window_counts_whole():
    ctx = make_ctx([], [row(30, 4.0)])
    assert reader("egress_bytes_share_pct")(ctx) == pytest.approx(80.0)
    assert reader("egress_landed_mb_s")(ctx) is None         # no first read: no clock to divide by


@pytest.mark.parametrize("name", ["egress_bytes_share_pct", "egress_landed_mb_s"])
@pytest.mark.parametrize("case", ["no_byte_counters", "unwatched", "no_batches"])
def test_nothing_to_read_is_none(name, case):
    ctx = {
        # every commit before PR 37: the blocks are there, the counters are not
        "no_byte_counters": make_ctx([row(100, 50.0, with_bytes=False)],
                                     [row(1000, 90.0, with_bytes=False)]),
        "unwatched": make_ctx(None, None),
        "no_batches": make_ctx([row(100, 50.0)], [row(100, 90.0)]),
    }[case]
    assert reader(name)(ctx) is None


def test_benchmark_lists_both_readers_for_the_upscaling_cell_only():
    bench = spec.benchmark()
    for name in ("egress_bytes_share_pct", "egress_landed_mb_s"):
        (m,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert m["workloads"] == ["sr2x_540p.bulk"] and m["moves"] == "delivered_fps"
        assert m["source"] == "program_counter" and m["layer"] == "egress (D2H + slabs)"
    cell = spec.Cell("sr2x_540p.bulk")
    assert {"egress_bytes_share_pct", "egress_landed_mb_s", "step_roofline", "step_ms.bulk",
            "batch_fill_pct.bulk", "permit_wait_ms.bulk"} <= {m["name"] for m in cell.per_layer}
    assert [m["name"] for m in cell.end_to_end] == ["delivered_fps", "setup_s"]


def test_costs_count_the_output_geometry():
    cell = spec.Cell("sr2x_540p.bulk")
    cost = cell.cost(cell.config, 16)
    assert cost["flops"] == pytest.approx(16 * 27.67e9, rel=1e-3)
    weights = 4 * (25 * 3 * 64 + 64 + 9 * 64 * 32 + 32 + 9 * 32 * 12 + 12)
    assert cost["bytes"] == 16 * (IN_ROW + OUT_ROW) + weights
    out = cell.config["output_geometry"]
    assert out["height"] * out["width"] * out["channels"] == OUT_ROW


# -- through the frontend, at toy size on the CPU ---------------------------

def test_toy_run_reads_what_the_blocks_counted():
    import os
    import threading
    import time

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from chipbench import run

    reads, logs = [], []

    def record_counters(front):
        sound = front.counters

        def counters():
            got = sound()
            if threading.current_thread().name == "chipbench-window-watch":
                reads.append(got)
            return got

        front.counters = counters

    cell = spec.Cell("sr2x_540p.bulk", toy=True)
    result = run.run_cell(cell, seed=2147483803, seconds=3.0, trace=True, require_tpu=False,
                          t_start=time.time(), front_hook=record_counters,
                          log=logs.append)
    assert result["correct"] and result["failed"] == 0, (
        result["failed"], [ln for ln in logs if "[check]" in ln or "[acct]" in ln])
    assert len(reads) == 2                                  # the watch's reads at t0 and t1
    (b,), (a,) = reads[0]["buckets"], reads[1]["buckets"]
    assert a["out_geometry"] == [64, 96, 3] and a["step_donates_input"] is False
    staged = a["ingest"]["bytes_total"] - b["ingest"]["bytes_total"]
    landed = a["egress"]["bytes_total"] - b["egress"]["bytes_total"]
    assert staged > 0 and staged % (16 * 32 * 48 * 3) == 0 and landed % (16 * 64 * 96 * 3) == 0
    assert result["metrics"]["egress_bytes_share_pct"]["value"] == pytest.approx(
        100.0 * landed / (staged + landed))
    assert result["metrics"]["egress_bytes_share_pct"]["value"] == pytest.approx(80.0, abs=0.5)
    seconds = a["stages"]["t"] - b["stages"]["t"]
    assert result["metrics"]["egress_landed_mb_s"]["value"] == pytest.approx(
        landed / 1e6 / seconds)
