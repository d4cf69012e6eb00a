"""The three readers of the video denoiser's cell (PR 52), on contexts made
by hand and through one toy run of the cell that lists them, and the
reference's two controls at toy size.

    python3 -m pytest chipbench/tests/test_fastdvd_readers.py -q

``before`` / ``after`` hold bucket rows as ``frontends.counters()`` passes
them; the ``state`` block on a row is what ``serve/server.py::_Bucket``
states of its session table (``warm_rows_total`` and ``row_bytes`` since
PR 52). A program without those two, a program without per-session state, a
window that was not watched, a window with no batch and a run without a
trace read None and nothing more.
"""

import pytest

from chipbench import check, controls, frames, run, spec

CELL = "fastdvd_540p.bulk"
ROW_BYTES = 4 * 540 * 960 * 3 * 4 + 4
READERS = ("denoise_conv_share_pct", "denoise_warm_rows_in_window", "denoise_state_rows_mib")


def state(table, warm=0, new=True):
    block = {"table_rows_total": table, "chain_rows_total": table, "fresh_rows_total": 16,
             "rows": 16, "bound": 16, "bytes": 16 * ROW_BYTES,
             "resets_total": {"admission": 16, "rebuild": 0, "migrate": 0}}
    if new:
        block.update(warm_rows_total=warm, row_bytes=ROW_BYTES, depth=4, lag_frames=2)
    return block


CONVS = ["fusion.64", "fusion.58", "convolution_convert_fusion.1"]


def make_ctx(batches=(5, 30), warm=(64, 64), new=True, stateful=True, watched=True, ops=None,
             step_ms=250.0, busy_s=5.0, model=True):
    """A window of ``batches[1] - batches[0]`` batches of 16 sessions each,
    traced as ``busy_s`` busy seconds of 250 ms steps."""
    def row(n, w):
        r = {"signature": "sig", "batches": n,
             "hold": {"short_batches_total": 0, "full_batches_total": n,
                      "held_batches_total": 0, "hold_ms_total": 0.0}}
        if stateful:
            r["state"] = state(16 * n, w, new)
        if model:
            r["model"] = {"name": "fastdvdnet", "conv_ops": list(CONVS) if new else None}
        return r

    if ops is None:
        ops = [["%fusion.64", 0.30 * busy_s], ["%fusion.58", 0.25 * busy_s],
               ["%convolution_convert_fusion.1", 0.25 * busy_s], ["%copy.12", 0.10 * busy_s],
               ["%fusion.71", 0.06 * busy_s], ["%while.100", 0.04 * busy_s]]
    trace = None if step_ms is None else {
        "step_ms": step_ms, "fullest_busy_s": busy_s, "busy_s": busy_s, "window_s": busy_s,
        "breakdown": {"device_ops": ops, "idle_gaps": []}}
    logs = []
    return {"cell": spec.Cell(CELL),
            "before": {"buckets": [row(batches[0], warm[0])]} if watched else None,
            "after": {"buckets": [row(batches[1], warm[1])]} if watched else None,
            "trace": trace, "peak": spec.peaks("TPU v5 lite"), "log": logs.append, "logs": logs}


def reader(name):
    return spec.load_module(f"layer_metrics/{name}.py").read


def test_the_three_read_a_traced_window():
    ctx = make_ctx()
    assert reader("denoise_conv_share_pct")(ctx) == pytest.approx(80.0)
    assert reader("denoise_warm_rows_in_window")(ctx) == 0
    assert reader("denoise_state_rows_mib")(ctx) == pytest.approx(2 * 16 * ROW_BYTES / 2 ** 20)
    assert any(line.startswith("[layer] denoise_conv_share_pct: 3 of the 6 listed operations")
               and "%copy.12 25.00" in line and "200.00 of the 250.00 ms step" in line
               for line in ctx["logs"])


def test_the_share_is_of_the_step_and_not_of_the_ten():
    """What the ten leave out stays in the denominator: a lower bound on
    the convolutions' share of the step, which a faster operation outside
    the list cannot raise."""
    ops = [["%fusion.64", 1.0], ["%fusion.58", 0.5], ["%while.100", 0.5]]      # 2.0 of 5.0 busy seconds listed
    ctx = make_ctx(ops=ops)
    assert reader("denoise_conv_share_pct")(ctx) == pytest.approx(30.0)        # 75% of the listed
    assert any("leave 150.00 unlisted" in line for line in ctx["logs"])


def test_a_restart_under_load_shows_as_warm_rows():
    assert reader("denoise_warm_rows_in_window")(make_ctx(warm=(64, 72))) == 8


@pytest.mark.parametrize("what", ["no_counters", "no_state", "unwatched"])
def test_a_program_without_the_counters_reads_none(what):
    """Any commit before PR 52 (a ``state`` block without the two fields),
    a filter without per-session state, a window that was not watched."""
    ctx = make_ctx(new=what != "no_counters", stateful=what != "no_state",
                   watched=what != "unwatched")
    assert reader("denoise_warm_rows_in_window")(ctx) is None
    assert reader("denoise_state_rows_mib")(ctx) is None
    if what != "no_state":          # the model block is no part of the state block
        assert reader("denoise_conv_share_pct")(ctx) is None  # no conv_ops stated, or no rows
    assert reader("denoise_conv_share_pct")(make_ctx(model=False)) is None


def test_a_window_with_no_batch_reads_none():
    ctx = make_ctx(batches=(30, 30))
    assert reader("denoise_state_rows_mib")(ctx) is None
    assert reader("denoise_warm_rows_in_window")(ctx) == 0


@pytest.mark.parametrize("trace", ["none", "no_step", "nothing_listed"])
def test_without_a_trace_the_share_reads_none(trace):
    ctx = make_ctx(step_ms=None) if trace == "none" else make_ctx(ops=[])
    if trace == "no_step":
        ctx = make_ctx()
        ctx["trace"]["step_ms"] = None
    assert reader("denoise_conv_share_pct")(ctx) is None


def test_no_listed_operation_among_the_programs_convolutions_reads_none():
    """Names alone do not count, only what the program listed; and where
    nothing matches (names of another trace, or no convolution among the
    ten) there is no reading, with the reason on the line."""
    ops = [["%fusion.1", 1.0], ["%convolution_add_fusion.9", 1.0]]     # the egress pack's, another program
    ctx = make_ctx(ops=ops)
    assert reader("denoise_conv_share_pct")(ctx) is None
    assert any("none of the 2 listed operations" in line and "no reading" in line for line in ctx["logs"])


@pytest.mark.parametrize("control", ["control", "stale_cache"])
def test_the_controls_read_not_correct_at_toy_size(control):
    """fp8 operands, and stage 2 fed one cached plane three times, each
    put in the program's place over the toy cell's own pool."""
    cell = spec.Cell(CELL, toy=True)
    if control == "control":
        numbers = controls.control_numbers(cell, 7)
    else:
        pool = frames.make_pool(7, cell.frame_shape, int(cell.mix["pool_frames"]))
        params = cell.ref.make_params(7, cell.config)
        served = cell.ref.stale_cache(pool, cell.config, params)
        numbers = check.compare_numbers([(0, i, f) for i, f in enumerate(served)],
                                        cell.ref.reference(pool, cell.config, params), len(pool))
    assert not check.decide(numbers, cell.config["limits"], log=lambda line: None), numbers


def test_the_toy_cell_runs_clean_and_lists_the_counter_readers():
    """chipbench/rehearse.py's run of the cell: every delivery the window
    samples has a full window behind it (frame index 10 or later)."""
    import time

    cell = spec.Cell(CELL, toy=True)
    lines = []
    result = run.run_cell(cell, seed=2_300_000_017, seconds=2.0, trace=True, require_tpu=False,
                          t_start=time.time(), log=lines.append)
    assert result["correct"] and result["attempted"] and not result["failed"], lines[-25:]
    assert {"denoise_warm_rows_in_window", "denoise_state_rows_mib", "state_table_rows_pct",
            "state_resets_in_window", "state_resident_mib"} <= set(result["metrics"])
    assert result["metrics"]["denoise_warm_rows_in_window"]["value"] == 0
    assert result["metrics"]["state_resets_in_window"]["value"] == 0
    assert result["metrics"]["state_table_rows_pct"]["value"] == pytest.approx(50.0)
    row_bytes = 4 * 24 * 32 * 3 * 4 + 4
    assert result["metrics"]["denoise_state_rows_mib"]["value"] == pytest.approx(
        2 * 4 * row_bytes / 2 ** 20)
    assert "denoise_conv_share_pct" not in result["metrics"]       # no device trace on the CPU
