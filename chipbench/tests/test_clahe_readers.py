"""The four readers of the CLAHE kernels (PR 49), on contexts made by hand
and through one toy run of the cell that lists them, and the reference's
two controls at toy size.

    python3 -m pytest chipbench/tests/test_clahe_readers.py -q

``after`` holds bucket rows as ``frontends.counters()`` passes them; the
``kernel`` block on a row is what the compiled step stated about its
kernels (``Engine.kernel_plan``: dvf_tpu/ops/histogram.py ``clahe_plan``).
A program without the block, a block that lists no ``kernels`` (the stencil
cell's), a window that was not watched and a trace without a kernel's op
read None and nothing more.
"""

import pytest

from chipbench import check, controls, run, spec

CELL = "clahe_1080p.bulk"
BLOCK = {"kernel": "clahe_hist", "kernels": ["clahe_hist", "clahe_apply"], "impl": "pallas",
         "grid": 8, "cells": 9, "bins": 256, "planes": 192, "tile_h": 135, "tile_w": 240,
         "tile_h_pad": 136, "tile_w_pad": 256, "clip_abs": 253, "hist_grid": [192, 8],
         "apply_grid": [192, 9], "vmem_scratch_bytes": 270336, "vmem_window_bytes": 313344,
         "vmem_limit_bytes": None, "io_dtype": "uint8", "compute_dtype": "int32"}
STENCIL_BLOCK = {"kernel": "sobel_bilateral", "impl": "pallas", "taps": 81}
PEAK = spec.peaks("TPU v5 lite")
READERS = ("clahe_hist_roofline", "clahe_apply_roofline", "clahe_kernels_share_pct",
           "clahe_tile_overwork_pct")


def make_ctx(kernel="block", ops=None, step_ms=100.0, busy_s=5.0, peak=PEAK, watched=True):
    """A traced window of ``busy_s`` busy seconds made of 100 ms steps in
    which ``clahe_hist`` takes 80 ms and ``clahe_apply`` 8 unless ``ops``
    says otherwise."""
    row = {"signature": "sig", "batches": 25}
    if kernel == "block":
        row["kernel"] = dict(BLOCK)
    elif kernel == "stencil":
        row["kernel"] = dict(STENCIL_BLOCK)     # a kernel of the repo's own, but not these
    elif kernel == "none":
        row["kernel"] = None                    # a filter of XLA's own ops
    if ops is None:
        ops = [["%clahe_hist.1", 0.8 * busy_s], ["%clahe_apply.1", 0.08 * busy_s],
               ["%pad_convert_fusion", 0.07 * busy_s], ["%copy.74", 0.05 * busy_s]]
    trace = None if step_ms is None else {
        "step_ms": step_ms, "fullest_busy_s": busy_s, "busy_s": busy_s, "window_s": busy_s,
        "breakdown": {"device_ops": ops, "idle_gaps": []}}
    logs = []
    return {"cell": spec.Cell(CELL), "before": {"buckets": [row]} if watched else None,
            "after": {"buckets": [row]} if watched else None, "trace": trace, "peak": peak,
            "log": logs.append, "logs": logs}


def reader(name):
    return spec.load_module(f"layer_metrics/{name}.py").read


def least_s(ctx, kernel):
    cell = ctx["cell"]
    cost = spec.load_module(cell.config["costs"]).kernel_cost(cell.config, cell.batch_size, kernel)
    return max(cost["bytes"] / PEAK["hbm_bytes_per_s"], cost["flops"] / PEAK["bf16_flops_per_s"])


def test_the_four_read_a_traced_window():
    ctx = make_ctx()
    assert reader("clahe_hist_roofline")(ctx) == pytest.approx(100.0 * least_s(ctx, "clahe_hist") / 0.080)
    assert reader("clahe_apply_roofline")(ctx) == pytest.approx(100.0 * least_s(ctx, "clahe_apply") / 0.008)
    assert reader("clahe_hist_roofline")(ctx) < 2.0             # counting on the VPU: under its bytes
    assert reader("clahe_kernels_share_pct")(ctx) == pytest.approx(88.0)
    assert reader("clahe_tile_overwork_pct")(ctx) == pytest.approx(
        100.0 * (64 * 136 * 256 / (1080 * 1920) - 1))
    assert any("the bytes bound binds" in line for line in ctx["logs"])
    assert any(line.startswith("[layer] clahe_kernels_share_pct: %clahe_hist.1 80.00 ms, "
                               "%clahe_apply.1 8.00 ms") and "%copy.74 5.00" in line
               for line in ctx["logs"])
    assert any("tile 135 x 240 walked as 136 x 256" in line and "9^2 cells (36.00% beyond" in line
               for line in ctx["logs"])


def test_the_costs_are_counted_low():
    """uint8 planes once each way and the tables: no int32, no padding."""
    cell = spec.Cell(CELL)
    costs = spec.load_module(cell.config["costs"])
    px = 64 * 3 * 1080 * 1920
    assert costs.cost(cell.config, 64) == {"flops": 19.0 * px, "bytes": 2.0 * px}
    assert costs.kernel_cost(cell.config, 64, "clahe_hist") == {
        "flops": 1.0 * px, "bytes": px + 192 * 64 * 256 * 4.0}
    assert costs.kernel_cost(cell.config, 64, "clahe_apply") == {
        "flops": 18.0 * px, "bytes": 2.0 * px + 192 * 81 * 256 * 4.0}
    with pytest.raises(KeyError):
        costs.kernel_cost(cell.config, 64, "sobel_bilateral")


@pytest.mark.parametrize("kernel", ["absent", "none", "stencil"])
def test_a_program_that_lists_no_kernels_reads_none(kernel):
    """The parent's rows of another cell, a filter of XLA's own ops, a
    block with one kernel and no list: none raises, none logs a number."""
    ctx = make_ctx(kernel=kernel)
    for name in READERS:
        assert reader(name)(ctx) is None
    assert not any("ms" in line for line in ctx["logs"])


def test_an_unwatched_window_reads_none():
    ctx = make_ctx(watched=False)
    for name in READERS:
        assert reader(name)(ctx) is None


def test_without_a_trace_only_the_counter_reads():
    for ctx in (make_ctx(step_ms=None), make_ctx(peak=None)):
        assert reader("clahe_hist_roofline")(ctx) is None
        assert reader("clahe_apply_roofline")(ctx) is None
        assert reader("clahe_tile_overwork_pct")(ctx) == pytest.approx(7.46, abs=0.01)
    assert reader("clahe_kernels_share_pct")(make_ctx(step_ms=None)) is None
    assert reader("clahe_kernels_share_pct")(make_ctx(peak=None)) == pytest.approx(88.0)


def test_a_kernel_outside_the_ten_longest_reads_none_and_says_so():
    ops = [["%clahe_hist.1", 4.0], ["%fusion.3", 0.5], ["%clahe_applyish.1", 0.5]]
    ctx = make_ctx(ops=ops)
    assert reader("clahe_apply_roofline")(ctx) is None
    assert any("no %clahe_apply kernel among the ten longest" in line for line in ctx["logs"])
    assert reader("clahe_hist_roofline")(ctx) is not None
    assert reader("clahe_kernels_share_pct")(ctx) == pytest.approx(80.0)     # the one it found
    nothing = make_ctx(ops=[["%fusion.3", 5.0]])
    assert reader("clahe_kernels_share_pct")(nothing) is None


def test_the_longest_call_of_a_kernel_is_taken():
    ops = [["%clahe_hist.2", 1.0], ["%clahe_hist.1", 3.0], ["%clahe_apply.1", 1.0]]
    ctx = make_ctx(ops=ops, busy_s=10.0)
    assert reader("clahe_kernels_share_pct")(ctx) == pytest.approx(40.0)


def test_a_share_over_105_raises():
    ctx = make_ctx(step_ms=0.5)          # clahe_hist in 0.4 ms for 64 frames: under the bytes' own time
    with pytest.raises(ValueError, match="over 105%"):
        reader("clahe_hist_roofline")(ctx)


@pytest.mark.parametrize("control", ["control", "residual_dropped"])
def test_the_controls_read_not_correct_at_toy_size(control):
    """The blend in bfloat16 and the residual pass dropped, each put in
    the program's place over the toy cell's own pool."""
    cell = spec.Cell(CELL, toy=True)
    if control == "control":
        numbers = controls.control_numbers(cell, 7)
    else:
        from chipbench import frames

        pool = frames.make_pool(7, cell.frame_shape, int(cell.mix["pool_frames"]))
        wanted = cell.ref.reference(pool, cell.config, None)
        served = cell.ref.residual_dropped(pool, cell.config, None)
        numbers = check.compare_numbers([(0, i, f) for i, f in enumerate(served)], wanted, len(pool))
    assert not check.decide(numbers, cell.config["limits"], log=lambda m: None), numbers


def test_a_toy_run_of_the_cell_reads_its_tiling():
    """The cell end to end at toy size on the CPU (36 x 52: the grid does
    not divide it, so the reflect pad runs): the counter's reader finds the
    block the program stated; the three trace readers find no device
    operation and read None."""
    cell = spec.Cell(CELL, toy=True)
    logs = []
    result = run.run_cell(cell, seed=2_300_000_017, seconds=1.5, trace=True, require_tpu=False,
                          log=logs.append)
    assert result["correct"] and result["attempted"] > 0
    # a 5 x 7 tile walked as 8 x 128: 64 of them for 36 x 52
    assert result["metrics"]["clahe_tile_overwork_pct"]["value"] == pytest.approx(
        100.0 * (64 * 8 * 128 / (36 * 52) - 1))
    for name in READERS[:3]:
        assert name not in result["metrics"]
    # planes: the batch's 16 x 3 on one device, a shard's share where the session has several
    assert any("kernels ['clahe_hist', 'clahe_apply'] (pallas, " in line
               and " planes, 256 bins, clip 1): tile 5 x 7 walked as 8 x 128" in line for line in logs)
