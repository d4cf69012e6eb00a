"""The three readers of the fused stencil kernel (PR 43), on contexts made
by hand and through one toy run of the cell that lists them.

    python3 -m pytest chipbench/tests/test_stencil_readers.py -q

``after`` holds bucket rows as ``frontends.counters()`` passes them; the
``kernel`` block on a row is what the compiled step stated about its kernel
(``Engine.kernel_plan``). A program without the block (every commit before
PR 43), a filter that states no kernel, a window that was not watched and
a trace without the kernel's op read None and nothing more.
"""

import pytest

from chipbench import run, spec

CELL = "sobel_bilateral_1080p.bulk"
BLOCK = {"kernel": "sobel_bilateral", "impl": "pallas", "taps": 81, "tile_h": 24, "h_pad": 1080,
         "grid": [64, 45], "slab_rows": 40, "w_aligned": 2048, "vmem_scratch_bytes": 983040,
         "vmem_limit_bytes": 67108864, "compute_dtype": "float32"}
PEAK = spec.peaks("TPU v5 lite")


def make_ctx(kernel="block", ops=None, step_ms=200.0, busy_s=5.0, peak=PEAK, watched=True):
    """A traced window of ``busy_s`` busy seconds made of 200 ms steps in
    which the kernel takes 180 ms unless ``ops`` says otherwise."""
    row = {"signature": "sig", "batches": 25}
    if kernel == "block":
        row["kernel"] = dict(BLOCK)
    elif kernel == "none":
        row["kernel"] = None            # a filter of XLA's own ops
    if ops is None:
        ops = [["%sobel_bilateral.1", 0.9 * busy_s], ["%fusion.3", 0.06 * busy_s],
               ["%copy.2", 0.04 * busy_s]]
    trace = None if step_ms is None else {
        "step_ms": step_ms, "fullest_busy_s": busy_s, "busy_s": busy_s, "window_s": busy_s,
        "breakdown": {"device_ops": ops, "idle_gaps": []}}
    logs = []
    return {"cell": spec.Cell(CELL), "before": {"buckets": [row]} if watched else None,
            "after": {"buckets": [row]} if watched else None, "trace": trace, "peak": peak,
            "log": logs.append, "logs": logs}


def reader(name):
    return spec.load_module(f"layer_metrics/{name}.py").read


def test_the_three_read_a_traced_window():
    ctx = make_ctx()
    cell = ctx["cell"]
    cost = spec.load_module(cell.config["costs"]).kernel_cost(cell.config, cell.batch_size)
    least_s = max(cost["bytes"] / PEAK["hbm_bytes_per_s"], cost["flops"] / PEAK["bf16_flops_per_s"])
    assert reader("stencil_kernel_share_pct")(ctx) == pytest.approx(90.0)
    assert reader("stencil_kernel_roofline")(ctx) == pytest.approx(100.0 * least_s / 0.180)
    assert 0.5 < reader("stencil_kernel_roofline")(ctx) < 5.0     # a few percent: a VPU kernel
    assert reader("stencil_slab_overread_pct")(ctx) == pytest.approx(100.0 * (40 * 2048 / (24 * 1920) - 1))
    assert any("the bytes bound binds" in line for line in ctx["logs"])
    assert any(line.startswith("[layer] stencil_kernel_share_pct: %sobel_bilateral.1 180.00 ms")
               and "%fusion.3 12.00" in line for line in ctx["logs"])
    assert any("tile 24 of h_pad 1080, grid [64, 45], slab 40 x 2048 for 24 x 1920" in line
               for line in ctx["logs"])


@pytest.mark.parametrize("kernel", ["absent", "none"])
def test_a_program_that_states_no_kernel_reads_none(kernel):
    """The parent's rows have no ``kernel`` key; a filter of XLA's own ops
    has it None. Neither raises, neither logs a number."""
    ctx = make_ctx(kernel=kernel)
    for name in ("stencil_kernel_roofline", "stencil_kernel_share_pct", "stencil_slab_overread_pct"):
        assert reader(name)(ctx) is None
    assert not any("ms" in line for line in ctx["logs"])


def test_an_unwatched_window_reads_none():
    ctx = make_ctx(watched=False)
    for name in ("stencil_kernel_roofline", "stencil_kernel_share_pct", "stencil_slab_overread_pct"):
        assert reader(name)(ctx) is None


def test_without_a_trace_only_the_counter_reads():
    for ctx in (make_ctx(step_ms=None), make_ctx(peak=None)):
        assert reader("stencil_kernel_roofline")(ctx) is None
        assert reader("stencil_slab_overread_pct")(ctx) == pytest.approx(77.78, abs=0.01)
    assert reader("stencil_kernel_share_pct")(make_ctx(step_ms=None)) is None
    assert reader("stencil_kernel_share_pct")(make_ctx(peak=None)) == pytest.approx(90.0)


def test_a_trace_without_the_kernels_op_reads_none_and_says_so():
    ops = [["%fusion.3", 3.0], ["%sobel_bilateral_other.1", 1.0], ["%copy.2", 1.0]]
    ctx = make_ctx(ops=ops)
    assert reader("stencil_kernel_roofline")(ctx) is None
    assert reader("stencil_kernel_share_pct")(ctx) is None
    assert any("no %sobel_bilateral kernel among the ten longest" in line for line in ctx["logs"])


def test_the_longest_call_is_taken_and_the_name_is_matched_whole():
    ops = [["%sobel_bilateral.2", 1.0], ["%sobel_bilateral.1", 3.0], ["%sobel_bilateralish.7", 4.0]]
    ctx = make_ctx(ops=ops, busy_s=10.0)
    assert reader("stencil_kernel_share_pct")(ctx) == pytest.approx(30.0)


def test_a_share_over_105_raises():
    ctx = make_ctx(step_ms=2.0)          # 1.8 ms for 64 frames: under the bytes' own time
    with pytest.raises(ValueError, match="over 105%"):
        reader("stencil_kernel_roofline")(ctx)


def test_a_toy_run_of_the_cell_reads_its_tiling():
    """The cell end to end at toy size on the CPU (36 x 48: the tile pick
    pads H): the counter's reader finds the block the program stated; the
    two trace readers find no device operation and read None."""
    cell = spec.Cell(CELL, toy=True)
    logs = []
    result = run.run_cell(cell, seed=2_300_000_017, seconds=1.5, trace=True, require_tpu=False,
                          log=logs.append)
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0
    # tile 32 of h_pad 64, slab 48 x 128 for 32 x 48
    assert result["metrics"]["stencil_slab_overread_pct"]["value"] == pytest.approx(300.0)
    assert "stencil_kernel_roofline" not in result["metrics"]
    assert "stencil_kernel_share_pct" not in result["metrics"]
    assert any("kernel sobel_bilateral (pallas, 81 taps, float32): tile 32 of h_pad 64" in line
               for line in logs)
