"""``clahe_hist``'s share of its roofline: max(bytes / HBM peak, FLOPs / bf16 peak) over the kernel's device time per step.

The step of ``clahe_1080p`` runs two kernels of the repo's own
(dvf_tpu/ops/histogram.py ``clahe_plan``: the bucket row's ``kernel`` block
lists them under ``kernels``, in order), named ``clahe_hist`` and
``clahe_apply`` in dvf_tpu/ops/pallas_kernels.py's ``pallas_call``s, so the
trace lists them as ``%clahe_hist.<n>`` / ``%clahe_apply.<n>``. A kernel's
time per step is its share of the fullest device's busy seconds times the
step's time (stencil_kernel_roofline.py has the reason, and why that reads
low, never high, where other programs run beside the step). Against
``costs/clahe_1080p.py::kernel_cost`` (uint8 planes once each way and the
tables: counted low) and ``peaks.json``, which has the HBM's rate and the
MXU's bf16 rate only: ``clahe_hist`` counts on the VPU, 768 operations a
pixel, so its share reads well under one percent by construction and says
how far the kernel is from being bound by its bytes. None where the trace
is missing, the program states no ``kernels`` (any commit before PR 49),
or the kernel is not among the ten longest operations.

This file is also the library of its siblings (clahe_apply_roofline,
clahe_kernels_share_pct, clahe_tile_overwork_pct)."""
from chipbench import spec
from chipbench.reduce import roofline_pct


def kernel_block(ctx):
    """The ``kernel`` block of the first bucket row that lists ``kernels``."""
    for row in (ctx["after"] or {}).get("buckets", []):
        if (row.get("kernel") or {}).get("kernels"):
            return row["kernel"]
    return None


def kernels_ms(ctx):
    """{kernel: (ms a step, op name)} of the stated kernels that are among
    the trace's ten longest operations; None without a trace or a block."""
    trace, block = ctx["trace"], kernel_block(ctx)
    if trace is None or trace["step_ms"] is None or block is None:
        return None
    found = {}
    for name, seconds in trace["breakdown"]["device_ops"]:
        kernel = name.lstrip("%").split(".")[0]
        if kernel in block["kernels"] and seconds > found.get(kernel, (0.0, None))[0]:
            found[kernel] = (seconds, name)
    scale = trace["step_ms"] / trace["fullest_busy_s"]
    return {k: (s * scale, name) for k, (s, name) in found.items()}


def roofline(ctx, kernel):
    """``kernel``'s share of its roofline, in percent, or None."""
    cell = ctx["cell"]
    kernel_cost = getattr(spec.load_module(cell.config["costs"]), "kernel_cost", None)
    found = kernels_ms(ctx) if kernel_cost is not None and ctx["peak"] is not None else None
    if not found or kernel not in found:
        if found is not None:
            ctx["log"](f"[layer] no %{kernel} kernel among the ten longest operations: "
                       f"{[n for n, _ in ctx['trace']['breakdown']['device_ops']]}")
        return None
    ms, name = found[kernel]
    pct, binds = roofline_pct(kernel_cost(cell.config, cell.batch_size, kernel), ctx["peak"], ms)
    ctx["log"](f"[layer] {kernel}_roofline: {name} takes {ms:.2f} ms of the "
               f"{ctx['trace']['step_ms']:.1f} ms step; the {binds} bound binds (against the "
               f"HBM's and the MXU's peaks: the VPU and the lane gather have none published)")
    return pct


def read(ctx):
    return roofline(ctx, "clahe_hist")
