"""The fused Sobel -> bilateral Pallas kernel's share of its roofline: max(bytes / HBM peak, FLOPs / bf16 peak) over the kernel's device time per step.

The kernel is named ``sobel_bilateral`` (dvf_tpu/ops/pallas_kernels.py),
so the trace lists it as ``%sobel_bilateral.<n>`` and the bucket row's
``kernel`` block states the same name. Its time per step is its share of
the fullest device's busy seconds times the step's time (a trace's first
and last steps are cut short: warp_kernel_roofline.py has the reason).
Where the device runs other programs than the step (the egress pack: 11
of a 117 ms period in sobel_bilateral_1080p.bulk, PR 43) that reads the
kernel's time low by their share of the busy seconds (67.6 ms where the
step probe reads 75.3): the share of a roofline reads low with it, never
high.
``peaks.json`` has the HBM's rate and the MXU's bf16 rate only; this
kernel's work is the VPU's (exp, divide, 81 taps a pixel), so the share
reads a few percent and says how far the kernel is from being bound by
its bytes, not how well it uses the unit it runs on. None where the trace
is missing, the configuration brings no ``kernel_cost``, the program
states no kernel (any commit before PR 43), or no such op is among the
ten longest operations."""
from chipbench import spec
from chipbench.reduce import roofline_pct


def kernel_block(ctx):
    """The ``kernel`` block of the first bucket row that states one."""
    for row in (ctx["after"] or {}).get("buckets", []):
        if row.get("kernel"):
            return row["kernel"]
    return None


def kernel_ms(ctx):
    """(ms a step, op name) of the stated kernel in the trace, or None."""
    trace, block = ctx["trace"], kernel_block(ctx)
    if trace is None or trace["step_ms"] is None or block is None:
        return None
    ops = [(s, name) for name, s in trace["breakdown"]["device_ops"]
           if name.lstrip("%").split(".")[0] == block["kernel"]]
    if not ops:
        ctx["log"](f"[layer] no %{block['kernel']} kernel among the ten longest operations: "
                   f"{[n for n, _ in trace['breakdown']['device_ops']]}")
        return None
    seconds, name = max(ops)
    return trace["step_ms"] * seconds / trace["fullest_busy_s"], name


def read(ctx):
    cell = ctx["cell"]
    kernel_cost = getattr(spec.load_module(cell.config["costs"]), "kernel_cost", None)
    found = kernel_ms(ctx) if kernel_cost is not None and ctx["peak"] is not None else None
    if found is None:
        return None
    ms, name = found
    pct, binds = roofline_pct(kernel_cost(cell.config, cell.batch_size), ctx["peak"], ms)
    ctx["log"](f"[layer] stencil_kernel_roofline: {name} takes {ms:.2f} ms of the "
               f"{ctx['trace']['step_ms']:.1f} ms step; the {binds} bound binds (against the "
               f"HBM's and the MXU's peaks: the VPU this kernel runs on has none published)")
    return pct
