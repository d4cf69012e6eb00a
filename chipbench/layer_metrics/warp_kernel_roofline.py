"""The final Pallas bounded warp's share of its roofline: max(bytes / HBM peak, FLOPs / bf16 peak) over the kernel's device time per step.

The kernel is named ``warp_bounded`` (dvf_tpu/ops/pallas_kernels.py), so
the trace lists each call of it as ``%warp_bounded.<n>``: the longest of
them is the final warp (three channels at full resolution; the inner-loop
calls run on the half-resolution pyramid). Its time per step is its share
of the fullest device's busy seconds times the step's time: a trace's
first and last steps are cut short, so seconds over whole steps would read
high. None where the trace is missing, the configuration brings no
``warp_cost``, or no such kernel is among the ten longest operations."""
from chipbench import spec
from chipbench.reduce import roofline_pct


def read(ctx):
    trace, cell = ctx["trace"], ctx["cell"]
    if trace is None or trace["step_ms"] is None or ctx["peak"] is None:
        return None
    warp_cost = getattr(spec.load_module(cell.config["costs"]), "warp_cost", None)
    if warp_cost is None:
        return None
    ops = [(s, name) for name, s in trace["breakdown"]["device_ops"] if "warp_bounded" in name]
    if not ops:
        ctx["log"]("[layer] warp_kernel_roofline: no warp_bounded kernel among the ten longest "
                   f"operations: {[n for n, _ in trace['breakdown']['device_ops']]}")
        return None
    kernel_s, name = max(ops)
    kernel_ms = trace["step_ms"] * kernel_s / trace["fullest_busy_s"]
    pct, binds = roofline_pct(warp_cost(cell.config, cell.batch_size), ctx["peak"], kernel_ms)
    ctx["log"](f"[layer] warp_kernel_roofline: {name} takes {kernel_ms:.2f} ms of the "
               f"{trace['step_ms']:.1f} ms step; the {binds} bound binds")
    return pct
