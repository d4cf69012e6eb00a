"""The fused stencil kernel's share of the step: its device milliseconds a step over ``step_ms``.

The rest of the step is what surrounds the kernel: ``stencil_prep`` (uint8
-> float32, NHWC -> NCHW, the reflect pad, row and column padding),
``stencil_finish`` (the slice, NCHW -> NHWC) and the rounding to uint8,
which the ``[layer]`` line names from the breakdown's other operations.
Exactly it is the kernel's share of the device's BUSY seconds (see
``kernel_ms``): the step's share only where nothing but the step runs on
the chip, and with the device never idle the kernel's share of the period.
None where stencil_kernel_roofline.py's ``kernel_ms`` finds nothing."""
from chipbench import spec


def read(ctx):
    lib = spec.load_module("layer_metrics/stencil_kernel_roofline.py")
    found = lib.kernel_ms(ctx)
    if found is None:
        return None
    ms, name = found
    trace = ctx["trace"]
    scale = trace["step_ms"] / trace["fullest_busy_s"]
    rest = ", ".join(f"{n} {s * scale:.2f}" for n, s in trace["breakdown"]["device_ops"]
                     if n != name)
    ctx["log"](f"[layer] stencil_kernel_share_pct: {name} {ms:.2f} ms of the "
               f"{trace['step_ms']:.2f} ms step; the other operations, ms a step "
               f"(the egress pack's among them): {rest}")
    return 100.0 * ms / trace["step_ms"]
