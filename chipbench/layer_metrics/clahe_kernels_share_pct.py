"""The two CLAHE kernels' share of the step: their device milliseconds a step over ``step_ms``.

The rest of the step is what surrounds the kernels: the planes cut into
tiles and padded to whole vregs (twice: the tiles for ``clahe_hist``, the
half-tile-shifted cells for ``clahe_apply``), the clip, redistribution and
cumulative sum of ``clahe_lut``, the cells cut back into the frame; the
``[layer]`` line names them from the breakdown's other operations. Exactly
it is the kernels' share of the device's BUSY seconds (see
stencil_kernel_share_pct.py). None where clahe_hist_roofline.py's
``kernels_ms`` finds neither kernel."""
from chipbench import spec


def read(ctx):
    found = spec.load_module("layer_metrics/clahe_hist_roofline.py").kernels_ms(ctx)
    if not found:
        return None
    trace = ctx["trace"]
    scale = trace["step_ms"] / trace["fullest_busy_s"]
    names = {name for _, name in found.values()}
    rest = ", ".join(f"{n} {s * scale:.2f}" for n, s in trace["breakdown"]["device_ops"]
                     if n not in names)
    ctx["log"]("[layer] clahe_kernels_share_pct: "
               + ", ".join(f"{name} {ms:.2f} ms" for ms, name in sorted(found.values(), reverse=True))
               + f" of the {trace['step_ms']:.2f} ms step; the other operations, ms a step "
               f"(the egress pack's among them): {rest}")
    return 100.0 * sum(ms for ms, _ in found.values()) / trace["step_ms"]
