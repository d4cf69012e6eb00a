"""``clahe_apply``'s share of its roofline: max(bytes / HBM peak, FLOPs / bf16 peak) over the kernel's device time per step.

The lookup-and-blend kernel of ``clahe_1080p``'s step, found in the trace
as ``%clahe_apply.<n>`` (clahe_hist_roofline.py has the method and the
cost function's assumptions: uint8 planes read once and written once, the
81 cells' packed tables read). The kernel really moves int32 planes and
walks 9 x 9 cells of 136 x 256 for a 1080 x 1920 plane; counted against
the uint8 bytes the algorithm needs, its share says how far it is from the
HBM's rate. None where clahe_hist_roofline.py's ``kernels_ms`` finds no
such op."""
from chipbench import spec


def read(ctx):
    return spec.load_module("layer_metrics/clahe_hist_roofline.py").roofline(ctx, "clahe_apply")
