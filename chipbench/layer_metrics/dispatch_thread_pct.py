"""Share of the window the dispatch thread spent staging, putting, dispatching the step (assemble_h2d) and starting the way back (prefetch); near 100 it paces. The [layer] line splits a batch's assemble_h2d."""
from chipbench import dispatchlib


def read(ctx):
    return dispatchlib.dispatch_thread_pct(ctx, "dispatch_thread_pct")
