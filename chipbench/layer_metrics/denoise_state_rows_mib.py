"""MiB of session-table rows a batch reads and writes back: the window's mean of (sessions in the batch) x (bytes of one session's row) x 2.

From the bucket rows' ``state`` block: ``table_rows_total`` counts, a batch,
the sessions whose row the step gathered out of the table and scattered
back (serve/server.py::_Bucket.note_state_rows), ``row_bytes`` is what one
row holds (runtime/engine.py::Engine.state_row_bytes: for the denoiser four
frame-sized planes and a count), and the ``hold`` block's short + full
batches are the batches submitted, counted on the same thread. None where
the window was not watched, no batch was submitted inside it, or the
program states no ``row_bytes`` (any commit before PR 52)."""
from chipbench import spec
from chipbench.layerlib import hold_window

state_delta = spec.load_module("layer_metrics/denoise_warm_rows_in_window.py").state_delta


def read(ctx):
    held = hold_window(ctx)
    rows = state_delta(ctx, lambda st: st["table_rows_total"] * st["row_bytes"]
                       if st.get("row_bytes") else None)
    if rows is None or not held or held["batches"] <= 0:
        return None
    return 2.0 * rows / held["batches"] / 2.0 ** 20
