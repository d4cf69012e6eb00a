"""The convolutions' share of the step, from below: device milliseconds a step of the LISTED operations that are convolutions, over ``step_ms``.

``breakdown.device_ops`` lists the ten longest operations of the fullest
device by HLO name, and ``reduce.py`` keeps no more. XLA names most fusions
``%fusion.<n>`` whatever they hold, so the program says which are
convolutions: the bucket row's ``model`` block lists ``conv_ops``, the
instructions of the step's own executable that hold a convolution
(runtime/engine.py ``conv_op_names``, read off the executable the engine
calls). The denoiser's step is 32 convolutions a batch and at most ten are
listed, so this is a LOWER BOUND on the convolutions' share of the step,
never the share itself: a convolution that gets faster lowers it by no more
than its own gain, and one that drops out of the ten takes its whole time
with it. The ``[layer]`` line names the listed operations that are not
convolutions (the table's gathers and scatter, ``state_table``; the lag
gathers, ``denoise_window``; the shuffles' rearrangements, the uint8 ends
and the pack) with their milliseconds a step, and how much of the step the
ten leave out. None without a trace or a step, where the program states no
``conv_ops`` (any commit before PR 52, any filter that states no model), or
where none of the listed operations is one of them (the names do not match
this trace, or no convolution is among the ten): the line says which."""


def conv_ops(ctx):
    for row in (ctx["after"] or {}).get("buckets", []):
        if (row.get("model") or {}).get("conv_ops"):
            return {"%" + name for name in row["model"]["conv_ops"]}
    return None


def read(ctx):
    trace, convs = ctx["trace"], conv_ops(ctx)
    if trace is None or trace["step_ms"] is None or convs is None:
        return None
    ops = trace["breakdown"]["device_ops"]
    scale = trace["step_ms"] / trace["fullest_busy_s"]
    mine = [s * scale for n, s in ops if n in convs]
    if not mine:
        ctx["log"](f"[layer] denoise_conv_share_pct: none of the {len(ops)} listed operations is one of the "
                   f"program's {len(convs)} convolutions ({', '.join(n for n, _ in ops)}): no reading")
        return None
    listed = sum(s for _, s in ops) * scale
    rest = ", ".join(f"{n} {s * scale:.2f}" for n, s in ops if n not in convs)
    ctx["log"](f"[layer] denoise_conv_share_pct: {len(mine)} of the {len(ops)} listed operations are among the "
               f"program's {len(convs)} convolutions, {sum(mine):.2f} of the {trace['step_ms']:.2f} ms step; the "
               f"ten hold {listed:.2f} ms and leave {trace['step_ms'] - listed:.2f} unlisted; the others listed, "
               f"ms a step: {rest or 'none'}")
    return 100.0 * sum(mine) / trace["step_ms"]
