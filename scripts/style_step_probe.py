#!/usr/bin/env python3
"""Where a served net's step goes, op by op, with each op's stage.

ROADMAP S2's op map (PR 28), for the style net by default. ``--model espcn`` reads the upscaling service's step the
same way (``super_resolution(scale=2)`` at 16 x 540 x 960 in, 1080 x 1920 out; the scopes of ``models/espcn.py``:
``feat``, ``map``, ``head``, ``shuffle``, each in the form ``models/espcn.py::stage_forms`` gives for the shape;
``--fast-convs`` probes the per-layer space-to-depth round trip); ``--model stencil`` the fused Sobel -> bilateral chain of
``chipbench/configs/sobel_bilateral_1080p.json`` (``sobel_bilateral(d=9, impl="pallas")`` at 1080 x 1920; the scopes of
``ops/pallas_kernels.py::sobel_bilateral_nhwc_pallas``: ``stencil_prep``, ``stencil_kernel``, ``stencil_finish``; ``--d 5`` and
``--impl chain`` probe the real-time window and the two-op jnp chain, which carries no scope); ``--model clahe`` the
counted CLAHE of ``chipbench/configs/clahe_1080p.json`` (``clahe(impl="pallas")`` at 1080 x 1920, uint8 in and out with
no float conversion, as the Engine steps a ``uint8_ok`` filter; the scopes of ``ops/histogram.py::_clahe_planes_pallas``:
``clahe_hist``, ``clahe_lut``, ``clahe_apply``; ``--impl sort`` probes the sort + gather form, at a batch it fits, and
the first frames of either are compared with ``chipbench/refs/clahe_1080p.py``); ``--model flow`` the Farneback flow
warp of ``chipbench/configs/flow_720p.json`` (``flow_warp`` with the bounded kernel for its final warp and its nine
inner warps, one session's pairs at 720 x 1280; the scopes of ``ops/flow.py``: ``flow_final_warp``, ``flow_inner_warp``,
the rest by op, and the ``warp_bounded`` calls summed by name, which a tree from before the scopes (``--tree``) has
too); ``--model fastdvd`` the streamed FastDVDnet of ``chipbench/configs/fastdvd_540p.json`` (``video_denoise`` at
540 x 960, one session's consecutive frames; the scopes of ``ops/denoise.py``: ``denoise_window`` (the four lag gathers),
``denoise_stage1``, ``denoise_stage2``: one DenBlock each, two a frame). Compiles the step program
of ``style_transfer(base_channels=32, n_residual=5)`` as the Engine builds it (uint8 batch in, uint8 batch out, the
weights as state) at the cell's shape, times it, traces a few steps, and prints every device op's milliseconds a step beside the ``jax.named_scope`` of ``_forward`` it was compiled
from (``stem``, ``down1``, ``down2``, ``trunk``, ``up1``, ``up2``, ``out``; the compiled HLO's ``op_name``) and its
result shape, then the sum by stage and, by stage, the time of each norm's two passes: ``norm_stats`` (the one
reduction pass, ``models/layers.py::_norm_stats``; ``conv+norm_stats`` where XLA put it into the fusion of the conv
that makes the activation, whose time it then shares) and ``norm_apply`` (the elementwise pass that also carries the
relu and the residual add). Run on the chip:

    chiprun -- python scripts/style_step_probe.py            # writes chiprun_out/style_step_probe.json

    chiprun -- python scripts/style_step_probe.py --model espcn   # chiprun_out/espcn_step_probe.json

    chiprun -- python scripts/style_step_probe.py --model stencil --batch 64   # chiprun_out/stencil_step_probe.json

    chiprun -- python scripts/style_step_probe.py --model clahe --batch 64     # chiprun_out/clahe_step_probe.json

    chiprun -- python scripts/style_step_probe.py --model flow --batch 64      # chiprun_out/flow_step_probe.json

    chiprun -- python scripts/style_step_probe.py --model fastdvd --batch 32   # chiprun_out/fastdvd_step_probe.json

``--toy`` runs a tiny shape on whatever backend jax has (the CPU here): it checks the script, and its times mean
nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

def _style_stages(kwargs, shape):
    from dvf_tpu.models.style_transfer import StyleNetConfig, stage_forms

    return stage_forms(StyleNetConfig(**kwargs), shape)


def _espcn_stages(kwargs, shape):
    from dvf_tpu.models.espcn import EspcnConfig, stage_forms, stage_phases

    if kwargs.get("fast_convs"):        # to phases and back around every conv: no stage carries a form
        return {"feat": "s2d", "map": "s2d", "head": "s2d", "shuffle": "plain"}
    config = EspcnConfig(scale=kwargs["scale"])
    phases = stage_phases(config, shape)
    return {stage: form if form == "plain" else "phase %dx%d" % phases.get(stage, phases["head"])
            for stage, form in stage_forms(config, shape).items()}


def _stencil_stages(kwargs, shape):
    from dvf_tpu.ops.pallas_kernels import sobel_bilateral_plan

    if kwargs["impl"] != "pallas":      # the jnp chain: XLA's own fusions, no scope and no tiling
        return {}
    plan = sobel_bilateral_plan(shape, kwargs["d"])
    rows = plan["h_pad"] - plan["tile_h"] + plan["slab_rows"]           # the last slab's end: 1096 at the cell's shape
    return {"stencil_prep": "%d float32 luma plane, reflect + %d x %d" % (plan["planes"], rows, plan["w_aligned"]),
            "stencil_kernel": "tile %d, grid %s, slab %d x %d" % (plan["tile_h"], plan["grid"], plan["slab_rows"],
                                                                  plan["w_aligned"]),
            "stencil_finish": "slice, broadcast to %d, NHWC" % shape[-1]}


def _clahe_stages(kwargs, shape):
    from dvf_tpu.ops.histogram import clahe_plan

    if kwargs["impl"] != "pallas":      # the sort + gather form: XLA's own ops, no scope and no tiling
        return {}
    plan = clahe_plan(shape, kwargs["clip_limit"], kwargs["grid"], kwargs["on_gray"])
    tile = "%d x %d as %d x %d" % (plan["tile_h"], plan["tile_w"], plan["tile_h_pad"], plan["tile_w_pad"])
    return {"clahe_hist": "%d planes, %d^2 tiles of %s, counted" % (plan["planes"], plan["grid"], tile),
            "clahe_lut": "clip %d, redistribution, cumulative tables, %d^2 cells packed" % (plan["clip_abs"], plan["cells"]),
            "clahe_apply": "%d^2 cells of %s, lane-gather lookup, float32 blend" % (plan["cells"], tile)}


def _flow_stages(kwargs, shape):
    from dvf_tpu.ops import get_filter

    plan = getattr(get_filter("flow_warp", **kwargs), "kernel_plan", None)     # None: a tree from before PR 51
    calls = (plan(shape) or {}).get("calls", []) if plan else []

    def form(role):
        return "; ".join("%d x %s planes %d taps %d tile %d grid %s" % (
            k["count"], "level %s" % k["level"] if role == "inner" else "frame", k["planes"], k["taps"], k["tile_h"],
            k["grid"]) for k in calls if k["role"] == role)

    return {"flow_final_warp": form("final"), "flow_inner_warp": form("inner")}


def _fastdvd_stages(kwargs, shape):
    from dvf_tpu.models.fastdvdnet import FULL, HALF, PLAIN

    block = "one DenBlock a row: 540p as phases %s, 270p as %s, 135p as %s" % (FULL, HALF, PLAIN)
    return {"state_table": "Engine._table_body: four planes a session gathered, and scattered back",
            "denoise_window": "four lags of a row's predecessor chain, planes float32",
            "denoise_stage1": block, "denoise_stage2": block}


# model -> (filter, the cell's (H, W), its kwargs, toy kwargs, {stage scope: form} of (kwargs, shape))
_CLAHE = {"clip_limit": 2.0, "grid": 8, "on_gray": False, "impl": "pallas"}     # as chipbench/configs/clahe_1080p.json
_STENCIL = {"d": 9, "sigma_color": 0.1, "sigma_space": 2.0, "magnitude_scale": 1.0, "impl": "pallas"}   # as the cell's file
_ESPCN = {"scale": 2, "fast_convs": False, "dtype": "bfloat16"}          # as chipbench/configs/sr2x_540p.json
_FLOW = {"levels": 3, "win_size": 15, "n_iters": 3, "flow_scale": 2, "warp_impl": "pallas", "max_disp": 4,
         "win_type": "gaussian", "inner_warp": "pallas"}                 # as chipbench/configs/flow_720p.json
_FASTDVD = {"sigma": 25.0 / 255.0, "dtype": "bfloat16"}   # as chipbench/configs/fastdvd_540p.json
MODELS = {
    "fastdvd": ("video_denoise", (540, 960), _FASTDVD, _FASTDVD, _fastdvd_stages),
    "flow": ("flow_warp", (720, 1280), _FLOW, _FLOW, _flow_stages),
    "style": ("style_transfer", (720, 1280), {"base_channels": 32, "n_residual": 5},
              {"base_channels": 8, "n_residual": 2}, _style_stages),
    "espcn": ("super_resolution", (540, 960), _ESPCN, _ESPCN, _espcn_stages),
    "stencil": ("sobel_bilateral", (1080, 1920), _STENCIL, _STENCIL, _stencil_stages),
    "clahe": ("clahe", (1080, 1920), _CLAHE, _CLAHE, _clahe_stages),
}


def norm_part(body):
    """Which pass of an instance norm a fused computation's lines hold, from the scopes of ``models/layers.py``: a
    reduce compiled under ``norm_stats`` (with the convolution, where they share the fusion), else anything compiled
    under ``norm_apply``, else nothing."""
    if any(" reduce(" in line and "/norm_stats/" in line for line in body):
        return "conv+norm_stats" if any(" convolution(" in line for line in body) else "norm_stats"
    return "norm_apply" if any("/norm_apply/" in line for line in body) else ""


def op_table(hlo_text, stages):
    """{op name: (stage, result type, norm part)} from a compiled module's text: each instruction with the first of
    ``stages`` (the scopes of ``_forward``) in its ``op_name``, ``-`` for what the Engine adds around the net, and
    :func:`norm_part` of the computation it calls (of its own line, for an op outside any fusion)."""
    bodies, body = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            body = bodies.setdefault(head.group(1), [])
        elif body is not None:
            body.append(line)
    table = {}
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\([^=]*?\)|\S+) ", line)
        if not m:
            continue
        scope = re.search(r'op_name="([^"]*)"', line)
        parts = scope.group(1).split("/") if scope else []
        stage = next((p for p in parts if p in stages), "-")
        called = re.search(r"calls=%([\w.\-]+)", line)
        table[m.group(1)] = (stage, m.group(2)[:72], norm_part(bodies.get(called.group(1), []) if called else [line]))
    return table


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--toy", action="store_true", help="tiny shape, any backend: checks the script only")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--steps", type=int, default=8, help="timed steps (three more are traced)")
    ap.add_argument("--top", type=int, default=30, help="ops printed")
    ap.add_argument("--model", choices=sorted(MODELS), default="style")
    ap.add_argument("--fast-convs", action="store_true", help="the filter's fast_convs=True (espcn has it)")
    ap.add_argument("--d", type=int, default=None, help="stencil: the bilateral's window (the cell serves 9)")
    ap.add_argument("--impl", choices=("pallas", "chain", "sort"), default=None,
                    help="stencil: the fused kernel or the jnp chain; clahe: the counted kernels or the sort + gather form")
    ap.add_argument("--out", default=None, help="default chiprun_out/<model>_step_probe.json")
    ap.add_argument("--sessions", type=int, default=None,
                    help="a filter with per-session state: probe the step the service runs, the Engine's table body "
                         "over this many session rows, the batch's rows dealt to them in turn (default: one session's "
                         "consecutive frames through Filter.fn)")
    ap.add_argument("--tree", default=None, help="another checkout whose dvf_tpu is probed with this script")
    args = ap.parse_args()
    if args.tree:
        sys.path.insert(0, os.path.abspath(args.tree))
    args.out = args.out or f"chiprun_out/{args.model}_step_probe.json"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import reduce
    from dvf_tpu.ops import get_filter
    from dvf_tpu.utils.image import to_float, to_uint8

    dev = jax.devices()[0]
    if dev.platform == "cpu" and not args.toy:
        print("no accelerator: run through chiprun, or pass --toy", file=sys.stderr)
        return 3
    name, (h, w), kwargs, toy_kwargs, stages_of = MODELS[args.model]
    shape = (2, 64, 96, 3) if args.toy else (args.batch, h, w, 3)
    kwargs = dict(toy_kwargs if args.toy else kwargs)
    if args.fast_convs:
        kwargs["fast_convs"] = True
    if args.d is not None:
        kwargs["d"] = args.d
    if args.impl is not None:
        kwargs["impl"] = args.impl
    filt = get_filter(name, **kwargs)

    def step(batch, state):            # the body of Engine._build_step
        y, new_state = filt.fn(batch if filt.uint8_ok else to_float(batch, filt.compute_dtype), state)
        return (y if y.dtype == jnp.uint8 else to_uint8(y)), new_state

    state = filt.init_state(shape, jnp.float32) if filt.init_state is not None else None
    batch = jnp.asarray(np.random.default_rng(0).integers(0, 256, shape, dtype=np.uint8))
    t = time.perf_counter()
    if args.sessions:                   # the served step: Engine._table_body, its table and the batch's row map
        from dvf_tpu.runtime.engine import Engine

        engine = Engine(filt, state_rows=args.sessions)
        engine.compile(shape, np.uint8)
        rows = np.stack([np.arange(shape[0]) % args.sessions, np.zeros(shape[0])]).astype(np.int32)
        host_batch = np.asarray(batch)
        compiled = engine.compiled_step()
        run = lambda: engine.submit(host_batch, rows)               # the same program
    else:
        compiled = jax.jit(step).lower(batch, state).compile()
        run = lambda: compiled(batch, state)
    compile_s = time.perf_counter() - t
    forms = stages_of(kwargs, shape)
    table = op_table(compiled.as_text(), forms)
    mem = compiled.memory_analysis()

    for _ in range(2):
        jax.block_until_ready(run())
    wall = []
    for _ in range(args.steps):
        t = time.perf_counter()
        jax.block_until_ready(run())
        wall.append((time.perf_counter() - t) * 1e3)

    traced = 3
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        for _ in range(traced):
            jax.block_until_ready(run())
        jax.profiler.stop_trace()
        planes = reduce.read_planes(reduce.find_xplane(trace_dir)) if dev.platform != "cpu" else {"devices": {}}

    ops = {}
    for plane in planes["devices"].values():
        for name, _, dur in plane["ops"]:
            key = reduce.short_name(name).lstrip("%")
            ops[key] = ops.get(key, 0.0) + dur / 1e6 / traced
        break
    rows = sorted(((ms, name) + table.get(name, ("?", "", "")) for name, ms in ops.items()), reverse=True)
    by_stage, by_part = {}, {}
    for ms, _, stage, _, part in rows:
        by_stage[stage] = by_stage.get(stage, 0.0) + ms
        if part:
            stages = by_part.setdefault(part, {})
            stages[stage] = stages.get(stage, 0.0) + ms

    report = {"device": f"{dev.platform}:{dev.device_kind}", "jax": jax.__version__, "toy": args.toy,
              "model": args.model, "sessions": args.sessions, "filter": filt.name, "filter_kwargs": kwargs, "shape": list(shape),
              "stage_forms": forms, "compile_s": compile_s,
              "temp_gib": mem.temp_size_in_bytes / 2 ** 30,
              "step_wall_ms": {"min": min(wall), "median": sorted(wall)[len(wall) // 2], "max": max(wall)},
              "traced_ms_a_step": sum(ops.values()), "by_stage_ms": by_stage, "norm_ms": by_part,
              "warp_bounded_ms": {name: ms for ms, name, *_ in rows if "warp_bounded" in name},
              "ops": [{"ms": ms, "op": name, "stage": stage, "norm": part, "result": result}
                      for ms, name, stage, result, part in rows]}
    print(f"[probe {report['device']}{' toy' if args.toy else ''}] shape {shape}: compile {compile_s:.1f} s, "
          f"scratch {report['temp_gib']:.2f} GiB, step wall min/median/max "
          f"{min(wall):.2f}/{report['step_wall_ms']['median']:.2f}/{max(wall):.2f} ms, "
          f"ops traced {report['traced_ms_a_step']:.2f} ms a step")
    print(f"[probe] stage_forms {forms}")
    for ms, name, stage, result, part in rows[:args.top]:
        print(f"[probe] {ms:8.3f} ms  {stage:6s} {name:32s} {part:15s} {result}")
    print("[probe] by stage: " + ", ".join(f"{k} {v:.2f}" for k, v in sorted(by_stage.items(), key=lambda kv: -kv[1])))
    for part, stages in sorted(by_part.items()):
        print(f"[probe] {part} {sum(stages.values()):.2f}: "
              + ", ".join(f"{k} {v:.2f}" for k, v in sorted(stages.items(), key=lambda kv: -kv[1])))
    if report["warp_bounded_ms"]:
        print(f"[probe] warp_bounded, {len(report['warp_bounded_ms'])} calls: {sum(report['warp_bounded_ms'].values()):.2f} ms a step")
    if args.model == "clahe":           # integers: the step's first frames against the benchmark's plain reference
        from chipbench import spec

        ref = spec.load_module("refs/clahe_1080p.py")
        got = np.asarray(compiled(batch, state)[0][:2])
        want = ref.reference(list(np.asarray(batch[:2])), {"filter": {"kwargs": {k: kwargs[k] for k in
                                                                                 ("clip_limit", "grid", "on_gray")}}})
        diff = np.abs(got.astype(np.int16) - np.stack(want).astype(np.int16))
        report["against_reference"] = {"max_abs_steps": int(diff.max()), "mean_abs_steps": float(diff.mean())}
        print(f"[probe] against chipbench/refs/clahe_1080p.py, 2 frames: {report['against_reference']}")
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
