"""On-chip per-layer timing for the neural configs (style 720p, SR 540p).

The measured companion to the static model in ``dvf_tpu.models.analysis``:
times each layer block of the style net / ESPCN separately on the real
chip (for ESPCN the reference lowering AND the exact space-to-depth
rewrite side by side; the style net's blocks in the plain composition, its
served form being scripts/style_step_probe.py's to time, PERF.md §5) —
so the 3.7x gap between style_720p's measured ms/frame and its per-layer
roofline sum can be attributed to specific layers instead of guessed at.

Each block is jitted and timed standalone (median of ``--reps`` dispatch
rounds, batch amortized), so a layer's number includes its own dispatch
overhead but not its neighbors' — sum-of-blocks vs the full net is
reported as ``fusion_gain_ms`` (positive = XLA's cross-layer fusion wins
back that much).

Results persist to benchmarks/NEURAL_LAYERS.json (timestamp + git rev);
exactly one JSON summary line goes to stdout. Exit 3 when the backend
came up non-TPU (numbers are still persisted under that label).

Usage: python benchmarks/neural_layers.py [--reps 15] [--batch 8] [--cpu]
       [--quick]  (quick: tiny geometry, mechanics only)
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchtools import git_rev  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--reps", type=int, default=15)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=os.path.join(REPO, "benchmarks",
                                                  "NEURAL_LAYERS.json"))
    args = ap.parse_args(argv)

    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["DVF_FORCE_PLATFORM"] = "cpu"
    from dvf_tpu.cli import _force_platform

    _force_platform()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dvf_tpu.models.layers import (
        conv2d_nb, conv2d_s2d, instance_norm, upsample_nearest)
    from dvf_tpu.models.style_transfer import (
        StyleNetConfig, apply_style_net, init_style_net)
    from dvf_tpu.models.espcn import EspcnConfig, apply_espcn, init_espcn

    backend = jax.default_backend()
    b = args.batch
    sh, sw = (48, 64) if args.quick else (720, 1280)
    eh, ew = (36, 48) if args.quick else (540, 960)
    cd = jnp.bfloat16

    rng = np.random.RandomState(0)

    def act(h, w, c):
        return jnp.asarray(rng.rand(b, h, w, c).astype(np.float32)).astype(cd)

    def timed(name, fn, *xs):
        f = jax.jit(fn)
        y = f(*xs)
        jax.tree.map(lambda a: a.block_until_ready(), y)  # compile
        samples = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            y = f(*xs)
            jax.tree.map(lambda a: a.block_until_ready(), y)
            samples.append((time.perf_counter() - t0) * 1e3)
        ms = sorted(samples)[len(samples) // 2] / b  # per frame
        results[name] = round(ms, 4)
        print(f"[layers] {name}: {ms:.3f} ms/frame", file=sys.stderr,
              flush=True)

    results = {}
    scfg = StyleNetConfig()
    sp = init_style_net(jax.random.PRNGKey(0), scfg)
    c1, c2, c3 = scfg.widths

    x_full = act(sh, sw, 3)
    x_c1 = act(sh, sw, c1)
    x_h2 = act(sh // 2, sw // 2, c2)
    x_h4 = act(sh // 4, sw // 4, c3)
    x_h2_c3 = act(sh // 2, sw // 2, c3)

    def norm_relu(p, y):
        return jax.nn.relu(instance_norm(p, y))

    # The plain composition, block by block (what stage_forms' "plain"
    # runs); the served net's own per-stage times on the chip are
    # scripts/style_step_probe.py's (PERF.md §5).
    timed("style/stem_ref", lambda x: norm_relu(
        sp["stem_norm"], conv2d_nb(sp["stem"], x, compute_dtype=cd,
                                   reflect=True)), x_full)
    timed("style/down1", lambda x: norm_relu(
        sp["down1_norm"], conv2d_nb(sp["down1"], x, stride=2,
                                    compute_dtype=cd, reflect=True)), x_c1)
    timed("style/down2", lambda x: norm_relu(
        sp["down2_norm"], conv2d_nb(sp["down2"], x, stride=2,
                                    compute_dtype=cd, reflect=True)),
        act(sh // 2, sw // 2, c2))

    def res_block(x):
        h = norm_relu(sp["res0_an"], conv2d_nb(sp["res0_a"], x,
                                               compute_dtype=cd, reflect=True))
        h = instance_norm(sp["res0_bn"], conv2d_nb(sp["res0_b"], h,
                                                   compute_dtype=cd,
                                                   reflect=True))
        return x + h

    timed("style/res_block_x1", res_block, x_h4)
    timed("style/up1_ref", lambda x: norm_relu(
        sp["up1_norm"], conv2d_nb(sp["up1"], upsample_nearest(x, 2),
                                  compute_dtype=cd, reflect=True)), x_h4)
    timed("style/up2_ref", lambda x: norm_relu(
        sp["up2_norm"], conv2d_nb(sp["up2"], upsample_nearest(x, 2),
                                  compute_dtype=cd, reflect=True)), x_h2)
    timed("style/out_ref", lambda x: conv2d_nb(
        sp["out"], x, compute_dtype=cd, reflect=True), x_c1)

    xs = jnp.asarray(rng.rand(b, sh, sw, 3).astype(np.float32))
    timed("style/full", lambda x: apply_style_net(sp, x, scfg), xs)

    # Sum of standalone plain blocks vs the full net as served (res block
    # x n_residual): the gain is fusion plus the phase-domain stages.
    ref_sum = (results["style/stem_ref"] + results["style/down1"]
               + results["style/down2"]
               + results["style/res_block_x1"] * scfg.n_residual
               + results["style/up1_ref"] + results["style/up2_ref"]
               + results["style/out_ref"])
    results["style/sum_of_blocks_ref"] = round(ref_sum, 4)
    results["style/fusion_gain_ms"] = round(
        ref_sum - results["style/full"], 4)

    ecfg = EspcnConfig()
    ep = init_espcn(jax.random.PRNGKey(0), ecfg)
    ex = act(eh, ew, 3)
    timed("espcn/feat_ref", lambda x: jax.nn.relu(
        conv2d_nb(ep["feat"], x, compute_dtype=cd)), ex)
    timed("espcn/feat_fast", lambda x: jax.nn.relu(
        conv2d_s2d(ep["feat"], x, compute_dtype=cd)), ex)
    e_c1 = act(eh, ew, ecfg.c1)
    timed("espcn/map_ref", lambda x: jax.nn.relu(
        conv2d_nb(ep["map"], x, compute_dtype=cd)), e_c1)
    timed("espcn/map_fast", lambda x: jax.nn.relu(
        conv2d_s2d(ep["map"], x, compute_dtype=cd)), e_c1)
    e_c2 = act(eh, ew, ecfg.c2)
    timed("espcn/head_ref", lambda x: conv2d_nb(
        ep["head"], x, compute_dtype=cd), e_c2)
    timed("espcn/head_fast", lambda x: conv2d_s2d(
        ep["head"], x, compute_dtype=cd), e_c2)
    exs = jnp.asarray(rng.rand(b, eh, ew, 3).astype(np.float32))
    timed("espcn/full_ref", lambda x: apply_espcn(ep, x, ecfg), exs)
    timed("espcn/full_fast", lambda x: apply_espcn(
        ep, x, EspcnConfig(fast_convs=True)), exs)

    doc = {
        "captured_utc": datetime.datetime.now(
            datetime.timezone.utc).isoformat(),
        "code_rev": git_rev(REPO),
        "backend": backend,
        "batch": b,
        "quick": args.quick,
        "geometry": {"style": [sh, sw], "espcn": [eh, ew]},
        "reps": args.reps,
        "ms_per_frame": results,
    }
    tmp = args.out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=2)
    os.replace(tmp, args.out)
    print(json.dumps({
        "written": args.out, "backend": backend,
        "style_full": results.get("style/full"),
        "espcn_full_ref": results.get("espcn/full_ref"),
        "espcn_full_fast": results.get("espcn/full_fast"),
    }), flush=True)
    return 0 if backend == "tpu" else 3


if __name__ == "__main__":
    sys.exit(main())
