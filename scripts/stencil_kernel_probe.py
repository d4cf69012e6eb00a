#!/usr/bin/env python3
"""The fused Sobel -> bilateral kernel from inside: its VLIW schedule with no chip, its three times on one.

``scripts/style_step_probe.py --model stencil`` stops at the kernel: a Mosaic kernel is ONE op in a device trace. This
script reads what the TPU compiler made of it. Without ``--chip`` (the session image: no accelerator needed) it
compiles ``sobel_bilateral_nhwc_pallas`` at the cell's shape (64 x 1080 x 1920, ``d`` 9, tile 24) for a DESCRIBED v5e
with the compiler's LLO dump on, and prints from ``*sobel_bilateral*final_bundles.txt``:

- **bundles a grid step**, dynamic: every bundle inside the grid's loop, a bundle inside an inner loop times that
  loop's trips (the ``/* loop exit test */`` compare of its backward branch). A bundle is a cycle of the v5e's 1.5 GHz
  clock unless the core stalls, so bundles / 1.5e3 is the grid step's microseconds before DMA waits and stalls;
- ``vld`` / ``vst`` and how many of them address ``#allocation*_spill`` (the register file is 64 vregs: a whole
  24 x 1920 tile is 45 an array), ``vpow2`` (the ``exp``: taps x vregs worked over), ``vrot.lane`` (lane shifts, three
  XLUs), ``vrot.slane`` and ``vsel`` (sublane realignment), the VALU arithmetic, each dynamic as above;
- the scoped VMEM the call holds: the compiler's own allocations (scratch operands, internal scratch, spill slots).

The compile runs in a child process, with ``JAX_PLATFORMS=cpu``: the dump's flags go in ``LIBTPU_INIT_ARGS`` before the
TPU library loads, and the dumper may abort the process AFTER the schedule is written (its VMEM report wants a
template file this installation lacks); the files are read whatever the child's exit code.

    python scripts/stencil_kernel_probe.py                                  # chiprun_out/stencil_kernel_probe.json
    python scripts/stencil_kernel_probe.py --tree chip_checkout/parent --out chiprun_out/stencil_kernel_probe_parent.json

``--chip`` (through ``chiprun``) times the kernel's ``pallas_call`` alone at ``f32[batch, h_pad + halo, w_aligned]``
three ways, so that the slab wait is split from the taps (PERF.md section 7c(4), ROADMAP M7):

- ``shipped``: as the step runs it;
- ``one_tap``: every ``range(d)`` of the kernel's body cut to one trip while it is traced (one tap; in the strip form
  also one column shift where it makes ``d``): slab DMA, Sobel and the store with next to no taps;
- ``no_slab_wait``: the slab copy started and awaited at the first grid step only (later steps compute on that slab):
  the compute with no DMA wait. ``shipped - no_slab_wait`` is the exposed slab wait, ``shipped - one_tap`` the taps.

The variants patch names in ``dvf_tpu.ops.pallas_kernels`` for the length of one trace; their results are garbage and
only timed. It also checks ``shipped`` against ``sobel_bilateral(impl="chain")`` on two frames. ``--tree DIR`` probes
another checkout's ``dvf_tpu`` (the parent's) with this script. ``--toy``: a tiny shape on whatever backend jax has, in
interpret mode off the TPU; it checks the script and its times mean nothing.

``--kernel clahe_hist`` (PR 50) reads the histogram family's counting kernel the same two ways: without ``--chip`` the
schedule of ``tile_hist_pallas`` at the cell's tiles (``u8[192, 8, 136, 2048]``: 64 frames' 192 planes, 8 rows of 8 tiles
of 136 x 256) with ``vpcnt`` (the population count a bin a word vreg), ``vand``, ``vcmp`` (the compare form's) and the rest;
with ``--chip`` the ``pallas_call`` alone on seeded noise, the least of ``--steps`` calls, and its counts against
``np.bincount`` on one plane. Its record is ``chiprun_out/clahe_hist_kernel_probe.json``.

    python scripts/stencil_kernel_probe.py --kernel clahe_hist [--tree chip_checkout/parent --out chiprun_out/clahe_hist_kernel_probe_parent.json]
"""

from __future__ import annotations

import argparse
import builtins
import collections
import contextlib
import glob
import json
import os
import re
import subprocess
import sys
import tempfile
import time
from unittest import mock

HERE = os.path.dirname(os.path.abspath(__file__))
CLOCK_GHZ = 1.5     # TPU v5e core clock: one VLIW bundle a cycle
COUNTED = ("vld", "vst", "vpow2", "vrot.lane", "vrot.slane", "vsel", "vmul", "vadd", "vsub", "vrcp", "vrsqrt",
           "vpcnt", "vand", "vor", "vcmp", "vshll", "vshrl")
HIST_TILES = (8, 136, 2048)     # clahe_1080p: rows of tiles a plane, a row of 8 tiles of 136 x 256, side by side
FLOW_CONFIG = os.path.join(HERE, "..", "chipbench", "configs", "flow_720p.json")

_BUNDLE = re.compile(r"^\s*(?:0x[0-9a-f]+|\d+)\s+(?:([A-Z]{2}):|:)\s*(?:> ?)*\{(.*)$")
_EXIT_TEST = re.compile(r"%(p\w+) = scmp\.ge\.s32\.totalorder .*?, (\d+) /\* loop exit test \*/")
_BACK_EDGE = re.compile(r"sbr\.rel \(!%(p\w+)\) target bundleno")
_VMEM = re.compile(r"#(allocation\w+) \[shape = '[^']*', space=vmem, size = (0x[0-9a-f]+)")


def read_bundles(text):
    """``(counts, loops)`` of one kernel's ``final_bundles`` text: dynamic counts a grid step (``bundles`` and each
    opcode of ``COUNTED``, spills apart) and the loops inside a grid step as first / last bundle (positions in the
    listing) and trips. A loop opens at an ``LB:`` bundle and closes at the next backward branch not yet paired
    (``sbr.rel (!%p)``, ``%p`` a loop's exit test; on any other predicate it is a forward branch, a pipelined grid's
    "skip this copy", and closes nothing): the listing nests them properly. The branch's own ``target bundleno`` counts
    in another numbering than the listing's and is not used."""
    bundles, trips_of, open_loops, loops = [], {}, [], []      # loops: [first, last, trips]
    for line in text.splitlines():
        m = _BUNDLE.match(line)
        if not m:
            continue
        here = len(bundles)
        bundles.append(m.group(2).split(" ;; "))
        if m.group(1) == "LB":
            open_loops.append(here)
        trips_of.update((name, int(n)) for name, n in _EXIT_TEST.findall(line))
        for name in _BACK_EDGE.findall(line):
            if name not in trips_of:            # a forward branch: a pipelined grid's "skip this copy"
                continue
            if not open_loops:
                raise ValueError(f"a backward branch with no open loop at bundle {here}")
            loops.append([open_loops.pop(), here, trips_of.get(name)])
    if open_loops or not loops:
        raise ValueError(f"{len(open_loops)} loops left open, {len(loops)} closed: not a Pallas grid's schedule?")
    grid = loops.pop()                                  # the grid's own loop closes last and holds every other
    if any(trips is None for _, _, trips in loops):
        raise ValueError(f"inner loops with no readable trip count: {loops}")
    counts = collections.Counter()
    for here in range(grid[0], grid[1] + 1):
        weight = 1
        for first, last, trips in loops:
            if first <= here <= last:
                weight *= trips
        counts["bundles"] += weight
        for ins in bundles[here]:
            m = re.search(r"= (v[a-z0-9]+(?:\.[a-z]+)?)", ins)
            op = m and next((name for name in COUNTED if m.group(1).startswith(name)), None)
            if op:
                counts[op] += weight
                if op in ("vld", "vst") and "_spill" in ins:
                    counts[op + "_spill"] += weight
    return dict(counts), [{"first": a, "last": b, "trips": t, "bundles": b - a + 1} for a, b, t in sorted(loops)]


def scoped_vmem(text):
    """Bytes of VMEM the compiler allocated for the call, by kind, from a late LLO dump's allocation table."""
    by_kind = collections.Counter()
    for name, size in _VMEM.findall(text):
        by_kind["spill" if "spill" in name else "scratch"] += int(size, 16)
    return {"scratch_bytes": by_kind["scratch"], "spill_bytes": by_kind["spill"], "bytes": sum(by_kind.values())}


def _import_kernels(tree):
    sys.path.insert(0, os.path.abspath(tree))
    from dvf_tpu.ops import pallas_kernels

    return pallas_kernels


def _hist_call(pk, tiles, interpret=False):
    """``tile_hist_pallas`` on rows of 8 tiles side by side, every pixel of a padded tile counted (no filler)."""
    return pk.tile_hist_pallas(tiles, 8, tiles.shape[2] * tiles.shape[3] // 8, "clahe_hist", interpret)


def warp_call(role, level, toy=False):
    """``((H, W, planes), max_disp)`` of one of the flow step's ten ``warp_bounded`` calls at the cell's geometry
    (``chipbench/configs/flow_720p.json``): the final warp of the previous frame, or an inner warp of the polynomial
    stack at pyramid ``level`` of the estimation grid (``ops/flow.py``: ``flow_warp``, ``_coarse_to_fine``)."""
    with open(FLOW_CONFIG) as f:
        config = json.load(f)
    g, kw = config["geometry"], config["filter"]["kwargs"]
    if toy:
        g = config["toy"]["geometry"]
    if role == "final":
        return (g["height"], g["width"], g["channels"]), kw["max_disp"]
    eh, ew = g["height"] // kw["flow_scale"], g["width"] // kw["flow_scale"]
    return ((max(8, round(eh * 0.5 ** level)), max(8, round(ew * 0.5 ** level)), 5),
            max(1, -(-kw["max_disp"] // kw["flow_scale"])))


def dump_child(args):
    """The child: compile for a described v5e with the dump on. May not return (see the module docstring)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    pk = _import_kernels(args.tree)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    jax.config.update("jax_enable_compilation_cache", False)   # a described device's entry cannot be read back
    one_chip = SingleDeviceSharding(topo.devices[0])
    if args.kernel == "clahe_hist":
        tiles = jax.ShapeDtypeStruct((3 * args.batch, *HIST_TILES), jnp.uint8, sharding=one_chip)
        jax.jit(lambda t: _hist_call(pk, t)).lower(tiles).compile()
        return
    if args.kernel == "warp_bounded":
        (h, w, c), max_disp = warp_call(args.role, args.level)
        img = jax.ShapeDtypeStruct((args.batch, h, w, c), jnp.float32, sharding=one_chip)
        flow = jax.ShapeDtypeStruct((args.batch, h, w, 2), jnp.float32, sharding=one_chip)
        jax.jit(lambda i, f: pk.warp_bounded_pallas(i, f, max_disp=max_disp, interpret=False)).lower(img, flow).compile()
        return
    batch = jax.ShapeDtypeStruct((args.batch, args.height, args.width, 3), jnp.float32, sharding=one_chip)
    jax.jit(lambda x: pk.sobel_bilateral_nhwc_pallas(x, d=args.d, tile_h=args.tile_h)).lower(batch).compile()


def schedule(args):
    with tempfile.TemporaryDirectory(prefix="stencil_llo_") as dump:
        env = dict(os.environ, JAX_PLATFORMS="cpu", LIBTPU_INIT_ARGS=" ".join(filter(None, [
            os.environ.get("LIBTPU_INIT_ARGS"), f"--xla_jf_dump_to={dump}", "--xla_jf_dump_llo_text=true"])))
        child = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--dump-child", "--kernel", args.kernel, "--tree", args.tree, "--d", str(args.d),
             "--tile-h", str(args.tile_h), "--batch", str(args.batch), "--height", str(args.height),
             "--width", str(args.width), "--role", args.role, "--level", str(args.level)], env=env, capture_output=True, text=True)
        final = [p for p in glob.glob(os.path.join(dump, f"*{args.kernel}*final_bundles.txt"))
                 if "schedule-analysis" not in p]
        if len(final) != 1:
            raise SystemExit(f"the compile left {len(final)} final_bundles files of the kernel (exit code "
                             f"{child.returncode}):\n{child.stderr[-3000:]}")
        with open(final[0]) as f:
            counts, loops = read_bundles(f.read())
        late = glob.glob(os.path.join(dump, f"*{args.kernel}*post-delay-converter.txt"))
        vmem = None
        if late:
            with open(late[0]) as f:
                vmem = scoped_vmem(f.read())
    counts["us_at_clock"] = round(counts["bundles"] / (CLOCK_GHZ * 1e3), 3)
    return {"bundles_a_grid_step": counts, "inner_loops": loops, "scoped_vmem": vmem, "child_exit_code": child.returncode}


def _variants(pk):
    """name -> the context (a patch of one name in ``pk``) under which the kernel is traced."""
    from jax.experimental import pallas as pl

    def one_trip(*a):                   # range(d) -> one trip; range(-r, r + 1) (the weights' table) as it is
        return builtins.range(*a) if len(a) != 1 else builtins.range(min(a[0], 1))

    class FirstStepOnly:                # the slab copy of grid step (0, 0) alone
        def __init__(self, copy):
            self.copy = copy

        def _first(self, do):
            pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))(do)

        def start(self):
            self._first(self.copy.start)

        def wait(self):
            self._first(self.copy.wait)

    pltpu = pk.pltpu

    class Pltpu:                        # pltpu, but for make_async_copy
        def __getattr__(self, name):
            return getattr(pltpu, name)

        @staticmethod
        def make_async_copy(*a, **k):
            return FirstStepOnly(pltpu.make_async_copy(*a, **k))

    return {"shipped": contextlib.nullcontext(),
            "one_tap": mock.patch.object(pk, "range", one_trip, create=True),
            "no_slab_wait": mock.patch.object(pk, "pltpu", Pltpu())}


def chip(args):
    import jax
    import jax.numpy as jnp
    import numpy as np

    pk = _import_kernels(args.tree)
    from dvf_tpu.ops import get_filter

    on_tpu = jax.default_backend() == "tpu"
    if not (on_tpu or args.toy):
        raise SystemExit("--chip times a TPU; there is none here (--toy checks the script on any backend)")
    shape = (args.batch, args.height, args.width, 3)
    plan = pk.sobel_bilateral_plan(shape, args.d, args.tile_h, not on_tpu)
    frames = jnp.asarray(np.random.default_rng(46).random(shape, dtype=np.float32))
    out = {"device": {"platform": jax.devices()[0].platform, "kind": jax.devices()[0].device_kind}, "kernel_ms": {}}

    def kernel_only(x):
        return pk.sobel_bilateral_nhwc_pallas(x, d=args.d, tile_h=args.tile_h, interpret=not on_tpu)

    for name, patch in _variants(pk).items():
        jax.clear_caches()              # or a variant is answered with the trace of the one before it
        with patch:
            jaxpr = jax.make_jaxpr(kernel_only)(frames)
            # the pallas_call's operand is the padded luma: time the call apart from its prep (named scopes add no eqn)
            (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
            run = jax.jit(lambda x, call=call: call.primitive.bind(x, **call.params)[0])
            slab = jax.ShapeDtypeStruct(call.invars[0].aval.shape, call.invars[0].aval.dtype)
            compiled = run.lower(slab).compile()
        x = jnp.asarray(np.random.default_rng(47).random(slab.shape, dtype=np.float32))
        jax.block_until_ready(compiled(x))
        times = []
        for _ in range(args.steps):
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(x))
            times.append((time.perf_counter() - t0) * 1e3)
        out["kernel_ms"][name] = {"min": round(min(times), 3), "median": round(sorted(times)[len(times) // 2], 3)}
        print(f"[probe] {name}: {out['kernel_ms'][name]} ms a call of {slab.shape}", flush=True)
    ms = {k: v["min"] for k, v in out["kernel_ms"].items()}
    steps = plan["grid"][0] * plan["grid"][1]
    out["us_a_grid_step"] = {k: round(v * 1e3 / steps, 3) for k, v in ms.items()}
    out["slab_wait_us_a_grid_step"] = round((ms["shipped"] - ms["no_slab_wait"]) * 1e3 / steps, 3)
    out["taps_us_a_grid_step"] = round((ms["shipped"] - ms["one_tap"]) * 1e3 / steps, 3)

    two = frames[:2]
    want = get_filter("sobel_bilateral", d=args.d, impl="chain").fn(two, None)[0]
    got = pk.sobel_bilateral_nhwc_pallas(two, d=args.d, tile_h=args.tile_h, interpret=not on_tpu)
    diff = np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64))
    out["against_chain"] = {"max_abs": float(diff.max()), "mean_abs": float(diff.mean()),
                            "uint8_steps_differing": int((np.rint(np.asarray(got) * 255) !=
                                                          np.rint(np.asarray(want) * 255)).sum()),
                            "values": int(diff.size)}
    return out


def chip_hist(args):
    """``--chip --kernel clahe_hist``: the counting kernel's ``pallas_call`` alone, and its counts."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    pk = _import_kernels(args.tree)
    on_tpu = jax.default_backend() == "tpu"
    if not (on_tpu or args.toy):
        raise SystemExit("--chip times a TPU; there is none here (--toy checks the script on any backend)")
    shape = (2, 2, 16, 8 * 128) if args.toy else (3 * args.batch, *HIST_TILES)
    tiles = np.random.default_rng(50).integers(0, 256, shape, dtype=np.uint8)
    x = jnp.asarray(tiles)
    # the pallas_call's own eqn, apart from the slice, swap and filler correction behind it
    (call,) = [e for e in jax.make_jaxpr(lambda t: _hist_call(pk, t, not on_tpu))(x).eqns
               if e.primitive.name == "pallas_call"]
    run = jax.jit(lambda t: call.primitive.bind(t, **call.params)[0])
    got = np.asarray(jax.block_until_ready(run(x)))         # (planes, rows of tiles, 256, 128): lane tx is tile tx
    times = []
    for _ in range(args.steps):
        t0 = time.perf_counter()
        jax.block_until_ready(run(x))
        times.append((time.perf_counter() - t0) * 1e3)
    lanes = shape[3] // 8
    want = np.stack([np.bincount(tiles[0, ty, :, tx * lanes:(tx + 1) * lanes].ravel(), minlength=256)
                     for ty in range(shape[1]) for tx in range(8)]).reshape(shape[1], 8, 256)
    ms = {"min": round(min(times), 3), "median": round(sorted(times)[len(times) // 2], 3)}
    print(f"[probe] clahe_hist: {ms} ms a call of u8{list(shape)}", flush=True)
    return {"device": {"platform": jax.devices()[0].platform, "kind": jax.devices()[0].device_kind},
            "kernel_ms": {"shipped": ms}, "tiles": list(shape),
            "us_a_grid_step": {"shipped": round(ms["min"] * 1e3 / (shape[0] * shape[1]), 3)},
            "against_bincount": {"tiles": int(want.shape[0] * 8),
                                 "equal": bool(np.array_equal(got[0, :, :, :8].swapaxes(1, 2), want))}}


def _pallas_calls(jaxpr):
    """The ``pallas_call`` equations of a jaxpr and of every jaxpr under it (the warp's wrapper is a jit of its own)."""
    import jax

    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_calls(sub)
    return found


def chip_warp(args):
    """``--chip --kernel warp_bounded``: one of the flow step's calls, its ``pallas_call`` alone, three ways (``one_tap``
    cuts every ``range`` as long as the taps' axis, ``2 max_disp + 2``, to one trip: in the strip form that is also
    one column-shifted copy), and ``shipped`` against ``ops.flow.warp_by_flow`` on the clipped flow on two frames."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    pk = _import_kernels(args.tree)
    from dvf_tpu.ops.flow import warp_by_flow

    on_tpu = jax.default_backend() == "tpu"
    if not (on_tpu or args.toy):
        raise SystemExit("--chip times a TPU; there is none here (--toy checks the script on any backend)")
    (h, w, c), max_disp = warp_call(args.role, args.level, args.toy)
    side = 2 * max_disp + 2
    rng = np.random.default_rng(51)
    img = jnp.asarray(rng.random((args.batch, h, w, c), dtype=np.float32))
    flow = jnp.asarray((rng.random((args.batch, h, w, 2), dtype=np.float32) - 0.5) * (2 * max_disp + 2))

    def warp(i, f):
        return pk.warp_bounded_pallas(i, f, max_disp=max_disp, interpret=not on_tpu)

    def one_trip(*a):                   # the taps' ranges (dy, dx: `side` long, however they are spelled) -> one trip
        r = builtins.range(*a)
        return r[:1] if len(r) == side else r

    variants = dict(_variants(pk), one_tap=mock.patch.object(pk, "range", one_trip, create=True))
    out = {"device": {"platform": jax.devices()[0].platform, "kind": jax.devices()[0].device_kind}, "kernel_ms": {}}
    for name in ("shipped", "one_tap", "no_slab_wait"):
        jax.clear_caches()              # or a variant is answered with the trace of the one before it
        with variants[name]:
            (call,) = _pallas_calls(jax.make_jaxpr(warp)(img, flow).jaxpr)
            run = jax.jit(lambda x, f, call=call: call.primitive.bind(x, f, **call.params)[0])
            operands = [jnp.asarray(rng.random(v.aval.shape, dtype=np.float32)) for v in call.invars]
            compiled = run.lower(*operands).compile()
        jax.block_until_ready(compiled(*operands))
        times = []
        for _ in range(args.steps):
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(*operands))
            times.append((time.perf_counter() - t0) * 1e3)
        out["kernel_ms"][name] = {"min": round(min(times), 3), "median": round(sorted(times)[len(times) // 2], 3)}
        out["grid"] = [int(v) for v in call.params["grid_mapping"].grid]
        print(f"[probe] {name}: {out['kernel_ms'][name]} ms a call, grid {out['grid']}", flush=True)
    ms = {k: v["min"] for k, v in out["kernel_ms"].items()}
    steps = out["grid"][0] * out["grid"][1]
    out["us_a_grid_step"] = {k: round(v * 1e3 / steps, 3) for k, v in ms.items()}
    out["slab_wait_us_a_grid_step"] = round((ms["shipped"] - ms["no_slab_wait"]) * 1e3 / steps, 3)
    out["taps_us_a_grid_step"] = round((ms["shipped"] - ms["one_tap"]) * 1e3 / steps, 3)
    got = np.asarray(warp(img[:2], flow[:2]), np.float64)
    want = np.asarray(warp_by_flow(img[:2], jnp.clip(flow[:2], -max_disp, max_disp)), np.float64)
    out["against_gather"] = {"max_abs": float(np.abs(got - want).max()), "mean_abs": float(np.abs(got - want).mean()),
                             "values": int(got.size)}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chip", action="store_true", help="time the kernel on the attached TPU (else: its schedule, no chip)")
    ap.add_argument("--toy", action="store_true", help="with --chip: a tiny shape on any backend; checks the script only")
    ap.add_argument("--kernel", choices=("sobel_bilateral", "clahe_hist", "warp_bounded"), default="sobel_bilateral")
    ap.add_argument("--role", choices=("final", "inner"), default="final", help="warp_bounded: which of the step's calls")
    ap.add_argument("--level", type=int, default=0, help="warp_bounded: an inner warp's pyramid level (0 = 360 x 640)")
    ap.add_argument("--tree", default=os.path.join(HERE, ".."), help="the checkout whose dvf_tpu is probed")
    ap.add_argument("--d", type=int, default=9)
    ap.add_argument("--tile-h", type=int, default=24)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--height", type=int, default=1080)
    ap.add_argument("--width", type=int, default=1920)
    ap.add_argument("--steps", type=int, default=10, help="--chip: timed calls a variant")
    ap.add_argument("--out", default=None, help="default chiprun_out/stencil_kernel_probe.json (clahe_hist_kernel_probe.json)")
    ap.add_argument("--dump-child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.dump_child:
        dump_child(args)
        return 0
    if args.toy:
        args.batch, args.height, args.width, args.tile_h, args.steps = 2, 32, 200, 16, 2
    stem = {"sobel_bilateral": "stencil", "warp_bounded": "warp"}.get(args.kernel, args.kernel)
    path = args.out or os.path.join(HERE, "..", "chiprun_out", f"{stem}_kernel_probe.json")
    result = {}
    if os.path.exists(path):            # the two halves run on two machines and share the file
        with open(path) as f:
            result = json.load(f)
    result["tree"] = os.path.relpath(os.path.abspath(args.tree), os.path.join(HERE, ".."))
    record = result
    if args.kernel == "clahe_hist":
        result["shape"] = {"batch": args.batch, "tiles": [3 * args.batch, *HIST_TILES]}
    elif args.kernel == "warp_bounded":     # a record a call of the step: final, inner_level0 ..
        call = "final" if args.role == "final" else f"inner_level{args.level}"
        record = result.setdefault("calls", {}).setdefault(call, {})
        (h, w, c), max_disp = warp_call(args.role, args.level, args.toy)
        record["shape"] = {"batch": args.batch, "height": h, "width": w, "planes": c, "max_disp": max_disp}
    else:
        result["shape"] = {"batch": args.batch, "height": args.height, "width": args.width, "d": args.d,
                           "tile_h": args.tile_h}
    half = "chip" if args.chip else "schedule"
    if args.chip:
        record["chip"] = {"clahe_hist": chip_hist, "warp_bounded": chip_warp}.get(args.kernel, chip)(args)
    else:
        record["schedule"] = schedule(args)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(result, f, indent=1, sort_keys=True)
    print(json.dumps(record[half], indent=1, sort_keys=True))
    print(f"[probe] wrote {os.path.relpath(path)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
