"""The Sobel -> bilateral deployment (chipbench's ``sobel_bilateral_1080p``)
on the normal serve path: ``ServeFrontend`` -> ``DeviceLane`` -> ``Engine``,
the fused Pallas stencil in interpret mode, toy geometries on the CPU.

The plain reference is the benchmark's (``chipbench/refs/
sobel_bilateral_1080p.py``, loaded by path: it imports nothing of the
program). What is held:

(a) the served path (several sessions through cross-session batches, order
    kept) equals the reference inside the configuration's limits, at the
    real-time window (d 5) and the offline one the cell serves (d 9), at a
    geometry whose H the tile pick has to pad (36 rows: ``h_pad > h``), one
    it tiles exactly (64) and one a single tile holds (24);
(b) the reference's controls (a bfloat16 body; the window's outer ring
    dropped) read not correct by ``chipbench/check.py::decide``;
(c) ``impl="pallas"`` against ``impl="chain"`` at d 9;
(d) a compiled step says which kernel it runs and how it tiled it: the
    bucket row's ``kernel`` block is what the tiling helpers give, None for
    a filter of XLA's own ops, and the dispatch span names the kernel;
(e) the lowered step carries the three stage scopes and the kernel's name;
(f) which kernels get the raised scoped-VMEM limit (the unpinned d 9 kernel
    did not compile on the chip before PR 43).
"""

import importlib.util
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check
from dvf_tpu.ops import get_filter
from dvf_tpu.ops import pallas_kernels as pk
from dvf_tpu.serve import ServeConfig, ServeFrontend
from dvf_tpu.utils.image import to_float, to_uint8

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BATCH = 4
GEOMETRIES = {"padded": (36, 48), "tiled": (64, 96), "whole": (24, 40)}


def _load(relpath, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("chipbench/refs/sobel_bilateral_1080p.py", "sobel_bilateral_1080p_ref")


@pytest.fixture(scope="module")
def costs():
    return _load("chipbench/costs/sobel_bilateral_1080p.py", "sobel_bilateral_1080p_costs")


def _config(toy=True, **kwargs):
    with open(os.path.join(ROOT, "chipbench", "configs", "sobel_bilateral_1080p.json")) as f:
        cfg = json.load(f)
    if toy:
        for key, val in cfg["toy"].items():
            cfg[key] = {**cfg[key], **val}
    cfg["filter"] = {**cfg["filter"], "kwargs": {**cfg["filter"]["kwargs"], **kwargs}}
    return cfg


def _frames(seed, n, h, w):
    """Coarse structure under fine noise, as the benchmark's pool."""
    rng = np.random.default_rng(seed)
    coarse = rng.integers(0, 256, (h // 8 + 1, w // 8 + 1 + n, 3), dtype=np.uint8)
    field = np.kron(coarse, np.ones((8, 8, 1), dtype=np.uint8)).astype(np.int16)
    noise = rng.integers(-24, 25, (h, w, 3), dtype=np.int16)
    return [np.clip(field[:h, 8 * i:8 * i + w] + np.roll(noise, 5 * i, axis=1), 0, 255)
            .astype(np.uint8) for i in range(n)]


def _serve(filt, streams, shape, trace=False):
    """``streams``: one list of frames per session, submitted round-robin
    so that the sessions share batches; the order check is here."""
    fe = ServeFrontend(filt, ServeConfig(batch_size=BATCH, max_inflight=2, queue_size=64,
                                         slo_ms=60_000.0, trace=trace))
    got = [[] for _ in streams]
    with fe:
        sids = [fe.open_stream(frame_shape=shape) for _ in streams]
        for i in range(max(len(s) for s in streams)):
            for sid, frames in zip(sids, streams):
                if i < len(frames):
                    fe.submit(sid, frames[i])
        for sid in sids:
            fe.close(sid, drain=True)
        deadline = time.time() + 120.0
        while time.time() < deadline and any(len(g) < len(s) for g, s in zip(got, streams)):
            for g, sid in zip(got, sids):
                g.extend(fe.poll(sid))
            time.sleep(0.002)
        stats = fe.stats()
    for g, s in zip(got, streams):
        assert [d.index for d in g] == list(range(len(s)))      # per session, in order, once
    assert stats["errors"] == 0 and stats["faults"]["by_kind"] == {}
    return got, stats, fe


def _numbers(got, wanted):
    """The benchmark's own comparison (chipbench/check.py), worst frame."""
    samples = [(0, i, g) for i, g in enumerate(got)]
    return check.compare_numbers(samples, wanted, len(wanted))


def _bucket_row(stats):
    (row,) = [r for r in stats["buckets"].values() if r.get("batches")]
    return row


# -- (a) the served path against the plain reference -------------------------

@pytest.mark.parametrize("d", [5, 9])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_served_path_equals_the_reference(ref, d, geometry):
    h, w = GEOMETRIES[geometry]
    cfg = _config(d=d)
    filt = get_filter(cfg["filter"]["name"], **cfg["filter"]["kwargs"])
    # three sessions, 5 + 5 + 3 frames: batches of four mix the sessions, the last is short
    streams = [_frames(d, 5, h, w), _frames(d + 50, 5, h, w), _frames(d + 100, 3, h, w)]
    got, stats, _ = _serve(filt, streams, (h, w, 3))
    want = [f for s in streams for f in ref.reference(s, cfg)]
    n = _numbers([dl.frame for g in got for dl in g], want)
    assert n["shape_mismatch"] == 0
    assert check.decide(n, cfg["limits"], log=lambda m: None), (n, cfg["limits"])
    # a float32 body against a float32 reference: a rounding moves here and there, no more
    assert n["max_abs_steps"] <= 1 and n["mean_abs_steps"] <= 0.01, n
    plan = _bucket_row(stats)["kernel"]
    assert (plan["h_pad"] > h) == (geometry == "padded")
    assert plan["taps"] == d * d


# -- (b) the controls read not correct ---------------------------------------

@pytest.mark.parametrize("control", ["control", "ring_dropped"])
@pytest.mark.parametrize("seed", [1, 2])
def test_controls_fail_the_check(ref, control, seed):
    cfg = _config()                        # the cell's own kwargs (d 9) and limits
    h, w = GEOMETRIES["padded"]
    frames = _frames(seed, 4, h, w)
    want = ref.reference(frames, cfg)
    n = _numbers(getattr(ref, control)(frames, cfg), want)
    assert not check.decide(n, cfg["limits"], log=lambda m: None), (control, n, cfg["limits"])
    sound = _numbers(ref.reference(frames, cfg), want)
    assert sound["max_abs_steps"] == 0 and check.decide(sound, cfg["limits"], log=lambda m: None)


def test_reference_imports_nothing_of_the_program(ref):
    with open(ref.__file__) as f:
        src = f.read()
    assert "dvf_tpu" not in src.split('"""', 2)[2]          # the docstring names the files it follows
    assert not any(line.startswith(("import dvf", "from dvf", "from chipbench"))
                   for line in (ln.strip() for ln in src.splitlines()))


def test_configuration_states_what_the_issue_fixed():
    cfg = _config(toy=False)
    assert cfg["reduced"] == [] and cfg["architecture"] is None and cfg["chips"] == 1
    kw = cfg["filter"]["kwargs"]
    assert (kw["d"], kw["sigma_color"], kw["sigma_space"], kw["magnitude_scale"]) == (9, 0.1, 2.0, 1.0)
    assert cfg["compute_dtype"] == "float32" and cfg["serve"]["batch_size"] == 64
    g = cfg["geometry"]
    # the pinned tile is the auto pick, so pinned and unpinned resolve to one program
    shape = (cfg["serve"]["batch_size"], g["height"], g["width"], g["channels"])
    assert kw["tile_h"] == pk._pick_tile_h(g["height"])[0] == 24
    assert pk.sobel_bilateral_plan(shape, kw["d"], kw["tile_h"]) \
        == pk.sobel_bilateral_plan(shape, kw["d"], None)
    # and that program is what sobel_bilateral(d=9, impl="pallas") names
    assert get_filter("sobel_bilateral", d=9, impl="pallas").name \
        == get_filter(cfg["filter"]["name"], **kw).name
    toy = _config()
    assert toy["filter"]["kwargs"]["tile_h"] is None
    assert pk._pick_tile_h(toy["geometry"]["height"])[1] > toy["geometry"]["height"]


# -- (c) the two implementations ---------------------------------------------

@pytest.mark.parametrize("geometry", ["padded", "tiled"])
def test_pallas_against_chain_at_the_offline_window(geometry):
    h, w = GEOMETRIES[geometry]
    batch = np.stack(_frames(3, BATCH, h, w))
    out = {}
    for impl in ("pallas", "chain"):
        filt = get_filter("sobel_bilateral", d=9, impl=impl)
        out[impl] = np.asarray(jax.jit(
            lambda b, f=filt: to_uint8(f.fn(to_float(b), None)[0]))(batch))
        assert (filt.kernel_plan is not None) == (impl == "pallas")
    diff = np.abs(out["pallas"].astype(np.int16) - out["chain"].astype(np.int16))
    assert diff.max() <= 1 and diff.mean() <= 0.01, (diff.max(), diff.mean())


# -- (d) a compiled step says which kernel it runs ----------------------------

@pytest.mark.parametrize("d", [5, 9])
@pytest.mark.parametrize("geometry", ["padded", "tiled"])
def test_bucket_row_states_the_kernel_and_its_tiling(d, geometry):
    h, w = GEOMETRIES[geometry]
    filt = get_filter("sobel_bilateral", d=d, impl="pallas")
    _, stats, fe = _serve(filt, [_frames(7, 6, h, w), _frames(8, 6, h, w)], (h, w, 3), trace=True)
    block = _bucket_row(stats)["kernel"]
    halo2 = 2 * (d // 2 + 1)
    th, h_pad = pk._resolve_tile_h(h, None, compiled=False)
    slab, w_al = pk._slab_rows(th, halo2), pk._round_up(w + halo2, pk._LANE)
    assert block == {
        "kernel": "sobel_bilateral", "impl": "pallas", "taps": d * d, "planes": 1, "tile_h": th,
        "h_pad": h_pad, "grid": [BATCH, h_pad // th], "slab_rows": slab, "w_aligned": w_al,
        "vmem_scratch_bytes": slab * w_al * 4, "vmem_limit_bytes": None,
        "compute_dtype": "float32",
        # PR 46: compiled, the taps run in register-sized strips over d column-shifted copies of the
        # edge map; interpret mode (this CPU) keeps the whole tile at once and holds no copies
        "strip": None, "vmem_shifted_bytes": 0}
    assert block == fe._buckets[0].engine.kernel_plan
    json.dumps(block)                                    # plain data: stats() is serialised
    spans = [e for e in fe.tracer._events if e["name"] == "dispatch:assemble_h2d"]
    assert spans and all(e["args"]["kernel"] == "sobel_bilateral" for e in spans)


@pytest.mark.parametrize("name,kwargs", [("invert", {}), ("sobel_bilateral", {"impl": "chain"}),
                                         ("gaussian_blur", {"ksize": 3})])
def test_a_filter_of_xlas_own_ops_states_no_kernel(name, kwargs):
    h, w = GEOMETRIES["padded"]
    _, stats, fe = _serve(get_filter(name, **kwargs), [_frames(9, 4, h, w)], (h, w, 3), trace=True)
    row = _bucket_row(stats)
    assert row["kernel"] is None and row["out_geometry"] == [h, w, 3]
    spans = [e for e in fe.tracer._events if e["name"] == "dispatch:assemble_h2d"]
    assert spans and all(e["args"]["kernel"] is None for e in spans)


def test_unserved_bucket_row_states_no_kernel():
    fe = ServeFrontend(get_filter("sobel_bilateral", impl="pallas"), ServeConfig(batch_size=BATCH))
    with fe:
        (row,) = fe.stats()["buckets"].values()
    assert row["kernel"] is None                         # resolved at the compile, not before


# -- (e) scopes and the kernel's name in the lowered step ---------------------

def _scoped_primitives(jaxpr, outer, found):
    for eqn in jaxpr.eqns:
        scope = "/".join(p for p in (outer, str(eqn.source_info.name_stack)) if p)
        found.append((scope, eqn.primitive.name, eqn.params.get("name")))
        for val in eqn.params.values():
            inner = getattr(val, "jaxpr", val)
            if hasattr(inner, "eqns") and eqn.primitive.name != "pallas_call":
                _scoped_primitives(inner, scope, found)


@pytest.mark.parametrize("d", [5, 9])
def test_step_carries_the_three_scopes_and_the_kernels_name(d):
    h, w = GEOMETRIES["padded"]
    filt = get_filter("sobel_bilateral", d=d, impl="pallas")

    def step(batch):                   # the body of Engine._build_step
        return to_uint8(filt.fn(to_float(batch, filt.compute_dtype), None)[0])

    jaxpr = jax.make_jaxpr(step)(jax.ShapeDtypeStruct((BATCH, h, w, 3), jnp.uint8))
    found = []
    _scoped_primitives(jaxpr.jaxpr, "", found)
    stage = lambda scope: next((s for s in ("stencil_prep", "stencil_kernel", "stencil_finish")
                                if s in scope.split("/")), None)
    by_stage = {}
    for scope, prim, _ in found:
        by_stage.setdefault(stage(scope), []).append(prim)
    calls = [(stage(scope), name) for scope, prim, name in found if prim == "pallas_call"]
    assert calls == [("stencil_kernel", "sobel_bilateral")], calls
    assert by_stage["stencil_kernel"] == ["pallas_call"]
    # the luma as a reduction, then the reflected strips and the filler, an axis a concatenate
    assert {"reduce_sum", "rev", "concatenate"} <= set(by_stage["stencil_prep"]), by_stage["stencil_prep"]
    assert by_stage["stencil_prep"].count("concatenate") == 2 and "pad" not in by_stage["stencil_prep"]
    assert {"slice", "broadcast_in_dim"} <= set(by_stage["stencil_finish"]), by_stage["stencil_finish"]
    # outside the filter: the engine's uint8 <-> float conversions only
    assert not {"pallas_call", "rev", "transpose"} & set(by_stage[None]), by_stage[None]
    text = jax.jit(step).lower(jax.ShapeDtypeStruct((BATCH, h, w, 3), jnp.uint8)).as_text(
        debug_info=True)
    for scope in ("stencil_prep", "stencil_kernel", "stencil_finish", "sobel_bilateral"):
        assert scope in text, scope


@pytest.mark.parametrize("fn,name", [
    (lambda x: pk.bilateral_nhwc_pallas(x, d=5, interpret=True), "bilateral"),
    (lambda x: pk.sep_blur_nhwc_pallas(x, [0.25, 0.5, 0.25], [0.25, 0.5, 0.25], interpret=True),
     "sep_blur"),
    (lambda x: pk.sobel_bilateral_nhwc_pallas(x, d=5, interpret=True), "sobel_bilateral"),
    (lambda x: pk.warp_bounded_pallas(x, x[..., :2], interpret=True), "warp_bounded"),
])
def test_every_stencil_kernel_is_named(fn, name):
    jaxpr = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((2, 24, 40, 3), jnp.float32))
    found = []
    _scoped_primitives(jaxpr.jaxpr, "", found)
    assert [n for _, prim, n in found if prim == "pallas_call"] == [name]


# -- (f) the scoped-VMEM rule -------------------------------------------------

@pytest.mark.parametrize("tile_h,interpret,taps,raised", [
    (None, False, 25, False),      # the default window at the auto tile: Mosaic's 16 MiB
    (None, False, 81, True),       # d 9 at the auto tile: 26.33 MB needed (PR 43)
    (None, False, 49, True),
    (24, False, 25, True),         # a pinned tile (chip_smoke.py's pins; PR 21)
    (24, False, 81, True),
    (None, True, 81, False),       # interpret mode has no VMEM
    (24, True, 81, False),
])
def test_which_stencils_get_the_raised_vmem_limit(tile_h, interpret, taps, raised):
    limit = pk._stencil_vmem_limit(tile_h, interpret, taps)
    assert limit == (64 * 1024 * 1024 if raised else None)
    params = pk._vmem_params(limit)
    assert (params is not None) == raised
    if raised:
        assert params.vmem_limit_bytes == limit
    d = int(round(taps ** 0.5))
    plan = pk.sobel_bilateral_plan((2, 1080, 1920, 3), d, tile_h, interpret)
    assert plan["vmem_limit_bytes"] == limit


def test_plan_at_the_cells_shape():
    plan = pk.sobel_bilateral_plan((64, 1080, 1920, 3), 9)
    assert (plan["tile_h"], plan["h_pad"], plan["grid"]) == (24, 1080, [64, 45])
    assert (plan["slab_rows"], plan["w_aligned"]) == (40, 2048)
    # one plane a grid step, in and out (PR 44): the luma's slab, the edge map's block
    assert plan["planes"] == 1 and plan["vmem_scratch_bytes"] == 40 * 2048 * 4
    # the taps' strips tile the 24 x 1920 block exactly: 3 x 5 of 8 x 384, three vregs an array (PR 46),
    # over nine shifted copies of the 32 map rows a tile's taps reach
    assert plan["strip"] == [8, 384]
    assert plan["vmem_shifted_bytes"] == 9 * 32 * 1920 * 4
    with pytest.raises(ValueError):
        pk.sobel_bilateral_plan((64, 1080, 1920, 3), 8)


# -- the yardstick's counts ---------------------------------------------------

def test_costs_follow_the_configurations_window_and_geometry(costs):
    cfg = _config(toy=False)
    pixels = 64 * 1080 * 1920
    step, kernel = costs.cost(cfg, 64), costs.kernel_cost(cfg, 64)
    assert step["bytes"] == 2 * 3 * pixels                       # uint8 frame in, uint8 frame out
    assert kernel["bytes"] == 4 * (3 + 1) * pixels               # three float32 planes in, one map out
    assert step["flops"] == (19 + 8 * 81 + 1 + 3) * pixels
    assert kernel["flops"] == (19 + 8 * 81 + 1) * pixels
    five = costs.kernel_cost(_config(toy=False, d=5), 64)
    assert five["bytes"] == kernel["bytes"] and five["flops"] == (19 + 8 * 25 + 1) * pixels
    assert costs.cost(cfg, 32)["flops"] * 2 == step["flops"]


# -- the kernel probe's reading of a schedule (scripts/stencil_kernel_probe.py) ------------------------------------

_BUNDLES = """\
// kernel: sobel_bilateral.1
     0x0   :  { %s1_s0 = smov 0 }
     0x1 LB: > { %v1_v0 = vld [vmem:[%s0_s1] sm:$0xff]  ;;  %s2_s2 = sphi %s1_s0, %s3_s2 /* phi copy */ }
     0x2 LB: >> { %v2_v1 = vld [vmem:[#allocation3_spill] sm:$0xff]  ;;  %4169 = vpow2.f32 %v2_v1 }
     0x3   : >> { %v4_v2 = vpop.eup %4169  ;;  %p5_p1 = scmp.ge.s32.totalorder %s4_s3, 5 /* loop exit test */ }
     0x4   : >> { %9 = vst [vmem:[#allocation3_spill] sm:$0xff] /*vst_source=*/%v4_v2  ;;  %7 = sbr.rel (!%p5_p1) target bundleno = 39 (0x27), region = 60 }
     0x5   : > { %v8_v3 = vmul.f32 %v1_v0, %v1_v0  ;;  %10 = vst [vmem:[%s9_s4] sm:$0xff] /*vst_source=*/%v8_v3  ;;  %p9_p2 = scmp.ge.s32.totalorder %s2_s2, 2881 /* loop exit test */ }
     0x6   :  { %13 = sbr.rel (!%p9_p2) target bundleno = 1 (0x1), region = 93 }
"""


def test_probe_counts_a_grid_steps_bundles_with_inner_loops_times_their_trips():
    """A grid step of the listing: one bundle, a three-bundle inner loop of
    five trips, two bundles after it. Spills are the loads and stores that
    address ``#allocation*_spill``; a listing with no loop is refused."""
    probe = _load("scripts/stencil_kernel_probe.py", "stencil_kernel_probe")
    counts, loops = probe.read_bundles(_BUNDLES)
    assert counts == {"bundles": 1 + 3 * 5 + 2, "vld": 1 + 5, "vld_spill": 5, "vpow2": 5, "vst": 5 + 1,
                      "vst_spill": 5, "vmul": 1}
    assert loops == [{"first": 2, "last": 4, "trips": 5, "bundles": 3}]
    with pytest.raises(ValueError):
        probe.read_bundles("     0x0   :  { %s1_s0 = smov 0 }\n")
    vmem = probe.scoped_vmem("#allocation2 [shape = 'f32[40,2048]{1,0}', space=vmem, size = 0x50000, scoped]\n"
                             "#allocation3_spill [shape = 'u8[4096]{0}', space=vmem, size = 0x1000, scoped]\n"
                             "#allocation9 [shape = 's32[1]{0}', space=sflag, size = 0x4, scoped]\n")
    assert vmem == {"scratch_bytes": 0x50000, "spill_bytes": 0x1000, "bytes": 0x51000}
