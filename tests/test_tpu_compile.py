"""What the TPU's compiler makes of the style net's norms, with no chip: the
compiler is installed here and compiles for a v5e that is described, not
attached (on-chip-measurement guide, section 2). Nothing runs, so nothing
here is a time. All such compiles live in this one file, behind a fixture:
one worker loads the TPU library, and only once a test of this file runs."""

import base64
import collections
import importlib.util
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _probe():
    path = os.path.join(os.path.dirname(__file__), "..", "scripts", "style_step_probe.py")
    spec = importlib.util.spec_from_file_location("style_step_probe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trunk_block_norm_sums_ride_in_their_convs_fusion(one_chip):
    """A residual block of the style trunk at the cell's size (16 frames'
    180x320x128, bfloat16): each of its two convs carries both of its
    norm's sums in its own fusion (``conv+norm_stats``), each norm has one
    apply pass, and no reduction over the activation is an op of its own —
    what PR 36's 13 ms of the 84 ms step rest on (PERF.md §5)."""
    from dvf_tpu.models.style_transfer import StyleNetConfig, _pp_res_block

    c = StyleNetConfig().widths[2]
    struct = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    conv = {"w": struct(3, 3, c, c), "b": struct(c)}
    norm = {"scale": struct(c), "bias": struct(c)}
    x = jax.ShapeDtypeStruct((16, 180, 320, c), jnp.bfloat16, sharding=one_chip)
    cache_was = jax.config.jax_enable_compilation_cache    # a described device's entry cannot be read back
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(_pp_res_block(StyleNetConfig())).lower(
            {"a": conv, "an": norm, "b": conv, "bn": norm}, x).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
    text = compiled.as_text()
    table = _probe().op_table(text, ())
    entry = re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) = ", text[text.index("\nENTRY"):], re.M)
    parts = [table[name][2] for name in entry if table[name][2]]
    assert sorted(parts) == ["conv+norm_stats"] * 2 + ["norm_apply"] * 2, parts


def test_espcn_step_at_the_cells_shape_carries_its_activations(one_chip):
    """The upscaling step as the Engine builds it (uint8 in, uint8 out) at
    16 x 540 x 960, for the described v5e: three conv fusions whose results
    are the phase images (16, 540, 480, 128), (16, 270, 480, 128) and
    (16, 270, 240, 96) — head's already uint8, the rounding inside the
    conv's own fusion — no tensor of the plain form's (16, 540, 960, c > 3),
    and no float tensor after head, so that the rearrangement moves bytes
    (PERF.md §5: what PR 41's 34.6 -> 14.9 ms rest on)."""
    from dvf_tpu.ops import get_filter
    from dvf_tpu.utils.image import to_float, to_uint8

    filt = get_filter("super_resolution", scale=2, fast_convs=False, dtype="bfloat16")
    shape = (16, 540, 960, 3)

    def step(batch, state):
        y, new_state = filt.fn(to_float(batch, filt.compute_dtype), state)
        return to_uint8(y), new_state

    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: filt.init_state(shape, jnp.float32)))
    batch = jax.ShapeDtypeStruct(shape, jnp.uint8, sharding=one_chip)
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(step).lower(batch, state).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
    made = []           # (dtype, dims, op, line) of the entry computation's instructions
    for line in text[text.index("\nENTRY"):].splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (\w+)\[([\d,]*)\]\S* (\w+)\(", line)
        if m:
            made.append(m.groups() + (line,))
    convs = [dims for _, dims, op, line in made
             if op == "fusion" and "conv_general_dilated" in line and dims.startswith("16,")]
    assert convs == ["16,540,480,128", "16,270,480,128", "16,270,240,96"], convs
    assert [dtype for dtype, dims, _, _ in made if dims == "16,270,240,96"][0] == "u8"
    plain_form = [dims for _, dims, _, _ in made
                  if dims.startswith("16,540,960,") and dims != "16,540,960,3"]
    assert not plain_form, plain_form
    big_floats = [(dtype, dims) for dtype, dims, _, _ in made
                  if dtype in ("f32", "bf16") and dims.startswith(("16,1080,", "16,270,4,", "16,270,240,"))]
    assert not big_floats, big_floats


_STENCIL_SHAPE = (2, 1080, 1920, 3)
_stencil_texts = {}


def _stencil_step_text(one_chip, d, tile_h, vmem="plan"):
    """The compiled text of the fused Sobel -> bilateral step as the Engine
    wraps it (uint8 in, uint8 out), for the described v5e; one compile a
    (d, tile_h, vmem) for the tests below. ``vmem="default"``: with
    ``_stencil_vmem_limit`` answering None, Mosaic's own 16 MiB of scope."""
    from dvf_tpu.ops import pallas_kernels as pk
    from dvf_tpu.utils.image import to_float, to_uint8

    if (d, tile_h, vmem) not in _stencil_texts:
        def step(batch):
            return to_uint8(pk.sobel_bilateral_nhwc_pallas(to_float(batch), d=d, tile_h=tile_h))

        batch = jax.ShapeDtypeStruct(_STENCIL_SHAPE, jnp.uint8, sharding=one_chip)
        cache_was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            with pytest.MonkeyPatch.context() as patch:
                if vmem == "default":
                    patch.setattr(pk, "_stencil_vmem_limit", lambda tile_h, interpret, taps: None)
                _stencil_texts[d, tile_h, vmem] = jax.jit(step).lower(batch).compile().as_text()
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_was)
    return _stencil_texts[d, tile_h, vmem]


@pytest.mark.parametrize("d,tile_h,vmem", [(5, None, "plan"), (9, None, "plan"), (9, 24, "plan"),
                                           (9, None, "default"), (9, 24, "default")],
                         ids=["d5", "d9", "d9_pinned", "d9_default_vmem", "d9_pinned_default_vmem"])
def test_stencil_kernel_compiles_through_mosaic_at_1080p(one_chip, d, tile_h, vmem):
    """The fused Sobel -> bilateral kernel at 1080 x 1920 for the described
    v5e, as the Engine's step wraps it. The kernel is in the step under its
    own name, inside its scope, with the limit ``sobel_bilateral_plan``
    states, and its result is the ONE plane of the edge map (PR 44; three
    equal ones before).

    The ``default_vmem`` cases: at d 9 (81 taps) the whole-tile form's
    unrolled temporaries needed 26.33 MB of scoped VMEM at the tile of 24
    rows, so under Mosaic's default 16 MiB the kernel did not compile
    (RESOURCE_EXHAUSTED, before PR 43 raised the limit; interpret mode on
    the CPU never sees it). In strips (PR 46) the call holds 3.25 MB, the
    slab, the nine shifted copies of the map and 0.34 MB of spill slots:
    it compiles with no limit raised."""
    from dvf_tpu.ops.pallas_kernels import sobel_bilateral_plan

    text = _stencil_step_text(one_chip, d, tile_h, vmem)
    (call,) = [ln for ln in text.splitlines() if 'custom_call_target="tpu_custom_call"' in ln]
    assert re.match(r"\s*%sobel_bilateral(\.\d+)? = f32\[2,1080,1920\]", call), call[:120]
    assert 'op_name="jit(step)/stencil_kernel/sobel_bilateral/pallas_call"' in call
    plan = sobel_bilateral_plan(_STENCIL_SHAPE, d, tile_h)
    assert (plan["tile_h"], plan["slab_rows"], plan["w_aligned"]) == (24, 32 if d == 5 else 40, 2048)
    assert plan["planes"] == 1 and plan["vmem_scratch_bytes"] == plan["slab_rows"] * 2048 * 4
    assert plan["strip"] == [8, 384] and plan["vmem_shifted_bytes"] == d * 32 * 1920 * 4
    raised = re.search(r'"scoped_memory_configs":\[\{[^]]*"size":"(\d+)"', call)
    assert (int(raised.group(1)) if raised else None) == (plan["vmem_limit_bytes"] if vmem == "plan" else None)
    assert plan["vmem_limit_bytes"] == (None if d == 5 else 64 * 1024 * 1024)


@pytest.mark.parametrize("gone", ["f32[2,3,1096,2048]", "f32[2,3,1080,1920]", "f32[2,1080,1920,3]",
                                  "a fourth prep pass"],
                         ids=["padded_planes", "result_planes", "nhwc_float", "prep_passes"])
def test_stencil_step_carries_one_plane(one_chip, gone):
    """The step of the cell's filter (d 9, tile 24) for the described v5e
    makes no float32 tensor of three planes: not the padded NCHW input the
    kernel took before PR 44, not its threefold result, not the float NHWC
    frame in front of the luma (which a luma written as three channel
    slices leaves behind). And ``stencil_prep`` is three passes over a
    plane: the luma straight from the uint8 frame, a concatenate an axis
    (a ``jnp.pad`` for the filler would be a fourth)."""
    text = _stencil_step_text(one_chip, 9, 24)
    b, h, w, _ = _STENCIL_SHAPE
    made = []           # (result types, op_name) of the entry computation's instructions
    for line in text[text.index("\nENTRY"):].splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (\([^=]*?\)|\S+) ", line)
        if m:
            scope = re.search(r'op_name="([^"]*)"', line)
            made.append((re.findall(r"(\w+)\[([\d,]*)\]", m.group(1)), scope.group(1) if scope else ""))
    assert any("/stencil_prep/" in scope for _, scope in made)
    if gone.startswith("f32["):
        dims = gone[4:-1]
        assert not [types for types, _ in made if ("f32", dims) in types]
    else:
        elements = lambda dims: math.prod(int(v) for v in dims.split(",") if v)
        passes = [types for types, scope in made if "/stencil_prep/" in scope
                  and any(elements(dims) >= b * h * w for _, dims in types)]
        assert len(passes) <= 3, passes
        assert [types[0][1] for types in passes][-1] == "2,1096,2048"      # what the kernel reads


@pytest.mark.parametrize("shape", [(32, 1080, 1920, 3), (64, 1080, 1920, 3), (16, 720, 1280, 3)],
                         ids=["invert_1080p", "sobel_bilateral_1080p", "style_720p"])
def test_ingest_join_is_a_copy_with_no_temporary(one_chip, shape):
    """The row path's program (``runtime/ingest.py::ingest_join``, PR 47) for
    the described v5e at the cells' batches: B frames in the layout a TPU
    holds a ``uint8[H,W,3]`` array in (channel-planar) go into the batch in
    the layout the step takes (``{2,1,3,0}``), a frame an operand, with no
    temporary and no relayout on the chip: the slab path's concatenate."""
    from dvf_tpu.runtime.ingest import ingest_join

    b, h, w, c = shape
    frame = jax.ShapeDtypeStruct((h, w, c), jnp.uint8, sharding=one_chip)
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(ingest_join).lower(*(frame,) * b).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
    text = compiled.as_text()
    entry = text[text.index("\nENTRY"):]
    root = next(line for line in entry.splitlines() if "ROOT" in line)
    assert f"u8[{b},{h},{w},{c}]{{2,1,3,0" in root, root
    assert len(re.findall(rf"u8\[{h},{w},{c}\]{{1,0,2[^}}]*}} parameter\(", entry)) == b
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def _eqns(jaxpr, trips):
    """``(eqn, times it runs)`` of a jaxpr and every jaxpr under it, a
    loop's body times its trip count."""
    for eqn in jaxpr.eqns:
        yield eqn, trips
        inner = trips * eqn.params.get("length", 1) if eqn.primitive.name == "scan" else trips
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, inner)


@pytest.mark.parametrize("name,calls", [
    ("clahe", {"clahe_hist": "s32[6,8,256,128]", "clahe_apply": "u8[6,9,136,2304]"}),
    ("equalize", {"equalize_hist": "s32[6,9,256,128]", "equalize_apply": "u8[6,9,128,1920]"})])
def test_histogram_kernels_compile_through_mosaic_at_1080p(one_chip, name, calls):
    """The counted form of the histogram family (ops/histogram.py, PR 49)
    at 1080 x 1920 for the described v5e, as the Engine steps a
    ``uint8_ok`` filter (uint8 in, uint8 out, no float conversion): both
    kernels are in the step under their own names (the lane gather of
    ``lut_apply_pallas`` lowers through Mosaic), inside their scopes for
    CLAHE, under Mosaic's default scoped VMEM, and the step holds no sort
    and no XLA gather (the sort + gather form's one sort and, for CLAHE,
    four image-sized gathers: tests/test_histogram_forms.py reads the sort
    in its lowering, PERF.md section 4 the chip's 2.9 s for 8 frames)."""
    from dvf_tpu.ops import get_filter

    filt = get_filter(name, impl="pallas", interpret=False)
    batch = jax.ShapeDtypeStruct((2, 1080, 1920, 3), jnp.uint8, sharding=one_chip)
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        traced = jax.jit(lambda b: filt.fn(b, None)[0]).trace(batch)
        lowered = traced.lower()
        text = lowered.compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
    # the counting kernel as Mosaic takes it (PR 50): a population count (``math.ctpop``: the op names of a
    # kernel's serialized module are plain bytes), and no compare a bin: the compare form traced 8 ``eq`` a trip of
    # 32, the bit-plane form compares a lane index with the tile's once a tile
    bodies = [base64.b64decode(b) for b in re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', lowered.as_text())]
    assert [b"ctpop" in body for body in bodies] == [True, False]
    hist = next(eqn for eqn, _ in _eqns(traced.jaxpr.jaxpr, 1)
                if eqn.primitive.name == "pallas_call" and eqn.params["name"].endswith("_hist"))
    ran = collections.Counter()
    for eqn, trips in _eqns(hist.params["jaxpr"], 1):
        ran[eqn.primitive.name] += trips
    assert ran["population_count"] >= 1 and ran["eq"] <= 8 and ran["select_n"] <= 8, ran
    made = {m.group(1): m.group(2) for m in (
        re.match(r"\s*%([a-z_]+)(?:\.\d+)? = (\w+\[[\d,]*\])\S* custom-call\(", ln)
        for ln in text.splitlines() if 'custom_call_target="tpu_custom_call"' in ln) if m}
    assert made == calls
    if name == "clahe":
        for kernel in calls:
            assert f'/{kernel}/{kernel}/pallas_call"' in text, kernel
    assert not re.search(r'"scoped_memory_configs":\[\{', text)     # no raised limit
    assert not re.findall(r" (sort|gather)\(", text)     # instructions, whatever their result type


_FLOW_KW = dict(levels=3, win_size=15, n_iters=3, flow_scale=2, warp_impl="pallas", max_disp=4,
                win_type="gaussian", inner_warp="pallas")        # as chipbench/configs/flow_720p.json


@pytest.mark.parametrize("hw,c,max_disp", [((720, 1280), 3, 4), ((360, 640), 5, 2), ((180, 320), 5, 2),
                                           ((90, 160), 5, 2)], ids=["final", "level0", "level1", "level2"])
def test_warp_kernel_compiles_through_mosaic_at_the_cells_shapes(one_chip, hw, c, max_disp):
    """``warp_bounded`` in strips (PR 51) at the four shapes flow_720p's
    step calls it with, for the described v5e: under its own name, its
    result the padded planes ``warp_plan`` states, and under Mosaic's
    DEFAULT scoped VMEM: the whole-tile form held 19.3 MB at the final
    warp's 16-row tile, 18.1 of it spill slots, and ran under a raised
    limit of 64 MiB; the strips spill nothing and the ten column-shifted
    copies of a 48-row tile's slab are 9.8 MB (scripts/stencil_kernel_probe.py
    --kernel warp_bounded reads the schedule)."""
    from dvf_tpu.ops.pallas_kernels import warp_bounded_pallas, warp_plan

    img = jax.ShapeDtypeStruct((2, *hw, c), jnp.float32, sharding=one_chip)
    flow = jax.ShapeDtypeStruct((2, *hw, 2), jnp.float32, sharding=one_chip)
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(lambda i, f: warp_bounded_pallas(i, f, max_disp=max_disp, interpret=False)).lower(
            img, flow).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
    plan = warp_plan(img.shape, max_disp)
    (call,) = [ln for ln in text.splitlines() if 'custom_call_target="tpu_custom_call"' in ln]
    made = re.match(r"\s*%warp_bounded(?:\.\d+)? = f32\[([\d,]+)\]", call)
    assert made and [int(v) for v in made.group(1).split(",")] == [2, c, plan["h_pad"], plan["w_out"]], call[:120]
    assert plan["vmem_limit_bytes"] is None and not re.search(r'"scoped_memory_configs":\[\{', call)
    assert not re.findall(r" gather\(", text)


def test_flow_step_holds_ten_warp_kernels_and_no_gather(one_chip, monkeypatch):
    """The flow step of flow_720p as the Engine builds it (uint8 in, uint8
    out, one session's pairs) at 2 x 720 x 1280 for the described v5e: the
    final warp and the nine inner warps are ten ``warp_bounded`` calls
    under their two scopes, and no XLA gather is left beside them (the
    gathers of the 5-plane stacks were 3.5 s of a 3.78 s step, PR 27).
    ``_auto_interpret`` is steered here: it asks ``jax.default_backend()``,
    which is the CPU's in this process."""
    from dvf_tpu.ops import get_filter
    from dvf_tpu.ops import pallas_kernels as pk
    from dvf_tpu.utils.image import to_float, to_uint8

    monkeypatch.setattr(pk, "_auto_interpret", lambda interpret: False)
    filt = get_filter("flow_warp", **_FLOW_KW)
    shape = (2, 720, 1280, 3)

    def step(batch, state):
        y, new_state = filt.fn(to_float(batch, filt.compute_dtype), state)
        return to_uint8(y), new_state

    state = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
                         jax.eval_shape(lambda: filt.init_state(shape, jnp.float32)))
    batch = jax.ShapeDtypeStruct(shape, jnp.uint8, sharding=one_chip)
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(step).lower(batch, state).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
    calls = [ln for ln in text.splitlines() if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 10 and all(re.match(r"\s*%warp_bounded(\.\d+)? = ", ln) for ln in calls)
    scopes = collections.Counter(
        next(s for s in ("flow_final_warp", "flow_inner_warp") if f"/{s}/" in ln) for ln in calls)
    assert scopes == {"flow_final_warp": 1, "flow_inner_warp": 9}
    assert not re.findall(r" gather\(", text)
    assert filt.kernel_plan(shape)["calls"][-1]["grid"] == [2, 15]


def test_denoise_step_at_the_cells_batch_fits_and_carries_phase_images(one_chip):
    """The video denoiser's served step (uint8 in, uint8 out, the Engine's
    table body over 16 session rows, the row map an operand) at the cell's
    32 x 540 x 960 for the described v5e. It compiles, which the plain NHWC
    form does not at this batch (20.3 GB of scratch: a
    ``f32[32,540,960,32]`` is stored four times padded); its scratch leaves
    the chip room for the table and the batches in flight; every
    convolution's result is a phase image whose columns fill the lanes
    (``models/fastdvdnet.py`` FULL / HALF / PLAIN) or the 12 columns of the
    residual, never a plain (32, 540, 960, c > 12) or (32, 270, 480, 64);
    and there are 32 convolutions, two DenBlocks of 16: the cached form."""
    import numpy as np

    from dvf_tpu.ops import get_filter
    from dvf_tpu.runtime.engine import Engine
    from dvf_tpu.utils.image import to_float, to_uint8

    filt = get_filter("video_denoise")
    shape = (32, 540, 960, 3)
    engine = Engine(filt, state_rows=16)
    engine._tabled = True                      # what compile() finds for such a filter
    body = engine._table_body(shape, np.uint8)

    def step(batch, state, row_map):
        y, new_state = body(to_float(batch, filt.compute_dtype), state, row_map)
        return to_uint8(y), new_state

    on_chip = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    state = on_chip(jax.eval_shape(lambda: engine._fresh_state(shape, np.uint8)))
    batch = jax.ShapeDtypeStruct(shape, jnp.uint8, sharding=one_chip)
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
            batch, state, on_chip(engine._row_map_aval(shape[0]))).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
    assert compiled.memory_analysis().temp_size_in_bytes < 7 * 2 ** 30
    text = compiled.as_text()
    from dvf_tpu.runtime.engine import conv_op_names

    entry = text[text.index("\nENTRY"):]
    convs = []              # result dims of each instruction that holds a convolution
    for name in conv_op_names(text):
        m = re.search(r"%" + re.escape(name) + r" = \(?\w+\[([\d,]*)\]", entry)
        convs.append(m.group(1))
    # two DenBlocks of 16; an instruction may hold two (XLA fuses four of the
    # 270p convolutions into their consumers' fusions at this batch)
    assert sum(" convolution(" in line for line in text.splitlines()) == 32
    assert 16 <= len(convs) <= 32, (len(convs), collections.Counter(convs))
    allowed = {"32,270,480,360", "32,270,480,128", "32,270,240,128", "32,135,240,128",
               "32,135,240,256", "32,270,240,256", "32,270,480,12"}
    assert set(convs) <= allowed, set(convs) - allowed
    scopes = collections.Counter(
        s for line in text.splitlines() if " convolution(" in line
        for s in ("denoise_stage1", "denoise_stage2") if f"/{s}/" in line)
    assert scopes == {"denoise_stage1": 16, "denoise_stage2": 16}, scopes
