"""The ×2 upscaling deployment (chipbench's ``sr2x_540p``) on the normal
serve path: ``ServeFrontend`` → ``DeviceLane`` → ``Engine`` →
``egress_pack`` → router, with a result that is NOT its input's geometry.

Toy size on the CPU (32×48 in, 64×96 out, batch 4: an even geometry, so the
served forward is the carried phase form of ``models/espcn.py::stage_forms``,
as at the cell's 540×960), seeded random weights
from the benchmark's plain reference (``chipbench/refs/sr2x_540p.py``,
loaded by path as test_session_state.py loads flow's: it imports nothing
of the program). What is held:

(a) the served path equals the reference within a stated tolerance, and
    the reference's fp8 control does not;
(b) the packed transfer layout round-trips bit-equal at an output
    geometry that is not the batch's;
(c) the byte counters of the ``ingest`` / ``egress`` blocks count rows ×
    bytes a row each way, and the bucket row says what the step returns
    and whether it donates its input;
(d) a dropped bias and a transposed shuffle read not correct by the
    configuration's own limits.
"""

import importlib.util
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dvf_tpu.obs.metrics import EgressStats, IngestStats
from dvf_tpu.ops import get_filter
from dvf_tpu.parallel import MeshConfig, make_mesh
from dvf_tpu.runtime import Engine
from dvf_tpu.runtime import egress as egress_mod
from dvf_tpu.runtime.egress import ShardedBatchFetcher
from dvf_tpu.serve import ServeConfig, ServeFrontend

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
H, W, BATCH, SCALE = 32, 48, 4, 2
IN_ROW = H * W * 3                       # bytes a submitted frame
OUT_ROW = (H * SCALE) * (W * SCALE) * 3  # bytes a delivered frame

# Served float32 program against the float32 reference: the same sums in
# another order (XLA's SAME convolution against the reference's at
# precision highest), which moves a rounding to uint8 here and there: one
# step at most, on a few pixels in a thousand.
F32_MAX_STEPS, F32_MEAN_STEPS = 1, 0.01
# Served bfloat16 program (bfloat16 operands AND bfloat16 activations and
# biases between the layers, which the reference's bfloat16_run does not
# round): the toy reading over seeds 1..4 is 0.24–0.30 mean, 2–3 max; the
# fp8 control reads 2.3–3.3 mean, 16–20 max. The tolerance sits between
# the two with room on both sides.
BF16_MAX_STEPS, BF16_MEAN_STEPS = 6, 0.8


def _load(relpath, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("chipbench/refs/sr2x_540p.py", "sr2x_540p_ref")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "chipbench", "configs", "sr2x_540p.json")) as f:
        cfg = json.load(f)
    for key, val in cfg["toy"].items():
        cfg[key] = {**cfg[key], **val}
    assert (cfg["geometry"]["height"], cfg["geometry"]["width"]) == (H, W)
    return cfg


def _frames(seed, n):
    """Coarse structure under fine noise, as the benchmark's pool."""
    rng = np.random.default_rng(seed)
    coarse = rng.integers(0, 256, (H // 8, W // 8 + n, 3), dtype=np.uint8)
    field = np.kron(coarse, np.ones((8, 8, 1), dtype=np.uint8)).astype(np.int16)
    noise = rng.integers(-24, 25, (H, W, 3), dtype=np.int16)
    return [np.clip(field[:, 8 * i:8 * i + W] + np.roll(noise, 5 * i, axis=1), 0, 255)
            .astype(np.uint8) for i in range(n)]


def _host(params):
    # Engine.compile donates the state it is given: hand it host copies.
    return jax.tree.map(np.asarray, params)


def _serve(filt, streams, engine=None, trace=False, batch=BATCH):
    """``streams``: one list of frames per session, submitted round-robin
    so that the sessions share batches. Returns (deliveries per session,
    stats, the frontend)."""
    cfg = ServeConfig(batch_size=batch, max_inflight=2, queue_size=64,
                      slo_ms=60_000.0, trace=trace)
    fe = ServeFrontend(filt, cfg, engine=engine)
    got = [[] for _ in streams]
    with fe:
        sids = [fe.open_stream(frame_shape=(H, W, 3)) for _ in streams]
        for i in range(max(len(s) for s in streams)):
            for sid, frames in zip(sids, streams):
                if i < len(frames):
                    fe.submit(sid, frames[i])
        for sid in sids:
            fe.close(sid, drain=True)
        deadline = time.time() + 60.0
        while time.time() < deadline and any(
                len(g) < len(s) for g, s in zip(got, streams)):
            for g, sid in zip(got, sids):
                g.extend(fe.poll(sid))
            time.sleep(0.002)
        stats = fe.stats()
    for g, s in zip(got, streams):
        assert [d.index for d in g] == list(range(len(s)))
    return got, stats, fe


def _numbers(got, wanted):
    """The benchmark's own comparison (chipbench/check.py), worst frame."""
    worst_max, worst_mean = 0, 0.0
    for g, w in zip(got, wanted):
        assert g.shape == w.shape == (H * SCALE, W * SCALE, 3) and g.dtype == np.uint8
        diff = np.abs(g.astype(np.int16) - w.astype(np.int16))
        worst_max, worst_mean = max(worst_max, int(diff.max())), max(worst_mean, float(diff.mean()))
    return {"max_abs_steps": worst_max, "mean_abs_steps": worst_mean}


def _within(numbers, limits):
    return all(numbers[k] <= v for k, v in limits.items())


def _served_numbers(ref, config, seed, dtype, params=None, wanted_params=None):
    """Two sessions sharing batches through the frontend against the
    reference on ``wanted_params`` (default: the served weights)."""
    made = ref.make_params(seed, config)
    served = made if params is None else params(made)
    streams = [_frames(seed, 6), _frames(seed + 100, 6)]
    filt = get_filter("super_resolution", params=_host(served),
                      **dict(config["filter"]["kwargs"], dtype=dtype))
    got, stats, _ = _serve(filt, streams)
    assert stats["errors"] == 0 and stats["faults"]["by_kind"] == {}
    want = [ref.reference(s, config, made if wanted_params is None else wanted_params(made))
            for s in streams]
    return _numbers([d.frame for g in got for d in g], [f for w in want for f in w])


# -- (a) the served path against the plain reference -------------------------

@pytest.mark.parametrize("seed", [1, 2])
def test_served_float32_equals_the_reference(ref, config, seed):
    n = _served_numbers(ref, config, seed, "float32")
    assert n["max_abs_steps"] <= F32_MAX_STEPS and n["mean_abs_steps"] <= F32_MEAN_STEPS, n


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_served_bfloat16_is_within_its_toy_reading(ref, config, seed):
    n = _served_numbers(ref, config, seed, "bfloat16")
    assert _within(n, {"max_abs_steps": BF16_MAX_STEPS, "mean_abs_steps": BF16_MEAN_STEPS}), n
    assert n["mean_abs_steps"] > F32_MEAN_STEPS      # it did run in bfloat16


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_fp8_control_is_outside_every_tolerance(ref, config, seed):
    frames = _frames(seed, 6)
    params = ref.make_params(seed, config)
    n = _numbers(ref.control(frames, config, params), ref.reference(frames, config, params))
    assert n["max_abs_steps"] > BF16_MAX_STEPS and n["mean_abs_steps"] > BF16_MEAN_STEPS, n
    assert not _within(n, config["limits"]), (n, config["limits"])
    # and a sound bfloat16 computation is inside the configuration's limits
    sound = _numbers(ref.bfloat16_run(frames, config, params),
                     ref.reference(frames, config, params))
    assert _within(sound, config["limits"]), sound


def test_reference_imports_nothing_of_the_program(ref):
    with open(ref.__file__) as f:
        src = f.read()
    assert "dvf_tpu" not in src.split('"""', 2)[2]          # the docstring names the file it follows
    assert not any(line.startswith(("import dvf", "from dvf", "from chipbench"))
                   for line in (ln.strip() for ln in src.splitlines()))


def test_reference_shuffle_is_the_programs_dcr_order(ref):
    from dvf_tpu.models.layers import depth_to_space

    x = jnp.arange(2 * 3 * 5 * 12, dtype=jnp.float32).reshape(2, 3, 5, 12)
    y = np.asarray(ref.shuffle(x, 2))
    np.testing.assert_array_equal(y, np.asarray(depth_to_space(x, 2)))
    for (i, j, c) in [(0, 1, 2), (1, 0, 0), (1, 1, 1)]:
        assert y[1, 2 * 2 + i, 4 * 2 + j, c] == x[1, 2, 4, (i * 2 + j) * 3 + c]
    assert not np.array_equal(y, np.asarray(ref.shuffle(x, 2, order="ji")))


# -- (d) broken programs read not correct ------------------------------------

def _without_bias(layer):
    def drop(params):
        return {k: {"w": v["w"], "b": v["b"] * (0.0 if k == layer else 1.0)}
                for k, v in params.items()}
    return drop


@pytest.mark.parametrize("layer", ["feat", "map", "head"])
def test_a_dropped_bias_reads_not_correct(ref, config, layer):
    n = _served_numbers(ref, config, 5, "bfloat16", params=_without_bias(layer))
    assert not _within(n, config["limits"]), (layer, n, config["limits"])


def test_a_transposed_shuffle_reads_not_correct(ref, config, monkeypatch):
    from dvf_tpu.models import espcn

    def transposed(x, factor):
        # J for I within each block of sub-pixels, at the carried form's one rearrangement
        # (a factor an axis) as at the plain body's: the columns read (j, i, c) for (i, j, c).
        b, h, w, n = x.shape
        fh, fw = factor
        x = x.reshape(b, h, w, fw, fh, n // (fh * fw)).swapaxes(3, 4)
        return x.transpose(0, 1, 3, 2, 4, 5).reshape(b, h * fh, w * fw, -1)

    monkeypatch.setattr(espcn, "depth_to_space", transposed)
    n = _served_numbers(ref, config, 6, "bfloat16")
    assert not _within(n, config["limits"]), (n, config["limits"])
    assert n["mean_abs_steps"] > 10 * config["limits"]["mean_abs_steps"]


# -- (b) the pack at a geometry that is not the batch's ----------------------

@pytest.fixture
def packed_on_cpu(monkeypatch):
    monkeypatch.setattr(egress_mod, "STREAM_ON_CPU", True)
    monkeypatch.setattr(egress_mod, "MIN_STREAM_D2H_MS", 0.0)


def test_pack_round_trip_at_the_output_geometry(packed_on_cpu):
    filt = get_filter("super_resolution", scale=SCALE, seed=3)
    eng = Engine(filt, mesh=make_mesh(MeshConfig(data=1)))
    eng.ensure_compiled((BATCH, H, W, 3), np.uint8)
    assert eng.out_shape == (BATCH, H * SCALE, W * SCALE, 3)
    assert eng.step_donates_input is False
    fetcher = ShardedBatchFetcher(eng.out_shape, eng.out_dtype, eng.output_sharding, slots=3)
    assert fetcher.out_shape != (BATCH, H, W, 3)
    for seq in range(4):
        batch = np.stack(_frames(20 + seq, BATCH))
        result = eng.submit(batch.copy())
        want = np.asarray(result)
        handle = fetcher.prefetch(result)
        assert isinstance(handle, egress_mod.PackedBatch) and handle.out_shape == eng.out_shape
        assert len(handle.rows) == BATCH
        out = fetcher.fetch(handle, seq)
        # a buffer a row, at the OUTPUT geometry, none sharing memory with another
        assert isinstance(out, egress_mod.LandedRows) and len(out) == BATCH
        assert all(r.shape == eng.out_shape[1:] and r.dtype == np.uint8 and not r.flags.writeable
                   for r in out)
        assert not any(np.shares_memory(a, b) for i, a in enumerate(out) for b in out[i + 1:])
        np.testing.assert_array_equal(np.stack(out), want)
    s = fetcher.stats.summary()
    assert s["transfer_layout"] == "u32rows" and s["packed_batches"] == s["batches"] == 4
    assert s["row_landed_batches"] == 4 and s["rows_landed_total"] == 4 * BATCH
    assert s["bytes_total"] == 4 * BATCH * OUT_ROW


# -- (c) byte counters and the bucket row ------------------------------------

def _bucket_row(stats):
    (row,) = [r for r in stats["buckets"].values() if r.get("batches")]
    return row


@pytest.mark.parametrize("packed", [False, True], ids=["slabs", "u32rows"])
def test_bytes_counted_each_way(ref, config, packed, request):
    engine = None
    filt = get_filter("super_resolution", params=_host(ref.make_params(7, config)),
                      **config["filter"]["kwargs"])
    if packed:
        request.getfixturevalue("packed_on_cpu")
        engine = Engine(filt, mesh=make_mesh(MeshConfig(data=1)))
    n_batches = 5
    streams = [_frames(7, 2 * n_batches), _frames(8, 2 * n_batches)]   # 20 rows: 5 full batches
    got, stats, _ = _serve(filt, streams, engine=engine)
    row = _bucket_row(stats)
    batches = row["ingest"]["batches"]
    assert batches == row["egress"]["batches"] == row["batches"] >= n_batches
    # whole padded batches go up; whole padded batches come down the slab path, and on the packed
    # layout the rows that carry a frame (a padding row is never transferred)
    rows = BATCH * n_batches
    padding = BATCH * batches - rows
    assert row["ingest"]["bytes_total"] == batches * BATCH * IN_ROW
    assert row["egress"]["bytes_total"] == (rows if packed else batches * BATCH) * OUT_ROW
    assert row["egress"]["rows_landed_total"] == (rows if packed else 0)
    assert row["egress"]["rows_skipped_total"] == (padding if packed else 0)
    assert row["egress"]["row_landed_batches"] == (batches if packed else 0)
    if not padding:
        assert row["egress"]["bytes_total"] == SCALE * SCALE * row["ingest"]["bytes_total"]
    assert row["out_geometry"] == [H * SCALE, W * SCALE, 3]
    assert row["step_donates_input"] is False
    assert row["egress"]["transfer_layout"] == ("u32rows" if packed else "plain")
    # the packed layout's deliveries are the 2H x 2W buffers their rows landed in; a slab's are copies
    assert stats["rows_handed_total"] == (rows if packed else 0)
    assert stats["rows_copied_total"] == (0 if packed else rows)
    assert all(d.frame.shape == (H * SCALE, W * SCALE, 3) and d.frame.flags.writeable != packed
               for g in got for d in g)


def test_a_short_batch_lands_its_valid_rows_at_the_output_geometry(ref, config, packed_on_cpu):
    """3 frames in a batch of 4: three 2H x 2W rows cross, the padding row's four-fold bytes do not."""
    filt = get_filter("super_resolution", params=_host(ref.make_params(9, config)),
                      **config["filter"]["kwargs"])
    engine = Engine(filt, mesh=make_mesh(MeshConfig(data=1)))
    streams = [_frames(9, 3)]
    got, stats, _ = _serve(filt, streams, engine=engine)
    row = _bucket_row(stats)
    eg = row["egress"]
    assert eg["rows_landed_total"] == 3 and eg["bytes_total"] == 3 * OUT_ROW
    assert eg["rows_skipped_total"] == BATCH * eg["batches"] - 3 > 0
    assert eg["row_landed_batches"] == eg["packed_batches"] == eg["batches"]
    assert stats["rows_handed_total"] == 3 and stats["rows_copied_total"] == 0
    want = ref.reference(streams[0], config, ref.make_params(9, config))
    assert _within(_numbers([d.frame for d in got[0]], want),
                   {"max_abs_steps": BF16_MAX_STEPS, "mean_abs_steps": BF16_MEAN_STEPS})


def test_invert_row_keeps_its_geometry_and_donates():
    streams = [_frames(9, 8)]
    got, stats, _ = _serve(get_filter("invert"), streams)
    for d, src in zip(got[0], streams[0]):
        np.testing.assert_array_equal(d.frame, 255 - src)
    row = _bucket_row(stats)
    assert row["out_geometry"] == [H, W, 3] and row["step_donates_input"] is True
    assert row["ingest"]["bytes_total"] == row["egress"]["bytes_total"] \
        == row["batches"] * BATCH * IN_ROW


def test_unserved_bucket_row_states_no_geometry():
    fe = ServeFrontend(get_filter("invert"), ServeConfig(batch_size=BATCH))
    with fe:
        (row,) = fe.stats()["buckets"].values()
    assert row["out_geometry"] is None and row["step_donates_input"] is None


@pytest.mark.parametrize("cls,record,key", [
    (IngestStats, lambda s, n: s.record_batch(1.0, 2.0, 3.0, nbytes=n), "stage_ms_total"),
    (EgressStats, lambda s, n: s.record_fetch(1.5, 0.5, nbytes=n), "d2h_wait_ms_total"),
], ids=["ingest", "egress"])
def test_stats_blocks_accumulate_bytes(cls, record, key):
    s = cls()
    assert s.summary()["bytes_total"] == 0
    record(s, 1000)
    record(s, 24)
    doc = s.summary()
    assert doc["bytes_total"] == 1024 and isinstance(doc["bytes_total"], int)
    assert doc["batches"] == 2 and doc[key] > 0


def test_dispatch_spans_carry_out_bytes(ref, config):
    filt = get_filter("super_resolution", params=_host(ref.make_params(11, config)),
                      **config["filter"]["kwargs"])
    _, _, fe = _serve(filt, [_frames(11, 8)], trace=True)
    spans = [e for e in fe.tracer._events
             if e["name"] in ("dispatch:permit_wait", "dispatch:assemble_h2d")]
    assert spans and all(e["args"]["out_bytes"] == BATCH * OUT_ROW for e in spans)


# -- the stage scopes --------------------------------------------------------

def _scoped_primitives(jaxpr, outer, found):
    """(scope path, primitive name) of every equation, through nested
    jaxprs (a pjit's or a custom_jvp's own stack is relative to its
    caller's)."""
    for eqn in jaxpr.eqns:
        scope = "/".join(p for p in (outer, str(eqn.source_info.name_stack)) if p)
        found.append((scope, eqn.primitive.name))
        for val in eqn.params.values():
            inner = getattr(val, "jaxpr", val)
            if hasattr(inner, "eqns"):
                _scoped_primitives(inner, scope, found)


@pytest.mark.parametrize("fast,h", [(False, H), (True, H), (False, H + 1)],
                         ids=["carried", "fast_convs", "plain"])
def test_stages_carry_named_scopes(fast, h):
    """Every convolution sits under its stage's scope and the rearrangement
    under ``shuffle``: what the HLO's ``op_name`` then says of each fusion
    (scripts/style_step_probe.py --model espcn sums the step by them). In
    the carried form (an even geometry) as in the plain body (an odd one),
    every op of the step but the cast of the batch is under a stage."""
    from dvf_tpu.models.espcn import EspcnConfig, apply_espcn, init_espcn, stage_forms

    cfg = EspcnConfig(scale=SCALE, fast_convs=fast)
    want_form = "phase" if (not fast and h % 2 == 0) else "plain"
    assert set(stage_forms(cfg, (2, h, W, 3)).values()) == {want_form}
    params = init_espcn(jax.random.PRNGKey(0), cfg)
    jaxpr = jax.make_jaxpr(lambda p, b: apply_espcn(p, b, cfg))(
        params, jax.ShapeDtypeStruct((2, h, W, 3), jnp.float32))
    found = []
    _scoped_primitives(jaxpr.jaxpr, "", found)
    stages = ("feat", "map", "head", "shuffle")
    convs = [next((st for st in stages if st in scope.split("/")), None)
             for scope, prim in found if prim == "conv_general_dilated"]
    assert convs and set(convs) == {"feat", "map", "head"}, convs
    assert convs == sorted(convs, key=stages.index)              # in the net's order
    under_shuffle = {prim for scope, prim in found if "shuffle" in scope.split("/")}
    assert "transpose" in under_shuffle, under_shuffle
    assert under_shuffle & {"clamp", "max", "min"}, under_shuffle          # the clip to [0, 1]
    outside = [prim for scope, prim in found if not set(stages) & set(scope.split("/"))]
    assert outside == ["convert_element_type"], outside         # batch.astype(compute_dtype)
    if want_form == "phase":
        # the kernel re-indexings (gathers on the weights) belong to their stage too
        gathers = [next(st for st in stages if st in scope.split("/"))
                   for scope, prim in found if prim == "gather"]
        assert {"feat", "map", "head"} <= set(gathers), gathers
