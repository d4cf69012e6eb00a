"""The video-denoising deployment (chipbench's ``fastdvd_540p``) on the
normal serve path: ``get_filter("video_denoise")`` → ``Engine``'s session
table → ``ServeFrontend``, a five-frame window with two frames of lookahead.

Toy size on the CPU (24×32, the configuration's ``toy`` block), seeded
weights from the benchmark's plain reference
(``chipbench/refs/fastdvd_540p.py``, loaded by path: it imports nothing of
the program, and is the UNCACHED form, four DenBlocks a window). What is
held:

(a) the model (``models/fastdvdnet.py``, phase-domain convolutions) equals
    the reference on single windows;
(b) the streamed, cached form through the Engine's table equals the
    reference a session at a time: sessions interleaved in arbitrary order,
    a session split across batches, a fresh session and a pad row in
    mid-batch, one session alone through ``fn``; how a session's frames
    fall across batches never shows in a bit of a result;
(c) the warm-up rule on a session's first frames;
(d) ``ServeFrontend`` end to end: delivery n is the denoised frame n − 2,
    in order, every frame accounted, and the rows say so;
(e) the reference's two controls fail the toy limits, a sound bfloat16
    computation passes them;
(f) the cost function counts two DenBlocks a frame.
"""

import importlib.util
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dvf_tpu.api.filter import session_leaves
from dvf_tpu.models import fastdvdnet
from dvf_tpu.ops import get_filter
from dvf_tpu.runtime import Engine
from dvf_tpu.serve import ServeConfig, ServeFrontend

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
H, W = 24, 32

# The float32 program against the float32 reference: the same sums in
# another order (phase-domain kernels, folded norms, XLA's convolution
# against the reference's at precision highest), which moves a rounding to
# uint8 on a pixel in a few thousand, by one step.
F32_MAX_STEPS, F32_MEAN_STEPS = 1, 0.01
# The bfloat16 program (bfloat16 operands and activations, float32 sums):
# the toy reading over seeds 1..4 is 0.15–0.24 mean, 1–2 max; the fp8
# control reads 2.2–2.9 mean, 17–20 max, the stale cache 5.2–5.7 and 38–39.
# The configuration's toy limits (0.7, 6) sit between, near the middle.


def _load(relpath, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("chipbench/refs/fastdvd_540p.py", "fastdvd_540p_ref")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "chipbench", "configs", "fastdvd_540p.json")) as f:
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


def _numbers(got, wanted):
    """The benchmark's own comparison (chipbench/check.py), worst frame."""
    worst_max, worst_mean = 0, 0.0
    for g, w in zip(got, wanted):
        assert g.shape == w.shape == (H, W, 3) and g.dtype == np.uint8
        diff = np.abs(g.astype(np.int16) - w.astype(np.int16))
        worst_max, worst_mean = max(worst_max, int(diff.max())), max(worst_mean, float(diff.mean()))
    return {"max_abs_steps": worst_max, "mean_abs_steps": worst_mean}


def _within(numbers, limits):
    return all(numbers[k] <= v for k, v in limits.items())


def _filter(config, params, **kw):
    return get_filter("video_denoise", params=_host(params), **dict(config["filter"]["kwargs"], **kw))


# -- (a) the model against the reference, a window at a time -----------------

@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_model_equals_the_reference_on_single_windows(ref, config, seed, dtype):
    params = ref.make_params(seed, config)
    frames = _frames(seed, 6)
    windows = [[frames[(j - 4 + t) % 6] for t in range(5)] for j in range(6)]
    want = ref.reference(frames, config, params)
    x = jnp.asarray(np.stack([np.stack(w) for w in windows]), jnp.float32) / 255.0
    y = fastdvdnet.apply_fastdvdnet(params, x, fastdvdnet.FastDvdConfig(
        sigma=config["filter"]["kwargs"]["sigma"], compute_dtype=jnp.dtype(dtype)))
    got = list(np.asarray(jnp.round(jnp.clip(y, 0.0, 1.0) * 255.0).astype(jnp.uint8)))
    n = _numbers(got, want)
    if dtype == "float32":
        assert n["max_abs_steps"] <= F32_MAX_STEPS and n["mean_abs_steps"] <= F32_MEAN_STEPS, n
    else:
        assert _within(n, config["limits"]) and n["mean_abs_steps"] > F32_MEAN_STEPS, n


def test_the_tree_is_the_published_one(ref, config):
    made = ref.make_params(3, config)
    own = fastdvdnet.init_fastdvdnet(jax.random.PRNGKey(0))
    assert jax.tree.structure(made) == jax.tree.structure(own)
    assert jax.tree.map(jnp.shape, made) == jax.tree.map(jnp.shape, own)
    assert fastdvdnet.conv_weights(made["stage1"]) == fastdvdnet.DENBLOCK_WEIGHTS == 1_237_320
    assert fastdvdnet.conv_weights(own) == 2 * 1_237_320
    assert made["stage1"]["inc"]["conv0"].shape == (3, 3, 4, 90)     # three groups of 4 -> 30


def test_reference_imports_nothing_of_the_program(ref):
    with open(ref.__file__) as f:
        src = f.read()
    assert "dvf_tpu" not in src.split('"""', 2)[2]
    assert not any(line.startswith(("import dvf", "from dvf", "from chipbench"))
                   for line in (ln.strip() for ln in src.splitlines()))


@pytest.mark.parametrize("h,w,c", [(26, 32, 3), (24, 30, 3), (24, 32, 1)])
def test_a_geometry_the_two_scales_do_not_divide_is_refused(h, w, c):
    with pytest.raises(ValueError, match="divide by 4"):
        get_filter("video_denoise").init_state((2, h, w, c), jnp.float32)


# -- (b) the streamed, cached form through the Engine's table ----------------

# Batches of four rows as (session, frame index) or None for a pad row.
# Session rows of the table are not in session order; session 2 opens in
# mid-batch beside a pad row; every session is split across batches, and
# rows of one session sit beside each other and apart.
_PLANS = {
    "interleaved": [[(0, 0), (1, 0), (0, 1), (0, 2)], [(1, 1), None, (2, 0), (1, 2)],
                    [(2, 1), (2, 2), (0, 3), (1, 3)], [(0, 4), (2, 3), None, (0, 5)],
                    [(1, 4), (2, 4), (2, 5), (1, 5)]],
    "a_row_a_batch": [[(0, n), None, (1, n), None] for n in range(6)],
    "whole_batches": [[(0, 0), (0, 1), (0, 2), (0, 3)], [(1, 0), (1, 1), (1, 2), (1, 3)],
                      [(0, 4), (0, 5), (1, 4), (1, 5)]],
}
_TABLE_ROW = {0: 2, 1: 0, 2: 3}


def _run_plans(engine, streams, plans):
    """{(session, index): uint8 frame} of ``plans`` through ``engine``."""
    seen, out = set(), {}
    for plan in plans:
        batch = np.zeros((len(plan), H, W, 3), np.uint8)
        rows = np.zeros((2, len(plan)), np.int32)
        rows[0] = -1
        for i, item in enumerate(plan):
            if item is None:
                continue
            k, n = item
            batch[i], rows[0, i] = streams[k][n], _TABLE_ROW[k]
            if k not in seen:
                rows[1, i] = 1
                seen.add(k)
        got = np.asarray(engine.submit(batch, rows))
        out.update({item: got[i] for i, item in enumerate(plan) if item is not None})
    return out


@pytest.fixture(scope="module")
def tabled(ref, config):
    params = ref.make_params(5, config)
    streams = {k: _frames(10 + k, 6) for k in range(3)}
    want = {k: ref.stream(v, config, params) for k, v in streams.items()}
    engine = Engine(_filter(config, params, dtype="float32"), state_rows=4)
    engine.compile((4, H, W, 3), np.uint8)
    return params, streams, want, engine


@pytest.mark.parametrize("plan", sorted(_PLANS))
def test_cached_form_through_the_table_equals_the_reference(tabled, plan):
    _, streams, want, engine = tabled
    engine.reset_state()
    out = _run_plans(engine, streams, _PLANS[plan])
    n = _numbers(list(out.values()), [want[k][i] for k, i in out])
    assert n["max_abs_steps"] <= F32_MAX_STEPS and n["mean_abs_steps"] <= F32_MEAN_STEPS, n
    assert engine.stats.compile_count == 1          # who shares a batch is data, never shape


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_how_frames_fall_across_batches_never_shows(ref, config, tabled, dtype):
    """Bit for bit: a plane is handed on as the table keeps it (float32)
    whether it crosses a batch boundary or not, whatever the
    convolutions' operands are."""
    params, streams, _, _ = tabled
    engine = Engine(_filter(config, params, dtype=dtype), state_rows=4)
    engine.compile((4, H, W, 3), np.uint8)
    outs = []
    for plan in ("whole_batches", "a_row_a_batch"):
        engine.reset_state()
        outs.append(_run_plans(engine, streams, _PLANS[plan]))
    common = sorted(set(outs[0]) & set(outs[1]))
    assert len(common) == 12
    for key in common:
        np.testing.assert_array_equal(outs[0][key], outs[1][key])


@pytest.mark.parametrize("batch", [1, 3])
def test_one_session_alone_through_fn(tabled, config, batch):
    params, streams, want, _ = tabled
    engine = Engine(_filter(config, params, dtype="float32"))       # state_rows 1, no row map
    engine.compile((batch, H, W, 3), np.uint8)
    got = []
    for at in range(0, 6, batch):
        got += list(np.asarray(engine.submit(np.stack(streams[0][at:at + batch]))))
    n = _numbers(got, want[0])
    assert n["max_abs_steps"] <= F32_MAX_STEPS and n["mean_abs_steps"] <= F32_MEAN_STEPS, n


def test_the_weights_are_stored_once_and_the_planes_a_session(config, ref):
    filt = _filter(config, ref.make_params(1, config))
    state = filt.init_state((4, H, W, 3), jnp.float32)
    mine = session_leaves(filt, state)
    assert not any(jax.tree.leaves(mine["weights"]))
    assert all(jax.tree.leaves({k: v for k, v in mine.items() if k != "weights"}))
    engine = Engine(filt, state_rows=5)
    engine.compile((4, H, W, 3), np.uint8)
    assert engine._state["raw"]["lag1"].shape == (5, H * W * 3 // 128, 128)      # rows of 128 lanes
    assert engine._state["weights"]["stage1"]["inc"]["conv0"].shape == (3, 3, 4, 90)
    assert engine.state_row_bytes() == 4 * H * W * 3 * 4 + 4      # four float32 planes, a count
    assert filt.window == {"depth": 4, "lag_frames": 2, "leaves": {"raw": 2, "stage1": 2},
                           "dtypes": {"raw": "float32", "stage1": "float32"}}
    assert not filt.pad_safe


# -- (c) warm-up --------------------------------------------------------------

@pytest.mark.parametrize("n", [0, 1, 2, 3, 4])
def test_warm_up_fills_missing_lags_with_the_first_frame(ref, config, tabled, n):
    """Delivery n of a stream is the window of frames n-4 .. n with every
    index before the start holding frame 0: its centre is max(n - 2, 0)."""
    params, streams, want, _ = tabled
    frames = streams[1]
    window = [frames[max(n - 4 + t, 0)] for t in range(5)]
    assert window[2] is frames[max(n - 2, 0)]
    # the reference's pool form on that window alone: entry 4 answers entries 0..4
    alone = ref.reference(window, config, params)[4]
    np.testing.assert_array_equal(alone, want[1][n])


# -- (d) ServeFrontend end to end ----------------------------------------------

@pytest.fixture(scope="module")
def served(ref, config):
    params = ref.make_params(7, config)
    streams = [_frames(20 + k, 9) for k in range(3)]
    cfg = ServeConfig(batch_size=4, max_inflight=2, max_sessions=4, queue_size=64,
                      slo_ms=60_000.0, replay_window=0, trace=True)
    fe = ServeFrontend(_filter(config, params), cfg)
    got = [[] for _ in streams]
    with fe:
        sids = [fe.open_stream(frame_shape=(H, W, 3)) for _ in streams]
        for i in range(9):
            for sid, frames in zip(sids, streams):
                fe.submit(sid, frames[i])
        for sid in sids:
            fe.close(sid, drain=True)
        deadline = time.time() + 120.0
        while time.time() < deadline and any(len(g) < 9 for g in got):
            for g, sid in zip(got, sids):
                g.extend(fe.poll(sid))
            time.sleep(0.002)
        stats = fe.stats()
        spans = [e for e in fe.tracer._events if e["name"] == "dispatch:assemble_h2d"]
    want = [ref.stream(s, config, params) for s in streams]
    return got, want, stats, spans


def test_served_delivery_n_is_the_denoised_frame_n_minus_2(served, config):
    got, want, stats, _ = served
    for g in got:
        assert [d.index for d in g] == list(range(9))           # in order, one out for one in
    n = _numbers([d.frame for g in got for d in g], [f for w in want for f in w])
    assert _within(n, config["limits"]), n
    assert stats["errors"] == 0 and stats["faults"]["by_kind"] == {}
    for row in stats["sessions"].values():
        assert row["submitted"] == row["delivered"] == 9 and row["shed"] == 0
        assert row["output_lag_frames"] == 2


def test_served_rows_state_the_window_and_the_model(served):
    _, _, stats, spans = served
    (row,) = [r for r in stats["buckets"].values() if r.get("batches")]
    state = row["state"]
    assert (state["depth"], state["lag_frames"]) == (4, 2)
    assert state["leaves"] == {"raw": 2, "stage1": 2}
    assert state["dtypes"] == {"raw": "float32", "stage1": "float32"}
    assert state["row_bytes"] == 4 * H * W * 3 * 4 + 4
    assert state["table_rows_total"] + state["chain_rows_total"] == 27
    assert state["fresh_rows_total"] == 3
    assert state["warm_rows_total"] == 3 * 4        # a session's first four frames
    assert state["resets_total"] == {"admission": 3, "rebuild": 0, "migrate": 0}
    model = dict(row["model"])
    assert len(model.pop("conv_ops")) == 32         # the compiled step's convolutions, by name
    assert model == {"name": "fastdvdnet", "form": "cached", "denblocks_per_frame": 2,
                     "params": 2 * 1_237_320, "compute_dtype": "bfloat16"}
    assert row["engine_compile_count"] == 1
    assert spans and all(e["args"]["lag"] == 2 for e in spans)


def test_a_filter_without_a_window_reads_depth_one_and_no_lag():
    fe = ServeFrontend(get_filter("ema_smooth"), ServeConfig(batch_size=2, max_sessions=2))
    with fe:
        sid = fe.open_stream(frame_shape=(8, 8, 3))
        for _ in range(3):
            fe.submit(sid, np.zeros((8, 8, 3), np.uint8))
        fe.close(sid, drain=True)
        deadline = time.time() + 60.0
        n = 0
        while time.time() < deadline and n < 3:
            n += len(fe.poll(sid))
            time.sleep(0.002)
        stats = fe.stats()
    (row,) = [r for r in stats["buckets"].values() if r.get("batches")]
    assert (row["state"]["depth"], row["state"]["lag_frames"], row["state"]["leaves"]) == (1, 0, None)
    assert row["state"]["warm_rows_total"] == row["state"]["fresh_rows_total"] == 1
    assert "model" not in row
    assert all(s["output_lag_frames"] == 0 for s in stats["sessions"].values())


# -- (e) the controls ----------------------------------------------------------

@pytest.mark.parametrize("seed", [1, 2, 3, 4])
@pytest.mark.parametrize("control", ["control", "stale_cache"])
def test_a_control_fails_the_toy_limits(ref, config, seed, control):
    frames = _frames(seed, 8)
    params = ref.make_params(seed, config)
    want = ref.reference(frames, config, params)
    n = _numbers(getattr(ref, control)(frames, config, params), want)
    assert n["max_abs_steps"] > config["limits"]["max_abs_steps"], n
    assert n["mean_abs_steps"] > config["limits"]["mean_abs_steps"], n
    sound = _numbers(ref.bfloat16_run(frames, config, params), want)
    assert _within(sound, config["limits"]), sound


# -- (f) the cost ----------------------------------------------------------------

def test_cost_counts_two_denblocks_a_frame(config):
    costs = _load("chipbench/costs/fastdvd_540p.py", "fastdvd_540p_costs")
    assert costs.denblock_macs_per_pixel() == 159_048
    assert 9 * sum(cin * cout for cin, cout, _ in costs.DENBLOCK_CONVS) == 1_237_320
    with open(os.path.join(ROOT, "chipbench", "configs", "fastdvd_540p.json")) as f:
        cell = json.load(f)
    one = costs.cost(cell, 1)
    assert one["flops"] == 2 * 2 * 159_048 * 540 * 960           # 329.8 GFLOP a frame
    batch = cell["serve"]["batch_size"]
    full = costs.cost(cell, batch)
    assert full["flops"] == batch * one["flops"]
    frame, planes = 540 * 960 * 3, 4 * 540 * 960 * 3 * 4
    sessions = min(batch, cell["serve"]["max_sessions"])
    assert full["bytes"] - one["bytes"] == pytest.approx(
        2 * frame * (batch - 1) + 2 * planes * (sessions - 1))
