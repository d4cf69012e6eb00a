"""The CLAHE deployment (chipbench's ``clahe_1080p``) on the normal serve
path: ``ServeFrontend`` -> ``DeviceLane`` -> ``Engine``, the counted form's
two Pallas kernels in interpret mode, toy geometries on the CPU.

The plain reference is the benchmark's (``chipbench/refs/clahe_1080p.py``,
loaded by path: it imports nothing of the program). What is held:

(a) the reference agrees with ``cv2.createCLAHE(2.0, (8, 8))`` on each
    channel within one step;
(b) the served path (several sessions through cross-session batches, order
    kept, uint8 in and uint8 out with no float conversion by the engine)
    equals the reference inside the configuration's limits, at the
    benchmark's toy geometry (36 x 52: the grid does not divide it) and
    one it divides;
(c) the reference's controls (the blend in bfloat16; the redistribution's
    residual pass dropped) read not correct by ``chipbench/check.py::decide``;
(d) a compiled step says which kernels it runs and how it tiled them: the
    bucket row's ``kernel`` block is ``clahe_plan``'s, and the dispatch span
    names the kernel that takes most of the step;
(e) the configuration states what the issue fixed.
"""

import importlib.util
import json
import os
import time

import cv2
import numpy as np
import pytest

from chipbench import check
from dvf_tpu.cli import BENCH_CONFIGS
from dvf_tpu.ops import get_filter
from dvf_tpu.ops import histogram as hg
from dvf_tpu.ops import pallas_kernels as pk
from dvf_tpu.serve import ServeConfig, ServeFrontend

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BATCH = 4
GEOMETRIES = {"reflect_padded": (36, 52), "divisible": (32, 64)}


def _load(relpath, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("chipbench/refs/clahe_1080p.py", "clahe_1080p_ref")


def _config(toy=True):
    with open(os.path.join(ROOT, "chipbench", "configs", "clahe_1080p.json")) as f:
        cfg = json.load(f)
    if toy:
        for key, val in cfg["toy"].items():
            cfg[key] = {**cfg[key], **val}
    return cfg


def _frames(seed, n, h, w):
    """Coarse structure under fine noise, saturating at 0 and 255, as the
    benchmark's pool (chipbench/frames.py)."""
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
    return check.compare_numbers([(0, i, g) for i, g in enumerate(got)], wanted, len(wanted))


def _bucket_row(stats):
    (row,) = [r for r in stats["buckets"].values() if r.get("batches")]
    return row


# -- (a) the reference against cv2 --------------------------------------------

@pytest.mark.parametrize("shape", [(36, 52), (64, 96), (61, 83)], ids=lambda s: "%dx%d" % s)
def test_reference_agrees_with_cv2_per_channel(ref, shape):
    """Geometries the grid divides both ways or neither way: where only one
    axis divides, cv2 pads the other by a whole grid more (the configuration's
    ``departures_from_cv2``; 1080 x 1920 divides both ways)."""
    cfg = _config()
    frames = _frames(shape[0], 3, *shape)
    clahe = cv2.createCLAHE(clipLimit=2.0, tileGridSize=(8, 8))
    for frame, got in zip(frames, ref.reference(frames, cfg)):
        want = np.stack([clahe.apply(np.ascontiguousarray(frame[..., c])) for c in range(3)], -1)
        diff = np.abs(got.astype(np.int16) - want.astype(np.int16))
        assert got.dtype == np.uint8 and diff.max() <= 1 and diff.mean() <= 0.01, (diff.max(), diff.mean())


# -- (b) the served path against the plain reference --------------------------

@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_served_path_equals_the_reference(ref, geometry):
    h, w = GEOMETRIES[geometry]
    cfg = _config()
    filt = get_filter(cfg["filter"]["name"], **cfg["filter"]["kwargs"])
    assert filt.uint8_ok
    # three sessions, 5 + 5 + 3 frames: batches of four mix the sessions, the last is short
    streams = [_frames(1, 5, h, w), _frames(51, 5, h, w), _frames(101, 3, h, w)]
    got, stats, _ = _serve(filt, streams, (h, w, 3))
    want = [f for s in streams for f in ref.reference(s, cfg)]
    n = _numbers([dl.frame for g in got for dl in g], want)
    assert n["shape_mismatch"] == 0
    assert check.decide(n, cfg["limits"], log=lambda m: None), (n, cfg["limits"])
    row = _bucket_row(stats)
    assert row["out_geometry"] == [h, w, 3] and row["step_donates_input"] is True


# -- (c) the controls read not correct ----------------------------------------

@pytest.mark.parametrize("control", ["control", "residual_dropped"])
@pytest.mark.parametrize("seed", [1, 2])
def test_controls_fail_the_check(ref, control, seed):
    cfg = _config()
    h, w = GEOMETRIES["reflect_padded"]
    frames = _frames(seed, 4, h, w)
    want = ref.reference(frames, cfg)
    n = _numbers(getattr(ref, control)(frames, cfg), want)
    assert not check.decide(n, cfg["limits"], log=lambda m: None), (control, n, cfg["limits"])
    sound = _numbers(ref.reference(frames, cfg), want)
    assert sound["max_abs_steps"] == 0 and check.decide(sound, cfg["limits"], log=lambda m: None)


def test_reference_imports_nothing_of_the_program(ref):
    with open(ref.__file__) as f:
        src = f.read()
    assert "dvf_tpu" not in src.split('"""', 2)[2]
    assert not any(line.startswith(("import dvf", "from dvf", "from chipbench"))
                   for line in (ln.strip() for ln in src.splitlines()))
    assert ref.make_params(1, _config()) is None


# -- (d) a compiled step says which kernels it runs ---------------------------

def test_bucket_row_states_the_kernels_and_their_tiling():
    h, w = GEOMETRIES["reflect_padded"]
    cfg = _config()
    filt = get_filter(cfg["filter"]["name"], **cfg["filter"]["kwargs"])
    _, stats, fe = _serve(filt, [_frames(7, 6, h, w), _frames(8, 6, h, w)], (h, w, 3), trace=True)
    block = _bucket_row(stats)["kernel"]
    assert block == hg.clahe_plan((BATCH, h, w, 3), 2.0, 8, False, interpret=True)
    assert block == fe._buckets[0].engine.kernel_plan
    assert block["kernels"] == ["clahe_hist", "clahe_apply"] and block["kernel"] in block["kernels"]
    assert (block["tile_h"], block["tile_w"], block["tile_h_pad"], block["tile_w_pad"]) == (5, 7, 8, 128)
    assert block["planes"] == BATCH * 3 and block["clip_abs"] == 1
    # how clahe_hist counts (PR 50): bit planes and the population count, and what a pixel of this tile costs
    assert block["hist_form"] == "bitplane"
    assert block["hist_ops_per_pixel"] == pk.hist_ops_per_pixel(8, 128) and block["vmem_scratch_bytes"] == 256 * 8 * 128 * 4
    json.dumps(block)                                    # plain data: stats() is serialised
    spans = [e for e in fe.tracer._events if e["name"] == "dispatch:assemble_h2d"]
    assert spans and all(e["args"]["kernel"] == "clahe_hist" for e in spans)


# -- (e) the configuration ----------------------------------------------------

def test_configuration_states_what_the_issue_fixed():
    cfg = _config(toy=False)
    assert cfg["reduced"] == [] and cfg["architecture"] is None and cfg["chips"] == 1
    assert cfg["filter"] == {"name": "clahe_pallas",
                             "kwargs": {"clip_limit": 2.0, "grid": 8, "on_gray": False}}
    assert cfg["serve"] == {"batch_size": 64, "max_inflight": 4, "max_sessions": 32, "queue_size": 64,
                            "slo_ms": 60000.0, "replay_window": 0}
    g = cfg["geometry"]
    assert (g["height"], g["width"], g["channels"]) == (1080, 1920, 3)
    assert hg.clahe_geometry(g["height"], g["width"], 8, 2.0)["clip_abs"] == 253
    # the CLI's entry names the same filter: clahe()'s defaults are the configuration's kwargs
    name, kwargs = BENCH_CONFIGS["clahe_1080p"]["filter"]
    assert get_filter(name, impl="pallas", **kwargs).name \
        == get_filter(cfg["filter"]["name"], **cfg["filter"]["kwargs"]).name
    toy = _config()["geometry"]
    assert toy["height"] % 8 and toy["width"] % 8           # the toy cell takes the reflect pad
    assert set(cfg["limits"]) == {"mean_abs_steps", "max_abs_steps"}
