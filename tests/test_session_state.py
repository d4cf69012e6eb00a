"""Per-session temporal state through cross-session batches.

A temporal filter's state is ONE session's (``Filter.rows``): the Engine
holds a table of session states and every batch carries a row map, so a
session's output depends on that session's frames only — whatever else
shares its batches, however the batcher happens to cut them, and whichever
row of the table the session was given.

The yardstick throughout is the same session alone: its frames, one at a
time, through a single-stream Engine. For flow_warp the plain reference of
the benchmark (``chipbench/refs/flow_720p.py``, loaded by path as
``chipbench/spec.py`` loads it; it imports nothing of the program) is held
against it too.
"""

import importlib.util
import os
import time

import numpy as np
import pytest

from dvf_tpu.ops import get_filter
from dvf_tpu.runtime.engine import Engine, device_row_map
from dvf_tpu.serve import ServeConfig, ServeFrontend
from dvf_tpu.serve.batcher import ContinuousBatcher
from dvf_tpu.serve.session import SessionConfig, StreamSession

H, W = 32, 48
FLOW_KW = dict(levels=2, win_size=7, n_iters=2, flow_scale=1,
               warp_impl="pallas", max_disp=4, win_type="gaussian",
               inner_warp="gather")
# flow_720p's own variant, the bounded kernel inside the iteration too
# (interpret mode here, hence only where the plain reference is held).
REF_KW = dict(FLOW_KW, inner_warp="pallas")
FILTERS = {
    "flow_warp": ("flow_warp", FLOW_KW),
    "ema_smooth": ("ema_smooth", {"alpha": 0.4}),
}
# Reference vs program, in uint8 steps, worst frame: both are float32 and
# differ in the order of their sums only (a 2-D correlation against
# separable shifted adds), which moves a rounding here and there: a step
# at most, on a handful of pixels. The leak and the bfloat16 body are tens
# of steps and whole steps of mean away (test_leak_control_fails).
REF_MAX_STEPS, REF_MEAN_STEPS = 2, 0.05


def _load_ref():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "chipbench", "refs", "flow_720p.py")
    spec = importlib.util.spec_from_file_location("flow_720p_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _stream(seed, n, shift):
    """n frames of one session: its own smooth texture sliding ``shift``
    pixels a frame under its own fine noise. Sessions of different seeds
    share nothing."""
    rng = np.random.default_rng(seed)
    coarse = rng.integers(0, 256, (H // 8 + 2, (W + n * abs(shift)) // 8 + 3, 3))
    tex = np.kron(coarse, np.ones((8, 8, 1)))
    for axis in (0, 1):     # soften the blocks: a texture flow can follow
        tex = (np.roll(tex, 1, axis) + 2 * tex + np.roll(tex, -1, axis)) / 4
    noise = rng.integers(-6, 7, (H, W, 3))
    out = []
    for i in range(n):
        x0 = i * abs(shift) if shift >= 0 else (n - 1 - i) * abs(shift)
        out.append(np.clip(tex[:H, x0:x0 + W] + noise, 0, 255).astype(np.uint8))
    return out


STREAMS = [_stream(11, 7, 1), _stream(23, 4, -2), _stream(37, 5, 2)]


def _alone(name, frames, kw=None):
    """The session alone: one frame a step through a one-stream Engine."""
    fname, kw = FILTERS[name][0], (kw or FILTERS[name][1])
    eng = Engine(get_filter(fname, **kw))
    out = [np.asarray(eng.submit(f[None]))[0] for f in frames]
    eng.free()
    return out


@pytest.fixture(scope="module")
def alone():
    return {name: [_alone(name, s) for s in STREAMS] for name in FILTERS}


def _worst(got, want):
    return max(int(np.abs(g.astype(int) - w.astype(int)).max())
               for g, w in zip(got, want))


def _serve(name, batch, order, streams=STREAMS, max_sessions=4):
    """Every frame of ``order`` ([(session k, frame i)]) submitted before
    the frontend starts, so the batcher meets them all at once and cuts
    batches by submit order alone. Returns ({k: [frames]}, stats)."""
    fname, kw = FILTERS[name]
    fe = ServeFrontend(get_filter(fname, **kw),
                       ServeConfig(batch_size=batch, max_sessions=max_sessions,
                                   queue_size=64, out_queue_size=64,
                                   slo_ms=600_000))
    sids = [fe.open_stream() for _ in streams]
    for k, i in order:
        fe.submit(sids[k], streams[k][i])
    got = {k: [] for k in range(len(streams))}
    want = {k: sum(1 for kk, _ in order if kk == k) for k in got}
    with fe:
        deadline = time.time() + 120.0
        while any(len(got[k]) < want[k] for k in got) and time.time() < deadline:
            for k, sid in enumerate(sids):
                got[k] += fe.poll(sid)
            time.sleep(0.005)
        stats = fe.stats()
    for k in got:
        assert [d.index for d in got[k]] == list(range(want[k]))
    assert stats["errors"] == 0
    return {k: [d.frame for d in v] for k, v in got.items()}, stats


def _frame_major(streams=STREAMS):
    return [(k, i) for i in range(max(map(len, streams)))
            for k in range(len(streams)) if i < len(streams[k])]


def _session_major(streams=STREAMS):
    return [(k, i) for k in range(len(streams)) for i in range(len(streams[k]))]


ORDERS = {"frame_major": _frame_major, "session_major": _session_major}


@pytest.mark.parametrize("name", sorted(FILTERS))
def test_sessions_sharing_batches_equal_each_alone(name, alone):
    """(a, f) Three sessions, unrelated content, 7 + 4 + 5 frames, batch 4:
    per session what that session alone gives (to a rounding of the uint8
    cast: the 8-device test mesh and the batch size may order a sum
    differently), first frame passed through; one program for all of it."""
    got, stats = _serve(name, 4, _frame_major())
    for k, frames in enumerate(STREAMS):
        assert _worst(got[k], alone[name][k]) <= 1
    if name == "flow_warp":
        for k, frames in enumerate(STREAMS):
            np.testing.assert_array_equal(got[k][0], frames[0])
    row = next(iter(stats["buckets"].values()))
    assert row["engine_compile_count"] == 1
    state = row["state"]
    assert state["rows"] == 4 and state["bound"] == 3
    assert state["fresh_rows_total"] == 3
    assert state["table_rows_total"] + state["chain_rows_total"] == 16
    assert state["resets_total"] == {"admission": 3, "rebuild": 0, "migrate": 0}
    assert state["bytes"] > 0


@pytest.mark.parametrize("name", sorted(FILTERS))
def test_served_row_says_which_kernels_the_step_runs(name):
    """The bucket row of a served flow session carries the step's
    ``warp_bounded`` calls as data (``flow_warp``'s ``kernel_plan`` ->
    ``Engine.kernel_plan`` -> the row's ``kernel`` block): here the final
    warp alone, the inner warps being gathers; a filter of XLA's own ops
    has none."""
    from dvf_tpu.ops.pallas_kernels import warp_plan

    _, stats = _serve(name, 2, [(0, 0), (0, 1), (1, 0), (1, 1)], streams=STREAMS[:2])
    block = next(iter(stats["buckets"].values()))["kernel"]
    if name != "flow_warp":
        assert block is None
        return
    assert block["kernel"] == "warp_bounded" and block["kernels"] == ["warp_bounded"]
    (call,) = block["calls"]
    assert (call["role"], call["level"], call["count"]) == ("final", None, 1)
    # the shape one device of the test mesh sees: the batch's share
    shard = (call["grid"][0], H, W, 3)
    assert {k: v for k, v in call.items() if k not in ("role", "level", "count")} == warp_plan(
        shard, FLOW_KW["max_disp"], interpret=True)
    assert call["taps"] == 100 and call["planes"] == 3 and call["strip"] == [8, 128]


@pytest.mark.parametrize("inner", ["gather", "pallas"])
def test_flow_matches_the_plain_reference(inner):
    """(a) The single-stream run the other tests are held to is itself the
    benchmark's plain reference, within the stated tolerance: frame i of a
    session is frame i - 1 warped onto it. Both inner warps: the exact
    gather, and the bounded kernel flow_720p states (the reference clips
    the iteration's flow as the kernel does)."""
    ref = _load_ref()
    kw = dict(FLOW_KW, inner_warp=inner)
    frames = STREAMS[0]
    got = _alone("flow_warp", frames, kw)
    wanted = ref.reference(frames, {"filter": {"kwargs": kw}}, None)   # entry j: j-1 onto j
    diffs = [np.abs(got[i].astype(int) - wanted[i].astype(int))
             for i in range(1, len(frames))]
    assert max(int(d.max()) for d in diffs) <= REF_MAX_STEPS
    assert max(float(d.mean()) for d in diffs) <= REF_MEAN_STEPS


@pytest.mark.parametrize("order", sorted(ORDERS))
@pytest.mark.parametrize("batch", [1, 2, 4])
@pytest.mark.parametrize("name", sorted(FILTERS))
def test_batch_composition_is_not_part_of_the_answer(name, batch, order, alone):
    """(b) The same frames under batch sizes 1, 2, 4 and two interleavings:
    the same per-session output (to a rounding of the uint8 cast: XLA may
    order a sum differently at another batch size)."""
    got, _ = _serve(name, batch, ORDERS[order]())
    for k in range(len(STREAMS)):
        assert _worst(got[k], alone[name][k]) <= 1


@pytest.mark.parametrize("name", sorted(FILTERS))
def test_frames_held_behind_a_backlog_keep_their_predecessors(
        name, alone, device_gate):
    """A short candidate set waits in ``pending`` while the device has a
    backlog (serve/batcher.py) and later rounds join it: the row map is
    built when the batch is bound, so each row's predecessor is still
    its own session's previous frame, whichever round it came in with."""
    fname, kw = FILTERS[name]
    fe = ServeFrontend(get_filter(fname, **kw),
                       ServeConfig(batch_size=4, max_sessions=4, queue_size=64,
                                   out_queue_size=64, slo_ms=600_000))
    rounds = max(map(len, STREAMS))
    got = {k: [] for k in range(len(STREAMS))}

    def row():
        return next(iter(fe.stats()["buckets"].values()))

    with fe:
        sids = [fe.open_stream() for _ in STREAMS]
        for i in range(rounds):
            live = [k for k in range(len(STREAMS)) if i < len(STREAMS[k])]
            # round 0 goes at once (an idle device) and reads busy until
            # round 3: rounds 1 and 2 wait behind it, a full batch of them
            # leaves at depth, the rest with round 3. Rows of one session
            # in one batch chain through the batch, the others through
            # the table.
            device_gate.busy = i < 3
            for k in live:
                fe.submit(sids[k], STREAMS[k][i])
            if i in (1, 2):
                before = row()["hold"]["hold_ms_total"]
                device_gate.until(
                    lambda: row()["hold"]["hold_ms_total"] > before + 5.0,
                    "held ticks")
            else:
                device_gate.until(
                    lambda: i == 0 or (row()["queue_depth"] == 0
                                       and row()["inflight_batches"] == 0),
                    f"round {i} through")
        for k, sid in enumerate(sids):
            device_gate.until(
                lambda: got[k].extend(fe.poll(sid))
                or len(got[k]) >= len(STREAMS[k]), f"session {k}")
        stats = fe.stats()
    assert stats["errors"] == 0
    for k in got:
        assert [d.index for d in got[k]] == list(range(len(STREAMS[k])))
        assert _worst([d.frame for d in got[k]], alone[name][k]) <= 1
    bucket = next(iter(stats["buckets"].values()))
    assert bucket["hold"]["held_batches_total"] >= 1
    state = bucket["state"]
    assert state["table_rows_total"] + state["chain_rows_total"] == 16
    assert state["chain_rows_total"] >= 1      # held rounds shared a batch
    assert bucket["engine_compile_count"] == 1


def _sessions(n):
    out = []
    for k in range(n):
        s = StreamSession(f"s{k}", SessionConfig(queue_size=64, slo_ms=1000.0))
        s.state_row, s.state_fresh = k, True
        out.append(s)
    return out


def _run_plan(eng, batcher, sessions, now):
    """One batcher tick through the engine, as the dispatch thread does it."""
    plan = batcher.plan(sessions, now)
    if plan is None:
        return {}
    out = np.asarray(eng.submit(plan.batch, plan.rows))
    batcher.mark_reached_device(plan.slots)
    for s in {slot.session for slot in plan.slots}:
        s.inflight = 0
    return {(slot.session.id, slot.index): out[row]
            for row, slot in enumerate(plan.slots)}


@pytest.mark.parametrize("name", sorted(FILTERS))
def test_pad_rows_and_shed_frames_leave_the_predecessor_as_stated(name):
    """(c) A pad row writes nothing, and a frame shed before it reached the
    device is skipped: the next frame's predecessor is the last frame of
    its session that DID reach the device."""
    fname, kw = FILTERS[name]
    a, b = STREAMS[0], STREAMS[2]
    eng = Engine(get_filter(fname, **kw), state_rows=2)
    batcher = ContinuousBatcher(4)
    sa, sb = _sessions(2)
    got = {}
    t = time.time()
    # tick 1: a0 b0 a1 + one pad row (a copy of a1, as the assembler pads)
    sa.submit(a[0], ts=t); sb.submit(b[0], ts=t + 1e-3); sa.submit(a[1], ts=t + 2e-3)
    got.update(_run_plan(eng, batcher, [sa, sb], t))
    # tick 2: b1 alone, three pad rows; a's row must keep a1
    sb.submit(b[1], ts=t + 3e-3)
    got.update(_run_plan(eng, batcher, [sa, sb], t))
    # a2 is shed (its deadline passes before any tick takes it) ...
    sa.submit(a[2], ts=t + 4e-3)
    assert _run_plan(eng, batcher, [sa, sb], t + 3600.0) == {}
    assert sa.shed == 1
    # ... so a3 follows a1, and b2 follows b1
    t2 = time.time() + 3600.0
    sa.submit(a[3], ts=t2); sb.submit(b[2], ts=t2 + 1e-3)
    got.update(_run_plan(eng, batcher, [sa, sb], t2))
    eng.free()
    want_a = _alone(name, [a[0], a[1], a[3]])
    want_b = _alone(name, [b[0], b[1], b[2]])
    assert _worst([got[("s0", 0)], got[("s0", 1)], got[("s0", 3)]], want_a) <= 1
    assert _worst([got[("s1", 0)], got[("s1", 1)], got[("s1", 2)]], want_b) <= 1
    assert eng.stats.compile_count == 1


def test_discarded_plan_keeps_the_fresh_mark():
    """(c) A plan that never reached the device consumes nothing: its
    sessions' rows still restart at their next frame."""
    batcher = ContinuousBatcher(2)
    (s,) = _sessions(1)
    s.submit(STREAMS[0][0])
    plan = batcher.plan([s], time.time())
    assert plan.rows.tolist() == [[0, -1], [1, 0]]
    s.inflight = 0                       # discarded: never submitted
    s.submit(STREAMS[0][1])
    plan = batcher.plan([s], time.time())
    assert plan.rows[1, 0] == 1
    batcher.mark_reached_device(plan.slots)
    s.inflight = 0
    s.submit(STREAMS[0][2])
    assert batcher.plan([s], time.time()).rows[1, 0] == 0


def test_stateless_step_takes_no_row_map():
    """A filter without per-session state compiles the program it always
    did: two operands, batch and state (the lowered HLO of invert,
    style_transfer and super_resolution at their cells' shapes is the
    parent's, byte for byte: PERF.md section 6, PR 27)."""
    for name, rows in (("invert", 8), ("super_resolution", 8)):
        eng = Engine(get_filter(name), state_rows=rows)
        eng.compile((2, H, W, 3), np.uint8)
        assert len(eng.step_operands()) == 2 and not eng._tabled
        eng.free()


def test_row_map_for_the_device():
    """The step's row map from the batcher's: predecessors, the sessions
    gathered, their fresh marks, and where each one's state is stored."""
    rows = np.array([[2, 0, 2, -1, 0, 2], [1, 0, 0, 0, 0, 0]], np.int32)
    m = device_row_map(rows, 6, 3)
    assert m.tolist() == ([0, 1, 3 + 0, 0, 3 + 1, 3 + 2]     # pred
                          + [2, 0, -1] + [1, 0, 0] + [5, 4, 0])
    with pytest.raises(ValueError, match="outside the table"):
        device_row_map(rows, 6, 2)
    one = device_row_map(None, 4, 8)      # one stream, consecutive frames
    assert one[:4].tolist() == [0, 4, 5, 6]
    assert one[4:8].tolist() == [0, -1, -1, -1] and one[12] == 3


@pytest.mark.parametrize("name", sorted(FILTERS))
def test_closed_sessions_row_rebound_starts_fresh(name, alone):
    """(d) max_sessions 1: the one row of the table serves two sessions in
    turn, and the second starts from pristine state, not from the first
    one's last frame. (e) No program compiles after the first batch."""
    fname, kw = FILTERS[name]
    fe = ServeFrontend(get_filter(fname, **kw),
                       ServeConfig(batch_size=2, max_sessions=1, queue_size=64,
                                   slo_ms=600_000))
    compiles = []
    with fe:
        for k in (0, 1):
            sid = fe.open_stream()
            for f in STREAMS[k]:
                fe.submit(sid, f)
            got = []
            deadline = time.time() + 120.0
            while len(got) < len(STREAMS[k]) and time.time() < deadline:
                got += fe.poll(sid)
                time.sleep(0.005)
            assert _worst([d.frame for d in got], alone[name][k]) <= 1
            row = next(iter(fe.stats()["buckets"].values()))
            compiles.append(row["xla_compiles_total"])
            fe.close(sid, drain=True)
            deadline = time.time() + 10.0
            while fe.open_count() and time.time() < deadline:
                time.sleep(0.005)
    assert compiles[1] == compiles[0]     # the second tenant compiled nothing
    assert row["engine_compile_count"] == 1
    assert row["state"]["resets_total"]["admission"] == 2
    assert row["state"]["rows"] == 1


def _one(fe, sid, frame):
    """One frame through the service and back (the session has nothing
    else outstanding); None when a contained fault ate it."""
    s = fe._session(sid)
    before = s.delivered + s.failed
    fe.submit(sid, frame)
    deadline = time.time() + 60.0
    while s.delivered + s.failed < before + 1:
        assert time.time() < deadline, "serve path deadlocked"
        time.sleep(0.002)
    got = fe.poll(sid)
    return got[-1].frame if got else None


def test_resize_carries_the_table_and_rebuild_restarts_every_row():
    """A batch resize swaps the program and migrates the session table
    leaf for leaf (no batch size shapes it): both sessions' EMAs go on. A
    supervised rebuild starts from a new table: every bound row restarts,
    counted under ``rebuild`` and on the ledger's event."""
    from dvf_tpu.obs import ledger as ledger_mod

    a, b = STREAMS[0], STREAMS[2]
    want = {0: _alone("ema_smooth", a[:4]), 1: _alone("ema_smooth", b[:4])}
    fe = ServeFrontend(get_filter("ema_smooth", alpha=0.4),
                       ServeConfig(batch_size=2, queue_size=64, slo_ms=600_000,
                                   telemetry_sample_s=0.0, stall_timeout_s=0.0,
                                   fault_budget=2))
    with fe:
        sids = [fe.open_stream(frame_shape=(H, W, 3)) for _ in range(2)]
        got = {0: [], 1: []}
        for i in (0, 1):
            for k, frames in enumerate((a, b)):
                got[k].append(_one(fe, sids[k], frames[i]))
        label = next(iter(fe.stats()["buckets"]))
        assert fe.request_batch_size(label, 1, reason="test resize")
        deadline = time.time() + 60.0
        while fe.swaps < 1 and time.time() < deadline:
            time.sleep(0.005)
        for i in (2, 3):
            for k, frames in enumerate((a, b)):
                got[k].append(_one(fe, sids[k], frames[i]))
        for k in (0, 1):
            assert _worst(got[k], want[k]) <= 1      # the EMA went on
        assert fe.stats()["buckets"][label]["state"]["resets_total"]["rebuild"] == 0

        def dead_step(*args, **kwargs):
            raise RuntimeError("engine died (forced)")

        fe.engine._step = dead_step
        i = 4
        while fe.recoveries < 1:          # contained faults, then the rebuild
            _one(fe, sids[0], a[i % len(a)])
            i += 1
            assert i < 40
        # a session's first frame after the rebuild seeds its EMA anew
        for k, frames in enumerate((a, b)):
            np.testing.assert_array_equal(_one(fe, sids[k], frames[0]), frames[0])
        state = fe.stats()["buckets"][label]["state"]
        assert state["resets_total"] == {"admission": 2, "rebuild": 2, "migrate": 0}
        rebuilds = [e for e in fe.ledger.snapshot()
                    if e["kind"] == ledger_mod.ENGINE_REBUILD]
        assert rebuilds and rebuilds[0]["state_rows_restarted"] == 2


def test_migrated_session_restarts_and_is_counted():
    """A session the fleet re-opens on another replica says so
    (``state_cause``): it binds a fresh row there, counted by cause. The
    id's spelling decides nothing: a client may put the fleet's ``@g`` in
    its own."""
    fe = ServeFrontend(get_filter("ema_smooth"), ServeConfig(batch_size=2))
    fe.open_stream(session_id="cam@garage")
    fe.open_stream(session_id="cam1@g1", state_cause="migrate")
    state = next(iter(fe.stats()["buckets"].values()))["state"]
    assert state["resets_total"] == {"admission": 1, "rebuild": 0, "migrate": 1}
    assert state["bound"] == 2
    with pytest.raises(ValueError, match="state_cause"):
        fe.open_stream(state_cause="rebuild")
    fe.stop()


def test_chain_tables_only_its_temporal_members_leaves():
    """In a chain a member whose state is read-only weights is stored
    once; only the temporal member's leaves get a row per session, and
    two sessions interleaved through the chain equal each alone."""
    import jax.numpy as jnp

    from dvf_tpu.api.filter import Filter, FilterChain

    gain = Filter(
        name="gain", fn=lambda x, w: (x * w["gain"], w), uint8_ok=False,
        init_state=lambda shape, dtype: {"gain": jnp.full((3,), 0.5, dtype)},
        constant_state=True)

    def chain():
        return FilterChain(gain, get_filter("ema_smooth", alpha=0.4))

    eng = Engine(chain(), state_rows=4)
    eng.compile((2, H, W, 3), np.uint8)
    weights, ema = eng._state
    assert weights["gain"].shape == (3,)
    assert all(leaf.shape[0] == 4 for leaf in ema.values())
    a, b = STREAMS[0][:3], STREAMS[2][:3]
    rows = np.array([[0, 2], [0, 0]], np.int32)
    got = []
    for i in range(3):
        rows[1] = int(i == 0)
        got.append(np.asarray(eng.submit(np.stack([a[i], b[i]]), rows)))
    assert eng.stats.compile_count == 1
    eng.free()
    for k, frames in enumerate((a, b)):
        one = Engine(chain())
        want = [np.asarray(one.submit(f[None]))[0] for f in frames]
        one.free()
        assert _worst([g[k] for g in got], want) == 0


def test_leak_control_fails():
    """(g) What the parent's sequence form would do to a shared batch, and
    the same mathematics in bfloat16, both read far outside the tolerance
    the sound program meets."""
    ref = _load_ref()
    config = {"filter": {"kwargs": REF_KW}}
    pool = STREAMS[0] + STREAMS[2]        # unrelated neighbours in the pool
    wanted = ref.reference(pool, config, None)
    n = len(pool)

    def worst(samples):
        diffs = [np.abs(f.astype(int) - wanted[(k + i) % n].astype(int))
                 for k, i, f in samples]
        return max(int(d.max()) for d in diffs), max(float(d.mean()) for d in diffs)

    leak_max, leak_mean = worst(ref.leaky(pool, config, None, sessions=3, rows=6))
    assert leak_max > 10 * REF_MAX_STEPS and leak_mean > 10 * REF_MEAN_STEPS
    served = ref.control(pool, config, None)
    bf_max, bf_mean = worst([(0, j, f) for j, f in enumerate(served)])
    assert bf_max > REF_MAX_STEPS and bf_mean > REF_MEAN_STEPS


def test_temporal_filter_without_rows_needs_one_state_row():
    """A temporal filter that defines only the one-stream body still runs,
    one stream at a time; a table of sessions needs ``Filter.rows``."""
    import dataclasses

    legacy = dataclasses.replace(get_filter("ema_smooth"), rows=None)
    eng = Engine(legacy)
    x = STREAMS[0][0][None]
    assert np.asarray(eng.submit(x)).shape == x.shape
    eng.free()
    with pytest.raises(ValueError, match="Filter.rows"):
        Engine(legacy, state_rows=2).compile(x.shape, np.uint8)
