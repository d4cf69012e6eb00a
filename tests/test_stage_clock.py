"""The serve path's one stage clock (PR 25).

One set of wall-clock stamps per batch (``BatchPlan.stamps``) and one per
frame (``Slot.t_pending``), always on; the per-bucket ``StageStats``
counters, the ``FrameLineage`` marks, the Tracer's dispatch/collect spans
and the tick-cost sample are all views of them. Pinned here:

- the eight frame components of a bucket sum, frame-weighted, to its
  delivered latency total;
- each pacing thread's states sum to its wall time;
- a histogram delta between two reads holds what was recorded between them;
- ``permit_wait`` is its own interval (not ``queue_bucket``), and
  ``inflight_wait`` (not ``device``) takes a held collect thread's time;
- the dispatch thread from inside (PR 40): ``prefetch`` is a state of its
  own and not ``idle``; the ingest block's five clocks stay inside
  ``assemble_h2d``; the bucket row's ``starved`` block books each device
  gap under what the dispatch thread was doing, from the batch's stamps;
- where a batch's H2D lands (PR 54): ``t_landed`` is stamped only where
  the collect thread saw the landing, the ``starved`` block books that
  wait beside the four states and leaves them as they were, and an
  unseen landing is counted and never timed;
- lineage and trace are views: same numbers, no clock of their own, and
  nothing is allocated for them when they are off;
- XLA compilations are counted process-wide; a flight dump's device
  capture records its host-clock epoch.
"""

import collections
import json
import threading
import time

import numpy as np
import pytest

from dvf_tpu.obs import metrics as M
from dvf_tpu.obs.lineage import BATCH_COMPONENTS, SERVE_COMPONENTS
from dvf_tpu.ops import get_filter
from dvf_tpu.resilience import FaultPlan
from dvf_tpu.serve import ServeConfig, ServeFrontend

H, W = 16, 24


def frame_u8(k, j):
    f = np.full((H, W, 3), 7, np.uint8)
    f[0] = k
    f[1] = j % 251
    return f


def drain(fe, sid, want, deadline_s=30.0):
    got = []
    deadline = time.time() + deadline_s
    while len(got) < want and time.time() < deadline:
        got += fe.poll(sid)
        time.sleep(0.002)
    return got


def serve(n_sessions=2, n_frames=12, pace_s=0.0, engine=None, **cfg):
    """One served run; returns (deliveries per session, stats, frontend)."""
    kw = dict(batch_size=4, queue_size=500, slo_ms=60_000.0,
              telemetry_sample_s=0.0)
    kw.update(cfg)
    fe = ServeFrontend(get_filter("invert"), ServeConfig(**kw), engine=engine)
    got = {}
    with fe:
        sids = [fe.open_stream() for _ in range(n_sessions)]
        for j in range(n_frames):
            for k, sid in enumerate(sids):
                fe.submit(sid, frame_u8(k, j))
            if pace_s:
                time.sleep(pace_s)
        for sid in sids:
            got[sid] = drain(fe, sid, n_frames)
            assert len(got[sid]) == n_frames
        stats = fe.stats()
    return got, stats, fe


def stages_of(stats):
    (row,) = [r for r in stats["buckets"].values() if r["batches"]]
    return row["stages"], row


# ---------------------------------------------------------------------------
# Closure 1: the components of a bucket sum to its delivered latency
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cfg", [
    dict(batch_size=4, max_inflight=4),
    dict(batch_size=4, max_inflight=1),
    dict(batch_size=3, max_inflight=2, pace_s=0.002),   # padded batches
], ids=["depth4", "depth1", "padded"])
def test_components_sum_to_delivered_latency(cfg):
    got, stats, _ = serve(**cfg)
    st, row = stages_of(stats)
    n = sum(len(v) for v in got.values())
    assert st["delivered"] == n
    assert set(st["components"]) == set(SERVE_COMPONENTS)
    total = sum(c["ms_total"] for c in st["components"].values())
    assert total == pytest.approx(st["latency_ms_total"], rel=1e-6, abs=1e-3)
    # ... and that latency total is the deliveries' own latency_ms
    assert st["latency_ms_total"] == pytest.approx(
        sum(d.latency_ms for v in got.values() for d in v), rel=1e-6, abs=1e-2)
    for name, c in st["components"].items():
        assert c["frames"] == n, name
        assert sum(k for _, k in c["hist"]) == n, name        # frame-weighted
        assert ("batches" in c) == (name in BATCH_COMPONENTS), name
        if "batches" in c:
            assert c["batches"] == row["batches"], name
    assert st["route"]["batches"] == row["batches"]
    assert sum(k for _, k in st["route"]["hist"]) == row["batches"]


# ---------------------------------------------------------------------------
# Closure 2: each pacing thread's states sum to its wall time
# ---------------------------------------------------------------------------


def _read_just_after_an_accrual(fe, thread):
    """A thread's ledger lags the wall by the time since it last accrued
    (a 2 ms tick for dispatch, a 50 ms queue poll for an idle collect
    thread). Spin until ``accounted_to`` moves, then read: the lag is the
    cost of one ``stats()`` call."""
    last = fe.stats()["threads"][thread]["accounted_to"]
    deadline = time.time() + 5.0
    while time.time() < deadline:
        stats = fe.stats()
        now = time.time()
        if stats["threads"][thread]["accounted_to"] != last:
            return now, stats
    raise AssertionError(f"{thread} thread's ledger stopped moving")


@pytest.mark.parametrize("thread,states", [
    ("dispatch", ("idle", "hold", "permit_wait", "assemble_h2d", "prefetch")),
    ("collect", ("idle", "device", "d2h", "route")),
])
def test_thread_states_sum_to_wall_time(thread, states):
    fe = ServeFrontend(get_filter("invert"), ServeConfig(
        batch_size=4, queue_size=500, slo_ms=60_000.0, max_inflight=2,
        telemetry_sample_s=0.0))
    with fe:
        sid = fe.open_stream()
        for burst in range(2):
            for j in range(16):
                fe.submit(sid, frame_u8(0, burst * 16 + j))
            assert len(drain(fe, sid, 16)) == 16
            time.sleep(0.6)
        now, stats = _read_just_after_an_accrual(fe, thread)
    row = stats["threads"][thread]
    assert {k[:-3] for k in row if k.endswith("_ms")} == set(states) | {"wall"}
    total = sum(row[f"{s}_ms"] for s in states)
    # the ledger never loses or double-counts an interval ...
    assert total == pytest.approx(
        (row["accounted_to"] - row["started"]) * 1e3, rel=1e-6, abs=0.01)
    # ... and it is the thread's wall time, to 1%
    wall_ms = (now - row["started"]) * 1e3
    assert wall_ms > 1200.0
    assert total == pytest.approx(wall_ms, rel=0.01)
    # the bucket rows hold the same per-bucket states (one bucket here)
    st, bucket_row = stages_of(stats)
    for s in states[1:]:
        if s == "hold":     # no batch interval: the bucket row's hold block
            assert bucket_row["hold"]["hold_ms_total"] == pytest.approx(
                row["hold_ms"], abs=0.01)
            continue
        cell = st[s] if s in ("route", "prefetch") else st["components"][s]
        assert cell["batch_ms_total"] == pytest.approx(row[f"{s}_ms"], abs=0.01)
    assert row[f"{states[-1]}_ms"] > 0.0


# ---------------------------------------------------------------------------
# Histogram: a delta between two reads holds what was recorded between them
# ---------------------------------------------------------------------------


def _fake_slot(ts, t_pending, st):
    class S:
        pass
    s = S()
    s.ts, s.t_pending, s.stamps = ts, t_pending, st
    return s


def _fold(stages, ms_values, base=1000.0):
    """Deliver frames whose queue_ingress is the given ms; every other
    component 1 ms."""
    for ms in ms_values:
        st = M.BatchStamps(stages, base + ms / 1e3 + 0.001)
        (st.t_permit, st.t_submit, st.t_taken, st.t_ready,
         st.t_fetched) = (st.t_chosen + 0.001 * i for i in range(1, 6))
        st.close_batch()
        slot = _fake_slot(base, base + ms / 1e3, st)
        stages.fold_delivered([(slot, st.t_fetched + 0.001)])


@pytest.mark.parametrize("before,between", [
    ([5.0] * 50, [200.0] * 100),
    ([0.01] * 10, [1.0 + i for i in range(100)]),
    ([], [0.02, 3.0, 3.0, 3.0, 90_000.0]),
], ids=["shifted", "spread", "edges"])
def test_histogram_delta_returns_the_windows_percentiles(before, between):
    stages = M.StageStats()
    _fold(stages, before)
    a = stages.summary()["components"]["queue_ingress"]
    _fold(stages, between)
    b = stages.summary()["components"]["queue_ingress"]
    # read the way the benchmark's readers do (chipbench/stagelib.py)
    from chipbench import stagelib

    doc = stages.summary()
    win = {"lo_ms": doc["hist_lo_ms"], "per_decade": doc["hist_bins_per_decade"],
           "bins": doc["hist_bins"]}
    delta = stagelib._cell_delta(b, a, win["bins"])["hist"]
    assert sum(delta) == len(between)
    assert b["frames"] - a["frames"] == len(between)
    assert b["ms_total"] - a["ms_total"] == pytest.approx(sum(between), rel=1e-6)
    ratio = 10.0 ** (1.0 / M.HIST_PER_DECADE)     # one bin's width
    for q in (0.5, 0.95):
        want = float(np.quantile(between, q, method="inverted_cdf"))
        est = stagelib.quantile(win, delta, q)
        if want < M.HIST_LO_MS:
            assert est < M.HIST_LO_MS
        else:
            assert want / ratio <= est <= want * ratio, (q, want, est)
    assert b["max_ms"] == pytest.approx(max(before + between), rel=1e-6)
    assert M.hist_bin(0.0) == 0 and M.hist_bin(-1.0) == 0
    assert M.hist_bin(1e9) == M.HIST_BINS - 1


def test_delivery_fold_loses_no_update_under_contention():
    """Deliveries fold from whichever thread delivers (the collect thread, or
    a finalize on the dispatch thread): more threads than cores, a tiny
    switch interval, and the closure still holds to the frame."""
    import sys

    stages = M.StageStats()
    threads, rounds = 12, 400
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [threading.Thread(target=_fold, args=(stages, [2.0] * rounds))
                   for _ in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60.0)
        assert not any(w.is_alive() for w in workers)
    finally:
        sys.setswitchinterval(old)
    doc = stages.summary()
    assert doc["delivered"] == threads * rounds
    assert doc["components"]["queue_ingress"]["ms_total"] == pytest.approx(
        2.0 * threads * rounds, rel=1e-6)
    assert sum(c["ms_total"] for c in doc["components"].values()) == pytest.approx(
        doc["latency_ms_total"], rel=1e-6)
    for c in doc["components"].values():
        assert sum(n for _, n in c["hist"]) == threads * rounds


# ---------------------------------------------------------------------------
# permit_wait is not queue_bucket; inflight_wait is not device
# ---------------------------------------------------------------------------


def test_permit_wait_is_split_from_queue_bucket():
    """max_inflight 1, the collect thread held 60 ms a round, four batches
    ready at once: each next one is chosen as soon as its predecessor is
    submitted and then waits a held round for the permit. That wait is
    ``permit_wait``; a frame's ``queue_bucket`` ends where its batch was
    chosen, so a batch's frames hold only their PREDECESSORS' permit waits
    there, never their own."""
    chaos = FaultPlan().add("freeze", every=1, delay_s=0.06)
    got, stats, _ = serve(n_sessions=1, n_frames=16, batch_size=4,
                          max_inflight=1, chaos=chaos)
    st, row = stages_of(stats)
    comp = st["components"]
    assert row["batches"] == 4
    # batches 3 and 4 each waited a whole held round for their permit
    assert comp["permit_wait"]["batch_ms_total"] > 100.0
    assert comp["permit_wait"]["max_ms"] > 50.0
    # the last batch's frames waited in the bucket through three rounds, and
    # its own permit wait is on top of that, not inside it: the two
    # components and the four after them still sum to the latency
    assert comp["queue_bucket"]["max_ms"] > comp["permit_wait"]["max_ms"]
    lat = st["latency_ms_total"]
    assert sum(c["ms_total"] for c in comp.values()) == pytest.approx(lat, rel=1e-6)
    # frozen-batch time is a real share of these frames' latency
    assert comp["permit_wait"]["ms_total"] > 0.1 * lat


def test_inflight_wait_takes_a_held_collect_thread():
    """The ``freeze`` chaos site holds the collect thread before it takes
    the next batch: the batch is long done on the device when it is taken,
    so the time is ``inflight_wait`` and ``device`` stays near zero."""
    chaos = FaultPlan().add("freeze", every=1, delay_s=0.08)
    got, stats, _ = serve(n_sessions=1, n_frames=16, batch_size=4,
                          max_inflight=4, chaos=chaos)
    comp = stages_of(stats)[0]["components"]
    # four batches in flight at once, taken one per held round
    assert comp["inflight_wait"]["batch_ms_total"] > 150.0
    assert comp["device"]["batch_ms_total"] < \
        comp["inflight_wait"]["batch_ms_total"] / 4


def test_hold_is_a_thread_state_a_bucket_block_and_queue_bucket(device_gate):
    """Ticks on which a short batch waits for the device's backlog
    (serve/batcher.py): the dispatch thread's ``hold`` state, the bucket
    row's ``hold`` block and one ``dispatch:hold`` span a hold are the
    same clock reads; per frame the wait is ``queue_bucket``, so the
    eight components still sum to the delivered latency."""
    fe = ServeFrontend(get_filter("invert"), ServeConfig(
        batch_size=4, queue_size=500, slo_ms=60_000.0, trace=True,
        telemetry_sample_s=0.0))

    def bucket_row():
        return next(iter(fe.stats()["buckets"].values()))

    with fe:
        sid = fe.open_stream()
        n = 0
        for hold_ms in (40.0, 80.0):
            device_gate.busy = True
            fe.submit(sid, frame_u8(0, n))          # idle device: at once
            device_gate.until(lambda: bucket_row()["batches"] == n + 1,
                              "the batch that makes the backlog")
            before = bucket_row()["hold"]["hold_ms_total"]
            fe.submit(sid, frame_u8(0, n + 1))      # held
            device_gate.until(
                lambda: bucket_row()["hold"]["hold_ms_total"]
                > before + hold_ms, "the hold")
            device_gate.busy = False
            device_gate.until(lambda: bucket_row()["batches"] == n + 2,
                              "the held batch")
            for _ in range(2):      # ... and a whole tick saw it ready
                _read_just_after_an_accrual(fe, "dispatch")
            n += 2
        now, stats = _read_just_after_an_accrual(fe, "dispatch")
        snap = fe.tracer.snapshot()
    st, row = stages_of(stats)
    hold, thread = row["hold"], stats["threads"]["dispatch"]
    assert hold["short_batches_total"] == row["batches"] == 4
    assert hold["full_batches_total"] == 0
    assert hold["held_batches_total"] == 2
    assert hold["hold_ms_total"] > 120.0
    assert thread["hold_ms"] == pytest.approx(hold["hold_ms_total"], abs=0.01)
    states = ("idle", "hold", "permit_wait", "assemble_h2d", "prefetch")
    assert sum(thread[f"{s}_ms"] for s in states) == pytest.approx(
        (thread["accounted_to"] - thread["started"]) * 1e3, rel=1e-6, abs=0.01)
    spans = [e for e in snap["events"] if e["name"] == "dispatch:hold"]
    assert len(spans) == 2 and {e["pid"] for e in spans} == {0}
    assert sum(e["dur"] for e in spans) / 1e3 == pytest.approx(
        thread["hold_ms"], abs=0.02)
    # per frame: no ninth component; the held frames' wait is queue_bucket
    assert set(st["components"]) == set(SERVE_COMPONENTS)
    assert sum(c["ms_total"] for c in st["components"].values()) == \
        pytest.approx(st["latency_ms_total"], rel=1e-6, abs=1e-3)
    assert st["components"]["queue_bucket"]["ms_total"] == pytest.approx(
        hold["hold_ms_total"], rel=0.1, abs=5.0)
    assert st["components"]["permit_wait"]["max_ms"] < 40.0


# ---------------------------------------------------------------------------
# The dispatch thread from inside: prefetch, the ingest split, starvation
# ---------------------------------------------------------------------------


def test_prefetch_is_a_dispatch_state_and_not_idle(monkeypatch):
    """A lane whose ``prefetch`` takes 20 ms: the dispatch thread's ledger
    shows 20 ms a batch under ``prefetch`` (booked as ``idle`` before PR
    40), the bucket's ``prefetch`` cell counts every batch, a frame's
    components do not change, and the egress block counts the transfers."""
    from dvf_tpu.runtime import lane as lane_mod

    real = lane_mod.DeviceLane.prefetch

    def slow(self, *batch):
        time.sleep(0.02)
        return real(self, *batch)

    monkeypatch.setattr(lane_mod.DeviceLane, "prefetch", slow)
    fe = ServeFrontend(get_filter("invert"), ServeConfig(
        batch_size=4, queue_size=500, slo_ms=60_000.0, trace=True,
        telemetry_sample_s=0.0))
    with fe:
        sid = fe.open_stream()
        for j in range(24):
            fe.submit(sid, frame_u8(0, j))
        assert len(drain(fe, sid, 24)) == 24
        now, stats = _read_just_after_an_accrual(fe, "dispatch")
        snap = fe.tracer.snapshot()
    st, row = stages_of(stats)
    thread = stats["threads"]["dispatch"]
    n = row["batches"]
    assert st["prefetch"]["batches"] == n >= 6
    assert "frames" not in st["prefetch"]       # a thread state, no component
    assert sum(k for _, k in st["prefetch"]["hist"]) == n
    assert 20.0 * n <= thread["prefetch_ms"] < 20.0 * n + 15.0 * n
    assert st["prefetch"]["batch_ms_total"] == pytest.approx(
        thread["prefetch_ms"], abs=0.01)
    states = ("idle", "hold", "permit_wait", "assemble_h2d", "prefetch")
    assert sum(thread[f"{s}_ms"] for s in states) == pytest.approx(
        (thread["accounted_to"] - thread["started"]) * 1e3, rel=1e-6, abs=0.01)
    # ... so idle is what the wall leaves, the 20 ms a batch not in it
    assert thread["idle_ms"] == pytest.approx(
        thread["wall_ms"] - sum(thread[f"{s}_ms"] for s in states[1:]), abs=0.01)
    assert set(st["components"]) == set(SERVE_COMPONENTS)
    assert sum(c["ms_total"] for c in st["components"].values()) == \
        pytest.approx(st["latency_ms_total"], rel=1e-6, abs=1e-3)
    # inflight_wait still starts at the submit: the 20 ms are inside it
    assert st["components"]["inflight_wait"]["batch_ms_total"] >= 20.0 * n
    spans = [e for e in snap["events"] if e["name"] == "dispatch:prefetch"]
    assert [e["args"]["seq"] for e in spans] == list(range(n))
    assert sum(e["args"]["rows"] for e in spans) == 24
    # the fetcher's own clock (inside the slowed call) and its transfers
    assert row["egress"]["prefetch_rows_total"] >= n
    assert 0.0 < row["egress"]["prefetch_ms_total"] < thread["prefetch_ms"]


def test_a_raising_prefetch_stays_under_idle(monkeypatch):
    from dvf_tpu.runtime import lane as lane_mod

    real = lane_mod.DeviceLane.prefetch
    calls = []

    def flaky(self, *batch):
        calls.append(1)
        if len(calls) == 2:
            time.sleep(0.03)
            raise RuntimeError("prefetch: injected")
        return real(self, *batch)

    monkeypatch.setattr(lane_mod.DeviceLane, "prefetch", flaky)
    fe = ServeFrontend(get_filter("invert"), ServeConfig(
        batch_size=4, queue_size=500, slo_ms=60_000.0, telemetry_sample_s=0.0))
    with fe:
        sid = fe.open_stream()
        for j in range(12):
            fe.submit(sid, frame_u8(0, j))
        assert len(drain(fe, sid, 8)) == 8          # one batch of three shed
        now, stats = _read_just_after_an_accrual(fe, "dispatch")
    st, row = stages_of(stats)
    thread = stats["threads"]["dispatch"]
    assert st["prefetch"]["batches"] == row["batches"] == 2
    assert thread["prefetch_ms"] < 30.0 <= thread["idle_ms"]
    assert thread["prefetch_ms"] == pytest.approx(
        st["prefetch"]["batch_ms_total"], abs=0.01)


SPLIT = ("stage_ms_total", "h2d_put_ms_total", "h2d_wait_ms_total",
         "join_ms_total", "step_dispatch_ms_total")


@pytest.mark.parametrize("mode,rows", [
    ("monolithic", False), ("streamed", False), ("streamed", True)],
    ids=["monolithic", "streamed", "streamed_rows"])
def test_ingest_split_stays_inside_assemble_h2d(mode, rows, monkeypatch):
    """stage + put + wait + join + step dispatch, each taken inside the call
    that does the work, are parts of ``assemble_h2d``: never more than it,
    and what they leave (the loop's own code: begin, the row loop, the
    stamps) is under 0.5 ms a batch + 25% at this size, warm. ``rows``: the
    frames go up from the client's arrays (the row path: no staging)."""
    from dvf_tpu.parallel import MeshConfig, make_mesh
    from dvf_tpu.runtime import ingest as ingest_mod
    from dvf_tpu.runtime.engine import Engine

    if mode == "streamed":
        monkeypatch.setattr(ingest_mod, "MIN_STREAM_H2D_MS", 0.0)
    if not rows:
        monkeypatch.setattr(ingest_mod.ShardedBatchAssembler, "_plan_rows",
                            lambda self: None)
    engine = Engine(get_filter("invert"), mesh=make_mesh(MeshConfig(data=1)))
    fe = ServeFrontend(get_filter("invert"), ServeConfig(
        batch_size=8, queue_size=500, slo_ms=60_000.0, trace=True,
        telemetry_sample_s=0.0), engine=engine)
    reads = []
    with fe:
        sid = fe.open_stream()
        for burst in range(2):      # the first takes the compiles in
            for j in range(64):
                fe.submit(sid, frame_u8(0, burst * 64 + j))
            assert len(drain(fe, sid, 64)) == 64
            reads.append(stages_of(fe.stats()))
        snap = fe.tracer.snapshot()
    (st0, row0), (st1, row1) = reads
    assert row1["ingest"]["mode"] == mode
    n = row1["batches"] - row0["batches"]
    assert n == row1["ingest"]["batches"] - row0["ingest"]["batches"] >= 8
    split = {k: row1["ingest"][k] - row0["ingest"][k] for k in SPLIT}
    asm = (st1["components"]["assemble_h2d"]["batch_ms_total"]
           - st0["components"]["assemble_h2d"]["batch_ms_total"])
    assert all(v >= 0.0 for v in split.values())
    assert (split["stage_ms_total"] == 0.0) is rows
    assert row1["ingest"]["rows_direct_total"] == (128 if rows else 0)
    assert split["step_dispatch_ms_total"] > 0.0
    if mode == "streamed":
        assert split["h2d_put_ms_total"] > 0.0 and split["join_ms_total"] > 0.0
    else:       # one host buffer: nothing is put or joined outside the engine
        assert split["h2d_put_ms_total"] == split["join_ms_total"] == 0.0
    total = sum(split.values())
    assert total <= asm + 0.01
    assert asm - total <= 0.5 * n + 0.25 * asm
    # the assemble_h2d spans carry the same split, batch by batch
    spans = [e["args"] for e in snap["events"]
             if e["name"] == "dispatch:assemble_h2d"]
    assert len(spans) == row1["batches"]
    args = ("stage_ms", "put_ms", "wait_ms", "join_ms", "step_dispatch_ms")
    for ours, theirs in zip(args, SPLIT):
        assert sum(a[ours] for a in spans) == pytest.approx(
            row1["ingest"][theirs], abs=0.001 * len(spans) + 0.001)


def _stamps(t_chosen, t_permit, t_submit, t_held=0.0, t_taken=0.0, t_landed=0.0):
    st = M.BatchStamps(None, t_chosen)
    st.t_held, st.t_permit, st.t_submit = t_held, t_permit, t_submit
    st.t_taken, st.t_landed = t_taken, t_landed
    return st


OLD_KEYS = {f"{s}_ms_total" for s in M.STARVED_STATES} | {"gaps_total", "max_gap_ms"}
LANDING_KEYS = {"landing_ms_total", "landing_unseen_ms_total", "landed_seen_total",
                "landed_unseen_total"}


@pytest.mark.parametrize("last_ready,stamps,want", [
    # the collect thread's first batch: no predecessor, no gap
    (0.0, (10.0, 10.1, 10.2), None),
    # the device still had batch n-1 when batch n was submitted
    (10.3, (10.0, 10.1, 10.2), None),
    (10.2, (10.0, 10.1, 10.2), None),
    # ran out before the bucket had frames: every state, cut at the stamps
    (9.0, (10.0, 10.1, 10.4), dict(idle=1000.0, permit_wait=100.0, assemble_h2d=300.0)),
    # ... held from 9.5: idle up to the hold's start, hold up to t_chosen
    (9.0, (10.0, 10.1, 10.4, 9.5),
     dict(idle=500.0, hold=500.0, permit_wait=100.0, assemble_h2d=300.0)),
    # ran out during the hold
    (9.8, (10.0, 10.1, 10.4, 9.5), dict(hold=200.0, permit_wait=100.0, assemble_h2d=300.0)),
    # ran out while the batch waited for its permit
    (10.05, (10.0, 10.1, 10.4, 9.5), dict(permit_wait=50.0, assemble_h2d=300.0)),
    # ran out while it was being staged: the invert cell's case
    (10.3, (10.0, 10.1, 10.4), dict(assemble_h2d=100.0)),
], ids=["first", "busy", "touching", "idle", "held", "in_hold", "in_permit_wait",
        "in_assemble"])
def test_starved_block_cuts_a_gap_at_the_batchs_stamps(last_ready, stamps, want):
    starved = M.StarvedStats()
    starved.note(last_ready, _stamps(*stamps))
    doc = starved.summary()
    assert set(doc) == OLD_KEYS | LANDING_KEYS
    assert not any(doc[k] for k in LANDING_KEYS)    # no probe: nothing of the landing's
    if want is None:
        assert doc["gaps_total"] == 0 and doc["max_gap_ms"] == 0.0
        assert all(doc[f"{s}_ms_total"] == 0.0 for s in M.STARVED_STATES)
        return
    for s in M.STARVED_STATES:
        assert doc[f"{s}_ms_total"] == pytest.approx(want.get(s, 0.0), abs=1e-6), s
    assert doc["gaps_total"] == 1
    assert doc["max_gap_ms"] == pytest.approx(sum(want.values()), abs=1e-6)
    # cumulative: a second, shorter gap adds up and leaves the maximum
    starved.note(20.0, _stamps(19.0, 19.5, 20.05))
    doc = starved.summary()
    assert doc["gaps_total"] == 2
    assert doc["assemble_h2d_ms_total"] == pytest.approx(
        want.get("assemble_h2d", 0.0) + 50.0, abs=1e-6)
    assert doc["max_gap_ms"] == pytest.approx(sum(want.values()), abs=1e-6)


def test_starved_block_through_a_served_run():
    """Two bursts 0.4 s apart: the device ran out of work between them, the
    dispatch thread had nothing to bind, so the wait is a gap under
    ``idle``; the states sum to the gaps, and there is at most one gap a
    batch after the first."""
    fe = ServeFrontend(get_filter("invert"), ServeConfig(
        batch_size=4, queue_size=500, slo_ms=60_000.0, telemetry_sample_s=0.0))
    with fe:
        sid = fe.open_stream()
        for burst in range(2):
            for j in range(8):
                fe.submit(sid, frame_u8(0, burst * 8 + j))
            assert len(drain(fe, sid, 8)) == 8
            time.sleep(0.4)
        _, row = stages_of(fe.stats())
    doc = row["starved"]
    assert 1 <= doc["gaps_total"] <= row["batches"] - 1
    assert doc["idle_ms_total"] >= 400.0
    assert doc["max_gap_ms"] >= 400.0
    assert doc["hold_ms_total"] == 0.0          # an idle device takes a short batch at once
    assert sum(doc[f"{s}_ms_total"] for s in M.STARVED_STATES) >= doc["max_gap_ms"]


def test_starved_block_holds_the_hold(device_gate):
    """A short batch held behind a device that reads busy (the gate) while
    the collect thread has long seen its predecessor ready: the gap's
    middle is ``hold``, from the stamp the dispatch thread kept."""
    fe = ServeFrontend(get_filter("invert"), ServeConfig(
        batch_size=4, queue_size=500, slo_ms=60_000.0, telemetry_sample_s=0.0))

    def bucket_row():
        return next(iter(fe.stats()["buckets"].values()))

    with fe:
        sid = fe.open_stream()
        device_gate.busy = True
        fe.submit(sid, frame_u8(0, 0))              # idle device: at once
        device_gate.until(lambda: bucket_row()["batches"] == 1, "the first batch")
        before = bucket_row()["hold"]["hold_ms_total"]
        fe.submit(sid, frame_u8(0, 1))              # held
        device_gate.until(
            lambda: bucket_row()["hold"]["hold_ms_total"] > before + 60.0, "the hold")
        device_gate.busy = False
        device_gate.until(lambda: bucket_row()["batches"] == 2, "the held batch")
        row = bucket_row()
    doc = row["starved"]
    assert doc["gaps_total"] == 1
    assert doc["hold_ms_total"] > 60.0
    assert doc["hold_ms_total"] == pytest.approx(row["hold"]["hold_ms_total"], abs=10.0)
    assert doc["max_gap_ms"] == pytest.approx(
        sum(doc[f"{s}_ms_total"] for s in M.STARVED_STATES), abs=0.01)


def test_first_batch_after_a_recovery_opens_no_gap(monkeypatch):
    """A supervised recovery starts a collect thread of its own: the batch
    it takes first has no predecessor on that thread, so the time the
    window was wedged is no gap of the dispatch thread's."""
    seen = []
    real = M.StarvedStats.note

    def spy(self, last_ready, st, *probed):
        seen.append((threading.get_ident(), last_ready))
        real(self, last_ready, st, *probed)

    monkeypatch.setattr(M.StarvedStats, "note", spy)
    chaos = FaultPlan().add("freeze", at=(3,), delay_s=1.5)
    fe = ServeFrontend(get_filter("invert"), ServeConfig(
        batch_size=4, queue_size=1000, slo_ms=60_000.0, stall_timeout_s=0.35,
        chaos=chaos, telemetry_sample_s=0.0))
    with fe:
        sid = fe.open_stream()
        i = 0
        deadline = time.time() + 20.0
        while fe.recoveries < 1:
            assert time.time() < deadline, "watchdog never tripped"
            fe.submit(sid, frame_u8(0, i))
            i += 1
            time.sleep(0.01)
        served = len(seen)
        while len(seen) < served + 2:               # ... and on after it
            assert time.time() < deadline, "nothing served after the recovery"
            fe.submit(sid, frame_u8(0, i))
            i += 1
            time.sleep(0.01)
    threads = list(dict.fromkeys(t for t, _ in seen))
    assert len(threads) >= 2
    for t in threads:
        first, *rest = [ready for who, ready in seen if who == t]
        assert first == 0.0 and all(r > 0.0 for r in rest)



# ---------------------------------------------------------------------------
# Where a batch's H2D lands (PR 54)
# ---------------------------------------------------------------------------

# (last_ready, (t_chosen, t_permit, t_submit, t_held, t_taken, t_landed),
#  the batch had a probe, what moves). The batch: submitted at 10.4, taken
# off the queue at 10.45.
LANDING_CASES = {
    # ran out at 10.3, the bytes landed at 10.5 under the thread's eyes:
    # 100 ms before the submit are assemble_h2d's, 100 ms after it the link's
    "seen": (10.3, (10.0, 10.1, 10.4, 0.0, 10.45, 10.5), True, dict(
        landing_ms_total=100.0, landed_seen_total=1)),
    # the step before was still running when they landed: a landing seen,
    # and no ms of the chip's (from 10.6 it had this batch to run)
    "seen_before_last_ready": (10.6, (10.0, 10.1, 10.4, 0.0, 10.45, 10.5), True, dict(
        landed_seen_total=1)),
    # there before the thread looked: counted, and bounded by its look
    "unseen": (10.3, (10.0, 10.1, 10.4, 0.0, 10.45, 0.0), True, dict(
        landing_unseen_ms_total=50.0, landed_unseen_total=1)),
    "unseen_before_last_ready": (10.6, (10.0, 10.1, 10.4, 0.0, 10.45, 0.0), True, dict(
        landed_unseen_total=1)),
    # the slab and monolithic paths: nothing to ask
    "no_probe": (10.3, (10.0, 10.1, 10.4, 0.0, 10.45, 0.0), False, {}),
    # a collect thread's first batch: the landing is the batch's own and is
    # counted; without a predecessor no ms of the chip's is booked
    "first_seen": (0.0, (10.0, 10.1, 10.4, 0.0, 10.45, 10.5), True, dict(
        landed_seen_total=1)),
    "first_unseen": (0.0, (10.0, 10.1, 10.4, 0.0, 10.45, 0.0), True, dict(
        landed_unseen_total=1)),
}


@pytest.mark.parametrize("case", LANDING_CASES)
def test_starved_block_books_a_landing(case):
    last_ready, stamps, probed, want = LANDING_CASES[case]
    starved = M.StarvedStats()
    starved.note(last_ready, _stamps(*stamps), probed)
    doc = starved.summary()
    assert set(doc) == OLD_KEYS | LANDING_KEYS
    for key in LANDING_KEYS:
        assert doc[key] == pytest.approx(want.get(key, 0), abs=1e-6), key
    # cumulative: a second seen landing, 20 ms of the chip's
    starved.note(20.0, _stamps(19.0, 19.5, 20.01, 0.0, 20.02, 20.03), True)
    doc = starved.summary()
    assert doc["landing_ms_total"] == pytest.approx(
        want.get("landing_ms_total", 0.0) + 20.0, abs=1e-6)
    assert doc["landed_seen_total"] == want.get("landed_seen_total", 0) + 1


@pytest.mark.parametrize("case", LANDING_CASES)
def test_a_landing_leaves_the_four_states_as_they_were(case):
    """``device_starved_pct`` sums its own four names: whatever the landing
    books, they, ``gaps_total`` and ``max_gap_ms`` read what they read."""
    last_ready, stamps, probed, _ = LANDING_CASES[case]
    with_landing, without = M.StarvedStats(), M.StarvedStats()
    for ready, st, had in ((last_ready, stamps, probed),
                           (20.0, (19.0, 19.5, 20.01, 0.0, 20.02, 20.03), True)):
        with_landing.note(ready, _stamps(*st), had)
        without.note(ready, _stamps(*st[:4]))
    doc, old = with_landing.summary(), without.summary()
    assert {k: doc[k] for k in OLD_KEYS} == {k: old[k] for k in OLD_KEYS}


def _one_chip_rows(monkeypatch, seen, **cfg):
    """A frontend on the row path at the tests' sizes (one shard on one
    device, streamed on the CPU): every batch has a landing probe. The CPU's
    transfers are over before anybody looks, so ``seen`` True makes every
    handle read not landed once (the collect thread then waits and stamps)."""
    from dvf_tpu.parallel import MeshConfig, make_mesh
    from dvf_tpu.runtime import Engine
    from dvf_tpu.runtime import ingest as ingest_mod
    from dvf_tpu.runtime import lane as lane_mod

    monkeypatch.setattr(ingest_mod, "MIN_STREAM_H2D_MS", 0.0)
    if seen:
        monkeypatch.setattr(lane_mod.InflightBatch, "landed",
                            lambda self: self._landing is None)
    return Engine(get_filter("invert"), mesh=make_mesh(MeshConfig(data=1)))


@pytest.mark.parametrize("seen", [True, False], ids=["seen", "unseen"])
def test_landing_through_a_served_run(monkeypatch, seen):
    """The bucket row carries the new keys; every batch with a probe is seen
    or unseen; the landing lies inside ``device`` and cuts nothing: the
    eight components still sum to the delivered latency, and ``device`` is
    ``t_taken`` -> ``t_ready`` as it was."""
    noted = []
    real = M.StarvedStats.note

    def spy(self, last_ready, st, probed=False):
        real(self, last_ready, st, probed)
        noted.append((st, probed))

    monkeypatch.setattr(M.StarvedStats, "note", spy)
    got, stats, fe = serve(n_sessions=2, n_frames=12, pace_s=0.002,
                           engine=_one_chip_rows(monkeypatch, seen))
    stages, row = stages_of(stats)
    doc = row["starved"]
    assert row["ingest"]["row_path"] and set(doc) == OLD_KEYS | LANDING_KEYS
    assert doc["landed_seen_total"] + doc["landed_unseen_total"] == row["batches"]
    assert len(noted) == row["batches"] and all(probed for _, probed in noted)
    for st, _ in noted:
        if seen:
            assert st.t_taken <= st.t_landed <= st.t_ready
        else:   # ready at t_taken: landed before the thread looked, never timed
            assert st.t_landed == 0.0
    if seen:
        assert doc["landed_seen_total"] == row["batches"]
        assert doc["landing_unseen_ms_total"] == 0.0
        # what the ledger booked lies inside the batches' device intervals
        assert 0.0 < doc["landing_ms_total"] <= sum(
            (st.t_landed - st.t_submit) * 1e3 for st, _ in noted) + 0.01
    else:
        assert doc["landed_unseen_total"] == row["batches"]
        assert doc["landing_ms_total"] == 0.0
    # nothing the landing touches: the stage cells, the frame components and
    # their exact sum
    assert set(stages) - {"components"} >= {"route", "prefetch"}
    assert "landing" not in stages
    assert stages["delivered"] == 24
    total = sum(stages["components"][c]["ms_total"] for c in SERVE_COMPONENTS)
    assert total == pytest.approx(stages["latency_ms_total"], abs=0.01)
    assert stages["components"]["device"]["batch_ms_total"] == pytest.approx(
        sum((st.t_ready - st.t_taken) * 1e3 for st, _ in noted), abs=0.01)
    assert stats["threads"]["collect"]["device_ms"] == pytest.approx(
        stages["components"]["device"]["batch_ms_total"], abs=0.01)


def test_a_monolithic_batch_has_no_probe_and_books_no_landing():
    got, stats, fe = serve(n_sessions=1, n_frames=8)
    _, row = stages_of(stats)
    assert row["ingest"]["mode"] == "monolithic"
    assert not any(row["starved"][k] for k in LANDING_KEYS)


@pytest.mark.parametrize("path,handed", [
    ("rows", True), ("rows", False), ("slab", True), ("monolithic", True)],
    ids=["rows", "rows_not_handed", "slab", "monolithic"])
def test_the_handle_drops_its_probe_once_it_has_answered(monkeypatch, path, handed):
    """``lane.prefetch(result, valid, builder)``: a row batch's landing probe
    (its last device frame) changes hands there, answers once, and is held
    by nobody afterwards; a caller that hands no builder over (the Pipeline,
    the worker) gets a handle that reads landed, and so does every batch of
    the slab and monolithic paths."""
    from dvf_tpu.parallel import MeshConfig, make_mesh
    from dvf_tpu.runtime import Engine, PipelineConfig
    from dvf_tpu.runtime import ingest as ingest_mod
    from dvf_tpu.runtime.lane import DeviceLane

    monkeypatch.setattr(ingest_mod, "MIN_STREAM_H2D_MS", 0.0)
    filt = get_filter("invert")
    engine = Engine(filt, mesh=make_mesh(MeshConfig(data=1)))
    options = PipelineConfig(
        ingest="monolithic" if path == "monolithic" else "streamed")
    lane = DeviceLane(engine, options, inflight=2)
    frames = [frame_u8(0, j) for j in range(4)]
    builder = lane.begin((4, H, W, 3), np.uint8, 0)
    if path != "rows" or not builder.put_rows(frames[:3]):
        assert path != "rows"
        for i, f in enumerate(frames[:3]):
            builder.write_row(i, f)
    result = lane.submit(builder, 3)
    assert (builder.landing is not None) is (path == "rows")
    handle = lane.prefetch(result, 3, builder if handed else None)
    probed = path == "rows" and handed
    assert handle.probed is probed and (handle._landing is not None) is probed
    if probed:      # one frame, and the builder's no more
        assert handle._landing.shape == (H, W, 3) and builder.landing is None
    handle.wait_landed()
    assert handle.landed() and handle._landing is None
    handle.wait()
    out = handle.fetch(0)
    for i in range(3):
        np.testing.assert_array_equal(np.asarray(out[i]), 255 - frames[i])
    lane.release()


@pytest.mark.parametrize("ready", [True, False], ids=["landed", "on_the_link"])
def test_a_probe_ready_when_the_thread_looks_is_never_stamped(ready):
    """The collect thread's three lines, on a handle with a fake probe: one
    that is ready at ``t_taken`` says only "landed before I looked", costs
    no wait, and is let go either way."""
    from dvf_tpu.runtime.lane import InflightBatch

    class _Array:
        def __init__(self):
            self.ready, self.waited = ready, 0

        def is_ready(self):
            return self.ready

        def block_until_ready(self):
            self.waited += 1
            self.ready = True

    arr = _Array()
    handle = InflightBatch.__new__(InflightBatch)
    handle._landing, handle.probed = arr, True
    st = M.BatchStamps(None, 9.0)
    seen = not handle.landed()
    handle.wait_landed()
    if seen:
        st.t_landed = time.time()
    assert seen is (not ready) and (st.t_landed == 0.0) is ready
    assert arr.waited == (0 if ready else 1)
    assert handle._landing is None and handle.landed()


@pytest.mark.parametrize("seen", [True, False], ids=["seen", "unseen"])
def test_the_landing_adds_no_span(monkeypatch, seen):
    """With ``trace`` on the transfer lane carries the put calls and nothing
    of the landing: ``t_landed`` has one reader, the ``starved`` ledger
    (a span that no reader takes is not emitted); ``ingest_overlap`` /
    ``ingest_stage`` went with PR 54."""
    got, stats, fe = serve(n_sessions=2, n_frames=12, trace=True,
                           engine=_one_chip_rows(monkeypatch, seen))
    _, row = stages_of(stats)
    names = collections.Counter(e["name"] for e in fe.tracer.snapshot()["events"])
    assert names["ingest_h2d"] == names["collect:device"] == row["batches"]
    assert not {"ingest_overlap", "ingest_stage", "h2d_flight",
                "collect:landing"} & set(names)
    assert row["starved"]["landed_seen_total"] == (row["batches"] if seen else 0)


@pytest.mark.parametrize("metric", ["device_landing_pct", "landing_seen_pct"])
def test_link_readers_find_the_programs_block(monkeypatch, metric):
    """The two readers of PR 54 (chipbench/layer_metrics) on the program's
    own rows: every key they take is there, and the window's delta is the
    rows' difference."""
    from chipbench import spec

    fe = ServeFrontend(get_filter("invert"), ServeConfig(
        batch_size=4, queue_size=500, slo_ms=60_000.0, telemetry_sample_s=0.0),
        engine=_one_chip_rows(monkeypatch, seen=True))
    reads = []
    with fe:
        sid = fe.open_stream()
        for burst in range(2):
            for j in range(24):
                fe.submit(sid, frame_u8(0, burst * 24 + j))
                if j % 4 == 3:
                    time.sleep(0.01)
            assert len(drain(fe, sid, 24)) == 24
            reads.append({"buckets": [r for r in fe.stats()["buckets"].values()
                                      if r["batches"]]})
    before, after = reads
    logs = []
    value = spec.load_module(f"layer_metrics/{metric}.py").read(
        {"before": before, "after": after, "log": logs.append})
    (b,), (a,) = before["buckets"], after["buckets"]
    wall_ms = (a["stages"]["t"] - b["stages"]["t"]) * 1e3

    def delta(key):
        return a["starved"][key] - b["starved"][key]

    assert delta("landed_seen_total") >= 6
    want = {"device_landing_pct": 100.0 * delta("landing_ms_total") / wall_ms,
            "landing_seen_pct": 100.0}[metric]
    assert value == pytest.approx(want, rel=1e-9, abs=1e-9)
    assert any(line.startswith(f"[layer] {metric}: ") for line in logs)


@pytest.fixture(scope="module")
def two_reads():
    """Bucket rows at two reads with a burst between them, as the
    benchmark's window watch passes them to its readers."""
    fe = ServeFrontend(get_filter("invert"), ServeConfig(
        batch_size=4, queue_size=500, slo_ms=60_000.0, telemetry_sample_s=0.0))
    reads = []
    with fe:
        sid = fe.open_stream()
        for burst in range(2):
            for j in range(24):
                fe.submit(sid, frame_u8(0, burst * 24 + j))
                if j % 4 == 3:
                    time.sleep(0.01)        # the device runs dry between batches
            assert len(drain(fe, sid, 24)) == 24
            reads.append({"buckets": [r for r in fe.stats()["buckets"].values()
                                      if r["batches"]]})
    return reads


@pytest.mark.parametrize("metric", [
    "dispatch_thread_pct", "ingest_stage_ms", "ingest_put_ms", "step_dispatch_ms",
    "prefetch_start_ms", "device_starved_pct"])
def test_benchmark_readers_find_the_programs_blocks(two_reads, metric):
    """The six readers of PR 40 (chipbench/layer_metrics) on the program's
    own rows: every key they take is there, and the window's delta is the
    rows' difference."""
    from chipbench import spec

    before, after = two_reads
    logs = []
    ctx = {"before": before, "after": after, "log": logs.append}
    value = spec.load_module(f"layer_metrics/{metric}.py").read(ctx)
    (b,), (a,) = before["buckets"], after["buckets"]
    wall_ms = (a["stages"]["t"] - b["stages"]["t"]) * 1e3
    n = a["ingest"]["batches"] - b["ingest"]["batches"]
    assert n >= 6 and value is not None

    def delta(block, key):
        return a[block][key] - b[block][key]

    want = {
        "dispatch_thread_pct": 100.0 * sum(
            a["stages"][c]["batch_ms_total"] - b["stages"][c]["batch_ms_total"]
            if c == "prefetch" else
            a["stages"]["components"][c]["batch_ms_total"]
            - b["stages"]["components"][c]["batch_ms_total"]
            for c in ("assemble_h2d", "prefetch")) / wall_ms,
        "ingest_stage_ms": delta("ingest", "stage_ms_total") / n,
        "ingest_put_ms": delta("ingest", "h2d_put_ms_total") / n,
        "step_dispatch_ms": delta("ingest", "step_dispatch_ms_total") / n,
        "prefetch_start_ms": delta("egress", "prefetch_ms_total") / (
            a["stages"]["prefetch"]["batches"] - b["stages"]["prefetch"]["batches"]),
        "device_starved_pct": 100.0 * sum(
            delta("starved", f"{s}_ms_total") for s in M.STARVED_STATES) / wall_ms,
    }[metric]
    assert value == pytest.approx(want, rel=1e-9, abs=1e-9)
    if metric == "device_starved_pct":
        assert value > 0.0 and any("not traced" in line for line in logs)
    if metric == "dispatch_thread_pct":
        assert any("unattributed" in line and "= assemble_h2d" in line for line in logs)


# ---------------------------------------------------------------------------
# Views: lineage and trace read the same stamps
# ---------------------------------------------------------------------------


def test_lineage_is_a_view_of_the_stamps():
    got, stats, _ = serve(n_sessions=2, n_frames=8, lineage=True)
    st, _ = stages_of(stats)
    sums = dict.fromkeys(SERVE_COMPONENTS, 0.0)
    for deliveries in got.values():
        for d in deliveries:
            marks = d.lineage.marks
            assert tuple(name for name, _ in marks) == SERVE_COMPONENTS
            comps = d.lineage.components_ms()
            assert sum(comps.values()) == pytest.approx(d.latency_ms, abs=1e-6)
            for k, v in comps.items():
                sums[k] += v
    # frame for frame the same intervals the always-on counters folded
    for name in SERVE_COMPONENTS:
        assert st["components"][name]["ms_total"] == pytest.approx(
            sums[name], rel=1e-6, abs=1e-3), name
    assert set(stats["attribution"]["components"]) == set(SERVE_COMPONENTS)


@pytest.fixture(scope="module")
def traced_run():
    t_begin = time.time()
    got, stats, fe = serve(n_sessions=2, n_frames=12, trace=True)
    return t_begin, time.time(), stats, fe.tracer.snapshot()


@pytest.mark.parametrize("span,lane,state", [
    ("dispatch:permit_wait", 0, ("dispatch", "permit_wait")),
    ("dispatch:assemble_h2d", 0, ("dispatch", "assemble_h2d")),
    ("collect:device", 2, ("collect", "device")),
    ("collect:d2h", 2, ("collect", "d2h")),
    ("collect:route", 2, ("collect", "route")),
    ("dispatch:prefetch", 0, ("dispatch", "prefetch")),
    ("batch_complete", 1, None),
])
def test_trace_lanes_carry_the_state_spans_on_the_wall_clock(
        traced_run, span, lane, state):
    t_begin, t_end, stats, snap = traced_run
    _, row = stages_of(stats)
    events = [e for e in snap["events"] if e["name"] == span]
    assert len(events) == row["batches"]
    assert {e["pid"] for e in events} == {lane}
    for e in events:        # µs from the tracer's wall-clock epoch
        t0 = snap["start_time"] + e["ts"] / 1e6
        assert t_begin - 0.001 <= t0 <= t_end
        assert t0 + e["dur"] / 1e6 <= t_end + 0.001
    if state is not None:   # the spans ARE the thread's ledger (µs rounding)
        want = stats["threads"][state[0]][f"{state[1]}_ms"]
        assert sum(e["dur"] for e in events) / 1e3 == pytest.approx(
            want, abs=0.002 * len(events) + 0.01)


def test_views_off_build_nothing(monkeypatch):
    """lineage and trace off: no FrameLineage is allocated and no Tracer
    event is built, per frame or per batch — the stamps are the cost."""
    from dvf_tpu.obs import lineage as lineage_mod
    from dvf_tpu.obs import trace as trace_mod

    made = []
    real_init = lineage_mod.FrameLineage.__init__

    def counting_init(self, *a, **kw):
        made.append(1)
        real_init(self, *a, **kw)

    calls = []
    monkeypatch.setattr(lineage_mod.FrameLineage, "__init__", counting_init)
    monkeypatch.setattr(trace_mod.Tracer, "complete",
                        lambda self, *a, **kw: calls.append(a[0]))
    monkeypatch.setattr(trace_mod.Tracer, "instant",
                        lambda self, *a, **kw: calls.append(a[0]))
    got, stats, fe = serve(n_sessions=2, n_frames=8, ledger=False)
    assert made == [] and calls == []
    assert all(d.lineage is None for v in got.values() for d in v)
    assert stages_of(stats)[0]["delivered"] == 16      # ... and still counted
    assert "attribution" not in stats and "trace" not in stats


def test_tick_cost_sample_comes_from_the_stamps(monkeypatch):
    """One batch: the bucket's tick-cost sample is submit returned → fetched,
    i.e. inflight_wait + device + d2h of the counters, to the float."""
    from dvf_tpu.serve import server as server_mod

    seen = []
    real = server_mod._Bucket.observe_tick

    def spy(self, wall_ms, **kw):
        seen.append(wall_ms)
        real(self, wall_ms, **kw)

    monkeypatch.setattr(server_mod._Bucket, "observe_tick", spy)
    got, stats, _ = serve(n_sessions=1, n_frames=4, batch_size=4)
    comp = stages_of(stats)[0]["components"]
    assert len(seen) == 1
    assert seen[0] == pytest.approx(sum(
        comp[c]["batch_ms_total"] for c in ("inflight_wait", "device", "d2h")),
        abs=1e-3)


# ---------------------------------------------------------------------------
# Counters beside the stamps
# ---------------------------------------------------------------------------


def test_xla_compiles_total_rises_when_a_second_signature_compiles():
    from dvf_tpu.obs.ledger import XLA_COMPILES

    fe = ServeFrontend(get_filter("invert"), ServeConfig(
        batch_size=2, queue_size=100, slo_ms=60_000.0, telemetry_sample_s=0.0))
    with fe:
        a = fe.open_stream(op_chain="invert", frame_shape=(H, W, 3))
        for j in range(2):
            fe.submit(a, frame_u8(0, j))
        assert len(drain(fe, a, 2)) == 2
        rows = fe.stats()["buckets"]
        before = {r["xla_compiles_total"] for r in rows.values()}
        assert len(before) == 1 and min(before) >= 1
        engines = sum(r["engine_compile_count"] for r in rows.values())
        # a geometry nothing else in this process compiles
        b = fe.open_stream(op_chain="invert", frame_shape=(H + 3, W + 5, 3))
        for j in range(2):
            fe.submit(b, np.zeros((H + 3, W + 5, 3), np.uint8))
        assert len(drain(fe, b, 2)) == 2
        rows = fe.stats()["buckets"]
        after = {r["xla_compiles_total"] for r in rows.values()}
        assert len(after) == 1                      # the same number on every row
        assert sum(r["engine_compile_count"] for r in rows.values()) == engines + 1
        assert min(after) >= min(before) + 1
        assert all(r["xla_compile_s_total"] > 0 for r in rows.values())
    # the listener goes with the last frontend; the totals stay
    count = XLA_COMPILES.totals()[0]
    assert XLA_COMPILES._refs == 0 or count >= min(after)


def test_flight_dump_records_the_device_trace_epoch(tmp_path, monkeypatch):
    import jax

    from dvf_tpu.obs.export import FlightRecorder

    started = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: started.append(time.time()))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    rec = FlightRecorder(str(tmp_path), stats_fn=lambda: {"ok": 1},
                         min_interval_s=0.0, jax_profile_s=0.05)
    t0 = time.time()
    dump = rec.trigger("test")
    assert dump is not None
    deadline = time.time() + 10.0
    meta = {}
    while "device_trace_epoch" not in meta and time.time() < deadline:
        time.sleep(0.02)
        with open(f"{dump}/meta.json") as f:
            try:
                meta = json.load(f)
            except json.JSONDecodeError:   # mid-rewrite
                meta = {}
    assert meta["reason"] == "test"
    assert t0 <= meta["device_trace_epoch"] <= started[0]
    for t in threading.enumerate():
        if t.name == "dvf-flight-profile":
            t.join(timeout=5.0)


@pytest.mark.parametrize("cls,record,totals", [
    (M.IngestStats, lambda s: s.record_batch(stage_ms=1.0, put_ms=2.0, wait_ms=3.0),
     {"stage_ms_total": 2.0, "h2d_put_ms_total": 4.0, "h2d_wait_ms_total": 6.0}),
    (M.EgressStats, lambda s: s.record_fetch(wait_ms=1.5, copy_ms=0.5),
     {"d2h_wait_ms_total": 3.0, "copy_ms_total": 1.0, "encode_ms_total": 0.0,
      "send_ms_total": 0.0}),
], ids=["ingest", "egress"])
def test_summaries_carry_totals_beside_the_means(cls, record, totals):
    s = cls()
    record(s)
    record(s)
    doc = s.summary()
    for k, v in totals.items():
        assert doc[k] == pytest.approx(v)
        mean_key = k[:-len("_total")]
        if doc["batches"] and mean_key in doc and v:
            assert doc[mean_key] * doc["batches"] == pytest.approx(v)
    assert not hasattr(s, "span_ms_total")      # written every batch, read by nothing
