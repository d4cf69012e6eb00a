"""Multi-stream serving frontend: N tenant sessions, one shared engine.

The acceptance surface of the serve subsystem on CPU: concurrent
synthetic sessions at different frame rates multiplexed through one
shared Engine, with per-session in-order delivery, zero cross-session
frame leakage, SLO-based shedding under oversubscription, admission
control at the session cap, and clean per-session teardown while other
streams keep flowing.
"""

import threading
import time

import numpy as np
import pytest

from dvf_tpu.ops import get_filter
from dvf_tpu.serve import (
    AdmissionError,
    ServeConfig,
    ServeFrontend,
    SessionClosedError,
)

H, W = 16, 24


def tagged_frame(session_no: int, frame_no: int) -> np.ndarray:
    """A frame whose content encodes (session, index): row 0 carries the
    session number, row 1 the frame number — invert maps v → 255 - v, so
    any cross-session or cross-index mixup is detectable per pixel."""
    f = np.full((H, W, 3), 7, np.uint8)
    f[0] = session_no
    f[1] = frame_no % 251
    return f


def drain(frontend, sids, deliveries, deadline_s=30.0, until_closed=False):
    """Poll every session until all streams are retired (or quiescent)."""
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        moved = 0
        for sid in sids:
            got = frontend.poll(sid)
            deliveries.setdefault(sid, []).extend(got)
            moved += len(got)
        stats = frontend.stats()
        if until_closed:
            if stats["open_sessions"] == 0:
                break
        else:
            sess = stats["sessions"]
            done = all(
                sess[sid]["delivered"] + sess[sid]["shed"]
                + sess[sid]["failed"] + sess[sid]["dropped_at_ingress"]
                >= sess[sid]["submitted"]
                and sess[sid]["inflight"] == 0
                for sid in sids)
            if done and moved == 0:
                break
        time.sleep(0.005)
    # Final sweep: anything that landed between the last poll and the
    # quiescence snapshot.
    for sid in sids:
        deliveries.setdefault(sid, []).extend(frontend.poll(sid))


class TestMultiSessionCorrectness:
    def test_four_sessions_ordered_no_leakage(self):
        """≥4 concurrent streams at different rates through one engine:
        every session sees exactly its own frames, in order, exactly
        once, with correct numerics."""
        n_sessions, n_frames = 4, 24
        fe = ServeFrontend(
            get_filter("invert"),
            ServeConfig(batch_size=4, queue_size=1000, slo_ms=60_000.0),
        )
        deliveries: dict = {}
        with fe:
            sids = [fe.open_stream() for _ in range(n_sessions)]

            def drive(k: int) -> None:
                period = 0.001 * (k + 1)  # different per-stream cadence
                for j in range(n_frames):
                    fe.submit(sids[k], tagged_frame(k, j))
                    time.sleep(period)

            threads = [threading.Thread(target=drive, args=(k,))
                       for k in range(n_sessions)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60.0)
                assert not t.is_alive(), "a driver thread never finished"
            drain(fe, sids, deliveries)
            stats = fe.stats()

        for k, sid in enumerate(sids):
            got = deliveries[sid]
            # Exactly once, in order (huge queues + huge SLO: no drops).
            assert [d.index for d in got] == list(range(n_frames)), (
                f"session {k}: indices {[d.index for d in got]}")
            for d in got:
                expected = 255 - tagged_frame(k, d.index)
                np.testing.assert_array_equal(
                    d.frame, expected,
                    err_msg=f"session {k} frame {d.index}: wrong content "
                            f"(cross-session leakage?)")
        assert stats["shed_total"] == 0
        # One shared engine compiled once, batches mixed across sessions.
        assert fe.engine.stats.compile_count == 1
        assert stats["engine_batches"] >= n_sessions * n_frames / 4 / 2

    def test_per_session_index_spaces_independent(self):
        """Both sessions' first frame is index 0 — private index spaces."""
        fe = ServeFrontend(get_filter("invert"),
                           ServeConfig(batch_size=2, slo_ms=60_000.0))
        deliveries: dict = {}
        with fe:
            a, b = fe.open_stream(), fe.open_stream()
            assert fe.submit(a, tagged_frame(0, 0)) == 0
            assert fe.submit(b, tagged_frame(1, 0)) == 0
            assert fe.submit(b, tagged_frame(1, 1)) == 1
            drain(fe, [a, b], deliveries)
        assert [d.index for d in deliveries[a]] == [0]
        assert [d.index for d in deliveries[b]] == [0, 1]


class TestSloShedding:
    def test_sheds_under_oversubscription(self):
        """A throttled engine + tight SLOs: frames that blow their budget
        before reaching a device slot are shed, not processed — and the
        frontend keeps delivering fresh frames throughout."""
        fe = ServeFrontend(
            get_filter("invert"),
            ServeConfig(batch_size=2, max_inflight=1, queue_size=500,
                        slo_ms=60.0),
        )
        orig_submit = fe.engine.submit

        def slow_submit(batch):
            time.sleep(0.03)  # ~15 fps device vs ~hundreds offered
            return orig_submit(batch)

        fe.engine.submit = slow_submit
        deliveries: dict = {}
        with fe:
            sids = [fe.open_stream() for _ in range(4)]
            for j in range(40):
                for k, sid in enumerate(sids):
                    fe.submit(sid, tagged_frame(k, j))
                time.sleep(0.002)
            drain(fe, sids, deliveries, deadline_s=20.0)
            stats = fe.stats()

        assert stats["shed_total"] > 0, "oversubscription never shed"
        total_delivered = sum(len(v) for v in deliveries.values())
        assert total_delivered > 0, "shedding starved delivery entirely"
        for sid in sids:
            s = stats["sessions"][sid]
            assert (s["delivered"] + s["shed"] + s["failed"]
                    + s["dropped_at_ingress"] == s["submitted"]), s
            # Order survives shedding (gaps allowed, regressions not).
            idxs = [d.index for d in deliveries[sid]]
            assert idxs == sorted(idxs)

    def test_no_shedding_when_undersubscribed(self):
        fe = ServeFrontend(get_filter("invert"),
                           ServeConfig(batch_size=4, queue_size=100,
                                       slo_ms=60_000.0))
        deliveries: dict = {}
        with fe:
            sid = fe.open_stream()
            for j in range(12):
                fe.submit(sid, tagged_frame(0, j))
            drain(fe, [sid], deliveries)
            assert fe.stats()["shed_total"] == 0
        assert len(deliveries[sid]) == 12


class TestAdmissionControl:
    def test_session_cap_rejects(self):
        fe = ServeFrontend(get_filter("invert"),
                           ServeConfig(max_sessions=2))
        a = fe.open_stream()
        fe.open_stream()
        with pytest.raises(AdmissionError):
            fe.open_stream()
        assert fe.stats()["admission_rejections"] == 1
        # Closing one readmits (the cap counts OPEN sessions).
        fe.close(a, drain=False)
        fe._finalize_drained()
        fe.open_stream()

    def test_duplicate_session_id_rejected(self):
        from dvf_tpu.serve import ServeError

        fe = ServeFrontend(get_filter("invert"))
        fe.open_stream(session_id="cam0")
        with pytest.raises(ServeError, match="already exists"):
            fe.open_stream(session_id="cam0")

    def test_temporal_filter_admitted_and_isolated_when_multi_tenant(self):
        """Temporal state is one session's: a default (multi-tenant)
        frontend admits flow_warp, two tenants share its batches, and
        each gets what an Engine fed that tenant's frames alone gives."""
        from dvf_tpu.runtime.engine import Engine

        kw = dict(levels=1, win_size=7, n_iters=1, flow_scale=1)
        rng = np.random.default_rng(5)
        streams = [[rng.integers(0, 255, (H, W, 3), np.uint8)
                    for _ in range(n)] for n in (5, 3)]
        want = []
        for frames in streams:
            ref = Engine(get_filter("flow_warp", **kw))
            want.append([np.asarray(ref.submit(fr[None]))[0]
                         for fr in frames])
        fe = ServeFrontend(get_filter("flow_warp", **kw),
                           ServeConfig(batch_size=4, queue_size=16,
                                       slo_ms=60_000))
        with fe:
            sids = [fe.open_stream(), fe.open_stream()]
            for i in range(5):
                for sid, frames in zip(sids, streams):
                    if i < len(frames):
                        fe.submit(sid, frames[i])
            got = {sid: [] for sid in sids}
            deadline = time.time() + 60.0
            while (any(len(got[sid]) < len(fr)
                       for sid, fr in zip(sids, streams))
                   and time.time() < deadline):
                for sid in sids:
                    got[sid] += fe.poll(sid)
                time.sleep(0.01)
            stats = fe.stats()
        for sid, frames, w in zip(sids, streams, want):
            assert [d.index for d in got[sid]] == list(range(len(frames)))
            np.testing.assert_array_equal(got[sid][0].frame, frames[0])
            for d in got[sid]:
                np.testing.assert_array_equal(d.frame, w[d.index])
        assert stats["errors"] == 0
        state = next(iter(stats["buckets"].values()))["state"]
        assert state["rows"] == ServeConfig().max_sessions
        assert state["fresh_rows_total"] == 2
        assert (state["table_rows_total"] + state["chain_rows_total"]
                == sum(len(fr) for fr in streams))

    def test_temporal_filter_served_single_tenant(self):
        """max_sessions=1 is the one-row table: the frontend serves
        flow_warp exactly as an Engine fed the same frames in order
        would, and the row, re-bound at every admission, starts from
        pristine state (a fresh stream's first frame passes through,
        as at engine start)."""
        from dvf_tpu.runtime.engine import Engine

        kw = dict(levels=1, win_size=7, n_iters=1, flow_scale=1)
        rng = np.random.default_rng(3)
        frames = [rng.integers(0, 255, (H, W, 3), np.uint8)
                  for _ in range(6)]
        ref = Engine(get_filter("flow_warp", **kw))
        want = [np.asarray(ref.submit(np.stack(frames[i:i + 2])))
                for i in (0, 2, 4)]
        want = [row for out in want for row in out]
        fe = ServeFrontend(get_filter("flow_warp", **kw),
                           ServeConfig(batch_size=2, max_sessions=1,
                                       queue_size=16, slo_ms=60_000))
        with fe:
            for _ in range(2):   # second tenant: state reset, same output
                sid = fe.open_stream()
                for fr in frames:
                    fe.submit(sid, fr)
                    # flow's output depends only on the session's
                    # previous frame, so how the batcher happens to pair
                    # the frames is not part of the comparison
                got = []
                deadline = time.time() + 60.0
                while len(got) < len(frames) and time.time() < deadline:
                    got += fe.poll(sid)
                    time.sleep(0.01)
                assert [d.index for d in got] == list(range(len(frames)))
                np.testing.assert_array_equal(got[0].frame, frames[0])
                for d in got:
                    assert np.abs(d.frame.astype(int)
                                  - want[d.index].astype(int)).max() <= 1
                fe.close(sid, drain=True)
                deadline = time.time() + 10.0
                while fe.open_count() and time.time() < deadline:
                    time.sleep(0.01)
            stats = fe.stats()
            assert stats["errors"] == 0
            resets = next(iter(stats["buckets"].values()))["state"][
                "resets_total"]
            assert resets == {"admission": 2, "rebuild": 0, "migrate": 0}

    def test_batch_larger_than_reorder_capacity_loses_nothing(self):
        """One tenant filling a batch larger than its reorder buffer
        (batch 64 against the default capacity of 50 — invert_1080p's
        own batch) must still get every frame: the router drains the
        buffer as it fills instead of letting the cap evict undelivered
        frames."""
        n = 96
        fe = ServeFrontend(get_filter("invert"),
                           ServeConfig(batch_size=64, queue_size=n,
                                       out_queue_size=n, slo_ms=60_000))
        with fe:
            sid = fe.open_stream()
            for i in range(n):
                fe.submit(sid, tagged_frame(0, i))
            got = []
            deadline = time.time() + 60.0
            while len(got) < n and time.time() < deadline:
                got += fe.poll(sid)
                time.sleep(0.01)
            row = fe.stats()["sessions"][sid]
        assert [d.index for d in got] == list(range(n))
        assert row["delivered"] == row["submitted"] == n

    def test_constant_state_filter_is_multiplexed(self):
        """A neural filter's state is its weights (Filter.constant_state):
        nothing flows from batch to batch, so two tenants share batches."""
        filt = get_filter("super_resolution", scale=2)
        assert filt.stateful and filt.constant_state and not filt.temporal
        fe = ServeFrontend(filt, ServeConfig(batch_size=2, queue_size=16,
                                             slo_ms=60_000))
        with fe:
            sids = [fe.open_stream(), fe.open_stream()]
            for sid in sids:
                for i in range(3):
                    fe.submit(sid, tagged_frame(sids.index(sid), i))
            got = {sid: [] for sid in sids}
            deadline = time.time() + 60.0
            while (any(len(v) < 3 for v in got.values())
                   and time.time() < deadline):
                for sid in sids:
                    got[sid] += fe.poll(sid)
                time.sleep(0.01)
        for sid in sids:
            assert [d.index for d in got[sid]] == [0, 1, 2]
            assert got[sid][0].frame.shape == (2 * H, 2 * W, 3)

    def test_geometry_mismatch_rejected(self):
        fe = ServeFrontend(get_filter("invert"),
                           ServeConfig(batch_size=2))
        with fe:
            sid = fe.open_stream()
            fe.submit(sid, tagged_frame(0, 0))
            with pytest.raises(ValueError, match="pinned signature"):
                fe.submit(sid, np.zeros((H + 4, W, 3), np.uint8))


class TestSessionTeardown:
    def test_close_one_session_others_keep_flowing(self):
        n_frames = 16
        fe = ServeFrontend(
            get_filter("invert"),
            ServeConfig(batch_size=4, queue_size=1000, slo_ms=60_000.0),
        )
        deliveries: dict = {}
        with fe:
            sids = [fe.open_stream() for _ in range(3)]
            # First half everywhere, then close stream 0 mid-flight.
            for j in range(n_frames // 2):
                for k, sid in enumerate(sids):
                    fe.submit(sid, tagged_frame(k, j))
            fe.close(sids[0], drain=True)
            with pytest.raises(SessionClosedError):
                fe.submit(sids[0], tagged_frame(0, 99))
            for j in range(n_frames // 2, n_frames):
                for k, sid in enumerate(sids[1:], start=1):
                    fe.submit(sid, tagged_frame(k, j))
            drain(fe, sids, deliveries)
            stats = fe.stats()

        # Graceful close: everything queued before close was delivered.
        assert [d.index for d in deliveries[sids[0]]] == list(range(n_frames // 2))
        assert stats["sessions"][sids[0]]["state"] == "closed"
        # Survivors were untouched: full ordered streams.
        for k, sid in enumerate(sids[1:], start=1):
            assert [d.index for d in deliveries[sid]] == list(range(n_frames))
            for d in deliveries[sid]:
                np.testing.assert_array_equal(
                    d.frame, 255 - tagged_frame(k, d.index))

    def test_retired_retention_bound_and_release(self):
        """Closed sessions stay poll-able only up to max_retired (oldest
        evicted), and release() forgets one explicitly."""
        from dvf_tpu.serve import ServeError

        fe = ServeFrontend(get_filter("invert"),
                           ServeConfig(max_sessions=100, max_retired=2))
        ids = []
        for _ in range(4):
            sid = fe.open_stream()
            fe.close(sid, drain=False)
            fe._finalize_drained()
            ids.append(sid)
        assert fe.stats()["retired_sessions"] == 2
        with pytest.raises(KeyError):
            fe.poll(ids[0])         # oldest: evicted by the bound
        assert fe.poll(ids[-1]) == []   # newest: still poll-able
        fe.release(ids[-1])
        with pytest.raises(KeyError):
            fe.poll(ids[-1])
        open_sid = fe.open_stream()
        with pytest.raises(ServeError, match="still open"):
            fe.release(open_sid)

    def test_stop_finalizes_all_sessions(self):
        fe = ServeFrontend(get_filter("invert"),
                           ServeConfig(batch_size=2, slo_ms=60_000.0))
        fe.start()
        sid = fe.open_stream()
        for j in range(6):
            fe.submit(sid, tagged_frame(0, j))
        # Let the engine finish what it can, then stop: the tail in the
        # reorder buffer must be flushed out, not dropped.
        deadline = time.time() + 10.0
        while time.time() < deadline:
            if fe.stats()["sessions"][sid]["inflight"] == 0 and \
                    len(fe._session(sid).ingress) == 0 and \
                    not fe._session(sid).pending:
                break
            time.sleep(0.005)
        fe.stop()
        got = fe.poll(sid)
        assert [d.index for d in got] == list(range(6))
        assert fe.stats()["sessions"][sid]["state"] == "closed"


class TestTenantIsolation:
    def test_raising_sink_contained_per_tenant(self):
        """One tenant's dying sink must not kill the shared frontend:
        its frames are dropped and counted, the other stream flows."""
        class ExplodingSink:
            def emit(self, index, frame, ts):
                raise RuntimeError("boom")

            def close(self):
                pass

        fe = ServeFrontend(get_filter("invert"),
                           ServeConfig(batch_size=2, queue_size=100,
                                       slo_ms=60_000.0))
        deliveries: dict = {}
        with fe:
            bad = fe.open_stream(sink=ExplodingSink())
            good = fe.open_stream()
            for j in range(8):
                fe.submit(bad, tagged_frame(0, j))
                fe.submit(good, tagged_frame(1, j))
            drain(fe, [good], deliveries)
            stats = fe.stats()
        assert [d.index for d in deliveries[good]] == list(range(8))
        assert stats["sessions"][bad]["sink_errors"] == 8
        assert stats["errors"] == 0  # contained at the session, not fatal

    def test_non_monotonic_ts_keeps_order_exact_once(self):
        """Client capture timestamps can jitter backwards; deadlines are
        clamped monotonic so EDF never duplicates or drops a frame."""
        fe = ServeFrontend(get_filter("invert"),
                           ServeConfig(batch_size=2, queue_size=100,
                                       slo_ms=60_000.0))
        deliveries: dict = {}
        with fe:
            sid = fe.open_stream()
            base = time.time()
            jitter = [0.0, -2.5, 1.0, -4.0, 0.5, -1.0]
            for j, dt in enumerate(jitter):
                fe.submit(sid, tagged_frame(0, j), ts=base + dt)
            drain(fe, [sid], deliveries)
        assert [d.index for d in deliveries[sid]] == list(range(len(jitter)))


class TestObservability:
    def test_per_session_and_aggregate_latency_export(self):
        fe = ServeFrontend(get_filter("invert"),
                           ServeConfig(batch_size=2, queue_size=100,
                                       slo_ms=60_000.0))
        deliveries: dict = {}
        with fe:
            sids = [fe.open_stream() for _ in range(2)]
            for j in range(8):
                for k, sid in enumerate(sids):
                    fe.submit(sid, tagged_frame(k, j))
            drain(fe, sids, deliveries)
            stats = fe.stats()
        for sid in sids:
            s = stats["sessions"][sid]
            assert s["count"] == 8
            assert s["p50_ms"] > 0 and s["p99_ms"] >= s["p50_ms"]
        agg = stats["aggregate"]
        assert agg["count"] == 16
        assert agg["p50_ms"] > 0 and agg["p99_ms"] >= agg["p50_ms"]
        # The merged percentiles select actual samples (no interpolation),
        # so they must land inside the union of per-session extremes.
        lo = min(min(stats["sessions"][s]["p50_ms"] for s in sids),
                 min(min(fe._session(s).latency.samples_ms) for s in sids))
        hi = max(max(fe._session(s).latency.samples_ms) for s in sids)
        assert lo <= agg["p50_ms"] <= agg["p99_ms"] <= hi + 1e-9

    def test_merged_latency_stats_weighting(self):
        from dvf_tpu.obs.metrics import LatencyStats

        a, b = LatencyStats(), LatencyStats()
        for v in (1.0, 2.0, 3.0):
            a.record(v / 1e3)
        for v in (100.0,):
            b.record(v / 1e3)
        m = LatencyStats.merged([a, b])
        assert m["count"] == 4
        assert 1.0 <= m["p50_ms"] <= 3.0
        assert m["p99_ms"] == 100.0
        assert LatencyStats.merged([])["count"] == 0


def test_zmq_bridge_reference_framing():
    """A reference-style app (ROUTER fan-out + PULL collect, the exact
    distributor.py framing) drives one frontend session through the
    ZmqStreamBridge: READY-credit requests in, results echoing the APP's
    frame indices out, while the session rides the shared batcher."""
    zmq = pytest.importorskip("zmq")

    from _util import free_port
    from dvf_tpu.serve import ZmqStreamBridge

    p_dist, p_coll = free_port(), free_port()
    ctx = zmq.Context()
    router = ctx.socket(zmq.ROUTER)
    router.bind(f"tcp://127.0.0.1:{p_dist}")
    pull = ctx.socket(zmq.PULL)
    pull.bind(f"tcp://127.0.0.1:{p_coll}")

    fe = ServeFrontend(
        get_filter("invert"),
        ServeConfig(batch_size=2, queue_size=100, slo_ms=60_000.0),
    )
    n, size = 6, 16  # the reference's raw wire is square (inverter.py:34)
    rng = np.random.default_rng(3)
    frames = {100 + j: rng.integers(0, 255, (size, size, 3), dtype=np.uint8)
              for j in range(n)}
    got = {}
    try:
        with fe:
            bridge = ZmqStreamBridge(
                fe, host="127.0.0.1", distribute_port=p_dist,
                collect_port=p_coll, use_jpeg=False, raw_size=size)
            bt = threading.Thread(target=bridge.run,
                                  kwargs={"max_frames": n}, daemon=True)
            bt.start()
            pending = sorted(frames)  # app-side index space starts at 100
            deadline = time.time() + 20.0
            while len(got) < n and time.time() < deadline:
                # App side: answer each READY with one [idx, bytes] frame.
                if router.poll(10):
                    ident, payload = router.recv_multipart()
                    assert payload == b"READY"
                    if pending:
                        idx = pending.pop(0)
                        router.send_multipart(
                            [ident, str(idx).encode(), frames[idx].tobytes()])
                while pull.poll(0):
                    idx_b, _pid, _t0, _t1, result = pull.recv_multipart()
                    got[int(idx_b.decode())] = np.frombuffer(
                        result, np.uint8).reshape(size, size, 3)
            bridge.stop()
            bt.join(timeout=5.0)
            bridge.close()
    finally:
        router.close(0)
        pull.close(0)
        ctx.term()

    assert sorted(got) == sorted(frames), "bridge lost or renumbered frames"
    for idx, frame in got.items():
        np.testing.assert_array_equal(frame, 255 - frames[idx])


def test_cli_serve_multi_demo(capsys):
    """`dvf serve --sessions 4` runs the local multi-stream demo end to
    end: 4 synthetic streams at different rates through one shared
    engine, one JSON line out."""
    import json

    from dvf_tpu.cli import main

    rc = main([
        "serve", "--sessions", "4", "--filter", "invert",
        "--height", str(H), "--width", str(W), "--frames", "12",
        "--rate", "120", "--batch", "4", "--queue-size", "1000",
        "--slo-ms", "60000", "--quiet", "--platform", "cpu",
    ])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert len(out["sessions"]) == 4
    assert len(set(out["rates"].values())) == 4  # genuinely different rates
    for sid, s in out["sessions"].items():
        assert s["submitted"] == 12
        assert s["delivered"] == 12          # big queues + big SLO: lossless
        assert out["polled"][sid] == 12
    assert out["aggregate"]["count"] == 48
    assert out["admission_rejections"] == 0
    assert out["errors"] == 0


class TestAdmissionSignatureCheck:
    """A geometry/dtype declared at open_stream ROUTES the session: a
    declaration matching a live bucket joins it, a new signature admits
    by creating a bucket (its program compiled at admission, never as a
    JIT stall on the serving path), and only past ``max_buckets`` is the
    open refused — with the warm-signature list in the message
    (tests/test_multitenant.py covers the multi-bucket matrix)."""

    def test_mismatched_declaration_routes_to_new_bucket(self):
        fe = ServeFrontend(get_filter("invert"),
                           ServeConfig(batch_size=2, slo_ms=60_000.0))
        with fe:
            a = fe.open_stream(frame_shape=(H, W, 3))
            fe.submit(a, tagged_frame(0, 0))
            before = fe.stats()
            b = fe.open_stream(frame_shape=(H + 8, W, 3))
            c = fe.open_stream(frame_shape=(H, W, 3),
                               frame_dtype=np.float32)
            stats = fe.stats()
            assert stats["admission_rejections"] == \
                before["admission_rejections"]
            assert stats["open_buckets"] == 3
            # Each declared signature got its own compiled program.
            assert stats["pool"]["misses"] == 2
            assert b != c

    def test_bucket_cap_refusal_enumerates_warm_signatures(self):
        """At max_buckets with no idle bucket, the refusal names what
        the pool CAN serve cheaply (satellite: actionable rejections)."""
        fe = ServeFrontend(get_filter("invert"),
                           ServeConfig(batch_size=2, max_buckets=1,
                                       slo_ms=60_000.0))
        with fe:
            a = fe.open_stream(frame_shape=(H, W, 3))
            assert a
            with pytest.raises(AdmissionError,
                               match=r"warm signatures.*invert\|16x24x3"):
                fe.open_stream(frame_shape=(H + 8, W, 3))
            st = fe.stats()
            assert st["admission_rejections"] == 1
            # The refusal happened BEFORE any compile: a full frontend
            # must not pay (and pool) seconds of JIT just to say no.
            assert st["pool"]["misses"] == 0

    def test_matching_declaration_joins_precompiled_engine(self):
        """A caller-built engine arrives already compiled: a matching
        declaration joins its bucket (no second program), a mismatch
        forks a new bucket."""
        from dvf_tpu.runtime.engine import Engine

        filt = get_filter("invert")
        engine = Engine(filt)
        engine.compile((2, H, W, 3), np.uint8)
        fe = ServeFrontend(filt, ServeConfig(batch_size=2), engine=engine)
        with fe:
            sid = fe.open_stream(frame_shape=(H, W, 3))  # match: joins
            assert sid
            assert fe.stats()["open_buckets"] == 1
            assert fe.stats()["pool"]["misses"] == 0
            fe.open_stream(frame_shape=(H * 2, W, 3))    # fork
            assert fe.stats()["open_buckets"] == 2

    def test_declaration_pins_default_bucket(self):
        """First declaration pins the default bucket: a later submit at
        a different geometry on THAT session gets the pinned-signature
        ValueError (per-stream geometry is still fixed)."""
        fe = ServeFrontend(get_filter("invert"),
                           ServeConfig(batch_size=2))
        with fe:
            sid = fe.open_stream(frame_shape=(H, W, 3))
            with pytest.raises(ValueError, match="pinned signature"):
                fe.submit(sid, np.zeros((H + 2, W, 3), np.uint8))


class TestReplicaLifecycleHooks:
    """Satellite: the fleet-facing drain/health hooks on the frontend."""

    def test_begin_drain_refuses_new_sessions(self):
        fe = ServeFrontend(get_filter("invert"),
                           ServeConfig(batch_size=2, slo_ms=60_000.0))
        with fe:
            a = fe.open_stream()
            fe.begin_drain()
            with pytest.raises(AdmissionError, match="draining"):
                fe.open_stream()
            # Existing sessions keep flowing while draining.
            fe.submit(a, tagged_frame(0, 0))
            deadline = time.time() + 20
            got = []
            while not got and time.time() < deadline:
                got = fe.poll(a)
                time.sleep(0.005)
            assert [d.index for d in got] == [0]
            assert fe.stats()["draining"] is True

    def test_drain_serves_tails_and_retires_everything(self):
        fe = ServeFrontend(get_filter("invert"),
                           ServeConfig(batch_size=2, slo_ms=60_000.0))
        with fe:
            sids = [fe.open_stream() for _ in range(3)]
            for j in range(4):
                for sid in sids:
                    fe.submit(sid, tagged_frame(0, j))
            assert fe.drain(timeout=30.0) is True
            assert fe.open_count() == 0
            # drained ≠ dropped: every queued frame was served and is
            # still poll-able off the retired sessions.
            for sid in sids:
                assert [d.index for d in fe.poll(sid)] == list(range(4))
            health = fe.health()
            assert health["ok"] and health["draining"]

    def test_latency_snapshot_matches_merged_aggregate(self):
        from dvf_tpu.obs.metrics import LatencyStats

        fe = ServeFrontend(get_filter("invert"),
                           ServeConfig(batch_size=2, slo_ms=60_000.0))
        with fe:
            sid = fe.open_stream()
            for j in range(6):
                fe.submit(sid, tagged_frame(0, j))
            deadline = time.time() + 20
            n = 0
            while n < 6 and time.time() < deadline:
                n += len(fe.poll(sid))
                time.sleep(0.005)
            snap = fe.latency_snapshot()
            agg = fe.stats()["aggregate"]
        merged = LatencyStats.merge_snapshots([snap])
        assert merged["count"] == agg["count"] == 6
        assert merged["p50_ms"] == pytest.approx(agg["p50_ms"])


def _bucket_row(fe):
    return next(iter(fe.stats()["buckets"].values()))


def _hold(fe):
    return _bucket_row(fe)["hold"]


def _poll_n(fe, sid, n, gate):
    got = []

    def more():
        got.extend(fe.poll(sid))
        return len(got) >= n

    gate.until(more, f"{n} deliveries of {sid}")
    return got


def _whole_tick(fe, gate):
    """Returns once a dispatch tick that began after this call has run to
    its end (its clock read is ``accounted_to``; a second one follows)."""
    def accounted():
        return fe.stats()["threads"]["dispatch"]["accounted_to"]

    for _ in range(2):
        mark = max(time.time(), accounted())
        gate.until(lambda: accounted() > mark, "a dispatch tick")


class TestShortBatchWaitsForTheDevice:
    """serve/batcher.py, "A short batch waits for the device, not in it":
    fewer frames than a batch are bound only once the device's backlog
    has run out. ``device_gate`` (conftest) stands in for the device's
    readiness as the dispatch thread reads it."""

    def test_short_set_held_behind_a_backlog_leaves_with_later_arrivals(
            self, device_gate):
        fe = ServeFrontend(get_filter("invert"),
                           ServeConfig(batch_size=4, slo_ms=60_000.0))
        with fe:
            sid = fe.open_stream()
            device_gate.busy = True
            fe.submit(sid, tagged_frame(0, 0))     # idle device: goes alone
            device_gate.until(
                lambda: _hold(fe)["short_batches_total"] == 1, "frame 0")
            tick_ms = fe._tick_s * 1e3

            def held_ms():
                return fe.stats()["threads"]["dispatch"]["hold_ms"]

            for j in (1, 2):                        # backlog: both wait
                before = held_ms()
                fe.submit(sid, tagged_frame(0, j))
                device_gate.until(lambda: held_ms() > before + 5 * tick_ms,
                                  "five held ticks")
                row = _bucket_row(fe)
                assert row["queue_depth"] == j
                assert row["hold"]["short_batches_total"] == 1
                assert row["hold"]["held_batches_total"] == 0
            device_gate.busy = False                # the backlog ran out
            got = _poll_n(fe, sid, 3, device_gate)
            hold = _hold(fe)
        assert [d.index for d in got] == [0, 1, 2]
        for d in got:
            np.testing.assert_array_equal(d.frame,
                                          255 - tagged_frame(0, d.index))
        # frames 1 and 2 rode together: two batches for three frames
        assert hold["short_batches_total"] == 2
        assert hold["full_batches_total"] == 0
        assert hold["held_batches_total"] == 1
        assert hold["hold_ms_total"] > 10 * tick_ms

    def test_full_set_goes_behind_a_backlog_up_to_the_permits(
            self, device_gate):
        fe = ServeFrontend(get_filter("invert"),
                           ServeConfig(batch_size=2, max_inflight=2,
                                       slo_ms=60_000.0))
        sid = fe.open_stream()
        for j in range(6):          # three full batches wait at the start
            fe.submit(sid, tagged_frame(0, j))
        device_gate.busy = True
        device_gate.collect.clear()                 # nothing comes back
        with fe:
            # two go at depth, the third is bound and waits for a permit
            device_gate.until(
                lambda: (_bucket_row(fe)["inflight_batches"] == 2
                         and _bucket_row(fe)["queue_depth"] == 0),
                "two in flight, the third bound")
            assert _hold(fe)["full_batches_total"] == 2
            time.sleep(0.01)
            assert _hold(fe)["full_batches_total"] == 2    # still blocked
            device_gate.collect.set()
            got = _poll_n(fe, sid, 6, device_gate)
            row = _bucket_row(fe)
            threads = fe.stats()["threads"]["dispatch"]
        assert [d.index for d in got] == list(range(6))
        assert row["hold"] == {"short_batches_total": 0,
                               "full_batches_total": 3,
                               "held_batches_total": 0, "hold_ms_total": 0.0}
        assert threads["hold_ms"] == 0.0
        permit = row["stages"]["components"]["permit_wait"]
        assert permit["batches"] == 3 and permit["max_ms"] >= 10.0

    def test_idle_device_never_holds_a_frame(self, device_gate):
        fe = ServeFrontend(get_filter("invert"),
                           ServeConfig(batch_size=4, slo_ms=60_000.0))
        with fe:
            sid = fe.open_stream()
            for j in range(3):      # each alone, the one before it back
                fe.submit(sid, tagged_frame(0, j))
                _poll_n(fe, sid, 1, device_gate)
            hold = _hold(fe)
            threads = fe.stats()["threads"]["dispatch"]
        # bound by the first tick that met it: no tick was ever a hold
        assert hold == {"short_batches_total": 3, "full_batches_total": 0,
                        "held_batches_total": 0, "hold_ms_total": 0.0}
        assert threads["hold_ms"] == 0.0

    def test_hold_ends_one_staging_before_the_backlog_should(
            self, device_gate):
        """With a measured device time to go by, a held set is bound when
        the backlog is due to run out, not when the handle says it has:
        here the handle never does."""
        fe = ServeFrontend(get_filter("invert"),
                           ServeConfig(batch_size=4, slo_ms=60_000.0))
        with fe:
            sid = fe.open_stream()
            device_gate.device_ms = 60.0    # what every batch "took"
            fe.submit(sid, tagged_frame(0, 0))      # leaves the estimate
            _poll_n(fe, sid, 1, device_gate)
            _whole_tick(fe, device_gate)            # ... and was seen ready
            device_gate.busy = True                 # for good
            t_first = time.time()
            for j in (1, 2):        # onto an idle device; then behind it
                fe.submit(sid, tagged_frame(0, j))
                assert [d.index for d in
                        _poll_n(fe, sid, 1, device_gate)] == [j]
            waited_ms = (time.time() - t_first) * 1e3
            hold = _hold(fe)
        assert hold["short_batches_total"] == 3
        assert hold["held_batches_total"] == 1      # frame 2
        # one device time from frame 1's submit, less a staging
        assert 30.0 <= hold["hold_ms_total"] <= waited_ms

    def test_held_frame_past_its_deadline_is_shed_not_dispatched(
            self, device_gate):
        fe = ServeFrontend(get_filter("invert"),
                           ServeConfig(batch_size=4, slo_ms=60_000.0))
        with fe:
            a = fe.open_stream()
            b = fe.open_stream(slo_ms=30.0)
            device_gate.busy = True
            fe.submit(a, tagged_frame(0, 0))
            device_gate.until(
                lambda: _hold(fe)["short_batches_total"] == 1, "frame a0")
            fe.submit(b, tagged_frame(1, 0))        # held, then too late
            device_gate.until(
                lambda: fe.stats()["sessions"][b]["shed"] == 1, "the shed")
            device_gate.busy = False
            assert [d.index for d in _poll_n(fe, a, 1, device_gate)] == [0]
            fe.submit(b, tagged_frame(1, 1))        # the service goes on
            assert [d.index for d in _poll_n(fe, b, 1, device_gate)] == [1]
            stats = fe.stats()
        srow = stats["sessions"][b]
        assert (srow["submitted"], srow["delivered"], srow["shed"]) == (2, 1, 1)
        hold = next(iter(stats["buckets"].values()))["hold"]
        assert hold["short_batches_total"] == 2     # a0, b1: b0 never ran
        assert hold["held_batches_total"] == 0
        assert hold["hold_ms_total"] > 0.0
        assert stats["threads"]["dispatch"]["hold_ms"] == pytest.approx(
            hold["hold_ms_total"], abs=0.01)

    def test_order_and_accounting_hold_across_holds(self, device_gate):
        """Three paced sessions while the device's readiness flaps: every
        frame comes back once, in order, bit-exact, whichever batch it
        was held for."""
        n_sessions, n_frames = 3, 30
        fe = ServeFrontend(get_filter("invert"),
                           ServeConfig(batch_size=4, slo_ms=60_000.0,
                                       queue_size=64))   # the first batch
        #   compiles on the dispatch thread: ingress holds the rest
        stop = threading.Event()

        def flap():
            while not stop.is_set():
                device_gate.busy = not device_gate.busy
                time.sleep(0.004)

        flapper = threading.Thread(target=flap)
        deliveries = {}
        with fe:
            sids = [fe.open_stream() for _ in range(n_sessions)]
            for k, sid in enumerate(sids):      # round 0 compiles
                fe.submit(sid, tagged_frame(k, 0))
            device_gate.until(
                lambda: _bucket_row(fe)["routed_frames_total"] == n_sessions,
                "the warm round")
            flapper.start()
            try:
                for j in range(1, n_frames):
                    for k, sid in enumerate(sids):
                        fe.submit(sid, tagged_frame(k, j))
                    time.sleep(0.003)
            finally:
                stop.set()
                flapper.join(timeout=10.0)
            assert not flapper.is_alive()
            device_gate.busy = False
            drain(fe, sids, deliveries)
            stats = fe.stats()
        for k, sid in enumerate(sids):
            assert [d.index for d in deliveries[sid]] == list(range(n_frames))
            for d in deliveries[sid]:
                np.testing.assert_array_equal(d.frame,
                                              255 - tagged_frame(k, d.index))
            srow = stats["sessions"][sid]
            assert srow["submitted"] == srow["delivered"] == n_frames
            assert srow["shed"] == srow["failed"] == srow["inflight"] == 0
        row = next(iter(stats["buckets"].values()))
        hold = row["hold"]
        assert (hold["short_batches_total"] + hold["full_batches_total"]
                == row["batches"])
        assert hold["held_batches_total"] >= 1
        assert row["routed_frames_total"] == n_sessions * n_frames

    def test_handle_that_raises_holds_nothing_up(self, device_gate):
        fe = ServeFrontend(get_filter("invert"),
                           ServeConfig(batch_size=4, slo_ms=60_000.0))
        with fe:
            sid = fe.open_stream()
            device_gate.busy = device_gate.fail = True
            for j in range(4):
                fe.submit(sid, tagged_frame(0, j))
                device_gate.until(
                    lambda: _bucket_row(fe)["queue_depth"] == 0, "dispatch")
            got = _poll_n(fe, sid, 4, device_gate)
            hold = _hold(fe)
        assert [d.index for d in got] == [0, 1, 2, 3]
        assert hold["held_batches_total"] == 0
        assert hold["hold_ms_total"] == 0.0

    def test_supervised_recovery_releases_a_hold(self, device_gate):
        """The window is shed with a frame held behind it: the handle the
        hold was reading belongs to the old generation, so the frame
        goes out on the rebuilt engine though that handle never reads
        ready."""
        from dvf_tpu.resilience import FaultPlan

        chaos = FaultPlan().add("freeze", at=(3,), delay_s=1.5)
        fe = ServeFrontend(
            get_filter("invert"),
            ServeConfig(batch_size=4, queue_size=1000, slo_ms=60_000.0,
                        stall_timeout_s=0.35, chaos=chaos))
        with fe:
            sid = fe.open_stream()
            s = fe._session(sid)
            device_gate.busy = True                 # for good
            i = 0
            deadline = time.time() + 20.0
            while fe.recoveries < 1:
                assert time.time() < deadline, "watchdog never tripped"
                fe.submit(sid, tagged_frame(0, i))
                i += 1
                time.sleep(0.01)
            device_gate.until(lambda: s.delivered >= 1,
                              "a held frame's delivery after the recovery")
            device_gate.busy = False
            device_gate.until(
                lambda: s.delivered + s.failed + s.shed == i, "the rest")
            got = fe.poll(sid)
            stats = fe.stats()
        idx = [d.index for d in got]
        assert idx == sorted(set(idx)) and len(idx) == s.delivered
        assert stats["recoveries"] >= 1 and s.failed >= 1
        assert fe._error is None


# ---------------------------------------------------------------------------
# The router's memory invariant: a kept delivery keeps one frame's bytes
# ---------------------------------------------------------------------------


def _fetched(path, frames, monkeypatch):
    """(what ``fetch`` returns for one batch of ``frames``, the handle)
    on one of the fetcher's four ends."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from dvf_tpu.parallel import MeshConfig, make_mesh
    from dvf_tpu.runtime import Engine
    from dvf_tpu.runtime import egress as egress_mod
    from dvf_tpu.runtime.egress import ShardedBatchFetcher

    monkeypatch.setattr(egress_mod, "STREAM_ON_CPU", True)
    monkeypatch.setattr(egress_mod, "MIN_STREAM_D2H_MS", 0.0)
    dev = jax.devices()[0]
    one = SingleDeviceSharding(dev)
    if path == "slab":  # a result sharded over two devices
        eng = Engine(get_filter("invert"), mesh=make_mesh(MeshConfig(data=2)))
        eng.ensure_compiled(frames.shape, np.uint8)
        f = ShardedBatchFetcher(eng.out_shape, eng.out_dtype,
                                eng.output_sharding, slots=2)
        result = eng.submit(255 - frames)
    else:
        shape = {"fallback": (4, 8, 8, 3)}.get(path, frames.shape)
        f = ShardedBatchFetcher(
            shape, np.uint8, one, slots=2,
            mode="monolithic" if path == "monolithic" else "streamed")
        result = jax.device_put(frames, dev)
    handle = f.prefetch(result, len(frames))
    return f, f.fetch(handle, 0)


class TestRouterKeepsOneFrameAlive:

    @pytest.mark.parametrize("path,handed", [
        ("rows", True),         # one device, packed: a buffer a row
        ("slab", False),        # sharded result: the pooled slab
        ("monolithic", False),  # the CPU backend, a degraded lane
        ("fallback", False),    # a batch of another geometry
    ])
    def test_rows_are_handed_on_or_copied(self, path, handed, monkeypatch):
        from dvf_tpu.runtime.egress import LandedRows
        from dvf_tpu.serve.batcher import ContinuousBatcher
        from dvf_tpu.serve.router import ResultRouter
        from dvf_tpu.serve.session import SessionConfig, StreamSession

        rng = np.random.default_rng(5)
        frames = rng.integers(0, 256, (4, H, W, 3), dtype=np.uint8)
        f, out = _fetched(path, frames, monkeypatch)
        assert isinstance(out, LandedRows) == handed
        assert f.owns(out) == (path == "slab")
        sessions = [StreamSession(n, SessionConfig(queue_size=8,
                                                   slo_ms=60_000.0))
                    for n in ("a", "b")]
        for i in range(4):
            sessions[i % 2].submit(frames[i])
        plan = ContinuousBatcher(4).plan(sessions, time.time())
        assert plan.valid == 4
        router = ResultRouter()
        assert router.route(plan, out) == 4
        stats = router.stats()
        assert stats["rows_handed_total"] == (4 if handed else 0)
        assert stats["rows_copied_total"] == (0 if handed else 4)
        got = {s.id: s.poll() for s in sessions}
        for row, slot in enumerate(plan.slots):
            d = got[slot.session.id][slot.index]
            np.testing.assert_array_equal(d.frame, frames[row])
            if handed:
                assert d.frame is out[row]  # the landed buffer itself
                assert not d.frame.flags.writeable
            else:
                assert not np.shares_memory(d.frame, out)
                assert d.frame.flags.owndata and d.frame.flags.writeable

    def test_a_kept_delivery_keeps_one_row_not_the_batch(self, monkeypatch):
        """One delivery left in a session's out queue after the batch,
        its handle and the other deliveries are gone keeps its own
        row's buffer alive and no other row's."""
        import gc
        import weakref

        from dvf_tpu.serve.batcher import ContinuousBatcher
        from dvf_tpu.serve.router import ResultRouter
        from dvf_tpu.serve.session import SessionConfig, StreamSession

        rng = np.random.default_rng(6)
        frames = rng.integers(0, 256, (4, H, W, 3), dtype=np.uint8)
        f, out = _fetched("rows", frames, monkeypatch)
        landed = [weakref.ref(r.base if r.base is not None else r)
                  for r in out]
        slow, quick = (StreamSession(n, SessionConfig(
            queue_size=8, slo_ms=60_000.0, replay_window=0))  # a replay
            #   ring would keep the polled frames too, each its one row
            for n in ("slow", "quick"))
        slow.submit(frames[0])
        for i in (1, 2, 3):
            quick.submit(frames[i])
        plan = ContinuousBatcher(4).plan([slow, quick], time.time())
        kept_row = [s.session for s in plan.slots].index(slow)
        ResultRouter().route(plan, out)
        assert len(quick.poll()) == 3  # polled and dropped
        del out, plan, f
        gc.collect()
        assert [r() is not None for r in landed] == [
            row == kept_row for row in range(4)]
        (d,) = slow.poll()  # the slow client's frame is still whole
        np.testing.assert_array_equal(d.frame, frames[0])


# ---------------------------------------------------------------------------
# Slab or rows (PR 47): a submitted frame goes up from the client's array
# ---------------------------------------------------------------------------


def _one_chip_frontend(monkeypatch, rows=True, **config):
    """A frontend whose batch is one shard on one device, streamed on the
    CPU at the tests' sizes; ``rows`` False: no row path, as on a lane
    whose batch spans devices (the slabs alone)."""
    from dvf_tpu.parallel import MeshConfig, make_mesh
    from dvf_tpu.runtime import Engine
    from dvf_tpu.runtime import ingest as ingest_mod

    monkeypatch.setattr(ingest_mod, "MIN_STREAM_H2D_MS", 0.0)
    if not rows:
        monkeypatch.setattr(ingest_mod.ShardedBatchAssembler, "_plan_rows",
                            lambda self: None)
    filt = get_filter("invert")
    engine = Engine(filt, mesh=make_mesh(MeshConfig(data=1)))
    config.setdefault("slo_ms", 60_000.0)
    return ServeFrontend(filt, ServeConfig(**config), engine=engine)


def _ingest(fe):
    return _bucket_row(fe)["ingest"]


def _deliveries(fe, sid, n, deadline_s=30.0):
    got, deadline = [], time.time() + deadline_s
    while len(got) < n:
        assert time.time() < deadline, f"{len(got)} of {n} delivered"
        got.extend(fe.poll(sid))
        time.sleep(0.002)
    return got


class TestSlabOrRows:
    """runtime/ingest.py, the row path, as the serve loop drives it: which
    batches go up as rows, and that nothing a client can observe moves."""

    @pytest.mark.parametrize("rows", [True, False], ids=["rows", "slab"])
    def test_both_paths_deliver_identical_frames(self, monkeypatch, rows):
        fe = _one_chip_frontend(monkeypatch, rows, batch_size=4,
                                queue_size=64, trace=True)
        n = 22                                  # five full batches, one of 2
        frames = {s: [tagged_frame(s, j) for j in range(n)] for s in (0, 1)}
        keep = {s: [f.copy() for f in fs] for s, fs in frames.items()}
        for fs in frames.values():
            for f in fs:
                f.flags.writeable = False       # nobody may write to a
                #                                 client's array
        deliveries = {}
        with fe:
            sids = [fe.open_stream() for _ in frames]
            for j in range(n):
                for s, sid in enumerate(sids):
                    fe.submit(sid, frames[s][j])
            drain(fe, sids, deliveries)
            ingest = _ingest(fe)
            spans = [e for e in fe.tracer._events
                     if e["name"] == "dispatch:assemble_h2d"]
        for s, sid in enumerate(sids):
            got = deliveries[sid]
            assert [d.index for d in got] == list(range(n))
            for d in got:
                np.testing.assert_array_equal(d.frame,
                                              255 - keep[s][d.index])
            for f, k in zip(frames[s], keep[s]):
                np.testing.assert_array_equal(f, k)
        assert ingest["mode"] == "streamed"
        assert ingest["row_path"] is rows
        direct, staged = ((2 * n, 0) if rows else (0, 2 * n))
        assert ingest["rows_direct_total"] == direct
        assert ingest["rows_staged_total"] == staged
        assert ingest["direct_batches"] + ingest["staged_batches"] \
            == ingest["batches"]
        assert (ingest["stage_ms_total"] == 0.0) is rows   # no host copy
        assert spans and all(e["args"]["direct"] is rows for e in spans)

    @pytest.mark.parametrize("case,direct", [
        ("contiguous", True),
        ("readonly", True),         # read, never written to
        ("strided", False),         # the door's downscale view
        ("fortran_order", False),
    ])
    def test_one_ineligible_frame_sends_its_batch_through_the_slab(
            self, monkeypatch, case, direct):
        fe = _one_chip_frontend(monkeypatch, batch_size=2)
        frames = [tagged_frame(0, j) for j in range(2)]
        if case == "readonly":
            frames[1].flags.writeable = False
        elif case == "strided":
            big = np.repeat(np.repeat(frames[1], 2, axis=0), 2, axis=1)
            frames[1] = big[::2, ::2]
        elif case == "fortran_order":
            frames[1] = np.asfortranarray(frames[1])
        assert frames[1].flags.c_contiguous is direct
        with fe:
            sid = fe.open_stream()
            for f in frames:
                fe.submit(sid, f)
            got = _deliveries(fe, sid, 2)
            ingest = _ingest(fe)
        for d in got:
            np.testing.assert_array_equal(d.frame,
                                          255 - tagged_frame(0, d.index))
        assert ingest["row_path"] is True
        assert ingest["rows_direct_total"] == (2 if direct else 0)
        assert ingest["rows_staged_total"] == (0 if direct else 2)
        assert ingest["batches"] == 1

    def test_a_short_batchs_padding_never_crosses_the_link(self,
                                                           monkeypatch):
        fe = _one_chip_frontend(monkeypatch, batch_size=4)
        with fe:
            sid = fe.open_stream()
            fe.submit(sid, tagged_frame(0, 0))
            (d,) = _deliveries(fe, sid, 1)
            ingest = _ingest(fe)
        np.testing.assert_array_equal(d.frame, 255 - tagged_frame(0, 0))
        assert (ingest["rows_direct_total"], ingest["batches"]) == (1, 1)
        assert ingest["bytes_total"] == tagged_frame(0, 0).nbytes

    def test_h2d_faults_degrade_rows_to_the_slab_and_the_run_goes_on(
            self, monkeypatch):
        from dvf_tpu.resilience import FaultPlan

        chaos = FaultPlan().add("h2d", every=1, count=3)
        fe = _one_chip_frontend(monkeypatch, batch_size=2,
                                queue_size=64, chaos=chaos, fault_budget=2)
        with fe:
            sid = fe.open_stream()
            s = fe._sessions[sid]
            for j in range(16):
                fe.submit(sid, tagged_frame(0, j))
                time.sleep(0.005)
            deadline = time.time() + 30.0
            while s.delivered + s.failed + s.shed < 16:
                assert time.time() < deadline, "the run did not go on"
                time.sleep(0.005)
            got = fe.poll(sid)
            stats = fe.stats()
            ingest = _ingest(fe)
        assert stats["faults"]["by_kind"] == {"h2d": 3}
        assert s.failed >= 1 and s.delivered >= 8
        for d in got:
            np.testing.assert_array_equal(d.frame,
                                          255 - tagged_frame(0, d.index))
        # the third fault overflowed the budget: the lane is monolithic
        # now, which has no row path; frames go through the slab
        assert ingest["mode"] == "monolithic"
        assert ingest["fallback_reason"] == "h2d_fault_budget"
        assert ingest["row_path"] is False
        assert ingest["rows_direct_total"] == 0
        assert ingest["staged_batches"] == ingest["batches"] > 0
        assert ingest["rows_staged_total"] == s.delivered
        assert fe._error is None

    def test_a_delivered_frames_input_is_kept_by_nobody(self, monkeypatch):
        """``slot.frame`` is dropped at the staging and the device frames
        go with the join: once the result is delivered nothing of the
        service holds the client's array."""
        import gc
        import weakref

        fe = _one_chip_frontend(monkeypatch, batch_size=2,
                                replay_window=0)
        with fe:
            sid = fe.open_stream()
            frames = [tagged_frame(0, j) for j in range(4)]
            refs = [weakref.ref(f) for f in frames]
            for f in frames:
                fe.submit(sid, f)
            del f, frames
            got = []
            deadline = time.time() + 30.0
            while len(got) < 4:
                assert time.time() < deadline
                got.extend(fe.poll(sid))
                time.sleep(0.002)
            assert _ingest(fe)["rows_direct_total"] == 4
            # the dispatch loop's own locals go with its next batch
            fe.submit(sid, tagged_frame(0, 4))
            while not fe.poll(sid):
                assert time.time() < deadline
                time.sleep(0.002)
            gc.collect()
            assert [r() for r in refs] == [None] * 4
