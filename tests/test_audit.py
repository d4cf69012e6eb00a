"""Audit-plane tests (ISSUE 15, obs.audit).

Pins, in tier-1:

- **Wire integrity, property layer**: the 8-byte blake2b envelope
  detects EVERY single-byte corruption of a framed payload across all
  three wire modes — raw, jpeg, and delta (including a delta frame's
  inner tile payloads) — and a mismatch is attributed to the decode
  hop that caught it (ring queue, worker ingress);
- **Shadow replay**: un-faulted traffic confirms zero corruptions
  (uint8 chain bit-exact, float chain within the pinned tolerance);
  the ``corrupt_device`` chaos site's one-element perturbation is a
  CONFIRMED corruption within K frames, carrying ledger context,
  counted under the ``integrity`` fault kind, tripping a flight dump
  whose ``audit.json`` holds the event — while the non-faulted
  session's deliveries stay bit-identical to a fault-free run;
- **Program-swap equivalence guard**: one run exercising a batch
  resize, a recovery rebuild, and a quality rebind ledgers a
  swap_guard verdict for each — zero unaudited substitutions — and a
  genuinely wrong program is flagged;
- **Cross-replica divergence**: identical replicas match; a rigged
  replica is flagged by majority vote (and quarantined through
  ``retire_replica`` when armed); two-way ties flag nobody;
- **Exports**: stats()/signals() schema conformance, the ``/audit``
  endpoint on serve AND the worker (endpoint parity: the worker's
  exporter serves ``/ledger`` too), flight-dump ``audit.json``
  rendered by trace-view; under live traffic every forced resize gets
  exactly one swap-guard probe and the sampled replays stay clean.
"""

import json
import os
import time
import urllib.request

import numpy as np
import pytest

from dvf_tpu.obs import audit as audit_mod
from dvf_tpu.obs.audit import (
    AuditPlane,
    DivergenceDetector,
    WireAudit,
    WireIntegrityError,
    frame_digest,
    frames_match,
    golden_execute,
    probe_frame,
    stamp_wire,
    verify_wire,
)
from dvf_tpu.obs.registry import walk_export
from dvf_tpu.ops import get_filter
from dvf_tpu.resilience.chaos import FaultPlan
from dvf_tpu.resilience.faults import FaultKind
from dvf_tpu.serve import ServeConfig, ServeFrontend

pytestmark = pytest.mark.audit


def _rng_frame(shape=(32, 32, 3), seed=0):
    return np.random.default_rng(seed).integers(
        0, 255, shape, dtype=np.uint8)


def _drain_session(fe, sid, want, deadline_s=30.0):
    got = []
    deadline = time.time() + deadline_s
    while len(got) < want and time.time() < deadline:
        got += fe.poll(sid)
        time.sleep(0.002)
    return got


# ---------------------------------------------------------------------------
# Wire integrity — unit + property layer
# ---------------------------------------------------------------------------


class TestWireEnvelope:
    def test_roundtrip_and_strictness(self):
        payload = b"the pixels themselves"
        env = stamp_wire(payload)
        assert verify_wire(env) == payload
        # Unstamped: strict raises, tolerant passes through.
        with pytest.raises(WireIntegrityError):
            verify_wire(payload, hop="h", strict=True)
        assert verify_wire(payload, hop="h", strict=False) == payload
        # Truncated envelope.
        with pytest.raises(WireIntegrityError):
            verify_wire(env[:6], hop="h")

    def test_wire_audit_counters(self):
        wa = WireAudit("hoptest")
        env = wa.stamp(b"abc")
        assert wa.verify(env) == b"abc"
        bad = env[:-1] + bytes([env[-1] ^ 0x10])
        with pytest.raises(WireIntegrityError) as ei:
            wa.verify(bad)
        assert ei.value.hop == "hoptest"
        assert ei.value.kind == FaultKind.INTEGRITY
        st = wa.stats()
        assert st["stamped_total"] == 1
        assert st["verified_total"] == 1
        assert st["mismatches_total"] == 1

    def _delta_payloads(self):
        """A keyframe + a genuine delta frame (dirty tiles) on each
        inner wire, via the real codec."""
        from dvf_tpu.transport.codec import DeltaCodec, RawCodec

        f0 = _rng_frame((64, 64, 3), seed=1)
        f1 = f0.copy()
        f1[8:24, 8:24] ^= 0xFF  # one moving block → dirty tiles
        out = []
        codec = DeltaCodec(RawCodec(64, 64), tile=16)
        try:
            out.append(codec.encode(f0))   # keyframe
            out.append(codec.encode(f1))   # delta with tile payloads
        finally:
            codec.close()
        return out

    def test_single_byte_corruption_detected_all_wires(self):
        """THE property: for every wire mode — raw, jpeg, delta
        (keyframe AND a dirty-tile delta frame) — flipping ANY single
        byte of the stamped envelope is detected at verify. The
        envelope's digest covers the complete framed payload, so inner
        tile payloads are covered byte-for-byte; corrupting the header
        region trips the strict framing/digest checks instead."""
        from dvf_tpu.transport.codec import make_codec

        frame = _rng_frame((32, 32, 3), seed=2)
        payloads = {"raw": frame.tobytes()}
        codec = make_codec(quality=90, threads=1)
        try:
            payloads["jpeg"] = codec.encode(frame)
        finally:
            codec.close()
        delta_key, delta_dirty = self._delta_payloads()
        payloads["delta_keyframe"] = delta_key
        payloads["delta_tiles"] = delta_dirty
        for mode, payload in payloads.items():
            env = stamp_wire(payload)
            # Every byte position, one flipped bit each: all caught.
            step = max(1, len(env) // 512)  # ≤ ~512 probes per mode
            positions = list(range(0, len(env), step))
            positions.append(len(env) - 1)
            for pos in positions:
                bad = bytearray(env)
                bad[pos] ^= 0x01
                with pytest.raises(WireIntegrityError):
                    verify_wire(bytes(bad), hop=mode)
            # And the uncorrupted envelope still passes.
            assert verify_wire(env, hop=mode) == payload

    def test_ring_queue_bit_flip_attributed_to_ring_hop(self):
        from dvf_tpu.transport.ring_queue import RingFrameQueue

        frame = _rng_frame()
        staging = np.empty((4, 32, 32, 3), np.uint8)
        plan = FaultPlan(seed=1).add("corrupt_wire", at=(1,))
        q = RingFrameQueue((32, 32, 3), capacity_frames=8, wire="raw",
                           audit_wire=True, chaos=plan)
        try:
            for i in range(3):
                q.put((i, frame, time.time()))
            items = q.pop_up_to(3)
            with pytest.raises(WireIntegrityError) as ei:
                q.decode_into(items, staging)
            assert ei.value.hop == "ring"
            assert q.wire_stats()["audit"]["mismatches_total"] == 1
        finally:
            q.close()

    def test_ring_queue_clean_roundtrip_all_wires(self):
        from dvf_tpu.transport.ring_queue import RingFrameQueue

        frame = _rng_frame((64, 64, 3), seed=3)
        for wire in ("raw", "delta"):
            staging = np.empty((2, 64, 64, 3), np.uint8)
            q = RingFrameQueue((64, 64, 3), capacity_frames=8, wire=wire,
                               audit_wire=True)
            try:
                q.put((0, frame, time.time()))
                q.put((1, frame, time.time()))
                q.decode_into(q.pop_up_to(2), staging)
                if wire == "raw":
                    assert (staging == frame).all()
                assert q.wire_stats()["audit"]["verified_total"] == 2
                assert q.wire_stats()["audit"]["mismatches_total"] == 0
            finally:
                q.close()

    def test_worker_ingress_verify(self):
        """The ZMQ worker's decode hop: a stamped raw payload
        processes; a corrupted one raises the integrity fault from
        ``_process_batch`` (run()'s containment classifies it)."""
        from dvf_tpu.transport.zmq_ingress import TpuZmqWorker

        frame = _rng_frame()
        worker = TpuZmqWorker(get_filter("invert"), batch_size=2,
                              use_jpeg=False, raw_size=32,
                              audit_wire=True,
                              distribute_port=39551,
                              collect_port=39552)
        try:
            good = stamp_wire(frame.tobytes())
            worker._process_batch([(0, good), (1, good)],
                                  str(os.getpid()).encode())
            assert worker.frames_processed == 2
            bad = bytearray(good)
            bad[-1] ^= 0x01
            with pytest.raises(WireIntegrityError) as ei:
                worker._process_batch([(2, bytes(bad))],
                                      str(os.getpid()).encode())
            assert ei.value.hop == "zmq_ingress"
            doc = worker.audit_document()
            assert doc["wire_mismatches_total"] == 1
            assert worker.stats()["audit"]["wire_enabled"] is True
            # Endpoint-parity surface: ledger carries the compile.
            assert worker.ledger.summary()["by_kind"].get("compile") == 1
        finally:
            worker.close()


# ---------------------------------------------------------------------------
# Golden path + plane unit layer
# ---------------------------------------------------------------------------


class TestGoldenAndPlane:
    def test_probe_frame_deterministic(self):
        a = probe_frame((8, 8, 3), np.uint8, tag="sig")
        b = probe_frame((8, 8, 3), np.uint8, tag="sig")
        c = probe_frame((8, 8, 3), np.uint8, tag="other")
        assert np.array_equal(a, b)
        assert not np.array_equal(a, c)

    def test_frames_match_tolerance(self):
        a = np.zeros((4, 4), np.uint8)
        b = a.copy()
        b[0, 0] = 2
        assert frames_match(a, a, 0) == (True, 0.0)
        ok, diff = frames_match(a, b, 1)
        assert not ok and diff == 2.0
        ok, _ = frames_match(a, b, 2)
        assert ok
        assert frames_match(a, np.zeros((2, 2), np.uint8), 99)[0] is False

    def test_golden_matches_engine(self):
        from dvf_tpu.runtime.engine import Engine

        filt = get_filter("invert")
        eng = Engine(filt)
        eng.compile((2, 16, 16, 3), np.uint8)
        frame = _rng_frame((16, 16, 3), seed=4)
        batch = np.zeros((2, 16, 16, 3), np.uint8)
        batch[0] = frame
        served = eng.run_probe(batch)[0]
        golden = golden_execute(filt, frame)
        assert np.array_equal(served, golden)
        # run_probe leaves serving stats untouched.
        assert eng.stats.batches == 0

    def test_sampler_deterministic_and_bounded_queue(self):
        p1 = AuditPlane(sample_every=4, seed=1, queue_depth=2)
        p2 = AuditPlane(sample_every=4, seed=1, queue_depth=2)
        seq1 = [p1.want_sample() for _ in range(16)]
        seq2 = [p2.want_sample() for _ in range(16)]
        assert seq1 == seq2
        assert sum(seq1) == 4
        # Overflow drops oldest, counted — the plane is bounded.
        filt = get_filter("invert")
        f = _rng_frame((8, 8, 3))
        for _ in range(5):  # worker not started: queue only fills
            p1.submit_replay(filt, f, f)
        assert p1.replays_dropped == 3
        assert p1.stats()["replays_sampled_total"] == 5
        # A queued swap guard is an OBLIGATION (zero unaudited
        # substitutions): overflow evicts replays around it, never the
        # guard itself.
        p1._enqueue(("guard", {"marker": True}))
        p1.submit_replay(filt, f, f)
        p1.submit_replay(filt, f, f)
        with p1._cv:
            kinds = [it[0] for it in p1._q]
        assert kinds.count("guard") == 1
        # Each post-guard insert evicted a REPLAY (guard enqueue evicted
        # one, then each new replay displaced the previous): 3 more.
        assert p1.replays_dropped == 6

    def test_swap_guard_flags_wrong_program(self):
        from dvf_tpu.runtime.engine import Engine

        eng = Engine(get_filter("invert"))
        eng.compile((2, 16, 16, 3), np.uint8)
        plane = AuditPlane(sample_every=4)
        # Lie about the chain: the compiled program computes invert,
        # the claimed filter is grayscale — the guard must refuse.
        ev = plane.swap_guard(engine=eng,
                              filt=get_filter("grayscale"),
                              kind="batch_resize", cause="resize",
                              signature="rigged", bucket="rigged")
        assert ev["verdict"] == "mismatch"
        assert plane.swap_guard_mismatches == 1
        assert plane.confirmed_corruptions == 1
        # And the honest filter passes.
        ev = plane.swap_guard(engine=eng, filt=get_filter("invert"),
                              kind="batch_resize", cause="resize",
                              signature="ok", bucket="ok")
        assert ev["verdict"] == "match"
        assert ev["digest_new"] == ev["digest_golden"]


# ---------------------------------------------------------------------------
# Serve: shadow replay + chaos acceptance
# ---------------------------------------------------------------------------


def _serve(audit=True, chaos=None, sample_every=1, filt_name="invert",
           **kw):
    cfg = ServeConfig(batch_size=2, queue_size=64, slo_ms=60_000.0,
                      audit=audit, audit_sample_every=sample_every,
                      chaos=chaos, **kw)
    return ServeFrontend(get_filter(filt_name), cfg).start()


class TestShadowReplay:
    def test_clean_run_zero_corruptions_and_schema(self):
        fe = _serve()
        try:
            sid = fe.open_stream()
            frame = _rng_frame()
            for _ in range(8):
                fe.submit(sid, frame)
            assert len(_drain_session(fe, sid, 8)) == 8
            assert fe.audit.drain(20.0)
            st = fe.stats()["audit"]
            assert st["replays_sampled_total"] >= 8
            assert st["replays_ok_total"] == st["replays_sampled_total"]
            assert st["replay_mismatches_total"] == 0
            assert st["confirmed_corruptions_total"] == 0
            assert st["replay_errors_total"] == 0
            # Export conformance: the audit document and the audit_*
            # signals walk clean through the registry name checks.
            assert walk_export({"audit": st}) == []
            sig = fe.signals()
            assert sig["audit_replays_total"] >= 8
            assert sig["audit_confirmed_corruptions_total"] == 0
            # dvf_audit_* samples ride the registry provider.
            names = {s.name for s in fe.registry.collect()}
            assert "audit_replays_total" in names
        finally:
            fe.stop()

    def test_float_chain_within_tolerance(self):
        fe = _serve(filt_name="gaussian_blur")
        try:
            sid = fe.open_stream()
            frame = _rng_frame()
            for _ in range(6):
                fe.submit(sid, frame)
            assert len(_drain_session(fe, sid, 6)) == 6
            assert fe.audit.drain(30.0)
            st = fe.stats()["audit"]
            assert st["replays_sampled_total"] >= 6
            assert st["replay_mismatches_total"] == 0
            assert st["replay_errors_total"] == 0
        finally:
            fe.stop()

    def test_chaos_device_corruption_acceptance(self, tmp_path):
        """THE acceptance pin: injected device corruption is caught by
        shadow replay within K frames, attributed to the right bucket
        and session, classified ``integrity``, trips a flight dump
        containing ``audit.json`` — and the NON-FAULTED session's
        deliveries stay bit-identical to a fault-free run."""
        rng_a = _rng_frame((32, 32, 3), seed=10)
        rng_b = _rng_frame((32, 32, 3), seed=11)

        def run(chaos, flight_dir=None):
            fe = _serve(chaos=chaos, sample_every=1,
                        flight_dir=flight_dir,
                        flight_min_interval_s=0.0)
            try:
                # A submits first each round → slot order [A, B] →
                # the corrupt_device perturbation (row 0) always lands
                # on A; B is the non-faulted control.
                sa = fe.open_stream(session_id="victim")
                sb = fe.open_stream(session_id="control")
                outs_b = {}
                for i in range(8):
                    fe.submit(sa, rng_a)
                    fe.submit(sb, rng_b)
                    got_a = _drain_session(fe, sa, 1)
                    got_b = _drain_session(fe, sb, 1)
                    assert len(got_a) == 1 and len(got_b) == 1
                    outs_b[got_b[0].index] = got_b[0].frame.copy()
                assert fe.audit.drain(30.0)
                return fe, outs_b
            except BaseException:
                fe.stop()
                raise

        # Fault-free reference run.
        fe, clean_b = run(None)
        st = fe.stats()["audit"]
        assert st["confirmed_corruptions_total"] == 0
        fe.stop()
        # Chaos run: every 2nd collected batch perturbed on row 0.
        plan = FaultPlan(seed=7).add("corrupt_device", every=2)
        fdir = str(tmp_path / "flight")
        fe, chaos_b = run(plan, flight_dir=fdir)
        try:
            st = fe.stats()["audit"]
            assert st["confirmed_corruptions_total"] >= 1
            assert st["replay_mismatches_total"] >= 1
            ev = [e for e in st["events"]
                  if e["kind"] == "shadow_replay"]
            assert ev, "no confirmed-corruption event recorded"
            assert ev[0]["session"] == "victim"
            assert ev[0]["bucket"]  # attributed to its bucket
            assert "ledger_tail" in ev[0]  # preceding ledger context
            # Integrity kind in the PR 4 taxonomy.
            assert fe.stats()["faults"]["by_kind"][
                FaultKind.INTEGRITY] >= 1
            # Non-faulted session: bit-identical to the clean run.
            assert set(chaos_b) == set(clean_b)
            for idx, f in chaos_b.items():
                assert np.array_equal(f, clean_b[idx]), \
                    f"control session frame {idx} corrupted"
            # Flight dump with audit.json (trigger is async).
            deadline = time.time() + 10.0
            dump = None
            while time.time() < deadline and dump is None:
                dumps = sorted(os.listdir(fdir)) if os.path.isdir(
                    fdir) else []
                for d in dumps:
                    p = os.path.join(fdir, d, "audit.json")
                    if os.path.exists(p):
                        dump = os.path.join(fdir, d)
                        break
                time.sleep(0.05)
            assert dump is not None, "no flight dump with audit.json"
            with open(os.path.join(dump, "audit.json")) as f:
                doc = json.load(f)
            assert doc["confirmed_corruptions_total"] >= 1
            assert any(e["kind"] == "shadow_replay"
                       for e in doc["events"])
            # trace-view renders the verdicts beside the ledger events.
            from dvf_tpu.obs.viewer import render_text, summarize_dump

            summary = summarize_dump(dump)
            assert summary["audit"]["confirmed_corruptions_total"] >= 1
            text = render_text(summary)
            assert "audit verdicts" in text
            assert "shadow_replay" in text
        finally:
            fe.stop()


# ---------------------------------------------------------------------------
# Program-swap equivalence guard: zero unaudited substitutions
# ---------------------------------------------------------------------------


class TestSwapGuardCoverage:
    def test_resize_quality_recovery_all_audited(self):
        """One audited run exercising all three live-path recompiles —
        every substitution must have a swap_guard verdict in the
        ledger (the acceptance bar item 1's hot swap inherits)."""
        fe = _serve(sample_every=4, control=True)
        try:
            sid = fe.open_stream()
            frame = _rng_frame((32, 32, 3), seed=5)
            for _ in range(4):
                fe.submit(sid, frame)
            assert len(_drain_session(fe, sid, 4)) == 4
            label = next(iter(fe.stats()["buckets"]))
            # (1) batch resize.
            assert fe.request_batch_size(label, 3, reason="test")
            deadline = time.time() + 30.0
            while time.time() < deadline:
                b = next(iter(fe.stats()["buckets"].values()))
                if b["batch_size"] == 3:
                    break
                time.sleep(0.01)
            # (2) quality rebind (control armed → submit decimates).
            assert fe.request_session_quality(sid, 1, reason="test")
            deadline = time.time() + 30.0
            while fe.quality_rebinds < 1 and time.time() < deadline:
                time.sleep(0.01)
            assert fe.quality_rebinds == 1
            # (3) recovery rebuild (deterministic direct invocation —
            # the chaos-driven path is pinned in test_chaos).
            with fe._lock:
                bucket = fe._buckets[0]
            fe._recover("audit coverage test",
                        kind=FaultKind.COMPUTE, bucket=bucket)
            assert fe.audit.drain(30.0)
            events = fe.ledger.snapshot()
            # The resize substitution now lands as a hot-swap event
            # (kind=swap, cause=resize); rebind and rebuild keep their
            # kinds. Every one still carries a swap_guard verdict.
            subs = [e for e in events if e["kind"] in
                    ("swap", "quality_rebind", "engine_rebuild")]
            guards = [e for e in events if e["kind"] == "swap_guard"]
            kinds = {e["kind"] for e in subs}
            assert kinds == {"swap", "quality_rebind",
                             "engine_rebuild"}
            # ZERO unaudited substitutions: every substitution kind has
            # a guard verdict, and no guard mismatched on this clean
            # run.
            guard_kinds = {e["swap_kind"] for e in guards}
            assert {"batch_resize", "quality_rebind",
                    "engine_rebuild"} <= guard_kinds
            assert len(guards) >= len(subs)
            assert all(e["verdict"] in ("match", "skipped")
                       for e in guards), guards
            st = fe.stats()["audit"]
            assert st["swap_guard_mismatches_total"] == 0
            # Resize guard also proved old-program bit-identity.
            rg = [e for e in guards if e["swap_kind"] == "batch_resize"]
            assert rg and rg[0].get("old_program_match") is True
        finally:
            fe.stop()


# ---------------------------------------------------------------------------
# Cross-replica divergence
# ---------------------------------------------------------------------------


class TestDivergence:
    def test_detector_verdicts(self):
        det = DivergenceDetector()
        # All equal → match.
        ev = det.check({"r0": {"digest": "aa"}, "r1": {"digest": "aa"}},
                       signature="s")
        assert ev["verdict"] == "match"
        # Majority flags the odd one out.
        ev = det.check({"r0": {"digest": "aa"}, "r1": {"digest": "aa"},
                        "r2": {"digest": "bb"}}, signature="s")
        assert ev["verdict"] == "mismatch"
        assert ev["divergent"] == ["r2"]
        # Two-way tie: divergence event, nobody provably wrong.
        ev = det.check({"r0": {"digest": "aa"}, "r1": {"digest": "bb"}},
                       signature="s")
        assert ev["verdict"] == "mismatch" and ev["divergent"] == []
        # < 2 probes → skipped, unreachables recorded.
        ev = det.check({"r0": {"digest": "aa"}, "r1": None},
                       signature="s")
        assert ev["verdict"] == "skipped"
        assert ev["unreachable"] == ["r1"]
        st = det.stats()
        assert st["checks_total"] == 4
        assert st["divergences_total"] == 2
        assert walk_export({"audit": st}) == []

    def test_detector_quarantine_cb(self):
        retired = []
        det = DivergenceDetector(
            quarantine_cb=lambda rid: retired.append(rid) or True)
        det.check({"r0": {"digest": "aa"}, "r1": {"digest": "aa"},
                   "r2": {"digest": "bb"}}, signature="s",
                  quarantine=True)
        assert retired == ["r2"]
        assert det.stats()["quarantined_total"] == 1

    @pytest.mark.fleet
    def test_fleet_divergence_and_quarantine(self):
        """3 local replicas serving one signature: identical probes
        match; a rigged replica is flagged by majority vote and —
        quarantine armed — retired through the scale-in seam."""
        from dvf_tpu.fleet import FleetConfig, FleetFrontend

        cfg = FleetConfig(
            replicas=3, mode="local", audit_quarantine=True,
            serve=ServeConfig(batch_size=2, queue_size=64,
                              slo_ms=60_000.0))
        fl = FleetFrontend(get_filter("invert"), cfg).start()
        try:
            frame = _rng_frame()
            for i in range(6):
                fl.open_stream(frame_shape=(32, 32, 3),
                               frame_dtype="uint8",
                               session_id=f"s{i}")
            for _ in range(3):
                for i in range(6):
                    fl.submit(f"s{i}", frame)
            # Wait until every replica has compiled + reported warm.
            deadline = time.time() + 30.0
            while time.time() < deadline:
                ev = fl.audit_divergence_check()
                if ev["replicas_probed"] == 3:
                    break
                time.sleep(0.2)
            assert ev["verdict"] == "match", ev
            assert ev["replicas_probed"] == 3
            # Rig one replica's probe → flagged + quarantined.
            victim = sorted(fl._replicas)[-1]
            fl._replicas[victim].audit_probe = (
                lambda sig=None: {"signature": sig,
                                  "digest": "deadbeefdeadbeef"})
            ev = fl.audit_divergence_check()
            assert ev["verdict"] == "mismatch"
            assert ev["divergent"] == [victim]
            st = fl.stats()["audit"]
            assert st["divergences_total"] == 1
            assert st["quarantined_total"] == 1
            assert victim not in fl._replicas  # retired for real
            assert fl.signals()["audit_divergences_total"] == 1.0
        finally:
            fl.stop()


# ---------------------------------------------------------------------------
# Endpoints + bench
# ---------------------------------------------------------------------------


def _get_json(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return json.loads(r.read().decode())


class TestEndpointsAndBench:
    def test_serve_audit_endpoint(self):
        from dvf_tpu.obs.export import MetricsExporter

        fe = _serve(sample_every=2)
        ex = None
        try:
            sid = fe.open_stream()
            frame = _rng_frame()
            for _ in range(4):
                fe.submit(sid, frame)
            _drain_session(fe, sid, 4)
            fe.audit.drain(20.0)
            ex = MetricsExporter(fe.registry, port=0,
                                 audit_fn=fe.audit.document).start()
            doc = _get_json(f"{ex.url}/audit")
            assert doc["replays_sampled_total"] >= 1
            assert doc["label"].startswith("serve")
            # dvf_audit_* series on the scrape.
            with urllib.request.urlopen(f"{ex.url}/metrics",
                                        timeout=10) as r:
                text = r.read().decode()
            assert "dvf_audit_replays_total" in text
        finally:
            if ex is not None:
                ex.stop()
            fe.stop()

    def test_worker_endpoint_parity_ledger_and_audit(self):
        """Satellite pin: the worker tier's exporter serves /ledger and
        /audit like serve and fleet do (wired exactly as cli.cmd_worker
        wires it)."""
        from dvf_tpu.obs.export import MetricsExporter
        from dvf_tpu.transport.zmq_ingress import TpuZmqWorker

        worker = TpuZmqWorker(get_filter("invert"), batch_size=2,
                              use_jpeg=False, raw_size=32,
                              audit_wire=True,
                              distribute_port=39553,
                              collect_port=39554)
        ex = None
        try:
            frame = _rng_frame()
            payload = stamp_wire(frame.tobytes())
            worker._process_batch([(0, payload)],
                                  str(os.getpid()).encode())
            ex = MetricsExporter(worker.registry, port=0,
                                 ledger_fn=worker.ledger.document,
                                 audit_fn=worker.audit_document).start()
            led = _get_json(f"{ex.url}/ledger")
            assert led["by_kind"].get("compile") == 1
            aud = _get_json(f"{ex.url}/audit")
            assert aud["wire_enabled"] is True
            assert aud["wire_hops"][0]["verified_total"] == 1
        finally:
            if ex is not None:
                ex.stop()
            worker.close()

    def test_audit_endpoint_404_when_unarmed(self):
        from dvf_tpu.obs.export import MetricsExporter
        from dvf_tpu.obs.registry import MetricsRegistry

        ex = MetricsExporter(MetricsRegistry(), port=0).start()
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get_json(f"{ex.url}/audit")
            assert ei.value.code == 404
        finally:
            ex.stop()

    def test_one_swap_guard_probe_per_forced_resize(self):
        """Two sessions stream while the bucket is resized three times:
        each resize ledgers exactly one swap-guard verdict, none
        mismatches, and the replays sampled across the swaps stay
        clean (counts only: what the run costs is the chip's to say)."""
        fe = _serve(sample_every=4)
        try:
            sids = [fe.open_stream() for _ in range(2)]
            frame = _rng_frame((32, 32, 3), seed=7)
            sent, got = 0, {sid: 0 for sid in sids}

            def step():
                nonlocal sent
                for sid in sids:
                    fe.submit(sid, frame)
                    got[sid] += len(fe.poll(sid))
                sent += 1
                time.sleep(0.005)

            for _ in range(8):
                step()
            for n_resizes, size in enumerate((3, 4, 2), start=1):
                label = next(iter(fe.stats()["buckets"]))
                assert fe.request_batch_size(label, size, reason="test")
                deadline = time.time() + 30.0
                while fe.swaps < n_resizes and time.time() < deadline:
                    step()
                assert fe.swaps == n_resizes
            for sid in sids:
                got[sid] += len(_drain_session(fe, sid, sent - got[sid]))
            assert got == {sid: sent for sid in sids}
            assert fe.audit.drain(30.0)
            guards = [e for e in fe.ledger.snapshot()
                      if e["kind"] == "swap_guard"]
            assert [e["swap_kind"] for e in guards] == ["batch_resize"] * 3
            assert all(e["verdict"] in ("match", "skipped")
                       for e in guards), guards
            st = fe.stats()["audit"]
            assert st["swap_guards_total"] == 3
            assert st["swap_guard_mismatches_total"] == 0
            assert st["replays_sampled_total"] >= 1
            assert st["replay_mismatches_total"] == 0
            assert fe.swap_aborts == 0
        finally:
            fe.stop()

    def test_audit_off_zero_surface(self):
        fe = _serve(audit=False)
        try:
            sid = fe.open_stream()
            fe.submit(sid, _rng_frame())
            _drain_session(fe, sid, 1)
            assert fe.audit is None
            assert "audit" not in fe.stats()
            assert not any(k.startswith("audit_") for k in fe.signals())
        finally:
            fe.stop()
