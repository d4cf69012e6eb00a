"""Fleet tier: N engine replicas behind one front door.

The acceptance surface of ``dvf_tpu/fleet`` on CPU: session affinity
(all of a session's frames on one replica, indices monotone through the
fleet index space), spillover admission, deterministic replica-loss
injection with drain → migrate → restart, kill-one-process-replica with
the survivor's sessions bit-identical to a fault-free run, and the
capacity-gated 2-replica scaling bar.

Local-mode tests run in-process (device-slice replicas — fast);
process-mode tests spawn real worker subprocesses (one jax runtime
each, bounded startup timeouts) — replica loss there is a real SIGKILL.
"""

import os
import time

import numpy as np
import pytest

from dvf_tpu.fleet import (
    FleetConfig,
    FleetFrontend,
    HEALTHY,
)
from dvf_tpu.ops import get_filter
from dvf_tpu.serve import AdmissionError, ServeConfig

pytestmark = pytest.mark.fleet

H, W = 16, 24


def tagged_frame(session_no: int, frame_no: int) -> np.ndarray:
    f = np.full((H, W, 3), 7, np.uint8)
    f[0] = session_no
    f[1] = frame_no % 251
    return f


def serve_cfg(**kw) -> ServeConfig:
    base = dict(batch_size=4, queue_size=1000, out_queue_size=1000,
                slo_ms=60_000.0)
    base.update(kw)
    return ServeConfig(**base)


def drain_fleet(fleet, sids, deliveries, want, deadline_s=60.0,
                grace_s=3.0):
    """Poll every session until each has ``want`` deliveries (or no
    movement for ``grace_s`` — sized generously where a fresh replica
    may still be compiling)."""
    deadline = time.time() + deadline_s
    last_move = time.time()
    while time.time() < deadline and time.time() - last_move < grace_s:
        moved = 0
        for sid in sids:
            got = fleet.poll(sid)
            deliveries.setdefault(sid, []).extend(got)
            moved += len(got)
        if moved:
            last_move = time.time()
        if all(len(deliveries.get(sid, [])) >= want for sid in sids):
            return
        time.sleep(0.005)


class TestLocalFleet:
    def test_affinity_ordered_no_leakage(self):
        """4 sessions over 2 replicas: sessions spread, every delivery
        comes from the session's own replica (engine frame counts
        reconcile per replica), indices exactly 0..N-1 in order, content
        bit-exact."""
        n_sessions, n_frames = 4, 16
        fleet = FleetFrontend(
            get_filter("invert"),
            FleetConfig(replicas=2, mode="local", serve=serve_cfg()))
        deliveries: dict = {}
        with fleet:
            sids = [fleet.open_stream() for _ in range(n_sessions)]
            by_replica: dict = {}
            st = fleet.stats()
            for sid in sids:
                by_replica.setdefault(
                    st["sessions"][sid]["replica"], []).append(sid)
            # Least-loaded placement spreads 4 sessions 2/2.
            assert sorted(len(v) for v in by_replica.values()) == [2, 2]
            for j in range(n_frames):
                for k, sid in enumerate(sids):
                    fleet.submit(sid, tagged_frame(k, j))
            drain_fleet(fleet, sids, deliveries, n_frames)
            st = fleet.stats()

        for k, sid in enumerate(sids):
            got = deliveries[sid]
            assert [d.index for d in got] == list(range(n_frames)), (
                f"session {sid}: {[d.index for d in got]}")
            for d in got:
                np.testing.assert_array_equal(
                    d.frame, 255 - tagged_frame(k, d.index),
                    err_msg=f"session {sid} frame {d.index}: wrong "
                            f"content (cross-replica leakage?)")
        # Affinity: each replica processed exactly its own sessions'
        # frames (engine frame counters include padding, so >=).
        for rid, row in st["replicas"].items():
            expected = len(by_replica.get(rid, [])) * n_frames
            assert row["engine_frames"] >= expected
        assert st["order_violations"] == 0
        assert st["replica_losses"] == 0
        assert st["faults"]["by_kind"] == {}

    def test_spillover_and_full_fleet_rejection(self):
        """A replica-side admission refusal spills the open to the next
        replica; when every replica refuses, the fleet rejects."""
        fleet = FleetFrontend(
            get_filter("invert"),
            FleetConfig(replicas=2, mode="local",
                        serve=serve_cfg(max_sessions=1)))
        with fleet:
            a = fleet.open_stream()
            b = fleet.open_stream()
            st = fleet.stats()
            assert (st["sessions"][a]["replica"]
                    != st["sessions"][b]["replica"])
            # Both gates full: the fleet-level rejection.
            with pytest.raises(AdmissionError):
                fleet.open_stream()
            assert fleet.stats()["rejections"] == 1
            # Force a spillover: free b's replica, then skew the load
            # heuristic so the still-full replica sorts first — its own
            # gate refuses and the open must land on the freed one
            # (correctness comes from the replica gate; the router's
            # ordering is only a heuristic).
            ra = fleet._sessions[a].replica_id
            rb = fleet._sessions[b].replica_id
            fleet.close(b, drain=True)
            deadline = time.time() + 20
            while (fleet._replicas[rb].frontend.open_count() > 0
                   and time.time() < deadline):
                time.sleep(0.01)  # replica-side slot frees at retirement
            with fleet._lock:
                fleet._load[ra] = 0
            c = fleet.open_stream()
            st = fleet.stats()
            assert st["sessions"][c]["replica"] == rb
            assert st["spillovers"] == 1

    def test_declared_signature_passthrough(self):
        """Signature-aware admission end to end (max_buckets=1 pins the
        pre-bucketing one-signature-per-replica contract): a follow-up
        open of the SAME declared signature prefers the replica that
        already compiled it (warm tiebreak over plain least-loaded); a
        NEW signature cold-admits on the other, still-unpinned replica;
        and a third signature — with every replica's bucket busy — is
        refused by the whole fleet with the warm-signature list in the
        rejection."""
        fleet = FleetFrontend(
            get_filter("invert"),
            FleetConfig(replicas=2, mode="local",
                        serve=serve_cfg(max_buckets=1)))
        with fleet:
            a = fleet.open_stream(frame_shape=(H, W, 3))
            fleet.submit(a, tagged_frame(0, 0))
            # Warm preference: plain least-loaded would pick the OTHER
            # (empty) replica; the warm tiebreak routes the same
            # signature back to the one that already holds its program.
            b = fleet.open_stream(frame_shape=(H, W, 3))
            st = fleet.stats()
            assert (st["sessions"][a]["replica"]
                    == st["sessions"][b]["replica"])
            assert st["warm_placements"] >= 1
            # A new signature cold-admits on the unpinned survivor…
            c = fleet.open_stream(frame_shape=(H + 2, W, 3))
            st = fleet.stats()
            assert (st["sessions"][c]["replica"]
                    != st["sessions"][a]["replica"])
            # …and a third, with both replicas' single bucket busy, is
            # refused fleet-wide with the warm signatures enumerated.
            with pytest.raises(AdmissionError,
                               match=r"warm signatures.*invert\|16x24x3"):
                fleet.open_stream(frame_shape=(H + 4, W, 3))

    def test_fleet_precompile_warms_every_replica(self):
        """FleetConfig.precompile (CLI --precompile): each replica AOT-
        compiles the manifest at start, so the signature is warm
        fleet-wide before any traffic and its first admission is a pool
        hit."""
        manifest = [{"op_chain": "grayscale",
                     "frame_shape": [H, W, 3], "dtype": "u8"}]
        fleet = FleetFrontend(
            get_filter("invert"),
            FleetConfig(replicas=2, mode="local", serve=serve_cfg(),
                        precompile=manifest))
        with fleet:
            key = f"grayscale|{H}x{W}x3|uint8"
            for r in fleet._replicas.values():
                assert key in r.health()["warm_signatures"]
            sid = fleet.open_stream(op_chain="grayscale",
                                    frame_shape=(H, W, 3))
            rid = fleet._sessions[sid].replica_id
            st = fleet._replicas[rid].frontend.stats()
            assert st["pool"]["hits"] >= 1
            assert st["pool"]["misses"] == 1  # the precompile itself

    def test_chaos_replica_loss_migrate_restart(self):
        """Deterministic replica-loss injection (chaos site 'replica'):
        the victim's sessions migrate with indices monotone, the loss is
        replica-attributed, the replica restarts and rejoins, and new
        sessions are admitted after the loss."""
        from dvf_tpu.resilience import FaultPlan

        # Event index 20 = monitor tick 10 (2 replicas/tick), replica r0
        # — ~0.5 s in at health_poll_s=0.05, safely after the sessions
        # open and mid-way through the submission loop below.
        chaos = FaultPlan(seed=3).add("replica", at=(20,), count=1)
        fleet = FleetFrontend(
            get_filter("invert"),
            FleetConfig(replicas=2, mode="local", serve=serve_cfg(),
                        health_poll_s=0.05, max_restarts=2, chaos=chaos))
        deliveries: dict = {}
        with fleet:
            sids = [fleet.open_stream() for _ in range(2)]
            # at=0 fires on the first health tick for r0 — both sessions
            # keep submitting across the loss.
            for j in range(30):
                for k, sid in enumerate(sids):
                    fleet.submit(sid, tagged_frame(k, j))
                time.sleep(0.02)
            drain_fleet(fleet, sids, deliveries, 1)
            st = fleet.stats()
            # Fleet still admits; the restarted replica is back.
            extra = fleet.open_stream()
            fleet.submit(extra, tagged_frame(9, 0))
            drain_fleet(fleet, [extra], deliveries, 1, grace_s=15.0)

        assert st["replica_losses"] >= 1
        assert st["faults"]["by_kind"].get("replica", 0) >= 1
        assert "r0" in st["faults"]["by_replica"]
        assert st["replicas"]["r0"]["restarts"] >= 1
        assert st["replicas"]["r0"]["state"] == HEALTHY
        assert st["migrated_sessions"] >= 1
        assert st["order_violations"] == 0
        for k, sid in enumerate(sids):
            idxs = [d.index for d in deliveries[sid]]
            assert idxs == sorted(set(idxs)), f"{sid} indices {idxs}"
            for d in deliveries[sid]:
                np.testing.assert_array_equal(
                    d.frame, 255 - tagged_frame(k, d.index))
        assert len(deliveries[extra]) == 1


class TestProcessFleet:
    """Real worker subprocesses (one jax runtime each). Startup is a
    few seconds per replica; keep frame counts small."""

    def _run_scenario(self, kill_victim: bool):
        """2 sessions on 2 process replicas, 40 deterministic frames
        each; optionally SIGKILL the second session's replica mid-run.
        Returns (per-session deliveries, fleet stats, post-kill session
        delivery count)."""
        cfg = FleetConfig(
            replicas=2, mode="process", filter_spec=("invert", {}),
            serve=serve_cfg(), health_poll_s=0.1, max_restarts=1,
            startup_timeout_s=180.0)
        fleet = FleetFrontend(config=cfg)
        deliveries: dict = {"A": [], "B": []}
        with fleet:
            a = fleet.open_stream("A")
            b = fleet.open_stream("B")
            rb = fleet.stats()["sessions"]["B"]["replica"]
            assert fleet.stats()["sessions"]["A"]["replica"] != rb
            for j in range(10):
                fleet.submit(a, tagged_frame(0, j))
                fleet.submit(b, tagged_frame(1, j))
            drain_fleet(fleet, ["A", "B"], deliveries, 10, grace_s=20.0)
            if kill_victim:
                fleet._replicas[rb].kill()  # real SIGKILL
                # Submit INTO the loss window (at-most-once territory),
                # then wait for the migration to land before the frames
                # whose delivery the test requires — detection timing is
                # load-dependent, the post-migration contract is not.
                for j in range(10, 20):
                    fleet.submit(a, tagged_frame(0, j))
                    fleet.submit(b, tagged_frame(1, j))
                    time.sleep(0.02)
                deadline = time.time() + 60
                while (fleet.stats()["migrated_sessions"] < 1
                       and time.time() < deadline):
                    time.sleep(0.05)
                start = 20
            else:
                start = 10
            for j in range(start, 40):
                fleet.submit(a, tagged_frame(0, j))
                fleet.submit(b, tagged_frame(1, j))
                time.sleep(0.02)
            drain_fleet(fleet, ["A", "B"], deliveries, 40, grace_s=20.0)
            # The fleet still accepts (and serves) a NEW session.
            c = fleet.open_stream("C")
            fleet.submit(c, tagged_frame(2, 0))
            deliveries["C"] = []
            drain_fleet(fleet, ["C"], deliveries, 1, grace_s=20.0)
            if kill_victim:
                # The respawn is asynchronous supervision (monitor
                # thread blocks in start() for the worker's ready
                # handshake, ~2-3 s of fresh jax init): like the
                # migration wait above, converge before snapshotting —
                # a fast test body must not race the restart it asserts.
                deadline = time.time() + 60
                while (time.time() < deadline
                       and not any(row["restarts"] >= 1
                                   and row["state"] == HEALTHY
                                   for row in fleet.stats()
                                   ["replicas"].values())):
                    time.sleep(0.1)
            stats = fleet.stats()
        return deliveries, stats

    def test_kill_one_replica_survivor_bit_identical(self):
        """SIGKILL one replica mid-run: the surviving replica's session
        must deliver a stream bit-identical to a fault-free run, the
        victim's session migrates (monotone, at-most-once), the loss is
        replica-attributed, and the fleet keeps admitting."""
        clean, clean_stats = self._run_scenario(kill_victim=False)
        faulted, stats = self._run_scenario(kill_victim=True)

        # Fault-free run: everything delivers, no faults recorded.
        assert [d.index for d in clean["A"]] == list(range(40))
        assert [d.index for d in clean["B"]] == list(range(40))
        assert clean_stats["faults"]["by_kind"] == {}
        assert clean_stats["replica_losses"] == 0

        # Survivor: complete AND bit-identical to the fault-free run.
        assert [d.index for d in faulted["A"]] == list(range(40))
        for d_clean, d_fault in zip(clean["A"], faulted["A"]):
            np.testing.assert_array_equal(d_clean.frame, d_fault.frame)

        # Victim session: migrated, strictly monotone, delivered both
        # pre-kill and post-migration frames (at-most-once in between).
        bi = [d.index for d in faulted["B"]]
        assert bi == sorted(set(bi))
        assert bi[:10] == list(range(10))          # pre-kill intact
        assert bi[-1] >= 30                        # streaming resumed
        for d in faulted["B"]:
            np.testing.assert_array_equal(
                d.frame, 255 - tagged_frame(1, d.index))

        # New session admitted and served post-loss.
        assert len(faulted["C"]) == 1

        # Accounting: one replica loss, attributed; session migrated;
        # the victim restarted and rejoined.
        assert stats["replica_losses"] == 1
        assert stats["faults"]["by_kind"].get("replica", 0) >= 1
        assert stats["migrated_sessions"] == 1
        assert stats["order_violations"] == 0
        b_row = stats["sessions"]["B"]
        assert b_row["migrations"] == 1
        restarted = [rid for rid, row in stats["replicas"].items()
                     if row["restarts"] >= 1]
        # On restart failure the error is in the fault record — surface
        # it instead of a bare state mismatch.
        diag = (stats["replicas"], stats["faults"]["last"])
        assert len(restarted) == 1, diag
        assert stats["replicas"][restarted[0]]["state"] == HEALTHY, diag

    def test_two_replica_scaling(self, record_property):
        """Two process replicas serve two sessions whole: every frame of
        both rounds is delivered. The aggregate throughput at 2 replicas
        over one is recorded (the junit property and the assertion's
        message) and no longer held to a bar: two core-pinned CPU
        processes beside five other xdist workers on a shared host say
        nothing about a fleet's scaling, which the chip cell
        ``style_720p_v5e4.bulk`` judges (4 replicas over 4 x the
        one-chip cell, PERF.md)."""
        from dvf_tpu.benchmarks import bench_fleet_scaling

        if (os.cpu_count() or 1) < 3:
            pytest.skip("needs >= 3 CPUs (2 pinned replicas + front door)")
        r = bench_fleet_scaling(sessions=2, frames_per_session=200)
        record_property("fleet_scaling_2_over_1", r["scaling"]["2"])
        for n, row in r["rounds"].items():
            assert row["delivered"] == row["expected"], (
                f"{n} replica(s) delivered {row['delivered']} of "
                f"{row['expected']}; scaling 2 over 1 read "
                f"{r['scaling']['2']}: {r}")


def test_cli_fleet_demo(capsys):
    """`dvf_tpu fleet --mode local` runs the multi-replica demo end to
    end: sessions spread over replicas, everything delivered, one JSON
    line out with fleet-level accounting."""
    import json

    from dvf_tpu.cli import main

    rc = main([
        "fleet", "--mode", "local", "--replicas", "2", "--sessions", "4",
        "--filter", "invert", "--height", str(H), "--width", str(W),
        "--frames", "10", "--rate", "120", "--batch", "4",
        "--queue-size", "1000", "--slo-ms", "60000", "--platform", "cpu",
    ])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert len(out["replicas"]) == 2
    assert len(out["sessions"]) == 4
    assert {s["replica"] for s in out["sessions"].values()} == {"r0", "r1"}
    for sid, n in out["polled"].items():
        assert n == 10, (sid, out["polled"])
    assert out["aggregate"]["count"] == 40
    assert out["order_violations"] == 0
    assert out["replica_losses"] == 0
    assert out["faults"] == {}
