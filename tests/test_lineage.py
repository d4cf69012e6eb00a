"""Frame-lineage tracing & latency attribution (dvf_tpu/obs/lineage.py).

Acceptance surface of the lineage PR:

- **Additivity**: for every delivered frame in an instrumented serve
  run, the lineage components sum to the measured end-to-end latency —
  exactly in-process, within tolerance across a ProcessReplica hop
  (whose lineage carries a clock re-base);
- **Exemplar capture**: a chaos-induced slow stage (h2d delay) breaches
  the session SLO, trips the burn-rate flight dump, and the dump's
  ``lineage.json`` exemplars attribute the breach to the injected stage;
- **Explain surface**: stats()['attribution'], attr_* signals, the
  /explain endpoint;
- **Stage-cost profiles**: persisted per-signature, merged across runs,
  loaded at bucket creation, annotated into control decisions;
- **trace-view**: the offline summary reads traces and flight dumps.
"""

import json
import os
import time
import urllib.request

import numpy as np
import pytest

from dvf_tpu.obs.lineage import (
    SERVE_COMPONENTS,
    AttributionAggregate,
    AttributionPlane,
    FrameLineage,
    load_stage_profile,
    save_stage_profile,
)
from dvf_tpu.ops import get_filter

pytestmark = pytest.mark.lineage

H, W = 16, 24


def frame_u8(k: int, j: int) -> np.ndarray:
    f = np.full((H, W, 3), 7, np.uint8)
    f[0] = k
    f[1] = j % 251
    return f


def drain(fe, sid, want, deadline_s=30.0):
    got = []
    deadline = time.time() + deadline_s
    while len(got) < want and time.time() < deadline:
        got += fe.poll(sid)
        time.sleep(0.005)
    return got


# ---------------------------------------------------------------------------
# Golden unit layer: the additivity invariant + clock re-base
# ---------------------------------------------------------------------------


class TestFrameLineageGolden:
    def test_components_telescope_to_total(self):
        """Satellite: the attribution additivity math pinned on a
        synthetic lineage — components are consecutive mark deltas, so
        they sum to last_mark − ts whatever the stamps are."""
        lin = FrameLineage("s0", 7, ts=1000.0)
        lin.mark("queue_ingress", 1000.010)
        lin.mark("queue_bucket", 1000.050)
        lin.mark("assemble_h2d", 1000.065)
        lin.mark("device", 1000.165)
        lin.mark("d2h", 1000.170)
        lin.mark("deliver", 1000.172)
        comps = lin.components_ms()
        assert comps == pytest.approx({
            "queue_ingress": 10.0, "queue_bucket": 40.0,
            "assemble_h2d": 15.0, "device": 100.0,
            "d2h": 5.0, "deliver": 2.0}, abs=1e-6)
        assert lin.total_ms() == pytest.approx(172.0, abs=1e-6)
        assert sum(comps.values()) == pytest.approx(lin.total_ms(),
                                                    abs=1e-9)
        doc = lin.to_dict()
        assert doc["session"] == "s0" and doc["index"] == 7
        json.dumps(doc)  # exemplar form is JSON-safe

    def test_repeated_component_accumulates(self):
        lin = FrameLineage("s", 0, ts=0.0)
        lin.mark("queue_ingress", 0.010)
        lin.mark("queue_ingress", 0.015)
        assert lin.components_ms() == pytest.approx(
            {"queue_ingress": 15.0}, abs=1e-9)

    def test_rebase_preserves_decomposition(self):
        """The cross-process discipline: shifting every stamp by the
        clock offset changes NOTHING about the decomposition — it only
        places the lineage on the other clock, so parent-side marks
        appended afterwards keep the telescoping sum exact."""
        lin = FrameLineage("s", 0, ts=1000.0)
        lin.mark("queue_ingress", 1000.020)
        lin.mark("deliver", 1000.100)
        before = lin.components_ms()
        lin.rebase(-2.5)  # replica clock was 2.5 s ahead of the parent
        assert lin.ts == pytest.approx(997.5)
        assert lin.components_ms() == pytest.approx(before, abs=1e-6)
        assert lin.total_ms() == pytest.approx(100.0, abs=1e-6)
        # Parent-side extension on the parent clock stays additive.
        lin.mark("rpc", 997.650)
        comps = lin.components_ms()
        assert comps["rpc"] == pytest.approx(50.0, abs=1e-6)
        assert sum(comps.values()) == pytest.approx(lin.total_ms(),
                                                    abs=1e-9)

    def test_rebase_zero_is_noop(self):
        lin = FrameLineage("s", 0, ts=5.0)
        lin.mark("deliver", 6.0)
        marks = list(lin.marks)
        lin.rebase(0.0)
        assert lin.marks == marks and lin.ts == 5.0


class TestAggregateAndExplain:
    def test_percentiles_and_explain_tail_based(self):
        agg = AttributionAggregate(capacity=128)
        # 99 fast frames dominated by device, 1 slow frame dominated by
        # queue_bucket: the tail explain must name queue_bucket even
        # though the MEAN frame is device-dominated.
        for _ in range(99):
            agg.observe(10.0, {"queue_bucket": 1.0, "device": 9.0})
        agg.observe(200.0, {"queue_bucket": 190.0, "device": 10.0})
        s = agg.summary()
        assert s["count"] == 100 and s["window_frames"] == 100
        assert s["components"]["device"]["mean_ms"] == pytest.approx(
            9.01, abs=0.01)
        e = agg.explain(q=99.0)
        assert e["fractions"]["queue_bucket"] > 0.9
        assert e["text"].startswith("p99 = ")
        assert "queue_bucket" in e["text"].split(",")[0]

    def test_empty_aggregate(self):
        agg = AttributionAggregate()
        assert agg.summary() == {"count": 0, "window_frames": 0}
        assert agg.explain() is None

    def test_plane_exemplars_breach_and_slow_window(self):
        plane = AttributionPlane(exemplar_capacity=8, window_frames=10,
                                 slow_k=2)
        for i in range(9):
            lin = FrameLineage("s0", i, ts=0.0)
            lin.mark("deliver", 0.001 * (i + 1))
            plane.observe(lin, lin.total_ms(), slo_ms=100.0,
                          bucket_label="b")
        breach = FrameLineage("s0", 99, ts=0.0)
        breach.mark("queue_bucket", 0.150)
        breach.mark("deliver", 0.151)
        plane.observe(breach, breach.total_ms(), slo_ms=100.0,
                      bucket_label="b")
        snap = plane.snapshot()
        recs = snap["exemplars"]
        breaches = [r for r in recs if r["breach"]]
        assert len(breaches) == 1 and breaches[0]["index"] == 99
        assert breaches[0]["slo_ms"] == 100.0
        # The window's slowest non-breach frames are retained too.
        slow = [r for r in recs if not r["breach"]]
        assert slow and max(r["total_ms"] for r in slow) == \
            pytest.approx(9.0, abs=0.1)
        assert plane.frames_total == 10
        assert plane.exemplars.breaches_total == 1
        sig = plane.signals()
        assert sig["lineage_breaches_total"] == 1.0
        assert "attr_queue_bucket_p99_ms" in sig
        json.dumps(snap)  # the flight artifact is JSON-safe


class TestStageProfiles:
    def test_save_load_roundtrip_and_merge(self, tmp_path):
        d = str(tmp_path)
        sig = "invert|16x24x3|uint8"
        p = save_stage_profile(d, sig, {"device": {"mean_ms": 10.0}},
                               tick_cost_ms=4.0, count=10)
        assert p is not None and os.path.exists(p)
        doc = load_stage_profile(d, sig)
        assert doc["components_ms"]["device"]["mean_ms"] == 10.0
        assert doc["tick_cost_ms"] == 4.0 and doc["count"] == 10
        # Second run merges count-weighted, not clobbers.
        save_stage_profile(d, sig, {"device": {"mean_ms": 20.0}},
                           tick_cost_ms=8.0, count=30)
        doc = load_stage_profile(d, sig)
        assert doc["count"] == 40
        assert doc["components_ms"]["device"]["mean_ms"] == \
            pytest.approx(17.5)
        assert doc["tick_cost_ms"] == pytest.approx(7.0)
        # Distinct signatures get distinct files.
        save_stage_profile(d, "other|8x8x3|uint8", {}, tick_cost_ms=1.0)
        assert load_stage_profile(d, "other|8x8x3|uint8")[
            "tick_cost_ms"] == 1.0
        assert load_stage_profile(d, sig)["count"] == 40
        assert load_stage_profile(None, sig) is None
        assert load_stage_profile(d, "never-saved") is None

    def test_control_decisions_annotated_with_stage_cost(self):
        from dvf_tpu.control import ControlConfig, ControlPlane
        from dvf_tpu.control.controllers import Action

        plane = ControlPlane(actuator=None, config=ControlConfig())
        plane.batch.step = lambda row, prev, floor=None: [
            Action("resize", "bkt|16x24x3|uint8", 4, "occupancy")]
        plane.quality.step = lambda row, prev, floor=None: []
        plane.tiers.step = lambda row, prev: []
        cost = {"queue_bucket": 12.5, "device": 3.0}
        actions = plane.decide({
            "buckets": [{"label": "bkt|16x24x3|uint8",
                         "stage_cost_ms": cost}],
            "sessions": []})
        assert len(actions) == 1
        entry = plane.stats()["decisions"][-1]
        assert entry["kind"] == "resize"
        assert entry["stage_cost_ms"] == cost


# ---------------------------------------------------------------------------
# Instrumented serve run: the in-process additivity acceptance
# ---------------------------------------------------------------------------


class TestServeLineage:
    def _frontend(self, tmp_path=None, **kw):
        from dvf_tpu.serve import ServeConfig, ServeFrontend

        kw.setdefault("lineage", True)
        cfg = ServeConfig(batch_size=2, queue_size=100, slo_ms=60_000.0,
                          telemetry_sample_s=0.0, **kw)
        return ServeFrontend(get_filter("invert"), cfg)

    def test_every_delivered_frame_is_additive(self):
        """ACCEPTANCE: every delivered frame's components sum to its
        measured end-to-end latency (exact — one clock read closes both),
        across every serve-path hop."""
        fe = self._frontend()
        with fe:
            sids = [fe.open_stream() for _ in range(2)]
            for j in range(8):
                for k, sid in enumerate(sids):
                    fe.submit(sid, frame_u8(k, j))
            for k, sid in enumerate(sids):
                got = drain(fe, sid, 8)
                assert len(got) == 8
                for d in got:
                    lin = d.lineage
                    assert lin is not None
                    comps = lin.components_ms()
                    assert set(comps) == set(SERVE_COMPONENTS), comps
                    assert sum(comps.values()) == pytest.approx(
                        d.latency_ms, abs=1e-6)
                    assert lin.total_ms() == pytest.approx(
                        d.latency_ms, abs=1e-6)
                    assert lin.session_id == sid
            st = fe.stats()
            attr = st["attribution"]
            assert attr["frames_total"] == 16
            assert set(attr["components"]) == set(SERVE_COMPONENTS)
            assert "explain" in attr and attr["explain"]["text"]
            # Per-bucket and per-session windows exist.
            assert any("invert" in k for k in attr["by_bucket"])
            assert set(attr["by_session"]) == set(sids)
            sig = fe.signals()
            assert sig["lineage_frames_total"] == 16.0
            for comp in SERVE_COMPONENTS:
                assert f"attr_{comp}_p99_ms" in sig
            ex = fe.explain()
            assert ex["lineage"] is True and ex["text"]
            # Lineage-armed export surfaces stay registry-conformant
            # (the schema gate the exporter applies).
            from dvf_tpu.obs.registry import walk_export

            for label, doc in (("stats", st), ("signals", sig),
                               ("explain", ex),
                               ("snapshot", fe.attribution.snapshot())):
                bad = walk_export(doc)
                assert not bad, (label, bad)

    def test_same_deliveries_with_lineage_on_and_off(self):
        """Three sessions in a closed loop (a few batches in flight)
        through a frontend with attribution armed and one without: each
        session gets the same indices and bytes either way, and where it
        is armed every frame's components still sum to its latency."""
        n, window = 24, 6
        runs = {}
        for armed in (True, False):
            fe = self._frontend(lineage=armed)
            got = {}
            with fe:
                sids = [fe.open_stream() for _ in range(3)]
                sent = 0
                while sent < n:
                    for k, sid in enumerate(sids):
                        fe.submit(sid, frame_u8(k, sent))
                    sent += 1
                    for sid in sids:
                        got.setdefault(sid, []).extend(fe.poll(sid))
                    while sent - min(len(v) for v in got.values()) \
                            >= window:
                        for sid in sids:
                            got[sid].extend(fe.poll(sid))
                        time.sleep(0.001)
                for sid in sids:
                    got[sid] += drain(fe, sid, n - len(got[sid]))
            runs[armed] = [got[sid] for sid in sids]
        for on, off in zip(runs[True], runs[False]):
            assert [d.index for d in on] == list(range(n))
            assert [d.index for d in off] == list(range(n))
            assert [d.frame.tobytes() for d in on] == \
                [d.frame.tobytes() for d in off]
            assert all(d.lineage is None for d in off)
            for d in on:
                assert sum(d.lineage.components_ms().values()) == \
                    pytest.approx(d.latency_ms, abs=1e-6)

    def test_lineage_off_is_zero_cost_surface(self):
        from dvf_tpu.serve import ServeConfig, ServeFrontend

        fe = ServeFrontend(get_filter("invert"),
                           ServeConfig(batch_size=2, queue_size=100,
                                       slo_ms=60_000.0,
                                       telemetry_sample_s=0.0))
        with fe:
            sid = fe.open_stream()
            for j in range(2):
                fe.submit(sid, frame_u8(0, j))
            got = drain(fe, sid, 2)
        assert all(d.lineage is None for d in got)
        assert "attribution" not in fe.stats()
        assert "lineage_frames_total" not in fe.signals()
        assert fe.explain()["lineage"] is False

    def test_explain_endpoint(self):
        from dvf_tpu.obs.export import MetricsExporter

        fe = self._frontend()
        with fe:
            sid = fe.open_stream()
            for j in range(4):
                fe.submit(sid, frame_u8(0, j))
            assert len(drain(fe, sid, 4)) == 4
            with MetricsExporter(fe.registry, health_fn=fe.health,
                                 explain_fn=fe.explain) as ex:
                doc = json.loads(urllib.request.urlopen(
                    f"{ex.url}/explain", timeout=10).read().decode())
        assert doc["lineage"] is True
        assert "fractions" in doc and doc["text"].startswith("p")

    def test_profiles_persist_and_reload(self, tmp_path):
        prof_dir = str(tmp_path / "profiles")
        fe = self._frontend(profile_dir=prof_dir)
        with fe:
            sid = fe.open_stream(op_chain="invert",
                                 frame_shape=(H, W, 3))
            for j in range(6):
                fe.submit(sid, frame_u8(0, j))
            assert len(drain(fe, sid, 6)) == 6
        # stop() persisted the measured profile for the pinned signature.
        sig = "invert|16x24x3|uint8"
        doc = load_stage_profile(prof_dir, sig)
        assert doc is not None, os.listdir(prof_dir)
        assert doc["tick_cost_ms"] is None or doc["tick_cost_ms"] > 0
        assert "device" in doc["components_ms"]
        # A fresh frontend loads it at bucket creation and annotates its
        # control view with the measured stage costs.
        fe2 = self._frontend(profile_dir=prof_dir)
        try:
            fe2.open_stream(op_chain="invert", frame_shape=(H, W, 3))
            bucket = fe2._bucket_by_key[next(iter(fe2._bucket_by_key))]
            assert bucket.stage_profile is not None
            assert bucket.stage_profile["signature"] == sig
            view = fe2.control_view()
            rows = [b for b in view["buckets"]
                    if b.get("stage_cost_ms")]
            assert rows and "device" in rows[0]["stage_cost_ms"]
        finally:
            fe2.pool.close()  # never started: free the leased program


# ---------------------------------------------------------------------------
# Chaos acceptance: SLO-breach dump attributes the injected stage
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestBreachAttribution:
    def test_slo_breach_dump_names_the_injected_stage(self, tmp_path,
                                                      monkeypatch):
        """ACCEPTANCE: a chaos-injected h2d delay makes one bucket slow,
        frames breach their SLO, the burn-rate trigger dumps — and the
        dump's lineage.json exemplars attribute the breach to the
        injected stage (assemble_h2d dominates each breach's
        decomposition)."""
        import dvf_tpu.runtime.ingest as ingest_mod

        from dvf_tpu.resilience import FaultPlan
        from dvf_tpu.serve import ServeConfig, ServeFrontend

        # Keep the streamed path (and with it the h2d injection site)
        # on the CPU backend — test_chaos's discipline.
        monkeypatch.setattr(ingest_mod, "MIN_STREAM_H2D_MS", 0.0)
        # 8-way data mesh at batch_size=8 → one 1-row chunk per device,
        # 8 delayed h2d events per batch ≈ 0.24 s in assemble_h2d.
        chaos = FaultPlan().add("h2d", every=1, delay_s=0.03)
        fe = ServeFrontend(
            get_filter("invert"),
            ServeConfig(batch_size=8, queue_size=100, slo_ms=50.0,
                        lineage=True, chaos=chaos,
                        telemetry_sample_s=0.1,
                        slo_burn_threshold=0.5,
                        flight_dir=str(tmp_path),
                        flight_min_interval_s=0.0))
        with fe:
            sid = fe.open_stream()
            i = 0
            deadline = time.time() + 30.0
            while fe.flight.stats()["dumps"] == 0:
                assert time.time() < deadline, "burn trigger never fired"
                fe.submit(sid, frame_u8(0, i))
                i += 1
                fe.poll(sid)
                time.sleep(0.02)
        dump = next(p for p in sorted(tmp_path.iterdir())
                    if "slo-burn" in p.name)
        lin = json.loads((dump / "lineage.json").read_text())
        breaches = [r for r in lin["exemplars"] if r.get("breach")]
        assert breaches, lin["exemplars"]
        for rec in breaches:
            comps = rec["components"]
            guilty = max(comps, key=comps.get)
            assert guilty == "assemble_h2d", comps
            # Additivity survives into the dumped exemplar record.
            assert sum(comps.values()) == pytest.approx(
                rec["total_ms"], abs=0.01)
        # The explain line in the dump names the injected stage too.
        assert "assemble_h2d" in lin["explain"]["text"].split(",")[0]


# ---------------------------------------------------------------------------
# Cross-process: lineage over the ProcessReplica RPC
# ---------------------------------------------------------------------------


@pytest.mark.fleet
class TestFleetLineage:
    def test_additivity_across_a_process_replica_hop(self):
        """ACCEPTANCE: lineage crosses the ProcessReplica RPC, is
        re-based onto the front door's clock, gains the rpc component,
        and the components still sum to the end-to-end latency within
        tolerance (clock-offset estimate error ≤ RPC round trip)."""
        from dvf_tpu.fleet import FleetConfig, FleetFrontend
        from dvf_tpu.serve import ServeConfig

        fleet = FleetFrontend(config=FleetConfig(
            replicas=1, mode="process", filter_spec=("invert", {}),
            serve=ServeConfig(batch_size=2, queue_size=100,
                              slo_ms=60_000.0, lineage=True,
                              telemetry_sample_s=0.0),
            startup_timeout_s=180.0))
        with fleet:
            sid = fleet.open_stream()
            submit_ts = {}
            for j in range(4):
                ts = time.time()
                idx = fleet.submit(sid, frame_u8(0, j), ts=ts)
                submit_ts[idx] = ts
            deliveries = []
            deadline = time.time() + 60.0
            while len(deliveries) < 4 and time.time() < deadline:
                deliveries += fleet.poll(sid)
                time.sleep(0.01)
            assert len(deliveries) == 4
        for d in deliveries:
            lin = d.lineage
            assert lin is not None
            comps = lin.components_ms()
            # Every serve hop + the RPC hop crossed the boundary.
            assert set(SERVE_COMPONENTS) <= set(comps), comps
            assert "rpc" in comps
            # Telescoping additivity is exact by construction even
            # after the re-base...
            assert sum(comps.values()) == pytest.approx(lin.total_ms(),
                                                        abs=1e-6)
            # ...and the re-based total matches the front door's own
            # measurement of the frame's life within tolerance (the
            # clock-offset estimate is bounded by the health RPC's
            # round trip; one host, so generous 250 ms).
            wall_ms = (lin.marks[-1][1] - submit_ts[d.index]) * 1e3
            assert lin.total_ms() == pytest.approx(wall_ms, abs=250.0)

    def test_fleet_explain_fans_out_replicas(self):
        from dvf_tpu.fleet import FleetConfig, FleetFrontend
        from dvf_tpu.serve import ServeConfig

        fleet = FleetFrontend(
            get_filter("invert"),
            FleetConfig(replicas=1, mode="local",
                        serve=ServeConfig(batch_size=2, queue_size=100,
                                          slo_ms=60_000.0, lineage=True,
                                          telemetry_sample_s=0.0)))
        with fleet:
            sid = fleet.open_stream()
            for j in range(4):
                fleet.submit(sid, frame_u8(0, j))
            got = []
            deadline = time.time() + 30.0
            while len(got) < 4 and time.time() < deadline:
                got += fleet.poll(sid)
                time.sleep(0.01)
            assert len(got) == 4
            doc = fleet.explain()
            st = fleet.stats()
        assert doc["lineage"] is True
        assert "r0" in doc["replicas"], doc
        assert doc["replicas"]["r0"]["text"].startswith("p")
        # The per-replica attribution rides the fleet stats rows too.
        assert "attribution" in st["replicas"]["r0"]


# ---------------------------------------------------------------------------
# trace-view (offline summaries)
# ---------------------------------------------------------------------------


class TestTraceView:
    def _trace_file(self, tmp_path):
        from dvf_tpu.obs.trace import Tracer, merge_tracer_snapshots

        t = Tracer(enabled=True, process_name="serve:r0")
        t.start_time = 1000.0
        t.complete("serve_dispatch", 1000.0, 1000.050, track=0)
        t.complete("batch_complete", 1000.010, 1000.100, track=1)
        t.instant("frame_captured", ts=1000.0, track=0)
        path = str(tmp_path / "trace.pftrace")
        merge_tracer_snapshots([t.snapshot()], out_path=path)
        return path

    def test_summarize_trace(self, tmp_path):
        from dvf_tpu.obs.viewer import summarize

        s = summarize(self._trace_file(tmp_path), top=5)
        assert s["events"] == 3
        lanes = {row["lane"]: row for row in s["lanes"]}
        assert "serve:r0" in lanes and "serve:r0/1" in lanes
        dev = lanes["serve:r0/1"]
        assert dev["busy_ms"] == pytest.approx(90.0)
        assert dev["utilization"] == pytest.approx(1.0)
        assert s["slowest_spans"][0]["name"] == "batch_complete"
        assert s["slowest_spans"][0]["dur_ms"] == pytest.approx(90.0)

    def test_summarize_dump_with_lineage(self, tmp_path):
        from dvf_tpu.obs.viewer import render_text, summarize

        d = tmp_path / "dump-001"
        d.mkdir()
        os.rename(self._trace_file(tmp_path), d / "trace.pftrace")
        (d / "meta.json").write_text(json.dumps(
            {"reason": "slo burn rate 0.8 >= 0.5", "pid": 1,
             "utc": "2026-01-01T00:00:00Z"}))
        (d / "lineage.json").write_text(json.dumps({
            "explain": {"text": "p99 = 90% queue_bucket, 10% device"},
            "exemplars": [
                {"session": "s0", "index": 5, "total_ms": 120.0,
                 "breach": True, "slo_ms": 50.0,
                 "components": {"queue_bucket": 110.0, "device": 10.0}},
                {"session": "s1", "index": 2, "total_ms": 30.0,
                 "breach": False, "slo_ms": 50.0,
                 "components": {"device": 30.0}},
            ]}))
        s = summarize(str(d), top=5)
        assert s["meta"]["reason"].startswith("slo burn")
        assert s["explain"].startswith("p99 = 90% queue_bucket")
        assert [r["index"] for r in s["lineages"]] == [5, 2]
        text = render_text(s)
        assert "SLO-BREACH" in text
        assert "queue_bucket=110.0" in text
        assert "slowest spans:" in text

    def test_cli_subcommand(self, tmp_path, capsys):
        from dvf_tpu.cli import main

        path = self._trace_file(tmp_path)
        assert main(["trace-view", path]) == 0
        out = capsys.readouterr().out
        assert "serve:r0" in out and "slowest spans:" in out
        assert main(["trace-view", path, "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["events"] == 3
        assert main(["trace-view", str(tmp_path / "missing")]) == 2
        bad = tmp_path / "bad.json"
        bad.write_text("{}")
        assert main(["trace-view", str(bad)]) == 2
