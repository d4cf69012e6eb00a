"""Telemetry plane: registry, scrape endpoints, trace aggregation,
flight recorder, and the metric-name schema gate.

Acceptance surface of PR 8 (dvf_tpu/obs):

- ``/metrics`` against a live in-process ServeFrontend / FleetFrontend
  returns Prometheus text exposition with merged p50/p99, queue depth,
  and per-kind fault counters carrying ``replica`` labels;
- a chaos-induced watchdog trip produces a flight-recorder dump whose
  merged Perfetto file contains trace lanes from >= 2 replicas on one
  aligned clock (CPU mesh, local replicas);
- every ``stats()`` export and bench JSON writer stays registry-
  conformant (snake_case, unit-suffixed) so the exporter can never
  silently drop a renamed key.
"""

import gzip
import json
import os
import time
import urllib.request

import numpy as np
import pytest

from dvf_tpu.obs.export import (
    FlightRecorder,
    MetricsExporter,
    samples_from_signals,
)
from dvf_tpu.obs.registry import (
    MetricsRegistry,
    TimeSeriesRing,
    check_metric_name,
    walk_export,
)
from dvf_tpu.obs.trace import (
    LANE_STRIDE,
    Tracer,
    merge_tracer_snapshots,
    merge_with_device_trace,
)
from dvf_tpu.ops import get_filter

H, W = 16, 24


def tagged_frame(k: int, j: int) -> np.ndarray:
    f = np.full((H, W, 3), 7, np.uint8)
    f[0] = k
    f[1] = j % 251
    return f


def _get(url: str) -> str:
    return urllib.request.urlopen(url, timeout=10).read().decode()


def drain(fe, sid, want, deadline_s=30.0):
    got = []
    deadline = time.time() + deadline_s
    while len(got) < want and time.time() < deadline:
        got += fe.poll(sid)
        time.sleep(0.005)
    return got


# ---------------------------------------------------------------------------
# Name conformance + registry
# ---------------------------------------------------------------------------


class TestMetricNames:
    def test_conformant_names(self):
        for name in ("p50_ms", "fps", "capture_fps", "h2d_mbps",
                     "faults_total", "ms_per_frame",
                     "bytes_accessed_per_frame", "total_ms",
                     "overlap_efficiency", "queue_depth",
                     "heartbeat_ages_s", "d2h_fixed_ms"):
            assert check_metric_name(name) is None, name

    def test_rename_hazards_rejected(self):
        for name in ("msPerFrame", "p50-ms", "latency_ms_avg",
                     "total_frames_produced", "fps_mean", "Ms", "1abc",
                     "mbps_down_link"):
            assert check_metric_name(name) is not None, name

    def test_walker_skips_dynamic_keys_checks_their_values(self):
        doc = {"sessions": {"sid@g1": {"p50_ms": 1.0, "badKey": 2}},
               "by_kind": {"decode": 3}}
        bad = walk_export(doc)
        # The session id (data) passes; the nested stats key inside the
        # dynamic map is still checked.
        assert [p for p, _ in bad] == ["sessions.sid@g1.badKey"]

    def test_registry_refuses_nonconformant_registration(self):
        r = MetricsRegistry()
        with pytest.raises(ValueError, match="conformant"):
            r.counter("framesProcessed")
        with pytest.raises(ValueError, match="conformant"):
            r.gauge("latency_ms_avg")

    def test_provider_renamed_key_dropped_loudly(self):
        r = MetricsRegistry()
        r.register_provider(lambda: samples_from_signals(
            {"good_total": 1.0}, prefix="x"))
        from dvf_tpu.obs.registry import GAUGE, MetricSample

        r.register_provider(lambda: [MetricSample("brokenName", 1.0, (),
                                                  GAUGE)])
        names = {s.name for s in r.collect()}
        assert "x_good_total" in names
        assert "brokenName" not in names
        assert r.dropped_samples == 1


class TestRegistry:
    def test_counter_gauge_histogram_render(self):
        r = MetricsRegistry()
        r.counter("faults_total").inc(2, labels={"kind": "decode"})
        r.gauge("p99_ms").set(12.5)
        h = r.histogram("tick_ms", [1, 10])
        for v in (0.5, 5, 50):
            h.observe(v)
        text = r.to_prometheus()
        assert "# TYPE dvf_faults_total counter" in text
        assert 'dvf_faults_total{kind="decode"} 2' in text
        assert "dvf_p99_ms 12.5" in text
        assert 'dvf_tick_ms_bucket{le="1"} 1' in text
        assert 'dvf_tick_ms_bucket{le="+Inf"} 3' in text
        assert "dvf_tick_ms_count 3" in text
        doc = r.to_json()
        assert {"name": "p99_ms", "value": 12.5, "labels": {},
                "kind": "gauge"} in doc["samples"]

    def test_signals_adapter_pivots_fault_keys(self):
        out = samples_from_signals(
            {"fps": 30.0, "fault_decode_total": 2, "shed_total": 1,
             "skipped": None},
            prefix="serve", labels={"replica": "r1"})
        by_name = {s.name: s for s in out}
        assert by_name["serve_faults_total"].labels == (
            ("kind", "decode"), ("replica", "r1"))
        assert by_name["serve_shed_total"].kind == "counter"
        assert by_name["serve_fps"].kind == "gauge"
        assert len(out) == 3  # None dropped

    def test_non_numeric_gauge_drops_sample_not_scrape(self):
        r = MetricsRegistry()
        r.gauge("bad_gauge").set_fn(lambda: "oops")
        r.gauge("worse_gauge").set("not-a-number")
        r.gauge("fps").set(3.0)
        text = r.to_prometheus()  # must not raise
        assert "dvf_fps 3" in text
        assert "bad_gauge" not in text and "worse_gauge" not in text

    def test_json_documents_are_strict_rfc8259(self, tmp_path):
        """NaN percentiles (empty windows) must never reach a JSON
        document as the invalid literal ``NaN`` — rows treat them as
        gaps, flight dumps sanitize to null."""
        ring = TimeSeriesRing(lambda: {"p50_ms": float("nan"),
                                       "fps": 1.0}, interval_s=10.0)
        ring.sample_once()
        [row] = ring.series()["rows"]
        assert "p50_ms" not in row and row["fps"] == 1.0
        fr = FlightRecorder(str(tmp_path), min_interval_s=0.0,
                            stats_fn=lambda: {"p99_ms": float("nan"),
                                              "n": 2}, ring=ring)
        d = fr.trigger("nan check")
        for name in ("stats.json", "timeseries.json"):
            text = open(os.path.join(d, name)).read()
            assert "NaN" not in text, (name, text)
        assert json.loads(open(os.path.join(d, "stats.json")).read()) == {
            "p99_ms": None, "n": 2}

    def test_nan_and_inf_render(self):
        r = MetricsRegistry()
        r.gauge("p99_ms").set(float("nan"))
        r.gauge("capacity_fps").set(float("inf"))
        text = r.to_prometheus()
        assert "dvf_p99_ms NaN" in text
        assert "dvf_capacity_fps +Inf" in text


class TestTimeSeriesRing:
    def test_bounded_window_and_hook(self):
        seen = []
        n = {"v": 0}

        def sample():
            n["v"] += 1
            return {"x": float(n["v"]), "gap": None}

        ring = TimeSeriesRing(sample, interval_s=10.0, capacity=3,
                              on_sample=lambda prev, cur: seen.append(
                                  (prev or {}).get("x")))
        for _ in range(5):
            ring.sample_once()
        doc = ring.series()
        assert [row["x"] for row in doc["rows"]] == [3.0, 4.0, 5.0]
        assert all("gap" not in row and "t" in row for row in doc["rows"])
        assert seen == [None, 1.0, 2.0, 3.0, 4.0]
        assert len(ring) == 3

    def test_since_cursor_semantics(self):
        """The /timeseries incremental-scrape contract: ``since`` is an
        exclusive wall-clock cursor over row ``t``; ``cursor`` always
        reflects the newest retained row (pass it back as the next
        ``since``), even when the filtered rows are empty."""
        n = {"v": 0}

        def sample():
            n["v"] += 1
            return {"x": float(n["v"])}

        ring = TimeSeriesRing(sample, interval_s=10.0, capacity=10)
        for _ in range(4):
            ring.sample_once()
            time.sleep(0.002)  # distinct wall-clock stamps
        full = ring.series()
        assert [r["x"] for r in full["rows"]] == [1.0, 2.0, 3.0, 4.0]
        assert full["cursor"] == full["rows"][-1]["t"]
        mid = full["rows"][1]["t"]
        delta = ring.series(since=mid)
        # Strictly-after semantics: the row AT the cursor is not resent.
        assert [r["x"] for r in delta["rows"]] == [3.0, 4.0]
        assert delta["cursor"] == full["cursor"]
        # Caught up: empty rows, same cursor back (poll again later).
        done = ring.series(since=full["cursor"])
        assert done["rows"] == [] and done["cursor"] == full["cursor"]
        # A cursor older than the window's tail returns the whole
        # bounded window (the ring is a sliding window, not a log).
        assert len(ring.series(since=0.0)["rows"]) == 4
        # Empty ring: no rows, null cursor.
        empty = TimeSeriesRing(lambda: {}, interval_s=10.0)
        assert empty.series()["cursor"] is None

    def test_since_cursor_over_http(self):
        ring = TimeSeriesRing(lambda: {"x": 1.0}, interval_s=10.0)
        ring.sample_once()
        time.sleep(0.002)
        ring.sample_once()
        with MetricsExporter(MetricsRegistry(), ring=ring) as ex:
            full = json.loads(_get(f"{ex.url}/timeseries"))
            assert len(full["rows"]) == 2
            cur = full["rows"][0]["t"]
            delta = json.loads(_get(f"{ex.url}/timeseries?since={cur}"))
            assert len(delta["rows"]) == 1
            assert delta["rows"][0]["t"] > cur
            caught = json.loads(_get(
                f"{ex.url}/timeseries?since={full['cursor']}"))
            assert caught["rows"] == []
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(f"{ex.url}/timeseries?since=nonsense")
            assert ei.value.code == 400

    def test_sampler_thread_and_error_containment(self):
        boom = {"on": False}

        def sample():
            if boom["on"]:
                raise RuntimeError("sensor broke")
            return {"x": 1.0}

        ring = TimeSeriesRing(sample, interval_s=0.01).start()
        deadline = time.time() + 5.0
        while len(ring) < 2 and time.time() < deadline:
            time.sleep(0.005)
        assert len(ring) >= 2
        boom["on"] = True
        deadline = time.time() + 5.0
        while ring.sample_errors == 0 and time.time() < deadline:
            time.sleep(0.005)
        ring.stop()
        assert ring.sample_errors >= 1  # gap, not a dead sampler

    def test_rate_logger_lands_gauge_on_print_ticks(self):
        r = MetricsRegistry()
        from dvf_tpu.obs.metrics import RateLogger

        rl = RateLogger("capture", interval_s=0.0, quiet=True, registry=r)
        rate = rl.tick(5)
        assert rate is not None and rate == rl.last_rate
        sample = [s for s in r.collect() if s.name == "rate_fps"]
        assert len(sample) == 1
        assert sample[0].labels == (("stage", "capture"),)
        assert sample[0].value == pytest.approx(rate)


# ---------------------------------------------------------------------------
# Tracer ring + cross-process merge
# ---------------------------------------------------------------------------


class TestTracerRing:
    def test_bounded_with_dropped_counter(self):
        t = Tracer(enabled=True, max_events=4)
        for i in range(10):
            t.instant("ev", ts=t.start_time + i * 1e-3, track=0, i=i)
        assert len(t) == 4
        assert t.dropped == 6
        snap = t.snapshot()
        # The ring keeps the most RECENT window (the flight recorder's
        # black-box contract).
        assert [e["args"]["i"] for e in snap["events"]] == [6, 7, 8, 9]
        assert snap["dropped"] == 6

    def test_snapshot_cap_keeps_most_recent(self):
        """The over-RPC cap (the fleet trace op's transfer bound) keeps
        the newest window and counts the shed as dropped."""
        t = Tracer(enabled=True)
        for i in range(10):
            t.instant("ev", ts=t.start_time + i * 1e-3, i=i)
        snap = t.snapshot(max_events=3)
        assert [e["args"]["i"] for e in snap["events"]] == [7, 8, 9]
        assert snap["dropped"] == 7
        assert len(t.snapshot()["events"]) == 10  # uncapped untouched

    def test_disabled_tracer_records_nothing(self):
        t = Tracer(enabled=False, max_events=4)
        for _ in range(10):
            t.instant("ev")
            t.complete("sp", t.start_time, t.start_time + 1)
        assert len(t) == 0 and t.dropped == 0

    def test_snapshot_is_plain_values(self):
        import pickle

        t = Tracer(enabled=True, process_name="serve:r0")
        t.complete("span", t.start_time, t.start_time + 0.01, track=2,
                   frames=3)
        snap = pickle.loads(pickle.dumps(t.snapshot()))
        assert snap["process_name"] == "serve:r0"
        assert snap["events"][0]["args"] == {"frames": 3}
        json.dumps(snap)  # and JSON-safe


class TestMergeTracerSnapshots:
    def _tracer(self, name, epoch):
        t = Tracer(enabled=True, process_name=name)
        t.start_time = epoch
        return t

    def test_clock_alignment_and_lane_blocks(self):
        """Two tracers whose epochs differ by exactly 2 s: after the
        merge both lanes sit on ONE clock — the later tracer's events
        are shifted by +2e6 µs, lanes land in disjoint pid blocks."""
        e0 = 1_000_000.0
        a = self._tracer("serve:r0", e0)
        b = self._tracer("serve:r1", e0 + 2.0)
        a.complete("span", e0 + 0.5, e0 + 0.6, track=1)
        b.complete("span", b.start_time + 0.5, b.start_time + 0.6, track=1)
        doc = merge_tracer_snapshots([a.snapshot(), b.snapshot()])
        ev = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert len(ev) == 2
        by_pid = {e["pid"]: e for e in ev}
        # Lane blocks: snapshot 0 track 1 → pid 1; snapshot 1 track 1 →
        # pid LANE_STRIDE + 1.
        assert set(by_pid) == {1, LANE_STRIDE + 1}
        # Same relative instant in each process (epoch + 0.5 s), one
        # aligned clock: b's event lands exactly 2 s after a's.
        assert by_pid[LANE_STRIDE + 1]["ts"] - by_pid[1]["ts"] == 2_000_000
        lanes = doc["dvfTraceLanes"]
        assert [ln["process_name"] for ln in lanes] == ["serve:r0",
                                                        "serve:r1"]
        assert [ln["epoch_offset_us"] for ln in lanes] == [0, 2_000_000]
        metas = {m["pid"]: m["args"]["name"] for m in doc["traceEvents"]
                 if m.get("ph") == "M"}
        assert metas[1] == "serve:r0/1"
        assert metas[LANE_STRIDE + 1] == "serve:r1/1"

    def test_lane_stride_overflow_cannot_interleave_pid_blocks(self):
        """Satellite pin: a snapshot whose track ids exceed LANE_STRIDE
        must NOT spill into another snapshot's pid block — oversized
        tracks clamp into their own snapshot's last lane (folding is
        counted in the lane provenance), so two processes' lanes can
        never interleave in the merged Perfetto session."""
        a = self._tracer("serve:r0", 1000.0)
        a.complete("ok", 1000.0, 1000.01, track=1)
        # Track 150 would previously land at pid 150 — INSIDE snapshot
        # 1's block [100, 200) — and render as r1's lane.
        a.complete("big", 1000.0, 1000.01, track=LANE_STRIDE + 50)
        a.instant("neg", ts=1000.0, track=-3)
        b = self._tracer("serve:r1", 1000.0)
        b.complete("other", 1000.0, 1000.01, track=50)
        doc = merge_tracer_snapshots([a.snapshot(), b.snapshot()])
        ev = [e for e in doc["traceEvents"] if e.get("ph") != "M"]
        a_pids = {e["pid"] for e in ev
                  if e["name"] in ("ok", "big", "neg")}
        b_pids = {e["pid"] for e in ev if e["name"] == "other"}
        assert all(0 <= p < LANE_STRIDE for p in a_pids), a_pids
        assert all(LANE_STRIDE <= p < 2 * LANE_STRIDE for p in b_pids)
        # The oversized track folded into snapshot 0's LAST lane, the
        # negative one clamped to lane 0.
        big = next(e for e in ev if e["name"] == "big")
        assert big["pid"] == LANE_STRIDE - 1
        neg = next(e for e in ev if e["name"] == "neg")
        assert neg["pid"] == 0
        lanes = {ln["process_name"]: ln for ln in doc["dvfTraceLanes"]}
        assert lanes["serve:r0"]["folded_tracks"] == 2
        assert lanes["serve:r1"]["folded_tracks"] == 0
        # In-range lanes keep their identity mapping and meta names.
        metas = {m["pid"]: m["args"]["name"] for m in doc["traceEvents"]
                 if m.get("ph") == "M"}
        assert metas[1] == "serve:r0/1"
        assert metas[LANE_STRIDE + 50] == "serve:r1/50"

    def test_longest_duration_cut_and_empty(self):
        t = self._tracer("w", 1000.0)
        for i in range(6):
            t.complete(f"s{i}", 1000.0, 1000.0 + (i + 1) * 0.01)
        doc = merge_tracer_snapshots([t.snapshot()], max_events=2)
        ev = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert sorted(e["name"] for e in ev) == ["s4", "s5"]  # longest win

    def test_cut_preserves_instant_incident_markers(self):
        """Over-cap truncation must not cull the duration-less instant
        events (replica_lost / replica_stall — the markers a post-mortem
        reads first) in favor of ordinary spans."""
        t = self._tracer("fleet", 1000.0)
        t.instant("replica_lost", ts=1000.5, track=0, replica="r1")
        for i in range(6):
            t.complete(f"s{i}", 1000.0, 1000.0 + (i + 1) * 0.01)
        doc = merge_tracer_snapshots([t.snapshot()], max_events=3)
        kept = [e["name"] for e in doc["traceEvents"] if e.get("ph") != "M"]
        assert "replica_lost" in kept
        assert len(kept) == 3
        assert "s5" in kept and "s4" in kept  # longest spans fill the rest
        assert merge_tracer_snapshots([]) is None
        assert merge_tracer_snapshots([{"events": [], "start_time": 1.0,
                                        "process_name": "x"}]) is None

    def test_write_to_file(self, tmp_path):
        t = self._tracer("w", 1000.0)
        t.instant("ev", ts=1000.5)
        out = str(tmp_path / "merged.pftrace")
        doc = merge_tracer_snapshots([t.snapshot()], out_path=out)
        assert doc is not None
        on_disk = json.loads((tmp_path / "merged.pftrace").read_text())
        assert on_disk["traceEvents"] == doc["traceEvents"]


class TestMergeWithDeviceTrace:
    """The gzip-truncation best-effort path, the ``$``-prefixed event
    filtering, and the max_events longest-duration cut (satellite 4)."""

    def _host(self, tmp_path):
        host = tmp_path / "host.json"
        host.write_text(json.dumps({"traceEvents": [
            {"name": "frame_delivered", "ph": "i", "ts": 10, "pid": 0,
             "tid": 0, "s": "g"}]}))
        return str(host)

    def _device_dir(self, tmp_path, events):
        d = tmp_path / "dev" / "plugins" / "profile" / "run1"
        d.mkdir(parents=True)
        with gzip.open(d / "host.trace.json.gz", "wt") as f:
            json.dump({"traceEvents": events}, f)
        return str(tmp_path / "dev")

    def test_merge_filters_python_tracer_spam_and_offsets(self, tmp_path):
        dev = self._device_dir(tmp_path, [
            {"name": "process_name", "ph": "M", "pid": 3,
             "args": {"name": "/device:TPU:0"}},
            {"name": "$py_interp_frame", "ph": "X", "ts": 0, "dur": 999,
             "pid": 3},
            {"name": "fusion", "ph": "X", "ts": 5, "dur": 7, "pid": 3},
        ])
        out = str(tmp_path / "merged.json")
        assert merge_with_device_trace(self._host(tmp_path), dev, out,
                                       device_epoch_us=100) == out
        doc = json.loads((tmp_path / "merged.json").read_text())
        names = [e["name"] for e in doc["traceEvents"]]
        assert "$py_interp_frame" not in names        # spam filtered
        assert "frame_delivered" in names             # host lane kept
        fusion = next(e for e in doc["traceEvents"] if e["name"] == "fusion")
        assert fusion["pid"] == 10003                 # device pid offset
        assert fusion["ts"] == 105                    # epoch-aligned
        meta = next(e for e in doc["traceEvents"]
                    if e.get("ph") == "M" and e["pid"] == 10003)
        assert meta["args"]["name"].startswith("device")

    def test_truncated_gzip_is_best_effort_none(self, tmp_path):
        dev = self._device_dir(tmp_path, [
            {"name": "fusion", "ph": "X", "ts": 5, "dur": 7, "pid": 3}])
        gz = (tmp_path / "dev" / "plugins" / "profile" / "run1"
              / "host.trace.json.gz")
        gz.write_bytes(gz.read_bytes()[:-8])  # profiler killed mid-write
        out = str(tmp_path / "merged.json")
        assert merge_with_device_trace(self._host(tmp_path), dev, out,
                                       device_epoch_us=0) is None
        assert not os.path.exists(out)

    def test_no_candidates_is_none(self, tmp_path):
        assert merge_with_device_trace(
            self._host(tmp_path), str(tmp_path / "missing"),
            str(tmp_path / "merged.json"), 0) is None

    def test_max_events_keeps_longest_durations(self, tmp_path):
        dev = self._device_dir(tmp_path, [
            {"name": f"op{i}", "ph": "X", "ts": i, "dur": i, "pid": 1}
            for i in range(1, 6)])
        out = str(tmp_path / "merged.json")
        merge_with_device_trace(self._host(tmp_path), dev, out,
                                device_epoch_us=0, max_events=2)
        doc = json.loads((tmp_path / "merged.json").read_text())
        kept = sorted(e["name"] for e in doc["traceEvents"]
                      if e["name"].startswith("op"))
        assert kept == ["op4", "op5"]


# ---------------------------------------------------------------------------
# Scrape endpoints (acceptance: in-process frontends)
# ---------------------------------------------------------------------------


class TestServeMetricsEndpoint:
    def test_metrics_healthz_timeseries(self):
        from dvf_tpu.serve import ServeConfig, ServeFrontend

        fe = ServeFrontend(
            get_filter("invert"),
            ServeConfig(batch_size=2, queue_size=100, slo_ms=60_000.0,
                        telemetry_sample_s=0.05, trace=True))
        with fe:
            sid = fe.open_stream()
            for j in range(6):
                fe.submit(sid, tagged_frame(0, j))
            got = drain(fe, sid, 6)
            assert len(got) == 6
            deadline = time.time() + 5.0
            while len(fe.telemetry) < 2 and time.time() < deadline:
                time.sleep(0.01)
            with MetricsExporter(fe.registry, health_fn=fe.health,
                                 ring=fe.telemetry) as ex:
                text = _get(f"{ex.url}/metrics")
                health = json.loads(_get(f"{ex.url}/healthz"))
                series = json.loads(_get(f"{ex.url}/timeseries"))
                with pytest.raises(urllib.error.HTTPError):
                    _get(f"{ex.url}/nope")
        # Prometheus text exposition with the headline signals.
        assert "# TYPE dvf_serve_p50_ms gauge" in text
        for want in ("dvf_serve_p50_ms ", "dvf_serve_p99_ms ",
                     "dvf_serve_queue_depth ", "dvf_serve_fps ",
                     "dvf_serve_delivered_total 6",
                     "dvf_serve_engine_frames_total "):
            assert want in text, (want, text)
        assert health["ok"] is True
        rows = series["rows"]
        assert rows and all("t" in r and "queue_depth" in r for r in rows)
        # delivered_total is monotone in the window
        dl = [r["delivered_total"] for r in rows]
        assert dl == sorted(dl)

    def test_counters_monotone_across_retirement_eviction(self):
        """*_total series are Prometheus counters: evicting old sessions
        from the bounded retired map (or release()) must never shrink
        them — a backward step reads as a counter reset and fakes a
        rate() spike."""
        from dvf_tpu.serve import ServeConfig, ServeFrontend

        fe = ServeFrontend(
            get_filter("invert"),
            ServeConfig(batch_size=2, queue_size=100, slo_ms=60_000.0,
                        max_retired=1, telemetry_sample_s=0.0))
        seen = []
        with fe:
            for k in range(3):  # retirement bound 1: sessions 0,1 evict
                sid = fe.open_stream()
                for j in range(4):
                    fe.submit(sid, tagged_frame(k, j))
                assert len(drain(fe, sid, 4)) == 4
                fe.close(sid, drain=True)
                deadline = time.time() + 20.0
                while fe.open_count() and time.time() < deadline:
                    time.sleep(0.005)
                seen.append(fe.signals()["delivered_total"])
            fe.release(next(iter(fe._retired)))  # explicit release too
            seen.append(fe.signals()["delivered_total"])
        assert seen == sorted(seen), seen
        assert seen[-1] == 12.0  # nothing lost to the eviction arithmetic

    def test_fault_counters_labeled_by_kind(self):
        from dvf_tpu.resilience import FaultPlan
        from dvf_tpu.serve import ServeConfig, ServeFrontend

        chaos = FaultPlan().add("compute", at=(1,), count=1)
        fe = ServeFrontend(
            get_filter("invert"),
            ServeConfig(batch_size=2, queue_size=100, slo_ms=60_000.0,
                        chaos=chaos, telemetry_sample_s=0.0))
        with fe:
            sid = fe.open_stream()
            for j in range(8):
                fe.submit(sid, tagged_frame(0, j))
                time.sleep(0.02)
            deadline = time.time() + 20.0
            while fe.faults.total() == 0 and time.time() < deadline:
                time.sleep(0.01)
            with MetricsExporter(fe.registry) as ex:
                text = _get(f"{ex.url}/metrics")
        assert 'dvf_serve_faults_total{kind="compute"} ' in text


@pytest.mark.fleet
class TestFleetMetricsEndpoint:
    def test_fleet_merged_metrics_with_replica_labels(self):
        """The PR acceptance pin: /metrics against a running fleet
        returns fleet-merged p50/p99, per-replica queue depth, and
        per-kind fault counters with replica labels."""
        from dvf_tpu.fleet import FleetConfig, FleetFrontend
        from dvf_tpu.serve import ServeConfig

        fleet = FleetFrontend(
            get_filter("invert"),
            FleetConfig(
                replicas=2, mode="local",
                serve=ServeConfig(batch_size=4, queue_size=1000,
                                  out_queue_size=1000, slo_ms=60_000.0,
                                  telemetry_sample_s=0.0),
                # One contained compute fault per replica, replica-
                # attributed through the per-replica FaultStats labels.
                chaos_spec="compute:at=1:count=1",
                telemetry_sample_s=0.1))
        with fleet:
            sids = [fleet.open_stream() for _ in range(2)]
            for j in range(16):
                for k, sid in enumerate(sids):
                    fleet.submit(sid, tagged_frame(k, j))
                time.sleep(0.01)
            deliveries: dict = {}
            deadline = time.time() + 30.0
            while time.time() < deadline:
                for sid in sids:
                    deliveries.setdefault(sid, []).extend(fleet.poll(sid))
                st = fleet.stats()
                if (all(deliveries.get(s) for s in sids)
                        and len(st["faults"].get("by_replica", {})) >= 1):
                    break
                time.sleep(0.02)
            with MetricsExporter(fleet.registry, ring=fleet.telemetry) as ex:
                text = _get(f"{ex.url}/metrics")
        # Fleet-merged latency percentiles (weighted sample merge across
        # replicas — LatencyStats.merge_snapshots under the hood).
        assert "dvf_fleet_p50_ms " in text
        assert "dvf_fleet_p99_ms " in text
        # Fleet delivered counter: summed from the replicas' monotone
        # lifetime signals, present at fleet level and per replica.
        assert "dvf_fleet_delivered_total " in text
        assert 'dvf_fleet_replica_delivered_total{replica="r0"} ' in text
        # Per-replica series labeled replica=… for BOTH replicas.
        for rid in ("r0", "r1"):
            assert f'dvf_fleet_replica_queue_depth{{replica="{rid}"}} ' \
                in text, (rid, text)
            assert f'dvf_fleet_replica_up{{replica="{rid}"}} 1' in text
        # Per-kind fault counters carrying replica labels (the chaos-
        # injected compute fault, attributed by the replica that ate it).
        assert 'dvf_fleet_replica_faults_total{kind="compute",replica="' \
            in text, text


@pytest.mark.fleet
class TestProcessReplicaTrace:
    def test_trace_snapshot_crosses_the_rpc(self):
        """Per-replica event buffers ship over the existing length-
        prefixed pickle RPC: a PROCESS replica's tracer snapshot arrives
        with a foreign pid and merges into the front door's session."""
        from dvf_tpu.fleet import FleetConfig, FleetFrontend
        from dvf_tpu.serve import ServeConfig

        fleet = FleetFrontend(config=FleetConfig(
            replicas=1, mode="process", filter_spec=("invert", {}),
            serve=ServeConfig(batch_size=2, queue_size=100,
                              slo_ms=60_000.0, trace=True,
                              telemetry_sample_s=0.0),
            startup_timeout_s=180.0))
        with fleet:
            sid = fleet.open_stream()
            for j in range(4):
                fleet.submit(sid, tagged_frame(0, j))
            deliveries = []
            deadline = time.time() + 60.0
            while len(deliveries) < 4 and time.time() < deadline:
                deliveries += fleet.poll(sid)
                time.sleep(0.01)
            assert len(deliveries) == 4
            snaps = fleet.trace_snapshots()
        lanes = {s["process_name"]: s for s in snaps}
        assert "serve:r0" in lanes, lanes.keys()
        worker_snap = lanes["serve:r0"]
        assert worker_snap["pid"] != os.getpid()  # crossed the boundary
        assert any(e["name"] == "batch_complete"
                   for e in worker_snap["events"])
        doc = merge_tracer_snapshots(snaps)
        assert doc is not None


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_dump_artifacts_and_rate_limit(self, tmp_path):
        t = Tracer(enabled=True, process_name="w")
        t.instant("ev", ts=t.start_time)
        ring = TimeSeriesRing(lambda: {"fps": 1.0}, interval_s=10.0)
        ring.sample_once()
        fr = FlightRecorder(
            str(tmp_path), label="t", min_interval_s=60.0,
            trace_fn=lambda: [t.snapshot()],
            stats_fn=lambda: {"errors": 0}, ring=ring)
        d = fr.trigger("watchdog stall: oldest 1.2s")
        assert d is not None and os.path.isdir(d)
        assert sorted(os.listdir(d)) == ["meta.json", "stats.json",
                                         "timeseries.json", "trace.pftrace"]
        meta = json.loads(open(os.path.join(d, "meta.json")).read())
        assert meta["reason"].startswith("watchdog stall")
        assert "watchdog-stall" in os.path.basename(d)
        # Rate limit: an immediate second trigger is suppressed.
        assert fr.trigger("again") is None
        assert fr.suppressed == 1
        assert fr.stats()["dumps"] == 1

    def test_partial_sources_still_dump(self, tmp_path):
        fr = FlightRecorder(
            str(tmp_path), min_interval_s=0.0,
            trace_fn=lambda: (_ for _ in ()).throw(RuntimeError("gone")),
            stats_fn=lambda: {"ok": 1})
        d = fr.trigger("loss")
        assert sorted(os.listdir(d)) == ["meta.json", "stats.json"]
        assert fr.dump_errors == 1

    def test_max_dumps_cap(self, tmp_path):
        fr = FlightRecorder(str(tmp_path), min_interval_s=0.0, max_dumps=2)
        assert fr.trigger("a") and fr.trigger("b")
        assert fr.trigger("c") is None


class TestServeFlightTriggers:
    def test_watchdog_trip_dumps(self, tmp_path):
        """Chaos-frozen collect thread → supervisor trip → flight dump
        (fired via Supervisor.on_trip before recovery), and the serving
        path survives exactly as before."""
        from dvf_tpu.resilience import FaultPlan
        from dvf_tpu.serve import ServeConfig, ServeFrontend

        chaos = FaultPlan().add("freeze", at=(3,), delay_s=1.2)
        fe = ServeFrontend(
            get_filter("invert"),
            ServeConfig(batch_size=4, queue_size=1000, slo_ms=60_000.0,
                        stall_timeout_s=0.35, chaos=chaos, trace=True,
                        telemetry_sample_s=0.1,
                        flight_dir=str(tmp_path),
                        flight_min_interval_s=0.0))
        with fe:
            sid = fe.open_stream()
            i = 0
            deadline = time.time() + 20.0
            while fe.recoveries < 1:
                assert time.time() < deadline, "watchdog never tripped"
                fe.submit(sid, tagged_frame(0, i))
                i += 1
                fe.poll(sid)
                time.sleep(0.01)
            # The dump runs off-thread (recovery must not wait on disk
            # writes): converge before asserting.
            deadline = time.time() + 10.0
            while (fe.flight.stats()["dumps"] == 0
                   and time.time() < deadline):
                time.sleep(0.01)
            stats = fe.stats()
        assert stats["flight"]["dumps"] >= 1
        dump = sorted(tmp_path.iterdir())[0]
        assert "stall" in dump.name
        merged = json.loads((dump / "trace.pftrace").read_text())
        assert any(e.get("ph") == "X" for e in merged["traceEvents"])
        dumped_stats = json.loads((dump / "stats.json").read_text())
        assert "sessions" in dumped_stats

    def test_slo_burn_rate_dumps(self, tmp_path):
        """Deliveries missing their SLO faster than slo_burn_threshold
        within one sampling window trip a dump. The window rows are
        driven synthetically (wall-clock miss timing is not
        deterministic under a warm jit cache); the ring→hook wiring
        itself is exercised through sample_once on the live ring."""
        from dvf_tpu.serve import ServeConfig, ServeFrontend

        fe = ServeFrontend(
            get_filter("invert"),
            ServeConfig(batch_size=2, queue_size=100, slo_ms=50.0,
                        telemetry_sample_s=30.0,  # manual ticks only
                        slo_burn_threshold=0.5,
                        flight_dir=str(tmp_path),
                        flight_min_interval_s=0.0))
        with fe:
            # Wired through the chained hook (burn check + control
            # plane; the plane leg is a no-op when control is off).
            assert fe.telemetry.on_sample == fe._on_telemetry_sample
            # Healthy sample: 10 deliveries, 1 miss → 0.1 < 0.5: no dump.
            fe._check_slo_burn({"delivered_total": 0, "slo_miss_total": 0},
                               {"delivered_total": 10, "slo_miss_total": 1})
            assert fe.flight.stats()["dumps"] == 0
            # Burning window: 8/10 of the window's deliveries late.
            fe._check_slo_burn({"delivered_total": 10, "slo_miss_total": 1},
                               {"delivered_total": 20, "slo_miss_total": 9})
            st = fe.flight.stats()
        assert st["dumps"] == 1
        assert "slo burn rate" in st["last_reason"]
        dump = sorted(tmp_path.iterdir())[0]
        assert "slo-burn-rate" in dump.name
        # An idle window (no deliveries) never divides by zero / dumps.
        fe._check_slo_burn({"delivered_total": 20, "slo_miss_total": 9},
                           {"delivered_total": 20, "slo_miss_total": 9})
        assert fe.flight.stats()["dumps"] == 1

    def test_budget_exhaustion_failure_dumps(self, tmp_path):
        """A hard frontend failure (_fail) is a flight trigger: the
        post-mortem exists even though the frontend is dead."""
        from dvf_tpu.serve import ServeConfig, ServeFrontend
        from dvf_tpu.serve.session import ServeError

        fe = ServeFrontend(
            get_filter("invert"),
            ServeConfig(batch_size=2, queue_size=100, slo_ms=60_000.0,
                        resilient=False, telemetry_sample_s=0.0,
                        flight_dir=str(tmp_path),
                        flight_min_interval_s=0.0))
        fe.start()
        try:
            sid = fe.open_stream()
            for j in range(2):
                fe.submit(sid, tagged_frame(0, j))
            drain(fe, sid, 2)

            def dead_step(*a, **k):
                raise RuntimeError("engine died (forced)")

            fe.engine._step = dead_step
            deadline = time.time() + 20.0
            while fe._error is None and time.time() < deadline:
                try:
                    fe.submit(sid, tagged_frame(0, 99))
                except ServeError:
                    break
                time.sleep(0.01)
            # _fail sets _error before the (synchronous, other-thread)
            # dump finishes: poll rather than racing it.
            deadline = time.time() + 10.0
            while (fe.flight.stats()["dumps"] == 0
                   and time.time() < deadline):
                time.sleep(0.01)
            assert fe.flight.stats()["dumps"] >= 1
        finally:
            try:
                fe.stop()
            except Exception:  # noqa: BLE001 — fail-fast stop re-raises
                pass           # the stored engine error, as designed


class TestPipelineFlight:
    def test_pipeline_failure_dumps(self, tmp_path):
        """The single-stream tier honors flight_dir with serve's
        semantics: a hard pipeline failure dumps the black box (CLI
        satellite — serve --flight-dir was silently ignored in
        single-stream mode before)."""
        from dvf_tpu.io.sinks import NullSink
        from dvf_tpu.runtime.pipeline import Pipeline, PipelineConfig
        from dvf_tpu.ops import get_filter

        pipe = Pipeline([], get_filter("invert"), NullSink(),
                        PipelineConfig(flight_dir=str(tmp_path),
                                       flight_min_interval_s=0.0))
        assert pipe.flight is not None
        pipe._fail(RuntimeError("forced"))
        deadline = time.time() + 10.0
        while pipe.flight.stats()["dumps"] == 0 \
                and time.time() < deadline:
            time.sleep(0.01)  # trigger_async runs off-thread
        st = pipe.flight.stats()
        assert st["dumps"] == 1
        assert "pipeline failed" in st["last_reason"]
        dump = sorted(tmp_path.iterdir())[0]
        assert (dump / "meta.json").exists()
        assert (dump / "stats.json").exists()


@pytest.mark.fleet
@pytest.mark.chaos
class TestFleetFlightAcceptance:
    def test_chaos_watchdog_trip_dumps_two_replica_lanes(self, tmp_path):
        """The PR acceptance pin: a chaos-induced watchdog trip (frozen
        collect in a replica, PR-4 supervision recovers it) produces a
        fleet flight-recorder dump whose merged Perfetto file contains
        trace lanes from >= 2 replicas on one aligned clock."""
        from dvf_tpu.fleet import FleetConfig, FleetFrontend
        from dvf_tpu.serve import ServeConfig

        fleet = FleetFrontend(
            get_filter("invert"),
            FleetConfig(
                replicas=2, mode="local",
                serve=ServeConfig(batch_size=4, queue_size=1000,
                                  out_queue_size=1000, slo_ms=60_000.0,
                                  stall_timeout_s=0.35, trace=True,
                                  telemetry_sample_s=0.0),
                # Each replica parses its own freeze plan: its collect
                # thread wedges 1.2 s on the 4th iteration, outliving the
                # 0.35 s stall budget — a deterministic watchdog trip.
                chaos_spec="freeze:at=3:delay=1.2",
                health_poll_s=0.05,
                flight_dir=str(tmp_path),
                flight_min_interval_s=0.0))
        with fleet:
            sids = [fleet.open_stream() for _ in range(2)]
            i = 0
            deadline = time.time() + 40.0
            while fleet.flight.stats()["dumps"] == 0:
                assert time.time() < deadline, "no flight dump"
                for k, sid in enumerate(sids):
                    fleet.submit(sid, tagged_frame(k, i))
                for sid in sids:
                    fleet.poll(sid)
                i += 1
                time.sleep(0.01)
            st = fleet.stats()
        assert st["flight"]["dumps"] >= 1
        assert "stall" in st["flight"]["last_reason"]
        dump = next(p for p in sorted(tmp_path.iterdir())
                    if "stall" in p.name)
        merged = json.loads((dump / "trace.pftrace").read_text())
        lanes = merged["dvfTraceLanes"]
        replica_lanes = [ln for ln in lanes
                        if ln["process_name"].startswith("serve:r")]
        # >= 2 replicas contributed lanes...
        assert len({ln["process_name"] for ln in replica_lanes}) >= 2, lanes
        assert all(ln["events"] >= 1 for ln in replica_lanes)
        # ...on ONE aligned clock: every lane re-based onto the common
        # epoch, and both replicas' device spans overlap in merged time
        # (they served concurrently — disjoint ranges would mean the
        # clocks were NOT aligned).
        spans = {}
        for ln in replica_lanes:
            base = ln["pid_base"]
            ts = [e["ts"] for e in merged["traceEvents"]
                  if e.get("ph") in ("X", "i")
                  and base <= e.get("pid", -1) < base + LANE_STRIDE]
            assert ts and min(ts) >= 0
            spans[ln["process_name"]] = (min(ts), max(ts))
        (a0, a1), (b0, b1) = list(spans.values())[:2]
        assert max(a0, b0) <= min(a1, b1), spans


# ---------------------------------------------------------------------------
# Schema gate: every stats() export + bench JSON writer is conformant
# ---------------------------------------------------------------------------


class TestExportSchemas:
    """Walks the live export surfaces with the SAME conformance rules
    the exporter applies, so a renamed key breaks here instead of
    silently vanishing from the scrape endpoint (satellite 6)."""

    def _assert_clean(self, label, doc):
        bad = walk_export(doc)
        assert not bad, (label, bad)

    def test_obs_building_blocks(self):
        from dvf_tpu.obs.metrics import (EgressStats, IngestStats,
                                         LatencyStats)
        from dvf_tpu.resilience.faults import FaultStats

        ls = LatencyStats()
        ls.record(0.01)
        self._assert_clean("latency.summary", ls.summary())
        self._assert_clean("latency.snapshot", ls.snapshot())
        self._assert_clean("latency.merged", LatencyStats.merged([ls]))
        self._assert_clean("ingest", IngestStats().summary())
        self._assert_clean("egress", EgressStats().summary())
        fs = FaultStats("r0")
        fs.record("decode", ValueError("x"))
        self._assert_clean("faults", fs.summary())

    def test_serve_and_pipeline_exports(self):
        from dvf_tpu.io.sinks import NullSink
        from dvf_tpu.resilience import FaultPlan
        from dvf_tpu.runtime.pipeline import Pipeline, PipelineConfig
        from dvf_tpu.serve import ServeConfig, ServeFrontend

        fe = ServeFrontend(get_filter("invert"),
                           ServeConfig(telemetry_sample_s=0.0))
        fe.open_stream()
        # A second signature exercises the multi-tenant surfaces: the
        # per-bucket stats rows, the pool counters, and the bucket/
        # compile-cache registry samples (all walked below).
        fe.open_stream(op_chain="grayscale", frame_shape=(H, W, 3))
        st = fe.stats()
        assert st["open_buckets"] == 2 and len(st["buckets"]) == 2
        assert st["pool"]["misses"] == 1
        self._assert_clean("serve.stats", st)
        self._assert_clean("serve.signals", fe.signals())
        self._assert_clean("serve.health", fe.health())
        # The bucket provider's sample names pass the same conformance
        # gate the exporter applies (a bad name is silently dropped
        # there — so pin the series we promise exist).
        prom = fe.registry.to_prometheus()
        for series in ("dvf_compile_cache_hits_total",
                       "dvf_compile_cache_misses_total",
                       "dvf_pool_evictions_total",
                       "dvf_bucket_queue_depth"):
            assert series in prom, series
        assert 'bucket="grayscale|16x24x3|uint8"' in prom
        fe.pool.close()  # unstarted frontend: free the leased program

        pipe = Pipeline([], get_filter("invert"), NullSink(),
                        PipelineConfig())
        self._assert_clean("pipeline.stats", pipe.stats())
        self._assert_clean("pipeline.signals", pipe.signals())
        plan = FaultPlan.parse("compute:at=3,h2d:every=5:count=2", seed=1)
        self._assert_clean("chaos", plan.summary())

    def test_worker_exports(self):
        pytest.importorskip("zmq")
        from dvf_tpu.transport.zmq_ingress import TpuZmqWorker

        worker = TpuZmqWorker(get_filter("invert"), wire="delta",
                              batch_size=2, raw_size=H)
        try:
            self._assert_clean("worker.stats", worker.stats())
            self._assert_clean("worker.signals", worker.signals())
        finally:
            worker.close()

    @pytest.mark.fleet
    def test_fleet_exports(self):
        from dvf_tpu.fleet import FleetConfig, FleetFrontend
        from dvf_tpu.serve import ServeConfig

        fleet = FleetFrontend(
            get_filter("invert"),
            FleetConfig(replicas=2, mode="local",
                        serve=ServeConfig(telemetry_sample_s=0.0)))
        # Unstarted: rows render with state=dead — the schema is the
        # same shape the live export uses, without booting two engines.
        self._assert_clean("fleet.stats", fleet.stats())
        self._assert_clean("fleet.signals", fleet.signals())

    def test_bench_json_writers(self):
        from dvf_tpu.benchmarks import (
            bench_device_resident,
            bench_e2e_streaming,
            bench_stage_decomposition,
            bench_transfer,
            roofline_fields,
        )
        from dvf_tpu.transport.codec import jpeg_wire_budget

        self._assert_clean("bench_transfer", bench_transfer(2, 16, 16,
                                                            reps=2))
        r = bench_device_resident(get_filter("invert"), iters=3,
                                  batch_size=2, height=16, width=16)
        self._assert_clean("bench_device_resident", r)
        self._assert_clean("roofline",
                           roofline_fields(dict(
                               r, fps=100.0, platform="tpu",
                               device_kind="TPU v5 lite")))
        self._assert_clean(
            "bench_stage_decomposition",
            bench_stage_decomposition(get_filter("invert"), (1,), 16, 16,
                                      reps=2))
        self._assert_clean(
            "bench_e2e_streaming",
            bench_e2e_streaming(get_filter("invert"), 16, 4, 16, 16))
        self._assert_clean("jpeg_wire_budget",
                           jpeg_wire_budget(32, 32, threads=1))
