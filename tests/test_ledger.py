"""Glass-engine tests: compile/reconfiguration ledger and memory
accounting (ISSUE 13).

Pins, in tier-1:

- **Ledger unit layer**: bounded event ring, measured-stall window
  semantics (open at the last dispatch tick before an event, closed by
  the bucket's next tick), abandon-on-retire;
- **Serve acceptance**: a chaos run mixing one forced engine rebuild
  (compute budget overflow), one batch resize, and one quality
  downshift yields a ledger where every event carries cause +
  compile_ms + a measured bucket stall_ms > 0, the events land on the
  dedicated Perfetto lane of the merged trace AND in the flight dump's
  ``ledger.json``;
- **dvf_compile_ms** histogram labeled by signature and cause, through
  the registry conformance checks;
- **Memory accounting**: dvf_mem_* gauges, per-bucket attribution,
  zero occupied host slabs after stop, and the leak-trend watch;
- **Lineage additivity with the ledger armed** (the two planes must
  not perturb each other across a live resize), and deliveries
  byte-identical with the ledger on and off.
"""

import gc
import json
import os
import time
import urllib.request

import numpy as np
import pytest

from dvf_tpu.obs import ledger as ledger_mod
from dvf_tpu.obs.ledger import ReconfigLedger
from dvf_tpu.obs.memory import LeakTrendWatch, memory_summary
from dvf_tpu.obs.registry import walk_export
from dvf_tpu.ops import get_filter
from dvf_tpu.serve import ServeConfig, ServeFrontend

pytestmark = pytest.mark.ledger

H, W = 16, 24


def frame_u8(k: int, j: int) -> np.ndarray:
    f = np.full((H, W, 3), 11, np.uint8)
    f[0] = k
    f[1] = j % 251
    return f


def _drive_sync(fe, sid, frame, deadline_s=30.0):
    s = fe._session(sid)
    before = s.delivered + s.failed
    fe.submit(sid, frame)
    deadline = time.time() + deadline_s
    while s.delivered + s.failed < before + 1:
        assert time.time() < deadline, "serve path deadlocked"
        time.sleep(0.002)


def drain(fe, sid, want, deadline_s=30.0):
    got = []
    deadline = time.time() + deadline_s
    while len(got) < want and time.time() < deadline:
        got += fe.poll(sid)
        time.sleep(0.005)
    return got


def _events(fe, kind=None):
    evs = fe.ledger.snapshot()
    return [e for e in evs if kind is None or e["kind"] == kind]


def _wait(pred, deadline_s=20.0, msg="condition never held"):
    deadline = time.time() + deadline_s
    while not pred():
        assert time.time() < deadline, msg
        time.sleep(0.005)


# ---------------------------------------------------------------------------
# Unit layer
# ---------------------------------------------------------------------------


class TestReconfigLedgerUnit:
    def test_record_snapshot_and_counters(self):
        led = ReconfigLedger(capacity=4)
        led.record(ledger_mod.COMPILE, cause="admission", signature="s",
                   cache="miss", wall_ms=12.5, compile_ms=12.5)
        led.record(ledger_mod.POOL_ACQUIRE, cause="admission",
                   signature="s", cache="hit", wall_ms=0.0)
        s = led.summary()
        assert s["events_total"] == 2 and s["dropped_total"] == 0
        assert s["by_kind"] == {"compile": 1, "pool_acquire": 1}
        assert s["by_cause"] == {"admission": 2}
        ev = s["events"][0]
        assert ev["cause"] == "admission" and ev["wall_ms"] == 12.5
        assert ev["thread"]  # who ran it is always recorded
        # Bounded ring: overflow sheds oldest and counts it.
        for i in range(6):
            led.record(ledger_mod.BUCKET_CREATE, bucket=f"b{i}")
        s = led.summary()
        assert len(led.snapshot()) == 4
        assert s["events_total"] == 8 and s["dropped_total"] == 4
        assert not walk_export(s), walk_export(s)

    def test_stall_window_measures_dispatch_gap(self):
        led = ReconfigLedger()
        t0 = 1000.0
        ev = led.record(ledger_mod.BATCH_RESIZE, cause="resize",
                        bucket="b", wall_ms=50.0, stall_from=t0)
        assert led.has_pending_stalls
        # The export never leaks the open window's internal mark.
        assert "stall_from" not in led.snapshot()[-1]
        assert "stall_ms" not in led.snapshot()[-1]
        led.note_dispatch("other-bucket", t0 + 0.2)  # wrong bucket: open
        assert led.has_pending_stalls
        led.note_dispatch("b", t0 + 0.25)
        assert not led.has_pending_stalls
        assert ev["stall_ms"] == pytest.approx(250.0, abs=1e-6)
        s = led.summary()
        assert s["stall_events_total"] == 1
        assert s["stall_ms_total"] == pytest.approx(250.0, abs=1e-3)
        # Closed: a later tick does not re-close or double-count.
        led.note_dispatch("b", t0 + 9.0)
        assert led.summary()["stall_events_total"] == 1

    def test_abandon_stalls_drops_open_windows(self):
        led = ReconfigLedger()
        ev = led.record(ledger_mod.BATCH_RESIZE, bucket="b",
                        stall_from=5.0)
        led.abandon_stalls("b")
        assert not led.has_pending_stalls
        assert "stall_from" not in ev and "stall_ms" not in ev

    def test_signals_are_flat_counters(self):
        led = ReconfigLedger()
        led.record(ledger_mod.COMPILE, cause="admission")
        sig = led.signals()
        assert sig["ledger_events_total"] == 1.0
        assert not walk_export(sig)


class TestLeakTrendWatch:
    def test_staircase_trips_once_and_rearms(self):
        w = LeakTrendWatch(window=4, min_growth_bytes=100)
        trips = [w.observe(v) for v in (0, 50, 110, 170)]
        assert trips[:3] == [None, None, None]
        assert trips[3] and "leak trend" in trips[3]
        # Still rising: same episode, no second trip.
        assert w.observe(240) is None
        # Plateau re-arms; a fresh staircase trips again.
        assert w.observe(240) is None
        for v in (300, 380, 460):
            last = w.observe(v)
        assert last and w.trips_total == 2

    def test_noise_and_small_growth_do_not_trip(self):
        w = LeakTrendWatch(window=4, min_growth_bytes=1000)
        assert all(w.observe(v) is None
                   for v in (0, 50, 40, 90, 80, 130, 120, 170))
        # Monotone but under the growth floor: no trip.
        w2 = LeakTrendWatch(window=4, min_growth_bytes=10_000)
        assert all(w2.observe(v) is None for v in (0, 10, 20, 30, 40))


# ---------------------------------------------------------------------------
# Serve integration
# ---------------------------------------------------------------------------


def _frontend(**kw):
    cfg = ServeConfig(batch_size=2, queue_size=1000, slo_ms=60_000.0,
                      telemetry_sample_s=0.0, **kw)
    return ServeFrontend(get_filter("invert"), cfg)


class TestServeLedger:
    def test_admission_compile_event_and_histogram(self):
        fe = _frontend()
        with fe:
            fe.open_stream(op_chain="grayscale", frame_shape=(H, W, 3))
            evs = _events(fe, ledger_mod.COMPILE)
            assert len(evs) == 1
            ev = evs[0]
            assert ev["cause"] == "admission" and ev["cache"] == "miss"
            assert ev["compile_ms"] > 0 and ev["wall_ms"] > 0
            assert "grayscale" in ev["signature"]
            # A second identical admission JOINS the live bucket: no
            # new compile, no pool traffic — silence is the record.
            fe.open_stream(op_chain="grayscale", frame_shape=(H, W, 3))
            assert len(_events(fe, ledger_mod.COMPILE)) == 1
            # A precompiled signature's later admission is a pool HIT.
            warmed = fe.precompile([{"op_chain": "grayscale|invert",
                                     "frame_shape": [H, W, 3]}])
            assert warmed
            pre = [e for e in _events(fe, ledger_mod.COMPILE)
                   if e["cause"] == "precompile"]
            assert len(pre) == 1 and pre[0]["cache"] == "miss"
            fe.open_stream(op_chain="grayscale|invert",
                           frame_shape=(H, W, 3))
            hits = _events(fe, ledger_mod.POOL_ACQUIRE)
            assert hits and hits[-1]["cache"] == "hit"
            assert hits[-1]["cause"] == "admission"
            # dvf_compile_ms histogram: labeled by signature AND cause,
            # through the registry (conformance applied at registration).
            samples = [s for s in fe.registry.collect()
                       if s.name.startswith("compile_ms")]
            assert any(s.name == "compile_ms_count"
                       and dict(s.labels).get("cause") == "admission"
                       and "grayscale" in dict(s.labels)["signature"]
                       for s in samples)

    def test_chaos_mix_rebuild_resize_downshift(self, tmp_path):
        """ACCEPTANCE: one engine rebuild + one batch resize + one
        quality downshift in a single run. The resize rides the
        compile-aside hot swap (kind=swap, measured stall_ms ≈ 0, NO
        stall window), the rebind's cutover cost is its measured
        binding swing, and only the recovery rebuild — a real quiesce —
        opens a stall window; events appear in the merged Perfetto
        trace on the dedicated lane, and the flight dump carries
        ledger.json."""
        from dvf_tpu.control import ControlConfig

        # control=True arms the quality-rebind submit path (decimation
        # at the door); the 30 s cadence keeps the controllers inert —
        # every actuation below is manual, so the run is deterministic.
        fe = _frontend(stall_timeout_s=0.0, fault_budget=2, trace=True,
                       flight_dir=str(tmp_path / "flight"),
                       flight_min_interval_s=0.0, control=True,
                       control_config=ControlConfig(interval_s=30.0),
                       out_queue_size=500)
        with fe:
            sid = fe.open_stream(frame_shape=(H, W, 3))
            for j in range(3):  # healthy warm-up, pins the bucket
                _drive_sync(fe, sid, frame_u8(0, j))

            # -- leg 1: batch resize (hot swap: compile-aside + atomic
            # commit — the bucket never quiesces) ----------------------
            label = next(iter(fe.stats()["buckets"]))
            assert fe.request_batch_size(label, 1,
                                        reason="test resize")
            _wait(lambda: _events(fe, ledger_mod.SWAP),
                  msg="swap event never landed")
            for j in range(3, 6):   # post-swap traffic (new program)
                _drive_sync(fe, sid, frame_u8(0, j))
            swap = _events(fe, ledger_mod.SWAP)[0]
            assert swap["cause"] == "resize"
            assert swap["compile_aside_ms"] > 0   # background compile
            assert 0 <= swap["stall_ms"] < 1000.0  # measured commit
            #   swing, recorded directly — NOT a dispatch-gap window
            assert swap["reason"] == "test resize"
            assert not swap.get("aborted")
            assert fe.swaps >= 1

            # -- leg 2: forced engine rebuild (compute budget overflow)
            def dead_step(*a, **k):
                raise RuntimeError("engine died (forced)")

            fe.engine._step = dead_step
            for j in range(6, 9):  # 2 contained + overflow → rebuild
                _drive_sync(fe, sid, frame_u8(0, j))
            _wait(lambda: fe.recoveries >= 1, msg="rebuild never ran")
            for j in range(9, 12):  # rebuilt engine serves → closes
                _drive_sync(fe, sid, frame_u8(0, j))   # the stall window
            _wait(lambda: _events(fe, ledger_mod.ENGINE_REBUILD)
                  and all("stall_ms" in e for e in
                          _events(fe, ledger_mod.ENGINE_REBUILD)),
                  msg="rebuild event/stall never landed")
            rebuild = _events(fe, ledger_mod.ENGINE_REBUILD)[0]
            assert rebuild["cause"] == "recovery"
            assert rebuild["fault_kind"] == "compute"
            assert rebuild["compile_ms"] > 0
            assert rebuild["stall_ms"] > 0

            # -- leg 3: quality downshift (tier rebind WITHOUT a bucket
            # pause: the target program was compiled aside, the cutover
            # cost is the measured binding swing) -----------------------
            assert fe.request_session_quality(sid, 1,
                                              reason="test downshift")
            _wait(lambda: _events(fe, ledger_mod.QUALITY_REBIND),
                  msg="rebind event never landed")
            for j in range(12, 15):
                _drive_sync(fe, sid, frame_u8(0, j))
            rebind = _events(fe, ledger_mod.QUALITY_REBIND)[0]
            assert rebind["cause"] == "quality"
            assert rebind["level"] == 1 and rebind["session"] == sid
            assert 0 <= rebind["stall_ms"] < 1000.0  # measured swing
            # Its program compile was ledgered under cause=quality.
            qcompiles = [e for e in _events(fe, ledger_mod.COMPILE)
                         if e["cause"] == "quality"]
            assert qcompiles and qcompiles[0]["compile_ms"] > 0

            # Every event in the ledger carries a cause or kind + the
            # thread that ran it; the export walks clean. Only the
            # recovery rebuild — a true quiesce — opened a stall
            # window; the resize and rebind were stall-free.
            summary = fe.ledger.summary()
            assert summary["stall_events_total"] >= 1
            assert not walk_export(summary), walk_export(summary)

            # -- merged Perfetto trace: dedicated reconfig lane --------
            from dvf_tpu.obs.trace import merge_tracer_snapshots

            doc = merge_tracer_snapshots([fe.tracer.snapshot()])
            names = {e.get("name") for e in doc["traceEvents"]}
            assert "reconfig:swap" in names
            assert "reconfig:engine_rebuild" in names
            assert "reconfig:quality_rebind" in names
            assert "reconfig_stall_closed" in names
            # All on the ledger's own lane, clear of the stage lanes.
            lanes = {e.get("pid") for e in doc["traceEvents"]
                     if str(e.get("name", "")).startswith("reconfig")}
            assert lanes == {ledger_mod.TRACK_LEDGER}

            # -- flight dump carries ledger.json -----------------------
            dump = fe.flight.trigger("test: mixed reconfiguration run")
            assert dump is not None
            led_doc = json.load(open(os.path.join(dump, "ledger.json")))
            kinds = {e["kind"] for e in led_doc["events"]}
            assert {"swap", "engine_rebuild",
                    "quality_rebind"} <= kinds

            # -- trace-view renders the events inline ------------------
            from dvf_tpu.obs.viewer import render_text, summarize

            view = summarize(dump)
            assert view["reconfigurations"]
            vkinds = {e["kind"] for e in view["reconfigurations"]}
            assert "engine_rebuild" in vkinds
            text = render_text(view)
            assert "reconfiguration events" in text
            assert "engine_rebuild/recovery" in text

    def test_ledger_endpoint(self):
        from dvf_tpu.obs.export import MetricsExporter

        fe = _frontend()
        with fe:
            fe.open_stream(op_chain="grayscale", frame_shape=(H, W, 3))
            ex = MetricsExporter(fe.registry, port=0,
                                 ledger_fn=fe.ledger.document).start()
            try:
                with urllib.request.urlopen(f"{ex.url}/ledger") as r:
                    doc = json.loads(r.read())
                assert doc["events_total"] >= 1
                assert any(e["kind"] == "compile" for e in doc["events"])
                # /metrics carries the dvf_mem_* family.
                with urllib.request.urlopen(f"{ex.url}/metrics") as r:
                    text = r.read().decode()
                assert "dvf_mem_device_live_bytes" in text
                assert "dvf_mem_host_slab_bytes" in text
                assert "dvf_compile_ms_bucket" in text
            finally:
                ex.stop()

    def test_ledger_off_zero_surface(self):
        fe = _frontend(ledger=False)
        with fe:
            sid = fe.open_stream()
            _drive_sync(fe, sid, frame_u8(0, 0))
            st = fe.stats()
            assert "ledger" not in st and "memory" not in st
            sig = fe.signals()
            assert "ledger_events_total" not in sig
            assert "mem_host_slab_bytes" not in sig
            assert not any(s.name.startswith(("mem_", "compile_ms"))
                           for s in fe.registry.collect())

    def test_memory_accounting_and_release_at_stop(self):
        from dvf_tpu.runtime import egress, ingest

        fe = _frontend()
        with fe:
            sid = fe.open_stream()
            _drive_sync(fe, sid, frame_u8(0, 0))
            sig = fe.signals()
            assert sig["mem_host_slab_bytes"] > 0  # staging pool is live
            mem = fe.stats()["memory"]
            assert mem["host_slab_bytes"] == sig["mem_host_slab_bytes"]
            assert mem["by_bucket"]  # per-bucket attribution rows
            # Process-wide scrape document (the dvf_mem_* source).
            doc = memory_summary()
            assert doc["host_slab_bytes"] >= mem["host_slab_bytes"]
            assert doc["device_live_bytes"] is None \
                or doc["device_live_bytes"] >= 0
        # Stop released every slab this frontend pinned.
        gc.collect()
        assert sum(b.lane.slab_bytes() for b in fe._buckets) == 0
        # And nothing of this frontend's remains in the registries.
        assert all(a.slab_bytes() == 0 for a in ingest.live_assemblers())
        assert all(f.slab_bytes() == 0 for f in egress.live_fetchers())

    def test_leak_watch_trips_flight(self, tmp_path):
        """A synthetic rising mem_host_slab_bytes staircase through the
        telemetry hook trips the flight recorder once."""
        fe = _frontend(flight_dir=str(tmp_path / "flight"),
                       flight_min_interval_s=0.0)
        fe._leak_watch = LeakTrendWatch(window=3, min_growth_bytes=10)
        with fe:
            before = fe.flight.stats()["dumps"]
            for v in (0.0, 100.0, 250.0, 400.0):
                fe._on_telemetry_sample(None, {"mem_host_slab_bytes": v})
            _wait(lambda: fe.flight.stats()["dumps"] == before + 1,
                  msg="leak trend never dumped")
            assert "leak trend" in fe.flight.last_reason

    def test_lineage_additivity_with_ledger_armed(self):
        """Satellite: the two planes coexist — every delivered frame's
        lineage components still telescope to its e2e latency while the
        ledger records a live resize in the same run."""
        fe = _frontend(lineage=True, trace=True)
        with fe:
            sid = fe.open_stream(frame_shape=(H, W, 3))
            for j in range(4):
                _drive_sync(fe, sid, frame_u8(0, j))
            label = next(iter(fe.stats()["buckets"]))
            assert fe.request_batch_size(label, 1, reason="mid-run")
            _wait(lambda: _events(fe, ledger_mod.SWAP),
                  msg="resize swap never landed")
            for j in range(4, 10):
                _drive_sync(fe, sid, frame_u8(0, j))
            got = drain(fe, sid, 10)
            assert len(got) == 10
            for d in got:
                assert d.lineage is not None
                assert sum(d.lineage.components_ms().values()) == \
                    pytest.approx(d.latency_ms, abs=1e-6)
            assert fe.ledger.summary()["by_kind"]["swap"] >= 1
            assert not walk_export(fe.stats())

    def test_same_deliveries_with_the_ledger_on_and_off(self):
        """The same frames through the same resize, ledger armed and
        not: the deliveries are byte-identical, the armed run ledgers
        the reconfiguration, and both are scraped while serving (the
        armed leg pays its dvf_mem_* walk there)."""
        runs = {}
        for armed in (True, False):
            fe = _frontend(ledger=armed)
            with fe:
                sid = fe.open_stream(frame_shape=(H, W, 3))
                for j in range(6):
                    _drive_sync(fe, sid, frame_u8(3, j))
                label = next(iter(fe.stats()["buckets"]))
                assert fe.request_batch_size(label, 1, reason="mid-run")
                _wait(lambda: fe.swaps >= 1,
                      msg="resize swap never landed")
                fe.registry.collect()
                for j in range(6, 12):
                    _drive_sync(fe, sid, frame_u8(3, j))
                got = drain(fe, sid, 12)
                runs[armed] = ([d.index for d in got],
                               [d.frame.tobytes() for d in got],
                               fe.stats().get("ledger"))
        assert runs[True][0] == runs[False][0] == list(range(12))
        assert runs[True][1] == runs[False][1]
        assert runs[False][2] is None
        assert runs[True][2]["events_total"] >= 1
        assert runs[True][2]["by_kind"]["swap"] == 1
