"""Auto-plan plane tests (PR 20): plan-cache keying/invalidation, the
planner's analytic prune + measured search, calibration persistence,
the feed-forward predictive elasticity controller, a cold search and a
warm restart through a live frontend, and a live fleet's recorded window
replayed offline.

Keying discipline pinned here: a plan searched under one (op chain,
geometry, topology, planner version) must NEVER drive another — each
axis changing is a miss, a corrupt entry is a miss, and a miss re-plans
rather than crashes.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from dvf_tpu.control import plan_cache as pc
from dvf_tpu.control import planner as pl

TOPO = "cpu/cpu/n1/data=1,space=1,model=1"
GEO = (32, 32, 3)
SIG = "invert|32x32x3|uint8"


def _measured(**kw):
    return dataclasses.replace(
        pl.Plan(**kw), source=pl.PLAN_SOURCE_MEASURED, measured_fps=100.0)


# ---------------------------------------------------------------------------
# Plan cache: keying and invalidation
# ---------------------------------------------------------------------------


def test_plan_cache_round_trip(tmp_path):
    d = str(tmp_path)
    plan = _measured(batch_size=16, tick_s=0.001, ingest_depth=2)
    assert pc.save_plan(d, SIG, GEO, TOPO, plan.to_doc()) is not None
    got = pc.load_plan(d, SIG, GEO, TOPO)
    assert got is not None and got["batch_size"] == 16
    # The typed wrapper re-stamps provenance: a hit must SAY it's a hit.
    cached = pl.plan_from_cache(d, SIG, GEO, TOPO)
    assert cached is not None
    assert cached.source == pl.PLAN_SOURCE_CACHE
    assert cached.batch_size == 16 and cached.tick_s == 0.001


def test_plan_cache_every_key_axis_misses(tmp_path):
    d = str(tmp_path)
    pc.save_plan(d, SIG, GEO, TOPO, _measured().to_doc())
    assert pc.load_plan(d, SIG, GEO, TOPO) is not None
    # Op chain / signature changed.
    assert pc.load_plan(d, "blur|32x32x3|uint8", GEO, TOPO) is None
    # Geometry changed.
    assert pc.load_plan(d, SIG, (64, 64, 3), TOPO) is None
    # Topology changed (plan searched on 1 core must not drive 8).
    assert pc.load_plan(d, SIG, GEO, "tpu/v5e/n8/data=8") is None
    # Planner version bumped: grid/scoring changed shape, re-search.
    assert pc.load_plan(d, SIG, GEO, TOPO,
                        planner_version=pc.PLANNER_VERSION + 1) is None


def test_plan_cache_corrupt_and_foreign_entries_are_misses(tmp_path):
    d = str(tmp_path)
    path = pc.save_plan(d, SIG, GEO, TOPO, _measured().to_doc())
    # Corrupt JSON: a miss, never a raise.
    with open(path, "w") as f:
        f.write("{not json")
    assert pc.load_plan(d, SIG, GEO, TOPO) is None
    # An entry whose EMBEDDED key fields disagree with the request (a
    # hash collision or a hand-copied file) degrades to a miss too.
    doc = {"schema": pc.PLAN_SCHEMA, "planner_version": pc.PLANNER_VERSION,
           "signature": "other|sig", "geometry": list(GEO),
           "topology": TOPO, "plan": _measured().to_doc()}
    with open(path, "w") as f:
        json.dump(doc, f)
    assert pc.load_plan(d, SIG, GEO, TOPO) is None
    # Foreign schema.
    with open(path, "w") as f:
        json.dump({"schema": "somebody.elses.v9"}, f)
    assert pc.load_plan(d, SIG, GEO, TOPO) is None
    # Missing cache dir / None dir: a miss, not an error.
    assert pc.load_plan(os.path.join(d, "nope"), SIG, GEO, TOPO) is None
    assert pc.load_plan(None, SIG, GEO, TOPO) is None


def test_plan_to_cache_refuses_unmeasured(tmp_path):
    d = str(tmp_path)
    analytic = dataclasses.replace(pl.Plan(), source=pl.PLAN_SOURCE_ANALYTIC)
    assert pl.plan_to_cache(d, SIG, GEO, TOPO, analytic) is None
    assert pl.plan_from_cache(d, SIG, GEO, TOPO) is None
    assert pl.plan_to_cache(d, SIG, GEO, TOPO, _measured()) is not None
    assert pl.plan_from_cache(d, SIG, GEO, TOPO) is not None


# ---------------------------------------------------------------------------
# Plan validation / envelope
# ---------------------------------------------------------------------------


def test_plan_from_doc_rejects_garbage():
    assert pl.Plan.from_doc(None) is None
    assert pl.Plan.from_doc("not a dict") is None
    assert pl.Plan.from_doc({"batch_size": 0}) is None
    assert pl.Plan.from_doc({"batch_size": "eight"}) is None
    assert pl.Plan.from_doc({"tick_s": -1.0}) is None
    assert pl.Plan.from_doc({"ingest": "psychic"}) is None
    assert pl.Plan.from_doc({"wire": "carrier-pigeon"}) is None
    good = pl.Plan.from_doc(_measured(batch_size=4).to_doc())
    assert good is not None and good.batch_size == 4
    # Unknown keys are ignored (forward compatibility), not fatal.
    assert pl.Plan.from_doc({**_measured().to_doc(),
                             "new_field": 1}) is not None


def test_envelope_caps_ladder_at_planned_batch():
    env = pl.Plan(batch_size=8, tick_s=0.001).envelope()
    assert env["batch_ladder"] == (1, 2, 4, 8)
    assert env["batch_max"] == 8
    assert env["tick_busy_s"] == 0.001
    # Non-power-of-two planned batch still tops its own ladder.
    env = pl.Plan(batch_size=6).envelope()
    assert env["batch_ladder"][-1] == 6 and env["batch_max"] == 6


# ---------------------------------------------------------------------------
# Search: grid, analytic prune, measured ranking
# ---------------------------------------------------------------------------


def test_candidate_grid_shape():
    grid = pl.candidate_grid(batch_cap=8)
    # Ladder 1,2,4,8 x 3 ticks x 3 depths, wire/codec axes collapsed.
    assert len(grid) == 36
    assert {p.batch_size for p in grid} == {1, 2, 4, 8}
    assert len({p.label() for p in grid}) == len(grid)


def test_shortlist_keeps_at_most_a_third():
    grid = pl.candidate_grid(batch_cap=8)
    cal = {"h2d_block_ms": 0.5, "d2h_block_ms": 0.2, "step_block_ms": 2.0}
    short = pl.shortlist(grid, cal, cal_batch=8)
    assert len(short) <= len(grid) // 3
    assert all(p.predicted_frame_ms is not None for p in short)
    # Deterministic: same inputs, same order.
    again = pl.shortlist(grid, cal, cal_batch=8)
    assert [p.label() for p in short] == [p.label() for p in again]
    # live_budget narrows further but never widens past the third.
    assert len(pl.shortlist(grid, cal, 8, None, live_budget=2)) == 2
    assert len(pl.shortlist(grid, cal, 8, None,
                            live_budget=999)) <= len(grid) // 3


def test_plan_search_measured_winner():
    grid = pl.candidate_grid(batch_cap=8)
    cal = {"h2d_block_ms": 0.5, "d2h_block_ms": 0.2, "step_block_ms": 2.0}

    def measure(p):
        # Scripted: throughput rewards batch, penalizes slow ticks —
        # the search must surface the scripted optimum, not the
        # analytic front-runner.
        return {"fps": p.batch_size * 100.0 - p.tick_s * 1e4}

    plan, comp = pl.plan_search(grid, measure, cal=cal, cal_batch=8)
    assert plan.source == pl.PLAN_SOURCE_MEASURED
    assert plan.batch_size == 8
    assert plan.searched <= len(grid) // 3
    assert plan.grid == len(grid)
    assert comp["winner"] == plan.label()
    assert plan.measured_fps == pytest.approx(
        8 * 100.0 - plan.tick_s * 1e4)


def test_plan_search_all_legs_error_degrades_to_analytic():
    grid = pl.candidate_grid(batch_cap=4)
    plan, comp = pl.plan_search(
        grid, lambda p: {"error": "burst stalled"},
        cal={"h2d_block_ms": 0.5, "step_block_ms": 2.0}, cal_batch=4)
    assert plan.source == pl.PLAN_SOURCE_ANALYTIC
    # And an analytic plan never persists as if measured.
    assert pl.plan_to_cache("/tmp/x", SIG, GEO, TOPO, plan) is None


@pytest.mark.parametrize("case, measured, winner", [
    ("ranking", "abc", "b"),
    ("failed_leg", "abc", "c"),      # b errors: recorded, cannot win
    ("kept_prior", "bc", "a"),       # a's prior entry is fresh: seeded
])
def test_ab_comparison(case, measured, winner):
    """The leg machinery where it lives now: legs are measured in order
    and the highest ``fps`` wins; an erroring leg is recorded, never
    raised; a prior leg ``keep_leg`` accepts is seeded and not measured
    again (a prior leg that is no leg of this comparison is left out)."""
    fps = {"a": 10.0, "b": 30.0, "c": 20.0}
    prior = {"a": {"fps": 99.0, "fresh": True},
             "b": {"fps": 1.0, "fresh": False}, "zz": {"fps": 500.0}}
    seen, said = [], []

    def measure(label, payload):
        seen.append((label, payload))
        if case == "failed_leg" and label == "b":
            return {"error": "boom"}
        return {"fps": fps[label]}

    comp = pl.ab_comparison(
        [(k, k.upper()) for k in fps], measure,
        prior=prior if case == "kept_prior" else None,
        keep_leg=lambda entry: entry.get("fresh"), log=said.append)
    assert seen == [(k, k.upper()) for k in measured]
    assert comp.pop("winner") == winner
    assert set(comp) == set(fps)
    assert comp["b"] == ({"error": "boom"} if case == "failed_leg"
                         else {"fps": 30.0})
    assert (comp["a"] is prior["a"]) == (case == "kept_prior") == bool(said)
    every = pl.ab_comparison([("x", None)], lambda *_: {"error": "x"})
    assert every["winner"] == "n/a"


def test_predicted_tick_cost_ms_feeds_forward():
    assert pl.predicted_tick_cost_ms(None) is None
    assert pl.predicted_tick_cost_ms({}) is None
    # Measured EWMA wins.
    assert pl.predicted_tick_cost_ms({"tick_cost_ms": 3.5}) == 3.5
    # Falls back to per-frame component means x batch.
    prof = {"components_ms": {"assemble_h2d": {"mean_ms": 0.5},
                              "device": {"mean_ms": 1.0},
                              "d2h": {"mean_ms": 0.5}}}
    assert pl.predicted_tick_cost_ms(prof, batch_size=4) == 8.0


# ---------------------------------------------------------------------------
# Calibrations: persistence + warm-restart seeding
# ---------------------------------------------------------------------------


def test_calibration_round_trip_and_merge(tmp_path):
    d = str(tmp_path)
    cal = {"h2d_block_ms": 0.4, "d2h_block_ms": None,
           "step_block_ms": 2.25}
    assert pc.save_calibrations(d, TOPO, "b8|" + SIG, cal) is not None
    got = pc.load_calibrations(d, TOPO, "b8|" + SIG)
    # d2h None is preserved (legitimately unmeasured above the size
    # cap) — a seed must reproduce it, not invent a number.
    assert got == {"h2d_block_ms": 0.4, "d2h_block_ms": None,
                   "step_block_ms": 2.25}
    # Second signature merges into the same topology file.
    pc.save_calibrations(d, TOPO, "b4|other",
                         {"h2d_block_ms": 0.1, "step_block_ms": 1.0})
    assert pc.load_calibrations(d, TOPO, "b8|" + SIG) is not None
    assert pc.load_calibrations(d, TOPO, "b4|other") is not None
    # Other topology: miss.
    assert pc.load_calibrations(d, "tpu/v5e/n8/data=8",
                                "b8|" + SIG) is None


def test_calibration_incomplete_or_corrupt_is_miss(tmp_path):
    d = str(tmp_path)
    # A seed without a usable step cost is not worth skipping the
    # measurement passes for.
    pc.save_calibrations(d, TOPO, "s", {"h2d_block_ms": 0.4,
                                        "step_block_ms": None})
    assert pc.load_calibrations(d, TOPO, "s") is None
    pc.save_calibrations(d, TOPO, "s2", {"h2d_block_ms": None,
                                         "step_block_ms": 1.0})
    assert pc.load_calibrations(d, TOPO, "s2") is None
    with open(pc.calibration_path(d, TOPO), "w") as f:
        f.write("garbage")
    assert pc.load_calibrations(d, TOPO, "s") is None


def test_topology_fingerprint_meshless_matches_default_mesh():
    """The fleet front door plans with NO mesh; a serve Engine plans
    under its default mesh. The two fingerprints must agree or the
    door could never hit a plan a serve frontend cached."""
    import jax

    from dvf_tpu.parallel.mesh import auto_mesh_config, make_mesh

    meshless = pc.topology_fingerprint()
    cfg = auto_mesh_config(len(jax.devices()))
    meshed = pc.topology_fingerprint(make_mesh(cfg))
    assert meshless == meshed
    assert meshless != "unknown"


def test_engine_calibration_seed_skips_remeasure(tmp_path):
    """Warm-restart satellite: the first frontend MEASURES and persists
    the calibration triple; a second frontend on the same cache dir
    seeds its engine from disk (engine.calibration_seeded) instead of
    re-running the blocking measurement passes."""
    from dvf_tpu.runtime.signature import build_filter
    from dvf_tpu.serve import ServeConfig, ServeFrontend

    d = str(tmp_path)

    def boot():
        fe = ServeFrontend(build_filter("invert"), ServeConfig(
            batch_size=2, plan_cache_dir=d)).start()
        sid = fe.open_stream(op_chain="invert", frame_shape=(16, 16, 3))
        fe.submit(sid, np.zeros((16, 16, 3), np.uint8))
        while not fe.poll(sid):
            pass
        with fe._lock:
            eng = fe._sessions[sid].bucket.engine
        seeded = eng.calibration_seeded
        cal = {"h2d": eng.h2d_block_ms, "step": eng.step_block_ms}
        fe.stop()
        return seeded, cal

    cold_seeded, cold_cal = boot()
    assert cold_seeded is False
    assert cold_cal["step"] is not None
    warm_seeded, warm_cal = boot()
    assert warm_seeded is True
    # The adopted triple IS the one the cold boot measured.
    assert warm_cal["step"] == pytest.approx(cold_cal["step"])


# ---------------------------------------------------------------------------
# Predictive elasticity: determinism + the half-watermark guard
# ---------------------------------------------------------------------------


def _ctl(predictive):
    from dvf_tpu.control.fleet_elastic import (
        ElasticConfig,
        make_elasticity_controller,
    )

    cfg = ElasticConfig(min_replicas=1, max_replicas=4, out_after=2,
                        out_cooldown=4, predictive=predictive,
                        predict_slope_window=3, predict_horizon=4)
    return make_elasticity_controller(cfg)


def _row(bound, qd=0.0, cap=10.0, refusals=0.0):
    return {"bound_sessions": bound, "capacity_sessions": cap,
            "open_sessions": bound, "fleet_queue_depth": qd,
            "admission_refusals_total": refusals,
            "fleet_shed_total": 0.0, "fleet_slo_miss_total": 0.0,
            "replicas_desired": 1, "replicas_live": 1}


def _run(ctl, rows):
    prev, out = None, []
    for i, row in enumerate(rows):
        for a in ctl.step(dict(row), prev):
            out.append((i, a.kind, a.target, a.value, a.reason))
        prev = row
    return out


def test_predictive_spawns_before_reactive_on_a_ramp():
    # Occupancy climbing 1/sample toward high = 0.85*10: reactive fires
    # at bound >= 8.5; predictive projects 4 samples ahead and fires
    # once the current value clears the half-watermark guard.
    ramp = ([_row(float(b)) for b in range(1, 10)]
            + [_row(9.0)] * 4)
    p_act = _run(_ctl(True), ramp)
    r_act = _run(_ctl(False), ramp)
    p_out = next(i for i, k, *_ in p_act if k == "scale_out")
    r_out = next(i for i, k, *_ in r_act if k == "scale_out")
    assert p_out < r_out
    assert "projected" in p_act[0][4]


def test_predictive_half_watermark_guard_blocks_idle_slope():
    # One tenant opening on a near-idle fleet: slope > 0, projection
    # can cross anything, but the CURRENT value is nowhere near the
    # watermark — prediction must not invent pressure from noise.
    idle_blip = [_row(0.0), _row(1.0), _row(2.0), _row(2.0), _row(2.0),
                 _row(2.0)]
    assert _run(_ctl(True), idle_blip) == []


def test_predictive_is_a_strict_widening_of_reactive():
    # A window the reactive controller scales on (refusals advancing):
    # predictive scales too, no later.
    rows = [_row(3.0), _row(3.0, refusals=1.0), _row(3.0, refusals=2.0),
            _row(3.0, refusals=3.0)]
    r_act = _run(_ctl(False), rows)
    p_act = _run(_ctl(True), rows)
    r_out = [i for i, k, *_ in r_act if k == "scale_out"]
    p_out = [i for i, k, *_ in p_act if k == "scale_out"]
    assert r_out and p_out and p_out[0] <= r_out[0]


def test_predictive_replay_is_deterministic():
    rows = ([_row(float(b)) for b in range(1, 8)]
            + [_row(7.0, refusals=float(r)) for r in range(5)])
    assert _run(_ctl(True), rows) == _run(_ctl(True), rows)
    assert _run(_ctl(False), rows) == _run(_ctl(False), rows)


# ---------------------------------------------------------------------------
# The plane end to end: a warm plan cache, and a live window replayed
# ---------------------------------------------------------------------------


def test_warm_plan_cache_yields_one_plan_event_read_from_the_cache(tmp_path):
    """A cold boot searches (a miss: at most a third of the grid goes
    live, the winner is measured and cached); a second frontend on the
    same cache directory applies that plan from one read: one ``plan``
    event, a hit, no legs, the same operating point."""
    from dvf_tpu.ops import get_filter
    from dvf_tpu.serve import ServeConfig, ServeFrontend

    def boot():
        fe = ServeFrontend(get_filter("invert"), ServeConfig(
            batch_size=4, queue_size=32, out_queue_size=1024,
            slo_ms=60_000.0, autoplan=True, plan_cache_dir=str(tmp_path),
            autoplan_burst_frames=8))
        with fe:
            doc = fe.autoplan(GEO, "uint8")
            events = [e for e in fe.ledger.document()["events"]
                      if e["kind"] == "plan"]
        return doc, events

    cold, cold_events = boot()
    assert [e["cache"] for e in cold_events] == ["miss"]
    assert cold["source"] == pl.PLAN_SOURCE_MEASURED
    assert 1 <= cold["searched"] <= cold["grid"] // 3
    assert cold_events[0]["legs"] == cold["searched"]

    warm, warm_events = boot()
    assert [e["cache"] for e in warm_events] == ["hit"]
    assert warm_events[0]["legs"] == 0
    assert warm["source"] == pl.PLAN_SOURCE_CACHE
    assert [warm[k] for k in ("batch_size", "tick_s", "ingest_depth")] == \
        [cold[k] for k in ("batch_size", "tick_s", "ingest_depth")]


def test_live_predictive_window_replays_byte_identically_twice():
    """A local fleet under the predictive controller takes tenants one
    by one until it has scaled out; the window it recorded, replayed
    through two fresh predictive controllers, gives the recorded actions
    both times, and over the same rows the predictive controller scales
    out no later than the reactive one."""
    import time

    from dvf_tpu.control.fleet_elastic import (
        ElasticConfig,
        make_elasticity_controller,
    )
    from dvf_tpu.fleet import FleetConfig, FleetFrontend
    from dvf_tpu.ops import get_filter
    from dvf_tpu.serve import AdmissionError, ServeConfig

    elastic = ElasticConfig(
        min_replicas=1, max_replicas=2, interval_s=0.1, out_after=2,
        out_cooldown=4, in_after=30, in_cooldown=3, in_occupancy_frac=0.6,
        predictive=True, predict_slope_window=3, predict_horizon=4)
    fleet = FleetFrontend(get_filter("invert"), FleetConfig(
        replicas=1, mode="local", autoscale=(1, 2), standby_warm=1,
        elastic=elastic, health_poll_s=0.05,
        serve=ServeConfig(batch_size=2, queue_size=256, slo_ms=60_000.0,
                          max_sessions=4)))
    with fleet:
        deadline = time.time() + 60.0
        while fleet.signals()["replicas_live"] < 2:
            assert time.time() < deadline, fleet.stats()
            try:
                fleet.open_stream()     # the occupancy ramps
            except AdmissionError:
                pass
            time.sleep(0.25)            # a few samples a tenant
        window = fleet.elastic.replay_window()
    recorded = [tuple(a) for a in window["actions"]]
    assert "scale_out" in [a[0] for a in recorded]

    def replay(config):
        return _run(make_elasticity_controller(config), window["rows"])

    first, second = replay(elastic), replay(elastic)
    assert first == second
    assert [a[1:] for a in first] == recorded
    reactive = replay(dataclasses.replace(elastic, predictive=False))
    first_out = next(i for i, kind, *_ in first if kind == "scale_out")
    assert all(i >= first_out for i, kind, *_ in reactive
               if kind == "scale_out")
