"""The four-chip style deployment (chipbench's ``style_720p_v5e4``) on the
normal path: ``FleetFrontend(mode="local", replicas=4,
devices_per_replica=1)`` over four one-device replicas, each the one-chip
style service.

Toy size on the CPU (64×96, batch 4, the configuration's own ``toy``
block; conftest.py gives the CPU eight virtual devices, the fleet takes
the first four), seeded random weights from the benchmark's plain
reference (``chipbench/refs/style_720p.py``, loaded by path: it imports
nothing of the program), one tree handed to every replica. What is held:

(a) every delivered frame is within the configuration's limits of the
    reference, per-session order holds, every session is bound to one
    replica and all four replicas are used;
(b) the reference's fp8 control, put in the program's place, exceeds a
    limit;
(c) the front door's ``door`` block and admission's ``placement`` block:
    window deltas add up, the per-replica blocks sum to the fleet's, a
    replica's block rides its own bucket rows, and ``stats()`` carries
    each replica's bucket rows (R-M2);
(d) with ``trace`` on, ``fleet:submit`` / ``fleet:poll`` spans carry the
    replica and the deliveries.
"""

import importlib.util
import json
import os
import time

import jax
import numpy as np
import pytest

from dvf_tpu.fleet import FleetConfig, FleetFrontend
from dvf_tpu.fleet.admission import PLACEMENT_KEYS, SpilloverAdmission
from dvf_tpu.fleet.stats import DOOR_KEYS, DoorStats
from dvf_tpu.ops import get_filter
from dvf_tpu.serve import ServeConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SESSIONS, FRAMES, POOL = 8, 12, 8
SEEDS = (1, 2)


def _load(relpath, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "chipbench", "configs", "style_720p_v5e4.json")) as f:
        cfg = json.load(f)
    for key, val in cfg["toy"].items():
        cfg[key] = {**cfg[key], **val}
    assert cfg["fleet"] == {"replicas": 4, "mode": "local", "devices_per_replica": 1}
    return cfg


@pytest.fixture(scope="module")
def ref(config):
    return _load("chipbench/" + config["reference"]["module"], "style_720p_ref")


@pytest.fixture(scope="module")
def check():
    return _load("chipbench/check.py", "chipbench_check_for_fleet")


def _pool(seed, config):
    """The benchmark's own seeded frames (``chipbench/frames.py``)."""
    from chipbench.frames import make_pool

    g = config["geometry"]
    return make_pool(seed, (g["height"], g["width"], g["channels"]), POOL)


def _fleet(config, params, trace=False):
    kwargs = dict(config["filter"]["kwargs"])
    kwargs["params"] = jax.tree.map(np.asarray, params)      # one host tree for every replica
    f = config["fleet"]
    serve = ServeConfig(**config["serve"], trace=trace)
    return FleetFrontend(get_filter(config["filter"]["name"], **kwargs),
                         FleetConfig(replicas=f["replicas"], mode=f["mode"],
                                     devices_per_replica=f["devices_per_replica"], serve=serve))


def _drive(fe, config, pool, sessions=SESSIONS, frames=FRAMES, timeout=300.0):
    """Opens ``sessions``, sends ``frames`` each (session k's frame i is
    pool[(k + i) % POOL]) and reads everything back: {sid: [Delivery]}."""
    g = config["geometry"]
    shape = (g["height"], g["width"], g["channels"])
    sids = [fe.open_stream(frame_shape=shape, slo_ms=60000.0) for _ in range(sessions)]
    for i in range(frames):
        for k, sid in enumerate(sids):
            assert fe.submit(sid, pool[(k + i) % POOL]) == i
    got = {sid: [] for sid in sids}
    deadline = time.time() + timeout
    while sum(len(v) for v in got.values()) < sessions * frames:
        assert time.time() < deadline, {sid: len(v) for sid, v in got.items()}
        moved = 0
        for sid in sids:
            out = fe.poll(sid)
            got[sid] += out
            moved += len(out)
        if not moved:
            time.sleep(0.005)
    return sids, got


@pytest.fixture(scope="module")
def served(config, ref):
    """One run per seed through the fleet: what the tests below read."""
    runs = {}
    for seed in SEEDS:
        params = ref.make_params(seed, config)
        pool = _pool(seed, config)
        fe = _fleet(config, params)
        with fe:
            before = fe.stats()
            sids, got = _drive(fe, config, pool)
            runs[seed] = {"params": params, "pool": pool, "sids": sids, "got": got,
                          "before": before, "after": fe.stats(),
                          "full": {rid: r.stats_full()["stats"]
                                   for rid, r in sorted(fe._replicas.items())}}
    return runs


def _numbers(run, wanted, check):
    """The benchmark's own comparison (``chipbench/check.py``) over every
    delivery of the run: session k's frame i is pool[(k + i) % POOL], which
    is ``chipbench.frames.pool_index``."""
    samples = [(k, d.index, d.frame) for k, sid in enumerate(run["sids"]) for d in run["got"][sid]]
    numbers = check.compare_numbers(samples, wanted, POOL)
    assert numbers.pop("shape_mismatch") == 0
    return numbers


@pytest.mark.parametrize("seed", SEEDS)
def test_every_delivery_within_the_limits_of_the_reference(served, config, ref, check, seed):
    run = served[seed]
    wanted = ref.reference(run["pool"], config, run["params"])
    numbers = _numbers(run, wanted, check)
    assert check.decide(numbers, config["limits"], log=lambda _m: None), numbers
    assert numbers["max_abs_steps"] > 0          # bfloat16 against float32: not the same arithmetic


@pytest.mark.parametrize("seed", SEEDS)
def test_order_affinity_and_all_four_replicas(served, seed):
    run = served[seed]
    for sid in run["sids"]:
        assert [d.index for d in run["got"][sid]] == list(range(FRAMES))
    st = run["after"]
    assert st["order_violations"] == 0
    rows = st["sessions"]
    assert {rows[sid]["replica"] for sid in run["sids"]} == {"r0", "r1", "r2", "r3"}
    assert all(rows[sid]["migrations"] == 0 and rows[sid]["lost"] == 0 for sid in run["sids"])
    # a session's frames were served by its own replica and by no other
    for rid, full in run["full"].items():
        mine = {sid for sid in run["sids"] if rows[sid]["replica"] == rid}
        assert set(full["sessions"]) == mine
        assert all(full["sessions"][sid]["delivered"] == FRAMES for sid in mine)
    assert st["replica_restarts"] == 0 and st["replica_losses"] == 0
    assert set(st["recoveries"].values()) == {0}


@pytest.mark.parametrize("seed", SEEDS)
def test_fp8_control_exceeds_a_limit(config, ref, check, seed):
    params = ref.make_params(seed, config)
    pool = _pool(seed, config)
    wanted = ref.reference(pool, config, params)
    control = ref.control(pool, config, params)
    numbers = check.compare_numbers([(0, i, f) for i, f in enumerate(control)], wanted, POOL)
    assert not check.decide(numbers, config["limits"], log=lambda _m: None), numbers


@pytest.mark.parametrize("seed", SEEDS)
def test_door_block_adds_up(served, seed):
    run = served[seed]
    door0, door1 = run["before"]["door"], run["after"]["door"]
    assert door0["deliveries_total"] == 0 and door0["by_replica"] == {}
    assert door1["deliveries_total"] == SESSIONS * FRAMES       # = frames polled
    assert door1["submit_calls_total"] == SESSIONS * FRAMES
    assert door1["poll_calls_total"] >= SESSIONS                # empty polls count too
    assert door1["submit_us_total"] > 0 and door1["poll_us_total"] > 0
    assert set(door1["by_replica"]) == {"r0", "r1", "r2", "r3"}
    for key in DOOR_KEYS:
        assert door1[key] == pytest.approx(sum(b[key] for b in door1["by_replica"].values()), abs=1.0)
    per = SESSIONS // 4
    for rid, block in door1["by_replica"].items():
        assert block["replica"] == rid
        assert block["deliveries_total"] == per * FRAMES and block["submit_calls_total"] == per * FRAMES


@pytest.mark.parametrize("seed", SEEDS)
def test_a_replicas_door_rides_its_bucket_rows_and_stats_carries_them(served, seed):
    run = served[seed]
    for rid, full in run["full"].items():
        rows = [r for r in full["buckets"].values() if r.get("batches")]
        assert rows
        for row in rows:
            assert row["door"]["replica"] == rid
            assert row["door"]["deliveries_total"] == run["after"]["door"]["by_replica"][rid][
                "deliveries_total"]
        # R-M2: the same rows through FleetFrontend.stats(), no private access
        listed = run["after"]["replicas"][rid]["buckets"]
        assert set(listed) == set(full["buckets"])
        for label, row in listed.items():
            assert row["batches"] == full["buckets"][label]["batches"]
            assert {"stages", "ingest", "egress", "starved"} <= set(row) or not row["batches"]


@pytest.mark.parametrize("seed", SEEDS)
def test_placement_block_adds_up(served, seed):
    run = served[seed]
    p0, p1 = run["before"]["placement"], run["after"]["placement"]
    assert p0["placed_total"] == 0 and p0["by_replica"] == {}
    assert p1["placed_total"] == SESSIONS                        # = sessions opened
    assert p1["migrations_total"] == 0 and p1["spillovers_total"] == 0
    assert {rid: b["placed_total"] for rid, b in p1["by_replica"].items()} == dict.fromkeys(
        ("r0", "r1", "r2", "r3"), SESSIONS // 4)                 # least-loaded first: two each
    for key in PLACEMENT_KEYS:
        assert p1[key] == sum(b[key] for b in p1["by_replica"].values())
    assert p1["warm_hits_total"] == run["after"]["warm_placements"]


def test_submit_clock_grows_only_under_submits(config, ref):
    params = ref.make_params(3, config)
    pool = _pool(3, config)
    with _fleet(config, params, trace=True) as fe:
        sids, got = _drive(fe, config, pool, sessions=4, frames=4)
        a = fe.stats()["door"]
        for _ in range(50):                                      # polls of a drained session
            for sid in sids:
                assert fe.poll(sid) == []
        b = fe.stats()["door"]
        assert b["submit_us_total"] == a["submit_us_total"]
        assert b["submit_calls_total"] == a["submit_calls_total"]
        assert b["poll_calls_total"] == a["poll_calls_total"] + 50 * len(sids)
        assert b["poll_us_total"] > a["poll_us_total"]
        assert b["deliveries_total"] == a["deliveries_total"] == 16
        fe.submit(sids[0], pool[0])
        c = fe.stats()["door"]
        assert c["submit_calls_total"] == b["submit_calls_total"] + 1
        assert c["submit_us_total"] > b["submit_us_total"]
        bound = fe.stats()["sessions"][sids[0]]["replica"]
        assert c["by_replica"][bound]["submit_calls_total"] == b["by_replica"][bound][
            "submit_calls_total"] + 1
        # the spans, on the tracer's wall clock: one a submit, one a poll that handed something out
        events = fe.tracer.snapshot()["events"]
        submits = [e for e in events if e["name"] == "fleet:submit"]
        polls = [e for e in events if e["name"] == "fleet:poll"]
        assert len(submits) == c["submit_calls_total"]
        assert {e["args"]["replica"] for e in submits} == {"r0", "r1", "r2", "r3"}
        assert sum(e["args"]["deliveries"] for e in polls) == 16
        assert all(e["args"]["deliveries"] > 0 and e["dur"] >= 0 for e in polls)


def test_door_stats_alone():
    door = DoorStats()
    assert door.summary() == {**dict.fromkeys(DOOR_KEYS, 0), "by_replica": {},
                              "submit_us_total": 0.0, "poll_us_total": 0.0}
    door.note_submit("r1", 10e-6)
    door.note_poll("r1", 5e-6, 0)
    door.note_poll("r0", 20e-6, 3)
    assert door.row("r1") == {"replica": "r1", "submit_calls_total": 1, "submit_us_total": 10.0,
                              "poll_calls_total": 1, "poll_us_total": 5.0, "deliveries_total": 0}
    assert door.row("r9")["poll_calls_total"] == 0               # a replica nobody called yet
    s = door.summary()
    assert list(s["by_replica"]) == ["r0", "r1"]
    assert (s["submit_calls_total"], s["poll_calls_total"], s["deliveries_total"]) == (1, 2, 3)
    assert s["poll_us_total"] == 25.0


def test_placement_counters_alone():
    adm = SpilloverAdmission()
    adm.record_placement("r0")
    adm.record_placement("r0", warm=True)
    adm.record_placement("r1", hops=2)
    adm.record_placement("r1", migration=True)
    p = adm.placement()
    assert p["by_replica"]["r0"] == {"placed_total": 2, "warm_hits_total": 1, "spillovers_total": 0,
                                     "migrations_total": 0}
    assert p["by_replica"]["r1"] == {"placed_total": 1, "warm_hits_total": 0, "spillovers_total": 2,
                                     "migrations_total": 1}
    assert {k: p[k] for k in PLACEMENT_KEYS} == {"placed_total": 3, "warm_hits_total": 1,
                                                 "spillovers_total": 2, "migrations_total": 1}



def test_door_stats_loses_no_update_under_threads():
    """Clients' threads write the door's rows: 16 threads (more than the
    cores a test worker gets), a switch interval short enough to
    interleave them inside a read-modify-write, and every add has to be
    in the totals."""
    import sys
    import threading

    door, threads_n, calls = DoorStats(), 16, 2000
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def client(i):
            for _ in range(calls):
                door.note_submit(f"r{i % 4}", 1e-6)
                door.note_poll(f"r{i % 4}", 2e-6, 1)

        threads = [threading.Thread(target=client, args=(i,)) for i in range(threads_n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    s = door.summary()
    assert s["submit_calls_total"] == s["poll_calls_total"] == s["deliveries_total"] == threads_n * calls
    assert s["submit_us_total"] == pytest.approx(threads_n * calls * 1.0)
    assert all(b["deliveries_total"] == threads_n * calls // 4 for b in s["by_replica"].values())
