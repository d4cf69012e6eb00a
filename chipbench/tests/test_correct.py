"""``correct`` has to be able to fail, and ``failed`` has to mean lost.

Run from the repository's root, on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q

1. The control (the reference one precision step down) comes out as not
   correct under the configuration's own limits, and a sound stand-in (the
   reference with bfloat16 operands, what the served program's rounding
   looks like) comes out correct: the limits separate the two. Toy frames,
   full widths and depth.
2. The rest of a run, with the harness's look for a chip skipped and the
   timed path broken underneath: a filter that returns every other row of
   the batch unchanged, or a delivery path that hands two frames back in
   the wrong order, prints ``correct: false``; a delivery path that loses
   one frame counts one ``failed``.
3. The accounting, on records made by hand and on a stand-in service of
   fixed latency: a late delivery is not failed and raises the p95; shed,
   ingress-dropped and stuck frames are failed, and only those due in the
   window; ``delivered_fps`` counts the deliveries polled in [t0, t1).
"""

import collections
import dataclasses
import heapq
import os
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import check, controls, frames, frontends, generators, run, spec  # noqa: E402

SEEDS = (2147483801, 7, 3000000099)
QUIET = {"log": lambda msg: None}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("workload", ["invert_1080p.bulk", "style_720p.bulk"])
def test_control_is_not_correct(workload, seed):
    cell = spec.Cell(workload, toy=True)
    numbers = controls.control_numbers(cell, seed)
    assert not check.decide(numbers, cell.config["limits"], **QUIET), numbers


@pytest.mark.parametrize("seed", SEEDS)
def test_bfloat16_stand_in_is_correct(seed):
    cell = spec.Cell("style_720p.bulk", toy=True)
    pool = frames.make_pool(seed, cell.frame_shape, 4)
    params = cell.ref.make_params(seed, cell.config)
    wanted = cell.ref.reference(pool, cell.config, params)
    served = cell.ref.bfloat16_run(pool, cell.config, params)
    numbers = check.compare_numbers([(0, i, f) for i, f in enumerate(served)], wanted, 4)
    assert check.decide(numbers, cell.config["limits"], **QUIET), numbers


def _run(workload, front_hook=None, cell=None):
    cell = cell or spec.Cell(workload, toy=True)
    return run.run_cell(cell, seed=2147483802, seconds=1.5, trace=False,
                        require_tpu=False, front_hook=front_hook, **QUIET)


@pytest.mark.parametrize("workload", ["invert_1080p.bulk", "style_720p.live"])
def test_sound_toy_run_is_correct(workload):
    result = _run(workload)
    assert result["correct"] and result["attempted"] > 0 and not result["failed"]
    assert result["metrics"]["delivered_fps"]["value"] > 0


@pytest.mark.parametrize("workload", ["invert_1080p.bulk", "style_720p.bulk"])
def test_rows_left_unfiltered_are_not_correct(workload, monkeypatch):
    sound = frontends.Front._filter

    def broken(self):
        filt = sound(self)

        def fn(batch, state):
            out, state = filt.fn(batch, state)
            return out.at[::2].set(batch[::2].astype(out.dtype)), state

        return dataclasses.replace(filt, fn=fn)

    monkeypatch.setattr(frontends.Front, "_filter", broken)
    assert not _run(workload)["correct"]


def test_frames_out_of_order_are_not_correct():
    def swap_deliveries(front):
        sound = front.poll

        def poll(sid):
            got = sound(sid)
            return got[::-1] if len(got) > 1 else got

        front.poll = poll

    assert not _run("invert_1080p.bulk", front_hook=swap_deliveries)["correct"]


@pytest.mark.parametrize("workload", ["invert_1080p.bulk", "style_720p.live"])
def test_a_frame_lost_on_the_way_back_is_one_failed(workload):
    def lose_one(front):
        sound, state = front.poll, {"seen": 0, "lost": False}

        def poll(sid):
            got = sound(sid)
            state["seen"] += len(got)
            if got and not state["lost"] and state["seen"] > 80:   # past the warm-up and the ramp
                state["lost"] = True
                return got[:-1]
            return got

        front.poll = poll

    result = _run(workload, front_hook=lose_one)
    assert result["failed"] == 1 and result["attempted"] > 1


def test_fleet_kind_toy_run_is_correct():
    """The ``fleet`` frontend kind and its per-layer reader have no cell yet
    (PERF.md section 7, the first open question); this keeps them running,
    on four virtual devices where the test run has them."""
    bench = spec.benchmark()
    bench["per_layer"].append({"name": "replica_skew_pct", "unit": "%",
                               "workloads": ["invert_1080p.bulk"]})
    cell = spec.Cell("invert_1080p.bulk", bench, toy=True)
    cell.config["frontend"] = "fleet"
    cell.config["fleet"] = {"replicas": 4, "mode": "local", "devices_per_replica": 1}
    result = run.run_cell(cell, seed=2147483803, seconds=1.5, trace=True,
                          require_tpu=False, **QUIET)
    assert result["correct"] and not result["failed"]
    assert "replica_skew_pct" in result["metrics"]


# -- the accounting -----------------------------------------------------------

def _record(t0=1000.0, seconds=10.0):
    rec = generators.Record()
    rec.t0, rec.t1 = t0, t0 + seconds
    return rec


def test_late_deliveries_are_not_failed_and_raise_the_p95():
    on_time, late = _record(), _record()
    for rec, slow in ((on_time, 0), (late, 10)):
        for i in range(100):
            due = rec.t0 + 0.1 * i
            transit = 9.0 if i < slow else 1.5          # against an SLO of 6 s
            rec.transit.append((due, due + transit))
            rec.deliveries.append(due + transit)
        rec.attempted = 100
    a, b = run.account(on_time, 6000.0), run.account(late, 6000.0)
    assert a["failed"] == 0 and b["failed"] == 0
    assert a["beyond_slo"] == 0 and b["beyond_slo"] == 10
    assert run.percentile(b["transit_ms"], 95) > 5 * run.percentile(a["transit_ms"], 95)
    assert run.percentile(b["transit_ms"], 50) == run.percentile(a["transit_ms"], 50)


def test_delivered_fps_counts_the_deliveries_polled_in_the_window_only():
    rec = _record(t0=1000.0, seconds=10.0)
    rec.deliveries = [998.0, 999.999, 1000.0, 1004.2, 1009.999, 1010.0, 1012.5]
    assert run.account(rec, 6000.0)["delivered_in_window"] == 3


Delivery = collections.namedtuple("Delivery", "index frame")


class LossyService:
    """A stand-in for the frontend: every frame comes back after
    ``latency_s``, except that frame i of a session is shed, dropped at
    ingress or stuck inside for ever as ``fate(i)`` says."""

    def __init__(self, latency_s, fate):
        self.latency_s, self.fate = latency_s, fate
        self.rows, self.ready, self.lost = {}, {}, []

    def replicas(self):
        return 1

    def open_stream(self, slo_ms):
        sid = len(self.rows)
        self.rows[sid] = dict.fromkeys(("submitted", "delivered", "shed", "dropped_at_ingress",
                                        "failed", "dropped_unpolled", "inflight"), 0)
        self.ready[sid] = []
        return sid

    def submit(self, sid, frame, ts):
        row = self.rows[sid]
        index = row["submitted"]
        row["submitted"] += 1
        fate = self.fate(index)
        if fate is None:
            heapq.heappush(self.ready[sid], (time.time() + self.latency_s, index))
        else:
            row[fate] += 1
            self.lost.append((fate, ts))
        return index

    def poll(self, sid):
        out, now = [], time.time()
        while self.ready[sid] and self.ready[sid][0][0] <= now:
            out.append(Delivery(heapq.heappop(self.ready[sid])[1], None))
        self.rows[sid]["delivered"] += len(out)
        return out

    def counters(self):
        return {"sessions": self.rows, "buckets": [], "errors": 0, "faults": {}}


def test_lost_frames_are_failed_whatever_the_cause_and_only_the_windows():
    fates = {5: "shed", 9: "dropped_at_ingress", 13: "inflight", 14: "shed"}
    cell = spec.Cell("style_720p.live", toy=True)
    cell.mix.update(sessions=4, offered_fps=80.0, ramp_s=0.3, tail_s=0.6, rest_s=0.3)
    service = LossyService(0.05, lambda i: fates.get(i % 20))
    pool = [np.zeros((2, 2, 3), np.uint8)] * 4
    rec = generators.build(cell, service, pool, seed=3, seconds=1.0).run()
    due_in_window = [fate for fate, ts in service.lost if rec.t0 <= ts < rec.t1]
    acct = run.account(rec, cell.slo_ms)
    assert len(due_in_window) >= 8 and len(due_in_window) < len(service.lost)
    assert len(set(due_in_window)) == 3                      # every cause is among them
    assert acct["failed"] == len(due_in_window)
    assert acct["attempted"] == len(rec.transit) + acct["failed"]
    assert rec.order_violations == 0 and rec.tail_s >= 0.6   # the tail waited for the stuck
