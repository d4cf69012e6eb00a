"""Checks the trace reduction (chipbench/reduce.py) on a small recorded
trace and on hand-made intervals.

    python3 -m chipbench.selfcheck

The recorded trace (testdata/invert_1080p.bulk.xplane.pb) is five traced
seconds of invert_1080p.bulk on one v5e chip, host tracer off;
testdata/expected.json holds what the reduction read from it on the day it
was recorded, so a change to the reduction that moves a number shows.
Exits non-zero on the first disagreement.
"""

import json
import os
import sys

from chipbench import reduce, spec

TESTDATA = os.path.join(spec.HERE, "testdata")


def close(a, b, rel=1e-9):
    return abs(a - b) <= rel * max(abs(a), abs(b), 1e-30)


def check_synthetic():
    ms = 1_000_000
    # union: overlapping and touching intervals merge, disjoint ones do not
    assert reduce.union([(0, 10), (5, 20), (20, 30), (40, 50)]) == [(0, 30), (40, 50)]
    ops = [("%a", 0, 10 * ms), ("%b", 5 * ms, 10 * ms),        # busy 0-15 ms
           ("%a", 40 * ms, 10 * ms)]                           # busy 40-50 ms
    modules = [("jit_step(1)", 0, 15 * ms), ("jit_other(2)", 20 * ms, 1 * ms),
               ("jit_step(1)", 22 * ms, 6 * ms), ("jit_step(1)", 30 * ms, 8 * ms),
               ("jit_step(1)", 40 * ms, 10 * ms)]
    spans = [("chipbench.poll", 0, 30 * ms), ("chipbench.idle_wait", 18 * ms, 20 * ms),
             ("chipbench.submit", 50 * ms, 50 * ms)]
    planes = {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules},
                          "/device:TPU:1": {"ops": ops[:1], "modules": modules[:1]}},
              "spans": spans}
    r = reduce.reduce_trace(planes, "jit_step")
    assert close(r["window_s"], 0.050), r                 # first op's start .. last op's end
    assert close(r["fullest_busy_s"], 0.025), r           # TPU:0: 15 + 10 ms
    assert close(r["busy_s"], (0.025 + 0.010) / 2), r     # mean over both devices
    assert close(r["idle_pct"], 50.0), r
    # TPU:0's inner steps (6, 8 ms; its first and last are cut by the trace's ends), TPU:1's one
    assert r["steps"] == 3 and close(r["step_ms"], (6 + 8 + 15) / 3), r
    assert r["breakdown"]["device_ops"] == [["%a", 0.020], ["%b", 0.010]], r
    gaps = dict(r["breakdown"]["idle_gaps"])              # 15-40 ms; the spans are cut at 50
    assert gaps == {"chipbench.idle_wait": 0.025}, r
    assert reduce.reduce_trace({"devices": {}, "spans": spans}, "jit_step") is None
    # the roofline guard: a share over 105% is an error, never clipped
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    pct, binds = reduce.roofline_pct({"flops": 50.0, "bytes": 1.0}, peak, 1000.0)
    assert close(pct, 50.0) and binds == "flops"
    pct, binds = reduce.roofline_pct({"flops": 1.0, "bytes": 9.0}, peak, 1000.0)
    assert close(pct, 90.0) and binds == "bytes"
    try:
        reduce.roofline_pct({"flops": 1.0, "bytes": 11.0}, peak, 1000.0)
    except ValueError:
        pass
    else:
        raise AssertionError("a 110% roofline share was not refused")
    return 11


def check_recorded():
    with open(os.path.join(TESTDATA, "expected.json")) as f:
        expected = json.load(f)
    planes = reduce.read_planes(os.path.join(TESTDATA, expected["file"]))
    r = reduce.reduce_trace(planes, expected["step_name"])
    assert r is not None, "no device operation in the recorded trace"
    for key, want in expected["reduced"].items():
        assert close(r[key], want, rel=1e-6), (key, r[key], want)
    assert r["breakdown"]["device_ops"][0][0] == expected["top_device_op"], r["breakdown"]
    cost = spec.load_module(expected["costs"]).cost(
        spec.load_json("configs", expected["config"] + ".json"), expected["batch_size"])
    pct, binds = reduce.roofline_pct(cost, spec.peaks(expected["device_kind"]), r["step_ms"])
    assert close(pct, expected["step_roofline_pct"], rel=1e-6) and binds == expected["binds"]
    return len(expected["reduced"]) + 2


def main():
    n = check_synthetic() + check_recorded()
    print(f"chipbench.selfcheck: {n} checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
