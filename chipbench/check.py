"""Decides ``correct``: the window's sampled deliveries against the plain
reference, each number printed beside its limit.

The reference is run once per distinct input frame (the pool), after the
window has closed and the device's peak memory has been read. What is
compared, per sampled frame, in uint8 steps: the largest |served -
reference| and the mean of it; the run's number is the worst frame's. The
configuration's ``limits`` say which numbers are held and to what.
"""

import numpy as np

from chipbench.frames import pool_index


def compare_numbers(samples, wanted, n_pool):
    """{"max_abs_steps", "mean_abs_steps", "shape_mismatch"} over the
    samples; ``wanted[j]`` is the reference's answer to pool frame j."""
    worst_max, worst_mean, bad_shape = 0, 0.0, 0
    for k, index, got in samples:
        want = wanted[pool_index(k, index, n_pool)]
        if got.shape != want.shape or got.dtype != want.dtype:
            bad_shape += 1
            continue
        diff = np.abs(got.astype(np.int16) - want.astype(np.int16))
        worst_max = max(worst_max, int(diff.max()))
        worst_mean = max(worst_mean, float(diff.mean()))
    return {"max_abs_steps": worst_max, "mean_abs_steps": worst_mean,
            "shape_mismatch": bad_shape}


def decide(numbers, limits, log=print):
    """True when every held number is within its limit; prints each."""
    ok = True
    for name, limit in limits.items():
        if limit is None:
            raise SystemExit(f"chipbench: limit {name!r} is not set in the configuration")
        value = numbers[name]
        good = value <= limit
        ok &= good
        log(f"[check] {name} = {value} (limit {limit}) {'ok' if good else 'EXCEEDED'}")
    return ok


def check_run(cell, rec, pool, params, log=print):
    """Whether the window's sample is correct. Order is part of it: a frame
    that came back twice, backwards, or wrong makes the run not correct."""
    wanted = cell.ref.reference(pool, cell.config, params)
    numbers = compare_numbers(rec.samples, wanted, len(pool))
    numbers["samples"] = len(rec.samples)
    numbers["order_violations"] = rec.order_violations
    limits = dict(cell.config["limits"])
    limits.update({"order_violations": 0, "shape_mismatch": 0})
    log(f"[check] {len(rec.samples)} sampled deliveries against "
        f"{cell.config['reference']['what']}")
    ok = decide(numbers, limits, log)
    if not rec.samples:
        log("[check] no delivery was sampled: nothing was compared")
        ok = False
    return ok
