#!/usr/bin/env python3
"""When does each replica of a fleet cell become ready, and what holds the ramp?

ISSUE 45, item 3. Builds a ``fleet`` cell of BENCHMARK.json as ``chipbench.run`` does (the cell's own frontend,
frames and weights), opens the closed loop's sessions, fills their windows and then keeps the loop turning
(one frame sent for each read back) until every replica has delivered ``--batches`` batches. It prints, on
the clock that starts when the fill starts:

  per replica   the first delivery of any of its sessions (``t_first``), frames delivered by then fleet-wide,
                its ledger's compile events (start, wall ms, cache) and engine rebuilds, `recoveries`
  fleet         `replica_restarts`, `replica_losses`, the placement block, when the closed loop's ramp count
                (``ramp_turnovers x sessions x window`` frames polled in total) was reached, and how many
                replicas had delivered anything by then

    chiprun --chips 4 -- python scripts/fleet_ramp_probe.py --workload style_720p_v5e4.bulk --seed 7
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python scripts/fleet_ramp_probe.py --workload style_720p_v5e4.bulk --seed 7 --toy    # checks the script

Writes ``chiprun_out/fleet_ramp_probe_<tag>.json``. A time from ``--toy`` on the CPU says nothing about the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--toy", action="store_true")
    ap.add_argument("--batches", type=int, default=6, help="batches every replica delivers before the probe stops")
    ap.add_argument("--timeout", type=float, default=600.0)
    ap.add_argument("--tag", default="run")
    args = ap.parse_args()

    from chipbench import frames, frontends, run, spec
    from chipbench.frames import pool_index

    cell = spec.Cell(args.workload, toy=args.toy)
    if cell.config["frontend"] != "fleet":
        raise SystemExit(f"{args.workload} is not a fleet cell")
    run.arm_compile_cache()
    import jax

    device = run.device_report(jax, cell.chips, require_tpu=not args.toy)
    pool = frames.make_pool(args.seed, cell.frame_shape, int(cell.mix["pool_frames"]))
    params = cell.ref.make_params(args.seed, cell.config)
    front = frontends.build(cell, params).start()
    try:
        mix = cell.mix
        win = int(mix["window"])
        batches = cell.config["serve"]["max_inflight"] + int(mix["batches_beyond_inflight"])
        n = -(-batches * cell.batch_size * front.replicas() // win)
        ramp_frames = int(mix["ramp_turnovers"] * n * win)
        t_open0 = time.time()
        sids = [front.open_stream(cell.slo_ms) for _ in range(n)]
        opens_s = time.time() - t_open0
        bound = {sid: row["replica"] for sid, row in front.fe.stats()["sessions"].items()}
        rids = sorted(set(bound.values()))
        t0 = time.time()
        sent = [0] * n
        for _ in range(win):
            for k in range(n):
                front.submit(sids[k], pool[pool_index(k, sent[k], len(pool))], time.time())
                sent[k] += 1
        fill_s = time.time() - t0
        first, count = {}, dict.fromkeys(rids, 0)
        total, at_first, ramp_at, ready_at_ramp = 0, {}, None, None
        need = args.batches * cell.batch_size
        while min(count.values()) < need:
            now = time.time()
            if now - t0 > args.timeout:
                break
            moved = 0
            for k in range(n):
                got = front.poll(sids[k])
                if not got:
                    continue
                rid = bound[sids[k]]
                if rid not in first:
                    first[rid] = time.time() - t0
                    at_first[rid] = total + moved
                count[rid] += len(got)
                moved += len(got)
                for _ in got:
                    front.submit(sids[k], pool[pool_index(k, sent[k], len(pool))], time.time())
                    sent[k] += 1
            total += moved
            if ramp_at is None and total >= ramp_frames:
                ramp_at, ready_at_ramp = time.time() - t0, len(first)
            if not moved:
                time.sleep(0.001)
        fs = front.fe.stats()
        replicas = {}
        # the replicas' own ledgers are not in FleetFrontend.stats(): read from the handles, as
        # chipbench/frontends.py reads their bucket rows
        for rid, r in sorted(front.fe._replicas.items()):
            st = r.stats_full()["stats"]
            evs = (st.get("ledger") or {}).get("events", [])
            replicas[rid] = {
                "t_first_delivery_s": first.get(rid), "fleet_frames_polled_by_then": at_first.get(rid),
                "frames_delivered": count.get(rid),
                "compiles": [{"t_s": round(e["t"] - t0, 3), "wall_ms": e.get("wall_ms"),
                              "cache": e.get("cache"), "cause": e.get("cause")}
                             for e in evs if e.get("kind") == "compile"],
                "engine_rebuilds": sum(1 for e in evs if e.get("kind") == "engine_rebuild"),
                "xla_compiles_total": [b.get("xla_compiles_total") for b in st["buckets"].values()],
                "recoveries": st.get("recoveries")}
        out = {"workload": args.workload, "toy": args.toy, "device": device, "sessions": n,
               "opens_s": opens_s, "fill_s": fill_s, "ramp_frames": ramp_frames,
               "ramp_count_reached_s": ramp_at, "replicas_that_had_delivered_by_then": ready_at_ramp,
               "replicas": replicas, "replica_restarts": fs["replica_restarts"],
               "replica_losses": fs["replica_losses"], "recoveries": fs["recoveries"],
               "placement": fs.get("placement"), "warm_replicas": fs.get("warm_replicas")}
    finally:
        front.stop()
    ts = sorted(v["t_first_delivery_s"] for v in out["replicas"].values() if v["t_first_delivery_s"] is not None)
    print(f"[ramp] {args.workload} on {device['count']} x {device['kind']}: opens {opens_s:.3f} s, fill {fill_s:.3f} s; "
          f"first deliveries by replica (s from the fill's start): "
          + ", ".join(f"{rid} {v['t_first_delivery_s']}" for rid, v in out["replicas"].items())
          + (f"; first to last {ts[-1] - ts[0]:.3f} s" if ts else "")
          + f"; the ramp's count of {ramp_frames} frames was reached at {ramp_at} s with "
          f"{ready_at_ramp} of {len(rids)} replicas delivering", flush=True)
    for rid, v in out["replicas"].items():
        print(f"[ramp] {rid}: {v}", flush=True)
    print(f"[ramp] replica_restarts {out['replica_restarts']}, replica_losses {out['replica_losses']}, "
          f"recoveries {out['recoveries']}, placement {out['placement']}", flush=True)
    os.makedirs(os.path.join(spec.ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(spec.ROOT, "chiprun_out", f"fleet_ramp_probe_{args.tag}.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
