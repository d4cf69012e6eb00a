"""chipbench: one cell, one process — load, warm, measure, print, exit.

    python3 -m chipbench.run --workload <config>.<mix> --seed <n> \
        --seconds <s> --trace <0|1>

The last line of standard output is the contract's JSON object and holds
nothing else; everything a reader wants besides (sample counts, the
numbers compared beside their limits, the per-session accounting, which
roofline bound binds) is on earlier lines. Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""

import time

T_START = time.time()     # process start, as near as Python lets us see it

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

from chipbench import check, frames, frontends, generators, reduce, spec  # noqa: E402

TRACE_SECONDS = 5.0       # a traced run traces the window's last seconds


def log(msg):
    print(msg, flush=True)


def arm_compile_cache():
    """One fixed directory inside the checkout, unless the environment
    names one; the program's own resolver is handed the same."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(spec.ROOT, ".jax_compile_cache"))
    from dvf_tpu.runtime.engine import enable_compilation_cache

    return enable_compilation_cache(persist_small=True)


def device_report(jax, chips, require_tpu):
    """The first ``jax.devices()`` of a process brings up the TPU runtime;
    run_cell times this call alone and keeps it out of ``setup_s``."""
    devs = jax.devices()
    d0 = devs[0]
    if require_tpu and (d0.platform != "tpu" or len(devs) < chips):
        sys.stderr.write(f"chipbench: need {chips} TPU chip(s), jax reports "
                         f"{len(devs)} x {d0.platform}\n")
        raise SystemExit(3)
    return {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs)}


def memory_peak(jax):
    """Peak bytes on the fullest device, a high-water mark read after the
    tail; 0 where the backend reports none (the CPU)."""
    peak = 0
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        # On the TPU the runtime holds the compiled programs' scratch (XLA's
        # "temp") as reserved memory beside the allocations: both are the
        # program's, and a chip holds their sum.
        peak = max(peak, int(st.get("peak_bytes_in_use", 0))
                   + int(st.get("peak_bytes_reserved", 0)))
    return peak


def percentile(values, q):
    """q in (0, 100): statistics.quantiles' inclusive method."""
    if len(values) < 2:
        return values[0] if values else None
    cuts = statistics.quantiles(values, n=100, method="inclusive")
    return cuts[int(q) - 1]


def program_accounting(counters, rec):
    """Frames by the program's own per-session counters, after the tail.
    unaccounted = submitted - (delivered + shed + dropped + failed +
    inflight): a frame that is nowhere, or still queued."""
    tot = {k: 0 for k in ("submitted", "delivered", "shed", "dropped_at_ingress",
                          "failed", "dropped_unpolled", "inflight", "slo_miss")}
    for row in counters["sessions"].values():
        for k in tot:
            tot[k] += int(row.get(k) or 0)
    tot["unaccounted"] = tot["submitted"] - (tot["delivered"] + tot["shed"]
                                             + tot["dropped_at_ingress"] + tot["failed"]
                                             + tot["inflight"])
    tot["generator_submitted"] = sum(rec.submitted.values())
    tot["generator_polled"] = sum(rec.polled.values())
    return tot


def account(rec, slo_ms):
    """The generator's record reduced to what the result line says.

    ``failed`` counts attempted frames that the service lost or refused:
    whatever the cause (shed, dropped at ingress, failed in a contained
    fault, dropped unpolled, or still inside when the tail ended), such a
    frame never came back, and ``rec.unresolved`` counted it. A frame
    delivered late is not failed: it is in the transit percentiles with its
    full transit. ``delivered_in_window`` counts deliveries polled in
    [t0, t1), whatever frame they answer."""
    transit_ms = [(t - due) * 1e3 for due, t in rec.transit]
    return {"attempted": int(rec.attempted),
            "failed": int(min(rec.attempted, rec.unresolved)),
            "delivered_in_window": sum(1 for t in rec.deliveries if rec.t0 <= t < rec.t1),
            "transit_ms": transit_ms,
            "beyond_slo": sum(1 for ms in transit_ms if ms > slo_ms)}


class WindowWatch(threading.Thread):
    """A traced run's helper thread: reads the program's counters at the
    window's open and close and runs the profiler over the window's last
    seconds, so that none of it runs on the generator's thread."""

    def __init__(self, jax, rec, front, seconds):
        super().__init__(name="chipbench-window-watch", daemon=True)
        self.jax, self.rec, self.front = jax, rec, front
        self.trace_s = min(TRACE_SECONDS, 0.8 * float(seconds))
        self.before = self.after = self.trace_dir = None
        self.trace_origin = self.trace_end = None
        self.error = None
        self.give_up = threading.Event()

    def _sleep_until(self, when):
        """False when told to give up before ``when``."""
        return not self.give_up.wait(max(0.0, when - time.time()))

    def run(self):
        try:
            rec, jax = self.rec, self.jax
            while rec.t0 is None:                 # the closed loop's ramp ends when it ends
                if self.give_up.wait(0.005):
                    return
            if not self._sleep_until(rec.t0):
                return
            self.before = self.front.counters()
            if not self._sleep_until(rec.t1 - self.trace_s):
                return
            self.trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # device events only: the host
            opts.host_tracer_level = 0        # tracer writes 50 MB a second here
            opts.enable_hlo_proto = False
            self.trace_origin = time.time()
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            try:
                self._sleep_until(rec.t1)
                self.after = self.front.counters()
            finally:
                self.trace_end = time.time()
                jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 - reported by the caller, which must go on
            self.error = e


def run_cell(cell, seed, seconds, trace, require_tpu=True, t_start=None, log=log,
             front_hook=None):
    """Runs one cell and returns the result object. ``front_hook(front)``
    lets a test break the timed path underneath before traffic starts."""
    t_start = T_START if t_start is None else t_start
    marks = []

    def mark(what, at=None):
        marks.append(f"{what} {(time.time() if at is None else at) - t_start:.1f}")

    cache_dir = arm_compile_cache()
    import jax

    mark("imports")
    # Bringing up the TPU runtime is the machine's and not the program's: it
    # takes 6 to 19 s from one process to the next, and stops the whole host
    # while it does (PERF.md section 2). It is timed alone, printed, and left
    # out of setup_s, which is everything else from process start to the
    # window's first frame.
    t_reach = time.time()
    device = device_report(jax, cell.chips, require_tpu)
    chip_reach_s = time.time() - t_reach
    log(f"[run] {cell.name} seed {seed} seconds {seconds} trace {trace} on "
        f"{device['count']} x {device['kind']}; compile cache {cache_dir} "
        f"({len(os.listdir(cache_dir))} entries)")
    mark("device")
    pool = frames.make_pool(seed, cell.frame_shape, int(cell.mix["pool_frames"]))
    mark("frames")
    params = cell.ref.make_params(seed, cell.config)
    front = frontends.build(cell, params).start()
    mark("frontend")
    if front_hook is not None:
        front_hook(front)
    gen = generators.build(cell, front, pool, seed, seconds, spans=trace)
    rec = gen.rec
    watch = WindowWatch(jax, rec, front, seconds) if trace else None
    try:
        if watch is not None:
            watch.start()
        gen.run()                          # a dead frontend raises from submit
        # Only now, with the tail over, anything that blocks: peak memory is
        # a high-water mark and the counters are cumulative.
        if watch is not None:
            watch.join(timeout=60.0)
        peak = memory_peak(jax)
        final = front.counters()
        ok, err = front.health()
        if not ok:
            rec.health_error = str(err)
    finally:
        if watch is not None:
            watch.give_up.set()
            watch.join(timeout=60.0)
        front.stop()

    setup_s = rec.t0 - t_start - chip_reach_s
    mark("sessions open and warm", gen.t_opened)
    mark("ramp done = window open", rec.t0)
    log("[setup] seconds since process start: " + "; ".join(marks))
    log(f"[setup] chip_reach_s {chip_reach_s:.3f} (the TPU runtime's own start, inside "
        f"'device'; not in setup_s), setup_s {setup_s:.3f}")
    prog = program_accounting(final, rec)
    acct = account(rec, cell.slo_ms)
    transit_ms = acct["transit_ms"]
    log(f"[acct] program, whole run: {prog}")
    log(f"[acct] window {rec.t1 - rec.t0:.3f} s: attempted {acct['attempted']}, failed "
        f"{acct['failed']} (lost or refused: never came back by the end of the "
        f"{rec.tail_s:.1f} s tail or drain; the program's causes, whole run: shed "
        f"{prog['shed']}, dropped at ingress {prog['dropped_at_ingress']}, failed in a fault "
        f"{prog['failed']}, dropped unpolled {prog['dropped_unpolled']}, left inside "
        f"{rec.left_inside}); deliveries polled in the window {acct['delivered_in_window']}; "
        f"polls with frames {rec.poll_lumps}")
    log(f"[acct] information only: {acct['beyond_slo']} of {len(transit_ms)} attempted "
        f"deliveries took longer than the sessions' {cell.slo_ms:.0f} ms SLO (not failed: "
        f"they are in the transit percentiles); the program counted {prog['slo_miss']} over "
        f"the whole run")
    log(f"[mem] peak in use + reserved {peak} ({peak / 2**30:.2f} GiB) on the fullest device")
    log(f"[mem] host: peak resident set "
        f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20:.1f} GiB")
    if transit_ms:
        log(f"[transit] {len(transit_ms)} frames: p50 {percentile(transit_ms, 50):.1f} "
            f"ms, p95 {percentile(transit_ms, 95):.1f} ms, max {max(transit_ms):.1f} ms")
    log("[gen] longest stalls of the generator's loop (ms, at s from the window's open): "
        + ", ".join(f"{ms:.0f} at {at:.1f}" for ms, at in rec.stalls))
    if rec.late_ms:
        log(f"[gen] lateness over {len(rec.late_ms)} submits: p50 "
            f"{percentile(rec.late_ms, 50):.3f} ms, p95 {percentile(rec.late_ms, 95):.3f} ms, "
            f"max {max(rec.late_ms):.3f} ms")

    t_ref = time.time()
    correct = check.check_run(cell, rec, pool, params, log)
    log(f"[check] reference and comparison took {time.time() - t_ref:.1f} s (not set-up)")
    for name, bad in (("unaccounted frames", prog["unaccounted"]),
                      ("frontend errors", final["errors"])):
        log(f"[check] {name} = {bad} (limit 0) {'ok' if not bad else 'EXCEEDED'}")
        correct &= not bad
    log(f"[check] contained faults by kind (their frames are in failed): {final['faults']}")
    if rec.health_error:
        log(f"[check] the frontend failed: {rec.health_error}")
        correct = False

    window = rec.t1 - rec.t0
    values = {"delivered_fps": acct["delivered_in_window"] / window,
              "transit_p50_ms": percentile(transit_ms, 50),
              "transit_p95_ms": percentile(transit_ms, 95),
              "setup_s": setup_s}
    result_device = dict(device, memory_peak_bytes=peak)
    result = {"correct": bool(correct), "attempted": acct["attempted"],
              "failed": acct["failed"], "metrics": {}, "device": result_device}
    if not trace:
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
        return result

    reduced = None
    if watch.error is not None:
        log(f"[trace] the window's helper thread failed: {watch.error!r}")
    if watch.trace_dir is not None:
        try:
            planes = reduce.read_planes(reduce.find_xplane(watch.trace_dir))
            # the trace's clock starts where start_trace was called (to 0.05 ms
            # on the v5e, PERF.md section 3): host spans move onto it
            t_a = watch.trace_origin
            planes["spans"] = [(n, (a - t_a) * 1e9, (b - a) * 1e9) for n, a, b in gen.span_log]
            reduced = reduce.reduce_trace(planes, front.step_name)
            keep = os.environ.get("CHIPBENCH_KEEP_TRACE")
            if keep:       # how chipbench/testdata's recorded trace is made
                os.makedirs(keep, exist_ok=True)
                shutil.copy(reduce.find_xplane(watch.trace_dir),
                            os.path.join(keep, f"{cell.name}.xplane.pb"))
        finally:
            shutil.rmtree(watch.trace_dir, ignore_errors=True)
    if reduced is not None:
        log(f"[trace] window {reduced['window_s']:.3f} s (the devices' first event to their "
            f"last, of {watch.trace_end - watch.trace_origin:.3f} s between start_trace and "
            f"stop_trace), busy {reduced['busy_s']:.4f} s (mean of devices), "
            f"{reduced['steps']} step programs, step {reduced['step_ms']} ms")
        result_device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = reduced["breakdown"]
    else:
        log("[trace] no operation ran on a device in the traced window")
    ctx = {"cell": cell, "rec": rec, "before": watch.before, "after": watch.after,
           "trace": reduced, "log": log,
           "peak": spec.peaks(device["kind"]) if device["platform"] == "tpu" else None}
    for m in cell.per_layer:
        reader = spec.load_module(os.path.join("layer_metrics", m["name"] + ".py"))
        value = reader.read(ctx)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.Cell(args.workload)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
