"""The end-to-end pipeline: source → batch assembler → device → ordered sink.

Process-topology translation of SURVEY.md §3: the reference's 4 app threads
+ N worker processes collapse into one process with 3 threads around an
async device queue:

  ingest    — the capture thread (webcam_app.py:67-116): pulls frames from
              the source, indexes them (distributor.py:179-180), enqueues
              with drop-oldest backpressure (distributor.py:188-203);
  dispatch  — replaces the distribute thread + worker pool
              (distributor.py:205-251 / worker.py:30-76): drains the queue
              into a fixed-size batch (the batch generalizes the
              latest-frame slot, distributor.py:214-217), pads it, submits
              to the Engine; in-flight depth is bounded to cap latency;
  collect   — replaces the collect thread (distributor.py:253-289): waits
              for device results in submission order, feeds the reorder
              buffer, advances the display cursor, emits to the sink.

Ordering inside a batch is free (arrays are ordered); across batches it is
submission order on one mesh — the reorder buffer only really works when
results arrive from elastic out-of-order executors (ZMQ ingress mode), but
it is kept in-path so drop/delay semantics match the reference everywhere.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import threading
import time
from collections import deque
from typing import Any, Optional

from dvf_tpu.api.filter import Filter
from dvf_tpu.obs.export import attach_signal_provider
from dvf_tpu.obs.metrics import LatencyStats, RateLogger
from dvf_tpu.obs.registry import MetricsRegistry
from dvf_tpu.obs.trace import Tracer
from dvf_tpu.resilience.budget import ErrorBudget, escalate
from dvf_tpu.resilience.faults import FaultError, FaultKind, FaultStats, classify
from dvf_tpu.resilience.supervisor import Supervisor
from dvf_tpu.runtime.engine import Engine
from dvf_tpu.runtime.lane import DeviceLane
from dvf_tpu.sched.queues import DropOldestQueue
from dvf_tpu.sched.reorder import ReorderBuffer

# Trace track ids (the reference maps worker pids to tracks,
# distributor.py:129; our executors are stages, not processes). The
# per-shard transfer spans land on the lane's own tracks (runtime/lane.py).
TRACK_INGEST, TRACK_DEVICE, TRACK_SINK = 0, 1, 2


@dataclasses.dataclass
class PipelineConfig:
    batch_size: int = 8
    frame_delay: int = 5          # display-cursor lag, reference default (webcam_app.py:17)
    queue_size: int = 10          # ingest queue bound (distributor.py:11)
    reorder_capacity: int = 50    # reorder cap (distributor.py:23)
    max_inflight: int = 4         # batches in flight; bounds latency
    assemble_timeout_s: float = 0.01   # like the 10ms polls (distributor.py:224)
    trace: bool = False           # enable_trace_export (distributor.py:9)
    resilient: bool = False       # per-iteration error containment: one bad
    #   frame/batch is dropped+counted, the loops keep running — the
    #   reference's live-mode semantics (distributor.py:249-251,287-289,
    #   worker.py:71-76). Off by default so tests/benches fail fast.
    telemetry_interval_s: float = 0.0  # >0: print capture/deliver fps every
    #   N s, like the reference's 5 s prints (webcam_app.py:88-95,152-163)
    collect_mode: str = "thread"  # "thread": dedicated collect thread
    #   (default); "inline": the dispatch thread collects the oldest
    #   in-flight batch itself once the window fills — one consumer thread
    #   total, less GIL contention (XLA still overlaps compute with host
    #   staging via async dispatch). Ordering is identical: batches retire
    #   oldest-first either way.
    ingest: str = "streamed"      # batch staging → device transfer path:
    #   "streamed" (default) decodes frames into per-device-shard slabs
    #   and device_puts each shard the moment its rows fill, overlapping
    #   H2D with decode and with the previous batch's compute
    #   (runtime/ingest.py); "monolithic" is the escape hatch — the
    #   pre-streaming decode-all → stage-all → one blocking put path.
    ingest_depth: int = 4         # dispatch-depth knob: how many shard
    #   transfers may be in flight before the assembler blocks on the
    #   oldest (also the sub-chunking granularity of a device's shard)
    egress: str = "streamed"      # result fetch path: "streamed" (default)
    #   issues per-output-shard copy_to_host_async at submit and
    #   materializes shard-by-shard into a preallocated host slab at
    #   collect (runtime/egress.py — auto-degrades where streaming cannot
    #   win, e.g. the CPU backend's zero-copy np.asarray); "monolithic"
    #   is the escape hatch — the classic whole-batch np.asarray fetch.
    fault_budget: int = 16        # contained faults per kind inside
    #   fault_window_s before containment escalates (resilience.budget:
    #   drop → degrade → fail); resilient mode only
    fault_window_s: float = 30.0
    stall_timeout_s: float = 0.0  # >0: arm the stall watchdog
    #   (resilience.supervisor) — an in-flight batch older than this trips
    #   recovery (resilient + thread collect: shed the window, rebuild the
    #   engine; otherwise: abort with a stall FaultError). 0 = off, the
    #   pre-supervision behavior.
    chaos: Any = None             # resilience.chaos.FaultPlan — arms the
    #   deterministic fault-injection sites in the engine, assembler, and
    #   collect loop (--chaos CLI spec); None = zero overhead
    device_trace_dir: Optional[str] = None  # capture a jax.profiler device
    #   trace for the whole run into this dir — Perfetto-compatible, views
    #   alongside the host-side frame-lifecycle trace (obs.trace) in one
    #   UI; with trace=True the merged host+device export
    #   (dvf_merged_timing.pftrace) also lands in this dir
    flight_dir: Optional[str] = None  # flight recorder (obs.export): a
    #   watchdog trip or hard pipeline failure dumps the bounded
    #   post-mortem (trace window + stats) here — the single-stream
    #   tier's spelling of serve/fleet --flight-dir. None = off.
    flight_min_interval_s: float = 10.0  # dump rate limit


class Pipeline:
    def __init__(
        self,
        source: Any,
        filt: Filter,
        sink: Any,
        config: Optional[PipelineConfig] = None,
        engine: Optional[Engine] = None,
        queue: Optional[Any] = None,
    ):
        if filt.stateful and not filt.pad_safe:
            # The dispatch loop pads short batches (end-of-stream tail, slow
            # sources) by repeating the last frame; a pad-unsafe stateful
            # filter would silently corrupt its temporal state (Filter.pad_safe).
            raise ValueError(
                f"filter {filt.name!r} is stateful and not pad-safe; the "
                f"pipeline pads short batches and cannot run it"
            )
        self.source = source
        self.sink = sink
        self.config = config or PipelineConfig()
        if self.config.collect_mode not in ("thread", "inline"):
            raise ValueError(
                f"collect_mode must be 'thread' or 'inline', got "
                f"{self.config.collect_mode!r}")
        self.engine = engine or Engine(filt, chaos=self.config.chaos)
        if self.config.chaos is not None and self.engine.chaos is None:
            self.engine.chaos = self.config.chaos  # arm a caller-built engine
        self.tracer = Tracer(enabled=self.config.trace)
        # The batches' way onto and off the chip (runtime/lane.py). The
        # semaphore guarantees at most max_inflight batches outstanding,
        # so a staging or delivery slot being rewritten belongs to a
        # batch that has already been collected.
        self._lane = DeviceLane(
            self.engine, self.config, self.config.max_inflight,
            tracer=self.tracer, chaos=self.config.chaos, name="pipeline")
        # Injectable ingest queue: default is the Python drop-oldest queue;
        # `--transport ring` passes a transport.ring_queue.RingFrameQueue,
        # putting the native C++ ring on the hot path (frames then cross
        # ingest→assembler as serialized payloads, decoded straight into
        # the dispatch staging buffer via queue.decode_into).
        self.queue = queue if queue is not None else DropOldestQueue(
            maxsize=self.config.queue_size)
        self.reorder = ReorderBuffer(
            frame_delay=self.config.frame_delay,
            capacity=self.config.reorder_capacity,
        )
        self.latency = LatencyStats()
        self.frame_counter = 0
        self.errors = 0
        self.faults = FaultStats()      # per-kind counters + last errors
        self.recoveries = 0             # supervisor engine rebuilds
        self._budget = ErrorBudget(limit=self.config.fault_budget,
                                   window_s=self.config.fault_window_s)
        # Stall escalation is consecutive, not time-windowed: stalls
        # arrive at most once per stall_timeout_s, so a sliding window
        # could never fill. Recoveries with no delivered batch in between
        # (delivery resets the counter) fail hard — the pipeline cannot
        # replace a permanently wedged collect thread, so it must not
        # shed-rebuild at 0 fps forever.
        self._stalls_since_progress = 0
        self._stall_fail_after = max(2, self.config.fault_budget // 4)
        self._supervisor: Optional[Supervisor] = None
        self._recovering = threading.Event()  # dispatch parks while the
        #   supervisor swaps the engine under the lane (see _on_stall)
        # Metrics registry (obs.registry): the scrape endpoint's source
        # for this pipeline. The RateLoggers land their computed rates as
        # the rate_fps gauge ON THE SAME TICKS they print, so the every-5s
        # stderr numbers and /metrics can never disagree; the provider
        # adapts signals() (delivered/dropped/faults/overlap) at scrape.
        self.registry = MetricsRegistry()
        attach_signal_provider(self.registry, "pipeline", self.signals)
        self.flight = None
        if self.config.flight_dir:
            from dvf_tpu.obs.export import FlightRecorder

            self.flight = FlightRecorder(
                self.config.flight_dir, label="pipeline",
                min_interval_s=self.config.flight_min_interval_s,
                trace_fn=lambda: [self.tracer.snapshot()],
                stats_fn=self.stats)
        _ti = self.config.telemetry_interval_s
        self._capture_rate = RateLogger("capture", _ti if _ti > 0 else 5.0,
                                        quiet=_ti <= 0,
                                        registry=self.registry)
        self._deliver_rate = RateLogger("deliver", _ti if _ti > 0 else 5.0,
                                        quiet=_ti <= 0,
                                        registry=self.registry)
        self._on_idle = None  # inline collect: drain-ready hook (_assemble)
        self._inflight: "DropOldestQueue" = DropOldestQueue(maxsize=1_000_000)
        self._inflight_sem = threading.Semaphore(self.config.max_inflight)
        self._eof = threading.Event()
        self._dispatch_done = threading.Event()
        self._abort = threading.Event()
        self._stop_requested = threading.Event()
        self._error: Optional[BaseException] = None

    def stop(self) -> None:
        """Graceful shutdown: stop ingesting, drain what's in flight,
        deliver the tail, then run() finishes normally (stats print, sink
        close, trace export) — the reference's cleanup() path
        (webcam_app.py:172-180 → distributor.py:356-376). Safe to call
        from signal handlers, the display's ESC callback, or any thread."""
        self._stop_requested.set()

    def abort(self) -> None:
        """Hard stop: drop everything in flight and unwind now (second
        Ctrl-C semantics)."""
        self._stop_requested.set()
        self._abort.set()

    # ------------------------------------------------------------------

    def _ingest(self) -> None:
        it = iter(self.source)
        try:
            while not self._abort.is_set() and not self._stop_requested.is_set():
                try:
                    frame, ts = next(it)
                except StopIteration:
                    break
                except Exception as e:  # noqa: BLE001 — bad read, maybe next works
                    if not self._contain(e, "ingest"):
                        return
                    continue
                if frame is None:
                    break
                idx = self.frame_counter
                self.frame_counter += 1
                evicted = self.queue.put((idx, frame, ts))
                if evicted is not None:
                    # The source is outrunning the pipeline (put evicted an
                    # older frame — drop-oldest semantics, so freshness is
                    # already preserved). Pace this thread: an unthrottled
                    # source spinning here starves dispatch/collect of the
                    # GIL and *triples* e2e frame time (measured on CPU:
                    # 44→135 fps at 1080p just from this yield). 200 µs
                    # caps the drop loop at ~5k puts/s, far above any
                    # full-frame delivery rate a host link can sustain.
                    time.sleep(0.0002)
                self._capture_rate.tick()
                self.tracer.instant("frame_captured", ts, TRACK_INGEST, frame=idx)
        except BaseException as e:  # noqa: BLE001
            self._fail(e)
        finally:
            self._eof.set()
            # Release the source promptly (camera handle — the reference
            # does cap.release() in cleanup(), webcam_app.py:174-177).
            # Generator sources run their finally on .close().
            if hasattr(it, "close"):
                try:
                    it.close()
                except Exception:
                    pass

    def _fail(self, e: BaseException) -> None:
        first = self._error is None
        if first:
            self._error = e
        self._abort.set()
        if first and self.flight is not None:
            # Hard failure: the post-mortem moment (serve's discipline —
            # off-thread, rate-limited in the recorder).
            self.flight.trigger_async(f"pipeline failed: {e!r}")

    def _flight_trip(self, reason: str) -> None:
        """Supervisor on_trip tap: dump the black box before recovery
        tears the evidence down (off-thread — a disk write must not
        extend the stall it records)."""
        if self.flight is not None:
            self.flight.trigger_async(reason)

    def _contain(self, e: BaseException, where: str) -> bool:
        """Resilient mode: drop, count, continue (the reference's
        per-iteration ``except: continue``, distributor.py:249-251,287-289)
        — but classified (resilience.faults) and bounded by the per-kind
        error budget: the first overflow degrades (a transfer fault puts
        its side of the lane on the monolithic path), the second fails
        hard, so a permanently broken stage surfaces instead of shedding
        frames forever.
        Fail-fast mode: abort the pipeline. Returns True to continue."""
        kind = classify(e, site=where)
        self.faults.record(kind, e)
        if not (self.config.resilient and isinstance(e, Exception)):
            self._fail(e)
            return False
        self.errors += 1
        if escalate(self._budget, kind,
                    self._lane.degrade) == ErrorBudget.CONTAIN:
            # stderr: stdout is a data channel (one-JSON-line contract in
            # the bench stack and CLI).
            print(f"[pipeline:{where}] {kind} fault (continuing): {e!r}",
                  file=sys.stderr, flush=True)
            return True
        self._fail(FaultError(
            kind,
            f"error budget exhausted for {kind!r} faults "
            f"(> {self.config.fault_budget} in "
            f"{self.config.fault_window_s:g}s, no degradation left); "
            f"last: {e!r}"))
        return False

    def _on_stall(self, reason: str) -> None:
        """Watchdog callback (supervisor thread): a submitted batch aged
        past stall_timeout_s. Resilient + thread-collect: shed the
        in-flight window (results written off, permits restored) and
        rebuild the engine — recompile, re-warm, re-calibrate — so a
        wedged device program can't freeze the stream forever. Inline
        collect (the dispatch thread is the one wedged) or fail-fast:
        abort with a stall fault."""
        e = FaultError(FaultKind.STALL, f"pipeline stalled: {reason}")
        self.faults.record(FaultKind.STALL, e)
        self._stalls_since_progress += 1
        recoverable = (self.config.resilient
                       and self.config.collect_mode == "thread"
                       and self._stalls_since_progress <= self._stall_fail_after)
        if not recoverable:
            self._fail(e)
            return
        self.errors += 1
        print(f"[pipeline] {reason}: shedding in-flight window and "
              f"rebuilding engine", file=sys.stderr, flush=True)
        # Park dispatch (it checks the flag between assembling and
        # staging): a batch submitted mid-recovery would route through
        # the old wedged engine and manufacture a follow-on stall. A
        # dispatch iteration already inside the staging/submit block
        # cannot be interrupted — its batch lands in the window and the
        # watchdog's next trip sheds it.
        self._recovering.set()
        try:
            shed = self._inflight.pop_up_to(len(self._inflight))
            for item in shed:
                self._supervisor.window.remove(item[0])
            # Rebuild BEFORE releasing the shed permits, so a dispatch
            # blocked on the semaphore wakes to the fresh engine.
            self.engine = self.engine.rebuild()
            self._lane.retarget(self.engine)  # both sides re-derive from
            #   the fresh engine's shardings and calibrations
            for _ in shed:
                self._inflight_sem.release()
            # A batch already popped by collect and still materializing
            # stays tracked only by that thread — its permit comes back
            # when np.asarray returns/raises there; clear its window
            # entry so the watchdog doesn't immediately re-trip on the
            # batch being shed.
            self._supervisor.window.drain()
            self.recoveries += 1
        finally:
            self._recovering.clear()

    def _assemble(self) -> Optional[list]:
        """Collect up to batch_size fresh frames; None = stream finished.

        FIFO consumption; drop-oldest freshness is enforced at the queue
        bound (put side), matching the reference (distributor.py:193-203).
        """
        b = self.config.batch_size
        items: list = self.queue.pop_up_to(b)
        deadline = None  # started at first frame, not at call time —
        # otherwise any source slower than the timeout per frame would
        # degenerate every batch to size 1.
        while len(items) < b and not self._abort.is_set():
            if items:
                if deadline is None:
                    deadline = time.perf_counter() + self.config.assemble_timeout_s
                elif time.perf_counter() > deadline:
                    break
            if self._eof.is_set() and len(self.queue) == 0:
                break
            got = self.queue.pop_up_to(b - len(items))
            if got:
                items.extend(got)
            else:
                if self._on_idle is not None:
                    # Inline collect mode: deliver any batch the device
                    # already finished while we wait for frames — a slow
                    # source must not hold completed results hostage to
                    # the in-flight window filling up.
                    self._on_idle()
                time.sleep(0.0005)
        if not items and (self._eof.is_set() or self._abort.is_set()):
            return None
        return items

    def _drain_ready(self, pending: "deque") -> bool:
        """Inline collect: retire the oldest batch when the window is full,
        plus any already-completed results (oldest-first — retiring out of
        order would break the staging-reuse guarantee and serve no purpose,
        the reorder buffer waits on the oldest anyway). Returns False only
        when an error escaped containment."""
        while pending:
            if len(pending) < self.config.max_inflight:
                try:
                    ready = pending[0][3].is_ready()
                except Exception:  # noqa: BLE001 — poisoned async result:
                    # retire it NOW so _collect_one's fetch surfaces
                    # the error through the normal containment path (a
                    # raise from here would bypass resilient mode and kill
                    # the stream on one bad batch).
                    ready = True
                if not ready:
                    break
            if not self._collect_one(*pending.popleft(), release=False):
                return False
        return True

    def _dispatch(self) -> None:
        seq = 0
        inline = self.config.collect_mode == "inline"
        pending: "deque" = deque()  # inline mode's in-flight window
        if inline:
            self._on_idle = lambda: self._drain_ready(pending)
        try:
            while not self._abort.is_set():
                items = self._assemble()
                if items is None:
                    break
                if not items:
                    continue
                while self._recovering.is_set() and not self._abort.is_set():
                    # Stall recovery is swapping the engine/assembler:
                    # park with the assembled frames in hand — submitting
                    # now would route them through the old wedged engine
                    # mid-rebuild and manufacture a follow-on stall.
                    time.sleep(0.001)
                valid = len(items)
                if inline:
                    # Single-consumer mode: collect in-flight batches HERE
                    # — no collect thread, no semaphore, one thread fewer
                    # fighting for the GIL. Retire the oldest when the
                    # window is full (the deque bound keeps staging reuse
                    # safe: pool is max_inflight + 1) plus anything the
                    # device already finished.
                    if not self._drain_ready(pending):
                        return
                else:
                    # Bounded in-flight depth; poll so a dead collect
                    # thread (which stops releasing permits) can't wedge
                    # dispatch. Acquired BEFORE touching the staging
                    # buffer — the permit is what makes buffer reuse safe
                    # (the lane keeps max_inflight + 1 slots).
                    while not self._inflight_sem.acquire(timeout=0.1):
                        if self._abort.is_set():
                            return
                try:
                    decode = getattr(self.queue, "decode_into", None)
                    if decode is not None:
                        # Ring transport: items carry serialized payloads;
                        # the queue decodes them (JPEG via the threaded
                        # codec) straight into the shard staging slabs,
                        # one window per shard chunk so the transfer of a
                        # decoded chunk overlaps the decode of the next.
                        builder = self._lane.begin(
                            (self.config.batch_size,
                             *self.queue.frame_shape),
                            self.queue.frame_dtype, seq)
                        for start, stop in builder.windows(valid):
                            decode(items[start:stop],
                                   builder.window_view(start, stop))
                            builder.commit_window(start, stop)
                    else:
                        f0 = items[0][1]
                        builder = self._lane.begin(
                            (self.config.batch_size, *f0.shape), f0.dtype,
                            seq)
                        for row, (_, frame, _) in enumerate(items):
                            builder.write_row(row, frame)
                    # submit pads short batches by repeating the last
                    # frame — static shapes mean one compilation; padded
                    # outputs are dropped (and repeat-last keeps temporal
                    # state correct, see Filter.pad_safe) — and flushes
                    # the remaining shard transfers.
                    result = self._lane.submit(builder, valid)
                    t0 = time.time()
                    # Start the D2H now, overlapped with the next batch's
                    # staging + device compute; the collect side's fetch
                    # then only waits for completion instead of initiating
                    # the copy. What rides the window is the handle.
                    result = self._lane.prefetch(result, valid)
                except Exception as e:  # noqa: BLE001 — drop this batch
                    if not inline:
                        self._inflight_sem.release()
                    if not self._contain(e, "dispatch"):
                        return
                    continue
                if self._supervisor is not None:
                    # Watchdog window: this batch is now in flight; the
                    # collect side removes it once materialized (either
                    # way), so its age is the stall signal.
                    self._supervisor.window.add(seq)
                meta = [(idx, ts) for idx, _, ts in items]
                if inline:
                    pending.append((seq, meta, valid, result, t0))
                else:
                    self._inflight.put((seq, meta, valid, result, t0))
                seq += 1
            # Inline mode: drain the window (graceful stop / end of
            # stream). Hard abort drops it, matching the collect thread.
            while pending and not self._abort.is_set():
                if not self._collect_one(*pending.popleft(), release=False):
                    return
        except BaseException as e:  # noqa: BLE001
            self._fail(e)
        finally:
            self._dispatch_done.set()

    def _collect_one(self, seq, meta, valid, result, t0, release=True) -> bool:
        """Materialize one batch into the reorder buffer + sink; returns
        False only when an error escaped containment."""
        try:
            # Blocks until the device is done and the batch is in host
            # memory (the D2H was issued at submit).
            out = result.fetch(seq)
        except Exception as e:  # noqa: BLE001 — device error: drop batch
            if self._supervisor is not None:
                self._supervisor.window.remove(seq)
            if release:
                self._inflight_sem.release()
            return self._contain(e, "collect")
        if self._supervisor is not None:
            self._supervisor.window.remove(seq)
            self._stalls_since_progress = 0  # engine made real progress
        if release:
            self._inflight_sem.release()
        t1 = time.time()
        self.tracer.complete(
            "batch_complete", t0, t1, TRACK_DEVICE,
            frames=[i for i, _ in meta],
        )
        # A pooled slab is rewritten after max_inflight + 1 batches —
        # rows that outlive this call (the reorder buffer holds them
        # across the frame_delay window) must own their bytes. A fresh
        # per-batch array keeps handing out views, landed rows
        # (egress.LandedRows) themselves.
        copy_rows = result.owns(out)
        for row, (idx, ts) in enumerate(meta[:valid]):
            frame = out[row].copy() if copy_rows else out[row]
            self.reorder.complete(idx, (frame, ts))
        self._deliver()
        return True

    def _collect(self) -> None:
        chaos = self.config.chaos
        try:
            while not self._abort.is_set():
                if chaos is not None:
                    chaos.fire("freeze")  # injection site: a delay rule
                    #   wedges this consumer so the stall watchdog has a
                    #   deterministic stall to catch
                try:
                    item = self._inflight.get(timeout=0.05)
                except TimeoutError:
                    if self._dispatch_done.is_set() and len(self._inflight) == 0:
                        break
                    continue
                if not self._collect_one(*item):
                    return
        except BaseException as e:  # noqa: BLE001
            self._fail(e)

    def _deliver(self, flush: bool = False) -> None:
        if flush:
            # End of stream: let the cursor catch up to the newest frame so
            # the tail (< frame_delay deep) still gets delivered.
            self.reorder.flush()
        self.reorder.advance()
        for idx, (frame, ts) in self.reorder.pop_ready():
            self.latency.record(time.time() - ts)
            self._deliver_rate.tick()
            self.tracer.instant("frame_delivered", track=TRACK_SINK, frame=idx)
            try:
                self.sink.emit(idx, frame, ts)
            except Exception as e:  # noqa: BLE001 — a display hiccup must not
                if not self._contain(e, "sink"):  # kill the stream
                    return

    # ------------------------------------------------------------------

    def run(self) -> dict:
        """Run to stream end (or Ctrl-C); returns a stats summary."""
        device_tracing = False
        if self.config.device_trace_dir:
            import jax

            jax.profiler.start_trace(self.config.device_trace_dir)
            # Host-clock epoch of the profiler session: what aligns the
            # device trace's relative timestamps with the host tracer's
            # in the merged export (obs.trace.merge_with_device_trace).
            self._device_trace_epoch = time.time()
            device_tracing = True
        threads = [
            threading.Thread(target=self._ingest, name="dvf-ingest", daemon=True),
            threading.Thread(target=self._dispatch, name="dvf-dispatch", daemon=True),
        ]
        if self.config.collect_mode != "inline":
            threads.append(
                threading.Thread(target=self._collect, name="dvf-collect", daemon=True))
        if self.config.stall_timeout_s > 0:
            self._supervisor = Supervisor(
                self.config.stall_timeout_s, on_stall=self._on_stall,
                name="dvf-pipeline-supervisor",
                on_trip=self._flight_trip).start()
        try:
            for t in threads:
                t.start()
            while any(t.is_alive() for t in threads):
                try:
                    for t in threads:
                        t.join(timeout=0.2)
                except KeyboardInterrupt:
                    # First Ctrl-C: graceful stop — drain, deliver the
                    # tail, print stats, export the trace (the reference's
                    # signal → cleanup path, webcam_app.py:46-48,62-65).
                    # Second: abort.
                    if self._stop_requested.is_set():
                        self.abort()
                    else:
                        print("\n[pipeline] stopping (Ctrl-C again to abort)…",
                              file=sys.stderr, flush=True)
                        self.stop()
        finally:
            if self._supervisor is not None:
                self._supervisor.stop()
            # Always stop the profiler — the abort path (double Ctrl-C /
            # escaping exception) is exactly the run someone inspects.
            if device_tracing:
                import jax

                jax.profiler.stop_trace()
        if self._error is not None:
            raise self._error
        if not self._abort.is_set():
            # Drain the trailing frame_delay window — but not on hard
            # abort, whose contract is "unwind now", not "emit up to
            # reorder_capacity buffered frames through the sink first".
            self._deliver(flush=True)
        self.sink.close()
        if hasattr(self.queue, "close"):
            self.queue.close()  # ring transport: release shm + codec pool
        if self.tracer.enabled:
            host_trace = self.tracer.export()
            if host_trace and device_tracing:
                # §5.1's "merge in one UI", made literal: one file with
                # the host frame-lifecycle lanes above the device lanes,
                # clocks aligned via the recorded profiler epoch.
                from dvf_tpu.obs.trace import merge_with_device_trace

                try:
                    # Into device_trace_dir, beside the device trace it
                    # merges — a CWD-relative path would scatter the
                    # artifacts (or silently lose the merge in a
                    # read-only CWD).
                    merge_with_device_trace(
                        host_trace, self.config.device_trace_dir,
                        os.path.join(self.config.device_trace_dir,
                                     "dvf_merged_timing.pftrace"),
                        int((self._device_trace_epoch
                             - self.tracer.start_time) * 1e6))
                except Exception as e:  # noqa: BLE001 — teardown garnish:
                    # a merge failure (unwritable CWD, odd profiler
                    # output) must not fail a run that delivered.
                    print(f"[trace] merged export failed: {e!r}",
                          file=sys.stderr)
        return self.stats()

    def health(self) -> dict:
        """Cheap liveness export (the /healthz surface, mirroring
        ``ServeFrontend.health``): no percentile work, safe to poll at
        hertz rates. ``ok`` flips False once the pipeline has failed
        (fail-fast fault / escaped error)."""
        err = self._error
        return {
            "ok": err is None,
            "error": repr(err) if err is not None else None,
            "delivered": self.latency.count,
            "errors": self.errors,
            "recoveries": self.recoveries,
        }

    def signals(self) -> dict:
        """Flat load-control signal row (registry-conformant keys): the
        single-stream twin of ``ServeFrontend.signals`` — what the
        ``/metrics`` provider scrapes and a TimeSeriesRing samples."""
        agg = self.latency.summary()
        out = {
            "fps": agg.get("fps"),
            "p50_ms": agg.get("p50_ms"),
            "p90_ms": agg.get("p90_ms"),
            "p99_ms": agg.get("p99_ms"),
            "queue_depth": float(len(self.queue)),
            "inflight_batches": float(len(self._inflight)),
            "produced_total": float(self.frame_counter),
            "delivered_total": float(self.latency.count),
            "dropped_at_ingest_total": float(self.queue.dropped),
            "errors_total": float(self.errors),
            "recoveries_total": float(self.recoveries),
            "engine_batches_total": float(self.engine.stats.batches),
            "trace_dropped_total": float(self.tracer.dropped),
        }
        out.update(self._lane.signals())
        for kind, n in self.faults.summary()["by_kind"].items():
            out[f"fault_{kind}_total"] = float(n)
        return out

    def stats(self) -> dict:
        """Superset of the reference's get_frame_stats (distributor.py:346-354)."""
        out = {
            **self.reorder.stats(),
            # (was total_frames_produced — renamed to the registry-
            # conformant counter form when the schema test landed)
            "frames_produced_total": self.frame_counter,
            "dropped_at_ingest": self.queue.dropped,
            "transport": type(self.queue).__name__,
            "errors": self.errors,
            "delivered": self.latency.count,
            "engine_batches": self.engine.stats.batches,
            # Classified fault counters + last-error records and the
            # number of supervisor engine rebuilds (resilience.faults) —
            # what a BENCH round asserts zero-unexpected-faults against.
            "faults": self.faults.summary(),
            "recoveries": self.recoveries,
            **self.latency.summary(),
        }
        out.update(self._lane.stats())
        if self.config.chaos is not None:
            out["chaos"] = self.config.chaos.summary()
        return out
