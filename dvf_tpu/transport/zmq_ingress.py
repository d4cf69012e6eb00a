"""ZMQ ingress: one TPU-backed "worker" that speaks the reference's wire
protocol, so the reference app can drive this framework unmodified.

Wire protocol (SURVEY.md §2 "Wire protocol"; behavior, not code, mirrored):
- distribute channel: DEALER connects to the app's ROUTER (default :5555)
  and requests work by sending ``[b"READY"]`` (worker.py:39); the app
  replies ``[frame_index_ascii, frame_bytes]`` (distributor.py:236-238 /
  worker.py:50-51), at most one frame per READY.
- collect channel: PUSH connects to the app's PULL (default :5556) and
  sends ``[frame_index, pid, start_time, end_time, payload]``, all
  metadata stringified (worker.py:63-67 / distributor.py:260-264).

Where the reference runs N single-frame Python workers, this ingress is
ONE process that keeps ``batch_size`` READY credits outstanding
(pipelining the request/reply channel), assembles arriving frames into a
batch, runs the jitted filter once on the TPU, and pushes each result
back individually. To the app it is indistinguishable from a very fast
worker pool: elastic (connect = join), at-most-once, order restored by
the app's reorder buffer.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Optional

import numpy as np

from dvf_tpu.api.filter import Filter
from dvf_tpu.obs.export import attach_signal_provider
from dvf_tpu.obs.registry import MetricsRegistry
from dvf_tpu.obs.trace import EGRESS_SEND, Tracer
from dvf_tpu.resilience.budget import ErrorBudget, escalate
from dvf_tpu.resilience.faults import FaultError, FaultKind, FaultStats, classify
from dvf_tpu.runtime.egress import AsyncCodecPlane
from dvf_tpu.runtime.engine import Engine
from dvf_tpu.runtime.lane import DeviceLane
from dvf_tpu.transport.codec import (
    WIRE_MODES,
    DeltaCodec,
    DeltaWireError,
    JpegGeometryError,
    make_wire_codec,
)

# ---------------------------------------------------------------------------
# Wire framing, shared with the multi-stream serving frontend
# (serve.server.ZmqStreamBridge): the worker request token, the app's
# frame reply, and the result message — one place owns the byte layout.

READY = b"READY"  # work-request token (worker.py:39)


def parse_frame_reply(parts: list) -> Optional[tuple]:
    """App → worker frame reply ``[frame_index_ascii, frame_bytes]``
    (distributor.py:236-238) → ``(index, payload)``; None if malformed
    (wrong part count, non-integer index)."""
    if len(parts) != 2:
        return None
    try:
        return int(parts[0].decode()), parts[1]
    except ValueError:
        return None


def result_msg(index: int, pid: bytes, t0: float, t1: float,
               payload: bytes) -> list:
    """Worker → app result ``[frame_index, pid, start_time, end_time,
    payload]``, metadata stringified (worker.py:63-67)."""
    return [str(index).encode(), pid, str(t0).encode(), str(t1).encode(),
            payload]


class TpuZmqWorker:
    """TPU-backed worker endpoint for the reference's socket pair.

    ``use_jpeg=False`` expects raw uint8 RGB frames of ``raw_size``²
    (the reference's non-JPEG path hardcodes its frame geometry the same
    way, inverter.py:34).
    """

    def __init__(
        self,
        filt: Filter,
        host: str = "localhost",
        distribute_port: int = 5555,
        collect_port: int = 5556,
        batch_size: int = 8,
        assemble_timeout_s: float = 0.01,
        use_jpeg: bool = True,
        raw_size: int = 512,
        jpeg_quality: int = 90,
        codec_threads: int = 4,
        engine: Optional[Engine] = None,
        poll_ms: int = 10,
        delay_s: float = 0.0,
        transport: str = "list",
        ingest: str = "streamed",
        ingest_depth: int = 4,
        egress: str = "streamed",
        egress_depth: int = 2,
        fault_budget: int = 16,
        fault_window_s: float = 30.0,
        chaos=None,
        tracer=None,
        trace: bool = False,
        wire: Optional[str] = None,
        delta_tile: int = 32,
        delta_keyframe_interval: int = 16,
        delta_threshold: int = 0,
        delta_device: bool = False,
        codec_assist: str = "none",
        audit_wire: bool = False,
        ledger: bool = True,
        heartbeat=None,
    ):
        import zmq

        if egress_depth < 1:
            raise ValueError("egress depth must be >= 1")
        if wire is None:
            wire = "jpeg" if use_jpeg else "raw"  # legacy flag spelling
        if wire not in WIRE_MODES:
            raise ValueError(f"wire must be one of {WIRE_MODES}, "
                             f"got {wire!r}")
        use_jpeg = wire != "raw"

        if filt.stateful and not filt.pad_safe:
            # Short batches are padded by repeating the last frame; a
            # pad-unsafe stateful filter would corrupt its temporal state
            # on every partial batch (see Filter.pad_safe).
            raise ValueError(
                f"filter {filt.name!r} is stateful and not pad-safe; "
                f"the ZMQ worker pads short batches and cannot serve it"
            )
        self.chaos = chaos  # resilience.chaos.FaultPlan ("decode" and
        #   "transport" injection sites live here; "h2d"/"d2h"/"compute"/
        #   "oom" ride on the lane and the engine)
        self.engine = engine or Engine(filt, chaos=chaos)
        if chaos is not None and self.engine.chaos is None:
            self.engine.chaos = chaos
        self.ingest = ingest
        self.ingest_depth = ingest_depth
        self.egress = egress
        self.egress_depth = egress_depth
        # The batches' way onto and off the chip, built before any socket
        # so that a bad mode raises with nothing to close. _process_batch
        # is synchronous up to the fetch: one staging slot. The encode
        # plane holds at most egress_depth batches' rows (encode futures,
        # raw memoryviews): egress_depth + 1 delivery slots.
        self._lane = DeviceLane(
            self.engine, self, egress_depth, staging_inflight=0,
            chaos=chaos, compile=self._compile, name="TpuZmqWorker")
        self._geometry: Optional[tuple] = None  # (h, w) of the jpeg
        #   stream as last probed; None = probe the next batch
        self.ctx = zmq.Context()
        self._dealer_endpoint = f"tcp://{host}:{distribute_port}"
        self.dealer = self.ctx.socket(zmq.DEALER)
        self.dealer.connect(self._dealer_endpoint)
        self.push = self.ctx.socket(zmq.PUSH)
        # A PUSH with no live peer blocks send() forever; bound it so a dead
        # collector drops the batch into run()'s containment (at-most-once,
        # like every other path here) instead of wedging close().
        self.push.setsockopt(zmq.SNDTIMEO, 1000)
        self.push.connect(f"tcp://{host}:{collect_port}")
        self._zmq = zmq
        self.filt = filt
        self.wire = wire
        self._wire_degrade_reason: Optional[str] = None
        if wire == "delta":
            # Temporal-delta wire, both directions: incoming delta frames
            # composite onto the cached previous frame (a sequence gap —
            # the app dropped an encoded frame — raises DeltaResyncError
            # into run()'s containment: at-most-once, recovered at the
            # peer's next keyframe); results are delta-encoded on the
            # egress plane, dirty bitmaps computed on DEVICE when
            # delta_device is set (runtime.codec_assist.DeviceDeltaProbe).
            self.codec = make_wire_codec(
                "delta", quality=jpeg_quality, threads=codec_threads,
                tile=delta_tile,
                keyframe_interval=delta_keyframe_interval,
                delta_threshold=delta_threshold,
                on_gap="raise")
        else:
            self.codec = make_wire_codec("jpeg", quality=jpeg_quality,
                                         threads=codec_threads)
        if codec_assist not in ("none", "probe", "full"):
            raise ValueError(f"codec_assist must be one of "
                             f"('none', 'probe', 'full'), got {codec_assist!r}")
        if codec_assist == "probe":
            delta_device = True  # alias: probe assist IS --delta-device
        self.codec_assist = codec_assist
        self._probe = None
        self._fused = None
        self._fused_geom_warned = False
        if wire == "delta" and codec_assist == "full":
            # Full-transform assist: probe→convert→DCT→quant fused into
            # ONE device program per batch (FusedDeltaTransform); the
            # host entropy-codes device-quantized coefficient blocks and
            # never touches pixels. Requires the native shim's
            # coefficient entry — fall back to the probe tier (device
            # bitmaps, host transform) when it is absent so the worker
            # still serves.
            inner = getattr(self.codec, "inner", None)
            lib = getattr(inner, "_lib", None)
            if (hasattr(inner, "encode_coefficients")
                    and hasattr(lib, "dvf_jpeg_encode_coefficients")):
                from dvf_tpu.runtime.codec_assist import FusedDeltaTransform

                self._fused = FusedDeltaTransform(tile=delta_tile,
                                                  quality=jpeg_quality)
                delta_device = True  # the fused pass embeds the probe;
                #   keep the probe tier armed as the fallback ladder
            else:
                print("[TpuZmqWorker] --codec-assist full: native shim "
                      "coefficient entry unavailable (cv2 fallback?); "
                      "degrading to probe assist", file=sys.stderr)
                delta_device = True
        if wire != "delta" and codec_assist != "none":
            print(f"[TpuZmqWorker] --codec-assist {codec_assist} ignored: "
                  f"assist rides the delta wire (wire={wire})",
                  file=sys.stderr)
        if wire == "delta" and delta_device:
            from dvf_tpu.runtime.codec_assist import DeviceDeltaProbe

            if delta_threshold > 0:
                # The device probe diffs consecutive frames, not the
                # shipped reference — exact at threshold 0, but lossy
                # thresholds lose the closed-loop drift bound (see
                # DeviceDeltaProbe docstring).
                print("[TpuZmqWorker] --delta-device with "
                      f"delta_threshold={delta_threshold}: sub-threshold "
                      "drift is bounded by the keyframe cadence only",
                      file=sys.stderr)
            self._probe = DeviceDeltaProbe(tile=delta_tile)
        # The worker's own trace lane (bounded ring, obs.trace): batch
        # spans + egress_encode/egress_send land on track 0; the
        # snapshot merges into a fleet-wide Perfetto session like every
        # other tier's. A caller-built tracer still wins (tests).
        self.tracer = (tracer if tracer is not None
                       else Tracer(enabled=trace, process_name="worker"))
        # Metrics registry for the worker's --metrics-port endpoint.
        self.registry = MetricsRegistry()
        attach_signal_provider(self.registry, "worker", self.signals)
        # Batch-level latency attribution (obs.lineage): the worker has
        # no per-session lineage (one stream, batch-synchronous loop),
        # but every batch stamps its assemble_h2d/device/d2h hops into a
        # bounded window — stats()['attribution'] + attr_* signals
        # answer "where did the worker's latency go" the same way the
        # serve tier's frame lineage does. Always on: four clock reads
        # per BATCH, not per frame.
        from dvf_tpu.obs.lineage import AttributionAggregate

        self.attribution = AttributionAggregate(1024)
        # Wire-integrity audit (obs.audit): incoming payloads must carry
        # (and pass) the digest envelope; outgoing results are stamped
        # post-encode. Strict on ingress — in audit mode an unstamped
        # payload is indistinguishable from one whose envelope header
        # was flipped. A digest mismatch raises WireIntegrityError
        # (kind ``integrity``) into run()'s containment, attributed to
        # the zmq_ingress hop. Off by default: the reference app does
        # not speak the envelope.
        self._wire_in = None
        self._wire_out = None
        if audit_wire:
            from dvf_tpu.obs.audit import WireAudit

            self._wire_in = WireAudit("zmq_ingress")
            self._wire_out = WireAudit("zmq_egress", chaos=chaos)
        # Worker-tier reconfiguration ledger (endpoint parity with
        # serve/fleet: --metrics-port serves /ledger here too): the
        # worker's only reconfigurations are engine compiles on
        # geometry change — each lands as one compile event.
        self.ledger = None
        if ledger:
            from dvf_tpu.obs.ledger import ReconfigLedger

            self.ledger = ReconfigLedger(tracer=self.tracer, track=2)
        self.faults = FaultStats()
        self.fault_budget = fault_budget
        self.fault_window_s = fault_window_s
        self._budget = ErrorBudget(limit=fault_budget, window_s=fault_window_s)
        # Continuity plane (resilience.continuity): an armed
        # HeartbeatConfig turns DEALER silence beyond timeout_s into a
        # measured PARTITION fault — budgeted like every other kind —
        # answered by a jittered-backoff socket rebuild. None = the
        # legacy posture (credit decay alone; a dead app is invisible).
        from dvf_tpu.resilience.continuity import (
            ContinuityStats, ReconnectPolicy)

        self.heartbeat = heartbeat.validate() if heartbeat else None
        self.continuity = ContinuityStats()
        self._reconnect = (ReconnectPolicy(self.heartbeat)
                           if self.heartbeat else None)
        # The asynchronous codec plane: encode/send of batch k overlap
        # the decode/H2D/compute of batch k+1, bounded by egress_depth
        # batches in flight.
        self._plane: Optional[AsyncCodecPlane] = None
        self._egress_seq = 0
        self.batch_size = batch_size
        self.assemble_timeout_s = assemble_timeout_s
        self.use_jpeg = use_jpeg
        self.raw_size = raw_size
        self.poll_ms = poll_ms
        self.delay_s = delay_s
        self.frames_processed = 0
        self.batches = 0
        self.errors = 0
        self._stop = threading.Event()
        self._run_lock = threading.Lock()  # held for the whole run() loop
        # transport="ring": arriving frame payloads are staged in the
        # native C++ ring instead of a Python list — the same hot-path
        # component the pipeline's --transport ring uses, here between the
        # socket recv and the batch assembler. Drop-oldest applies if the
        # app ever outruns assembly (sized for 4 batches of raw frames, so
        # only under pathological backlog).
        self._ring = None
        if transport == "ring":
            from dvf_tpu.transport.ring import FrameRing

            # 2× raw size per record: JPEG is *larger* than raw for
            # noise-like content (worst case ~1.5×), and the wire payload
            # here is whatever the app sent.
            rec_bytes = 2 * (raw_size * raw_size * 3) + 4096
            self._ring = FrameRing(
                capacity_bytes=4 * batch_size * rec_bytes,
                max_frame_bytes=rec_bytes,
            )

    # ------------------------------------------------------------------

    def stop(self) -> None:
        self._stop.set()

    def _compile(self, shape, dtype) -> None:
        """The lane's compile step, ledgered: the worker's only
        reconfigurations are engine compiles on a geometry change."""
        before = self.engine.stats.compile_count
        self.engine.ensure_compiled(shape, dtype)
        if (self.ledger is not None
                and self.engine.stats.compile_count != before):
            from dvf_tpu.obs import ledger as ledger_mod

            compile_ms = self.engine.last_compile_ms
            sig_key = self.engine.signature_key
            self.ledger.record(
                ledger_mod.COMPILE,
                cause=ledger_mod.CAUSE_ADMISSION,
                signature=(sig_key.render()
                           if sig_key is not None else None),
                wall_ms=compile_ms,
                compile_ms=(round(float(compile_ms), 3)
                            if compile_ms is not None else None),
                cache="miss")

    def _builder(self, h: int, w: int):
        """One staged batch at the stream's geometry. JPEG mode decodes
        each frame in place into the lane's staging slabs via the C shim
        — zero per-batch allocations."""
        return self._lane.begin((self.batch_size, h, w, 3), np.uint8, 0)

    def _plane_for(self):
        """The asynchronous codec plane, shared across batches: encodes
        on the codec's thread pool, drains in submission order, bounded
        at egress_depth batches in flight."""
        if self._plane is None:
            self._plane = AsyncCodecPlane(
                self.codec, jpeg=self.use_jpeg, depth=self.egress_depth,
                tracer=self.tracer)
        stats = self._lane.egress_sink()
        if self._plane.stats is not stats:
            # A new fetcher brought a new stats block: the plane reports
            # into the current one, which carries the plane's window.
            stats.depth = self.egress_depth
            self._plane.stats = stats
        return self._plane

    def _pump_egress(self, pid: bytes, block: bool = False) -> None:
        """Drain completed encode batches onto the wire, in order. A
        failed encode drops its row; a failed send drops the batch
        remainder (the pre-plane whole-batch at-most-once semantics) —
        both counted under the ``transport`` fault kind and bounded by
        the error budget, so a permanently dead collector still fails
        instead of silently dropping forever."""
        plane = self._plane
        if plane is None:
            return
        for batch in plane.ready(block=block):
            t_send = time.perf_counter()
            for (idx, t0, t1), payload, err in batch:
                if err is not None:
                    self.errors += 1
                    self.faults.record(FaultKind.TRANSPORT, err)
                    if (escalate(self._budget, FaultKind.TRANSPORT,
                                 self._lane.degrade) == ErrorBudget.FAIL):
                        raise FaultError(
                            FaultKind.TRANSPORT,
                            f"transport fault budget exhausted "
                            f"(> {self.fault_budget} encode failures in "
                            f"{self.fault_window_s:g}s); last: {err!r}",
                            fatal=True) from err
                    print(f"[TpuZmqWorker] encode failed (dropping "
                          f"frame {idx}): {err!r}", file=sys.stderr)
                    continue
                if self._wire_out is not None:
                    # Post-encode stamp (and the corrupt_wire chaos
                    # site): the digest covers exactly the bytes that
                    # ride the wire.
                    payload = self._wire_out.stamp(payload)
                try:
                    self.push.send_multipart(
                        result_msg(idx, pid, t0, t1, payload))
                except Exception as e:  # noqa: BLE001 — dead/stalled peer
                    self.errors += 1
                    self.faults.record(FaultKind.TRANSPORT, e)
                    if (escalate(self._budget, FaultKind.TRANSPORT,
                                 self._lane.degrade) == ErrorBudget.FAIL):
                        raise FaultError(
                            FaultKind.TRANSPORT,
                            f"transport fault budget exhausted "
                            f"(> {self.fault_budget} send failures in "
                            f"{self.fault_window_s:g}s); last: {e!r}",
                            fatal=True) from e
                    print(f"[TpuZmqWorker] send failed (dropping batch "
                          f"remainder): {e!r}", file=sys.stderr)
                    break  # at-most-once: drop this batch's tail
            t_done = time.perf_counter()
            if plane.stats is not None:
                plane.stats.record_send((t_done - t_send) * 1e3)
            if self.tracer is not None and self.tracer.enabled:
                off = time.time() - time.perf_counter()
                self.tracer.complete(EGRESS_SEND, t_send + off,
                                     t_done + off, 0, rows=len(batch))

    def drain_egress(self, pid: Optional[bytes] = None) -> None:
        """Flush the codec plane: block until every pending encode has
        completed and its sends were attempted (clean shutdown, tests)."""
        if self._plane is None:
            return
        if pid is None:
            pid = str(os.getpid()).encode()
        while len(self._plane):
            self._pump_egress(pid, block=True)

    def _decode_jpeg(self, blobs, valid):
        """Decode a JPEG batch chunk-by-chunk into the lane's shard
        slabs, so each decoded chunk's H2D streams out under the decode
        of the next; returns the staged builder."""
        if self._geometry is None:
            self._geometry = self.codec.probe(blobs[0])
        builder = self._builder(*self._geometry)
        for start, stop in builder.windows(valid):
            self.codec.decode_batch(blobs[start:stop],
                                    out=builder.window_view(start, stop))
            builder.commit_window(start, stop)
        return builder

    def _decode_wire(self, blobs, indices, valid):
        """Decode one codec-wire batch with DELTA resync recovery.

        Delta WIRE faults (truncated tile payload, sequence gap needing
        resync) are framing violations, not pixel decode errors: each is
        classified under the ``transport`` kind, bounded by the error
        budget (whose first overflow degrades the delta path back to
        full-frame JPEG via ``_degrade_delta``), and recovered by
        restarting from the batch's next KEYFRAME after the failing row
        — a gap can only heal at a keyframe, so retrying the same deltas
        (or dropping whole batches until a keyframe happens to lead one)
        would cascade the fault across the stream. The prefix before the
        fault is dropped with it (at-most-once: its staging was
        abandoned with the assembler, and its sequence numbers are
        already consumed so it cannot be replayed). Loops because the
        recovered suffix can itself contain another fault; every
        iteration strictly shrinks the batch. Returns ``(builder,
        indices, valid)`` — builder None when the faults consumed
        everything (drop, counted, not fatal)."""
        while True:
            try:
                return self._decode_jpeg(blobs, valid), indices, valid
            except DeltaWireError as de:
                self.faults.record(FaultKind.TRANSPORT, de)
                if (escalate(self._budget, FaultKind.TRANSPORT,
                             self._degrade_delta) == ErrorBudget.FAIL):
                    raise FaultError(
                        FaultKind.TRANSPORT,
                        f"transport fault budget exhausted "
                        f"(> {self.fault_budget} delta wire faults in "
                        f"{self.fault_window_s:g}s); last: {de!r}",
                        fatal=True) from de
                self.errors += 1
                # Release the abandoned half-staged batch eagerly (same
                # rationale as the geometry re-probe: the failed attempt
                # may hold in-flight shard transfers against the slot's
                # slabs), and probe again.
                self._geometry = None
                self._lane.restage()
                # A gap can only heal at a keyframe AFTER the failing
                # row: the decoder already consumed the sequence numbers
                # before it (replaying those deltas would just raise a
                # regression gap), and re-seeking from the batch head
                # would misattribute a mid-batch fault to a perfectly
                # decodable head keyframe. decode_batch annotates the
                # failing row; without it (defensive), skip at least the
                # first blob so the loop can never retry the same
                # failure forever.
                r = getattr(de, "row", None)
                search_from = (r + 1) if r is not None else 1
                nxt = DeltaCodec.seek_keyframe(blobs[search_from:])
                start = search_from + nxt if nxt is not None else 0
                if start == 0:
                    print(f"[TpuZmqWorker] delta wire fault (dropping "
                          f"batch): {de!r}", file=sys.stderr)
                    return None, indices, 0
                print(f"[TpuZmqWorker] delta wire fault: dropping {start} "
                      f"frame(s) to the next keyframe: {de!r}",
                      file=sys.stderr)
                indices, blobs, valid = (indices[start:], blobs[start:],
                                         valid - start)

    def _process_batch(self, pending, pid) -> None:
        """Decode → engine → encode → push for one assembled batch.

        Exceptions propagate to run()'s containment: one bad batch is
        dropped and counted, never fatal (worker.py:71-76 semantics).
        """
        t0 = time.time()
        indices = [i for i, _ in pending]
        valid = len(pending)
        blobs = [b for _, b in pending]
        if self._wire_in is not None:
            # Verify + strip the audit envelope on every payload BEFORE
            # any decode: a digest mismatch (a bit flip that would still
            # JPEG-parse) raises WireIntegrityError into run()'s
            # containment — the batch drops at-most-once under the
            # integrity budget, attributed to the zmq_ingress hop.
            blobs = [self._wire_in.verify(b) for b in blobs]
        # Geometry follows the STREAM (the app's target_size), not our
        # --target-size flag, which only governs the raw path's reshape
        # (reference inverter.py:34 hardcodes raw geometry the same way).
        # Probe only when the cached assembler is absent or proves stale
        # (the cv2 fallback codec's probe() is a full decode — probing
        # every batch would double-decode the first frame on that path).
        if self.use_jpeg:
            if self.chaos is not None:
                # Injection site "decode": one event per blob; a firing
                # rule mangles that blob so the codec rejects it.
                blobs = [self.chaos.corrupt("decode", b) for b in blobs]
            try:
                builder, indices, valid = self._decode_wire(
                    blobs, indices, valid)
                if builder is None:
                    return  # delta wire faults consumed the whole batch
            except JpegGeometryError as ge:
                # Stream geometry changed (the app restarted with a new
                # target_size): re-probe, rebuild the assembler, retry
                # once. Corrupt streams raise plain ValueError and go
                # straight to run()'s containment — no wasted second
                # decode. Counted under the geometry fault kind (a
                # geometry *storm* — a flapping producer — exhausts its
                # budget and fails instead of re-probing forever).
                self.faults.record(FaultKind.GEOMETRY, ge)
                # The re-probe IS the containment, so the degrade tier
                # keeps re-probing; only the second overflow fails.
                if (escalate(self._budget, FaultKind.GEOMETRY,
                             lambda _k: True) == ErrorBudget.FAIL):
                    raise FaultError(
                        FaultKind.GEOMETRY,
                        f"geometry fault budget exhausted "
                        f"(> {self.fault_budget} re-probes in "
                        f"{self.fault_window_s:g}s): {ge!r}",
                        fatal=True) from ge
                # Release the abandoned half-staged batch's slabs
                # explicitly: the raising frame's traceback pins the
                # builder (and through it every slab) for the whole
                # retry, doubling peak staging memory until GC otherwise.
                self._geometry = None
                self._lane.restage()
                builder = self._decode_jpeg(blobs, valid)
            except FaultError:
                raise  # already classified (h2d from the lane, chaos)
            except Exception as e:  # noqa: BLE001 — corrupt JPEG stream:
                # carry the decode kind into run()'s containment so the
                # fault counters attribute it correctly.
                raise FaultError(FaultKind.DECODE,
                                 f"jpeg decode failed: {e!r}") from e
        else:
            h = w = self.raw_size
            builder = self._builder(h, w)
            for row, b in enumerate(blobs):
                try:
                    frame = np.frombuffer(b, np.uint8).reshape(h, w, 3)
                except ValueError as e:  # poison payload: wrong byte count
                    raise FaultError(FaultKind.DECODE,
                                     f"raw frame reshape failed: {e!r}") from e
                builder.write_row(row, frame)
        if self.delay_s > 0:
            # Fault injection: simulate a slow worker to exercise the app's
            # drop/reorder logic, like the reference's --delay
            # (inverter.py:37-38,55-56).
            time.sleep(self.delay_s)
        # submit pads to the compiled batch signature (static shapes —
        # one compilation for every batch size; repeat-last keeps stateful
        # temporal windows correct, see Filter.pad_safe), ships what is
        # left of the shards and runs the step.
        result = self._lane.submit(builder, valid)
        t_sub = time.time()  # decode+assemble+H2D end / device start
        # Device-side change detection (delta wire): the per-tile
        # max-abs-diff reduction is queued right behind the filter
        # program by async dispatch; only the few-hundred-byte bitmap
        # crosses to the host, and the delta encoder skips its own
        # frame-sized reduction pass.
        bitmaps = None
        coeffs = None
        if self._fused is not None:
            # Full-transform assist: ONE fused dispatch runs the probe,
            # RGB→YCbCr 4:2:0, 8×8 DCT and quantization behind the
            # filter program; only the bitmap (synced here) and, later,
            # the dirty tiles' int16 coefficient blocks cross D2H — the
            # RGB fetch below is skipped entirely.
            shape = tuple(getattr(result, "shape", ()))
            if self._fused.supports(shape, self._fused.tile):
                try:
                    bitmaps, coeffs = self._fused.process(result)
                except Exception as e:  # noqa: BLE001 — assist is
                    # optional: degrade to the probe tier, keep serving
                    print(f"[TpuZmqWorker] fused codec transform failed "
                          f"(probe fallback): {e!r}", file=sys.stderr)
                    self._fused = None
            elif not self._fused_geom_warned:
                self._fused_geom_warned = True
                print(f"[TpuZmqWorker] --codec-assist full: geometry "
                      f"{shape} not tile-aligned (tile="
                      f"{self._fused.tile}); probe assist only",
                      file=sys.stderr)
        if bitmaps is None and self._probe is not None:
            try:
                bitmaps = self._probe.bitmaps(result)
            except Exception as e:  # noqa: BLE001 — assist is optional:
                # fall back to the host reduction rather than drop frames
                print(f"[TpuZmqWorker] device delta probe failed "
                      f"(host fallback): {e!r}", file=sys.stderr)
                self._probe = None
        # Issue the D2H immediately, fetch, and hand the rows to the
        # asynchronous codec plane — encode/send of THIS batch overlap the
        # decode/H2D/compute of the next one (bounded at egress_depth
        # batches). On the full-assist path there is no pixel fetch at
        # all: the codec gathers dirty coefficient blocks lazily at
        # encode time, and the device result is only waited for.
        if coeffs is None:
            result = self._lane.prefetch(result, valid)
        t_ready = None
        try:
            # Device/D2H attribution split: the fetch below blocks on
            # compute AND transfer at once; this sync (which the fetch
            # would pay anyway) marks where compute ended.
            if coeffs is None:
                result.wait()
            else:
                result.block_until_ready()
            t_ready = time.time()
        except Exception:  # noqa: BLE001 — attribution must never turn
            pass           # a poisoned batch into a new failure mode
        # coefficient wire: no host pixel batch exists
        out = result.fetch(self._egress_seq) if coeffs is None else None
        self._egress_seq += 1
        t1 = time.time()
        comps = {"assemble_h2d": (t_sub - t0) * 1e3}
        if t_ready is not None:
            comps["device"] = (t_ready - t_sub) * 1e3
            comps["d2h"] = (t1 - t_ready) * 1e3
        else:
            comps["device"] = (t1 - t_sub) * 1e3
        self.attribution.observe((t1 - t0) * 1e3, comps)
        self.tracer.complete("batch_complete", t0, t1, 0,
                             frames=valid, batch=self.batches)
        plane = self._plane_for()
        plane.submit([None] * valid if out is None else
                     [out[i] for i in range(valid)],
                     [(idx, t0, t1) for idx in indices],
                     bitmaps=None if bitmaps is None else
                     [bitmaps[i] for i in range(valid)],
                     coeffs=None if coeffs is None else
                     [coeffs[i] for i in range(valid)])
        self.frames_processed += valid
        self.batches += 1
        self._pump_egress(pid, block=len(plane) > plane.depth)

    def run(self, max_frames: Optional[int] = None) -> None:
        """Serve until stop() (or until ``max_frames`` processed — tests).

        Resilience contract (mirrors the reference loops, worker.py:71-76 /
        distributor.py:249-251): any per-iteration failure — malformed
        message, codec error, engine error — drops that message/batch,
        bumps ``errors``, and keeps serving.
        """
        pid = str(os.getpid()).encode()
        credits = 0
        pending = []  # (frame_index:int, frame_bytes)
        first_recv_t: Optional[float] = None

        with self._run_lock:
            self._run_loop(pid, credits, pending, first_recv_t, max_frames)

    def _repartition_dealer(self) -> float:
        """Declare the ingress link partitioned (liveness timeout):
        count + classify + budget the event, ledger it, rebuild the
        DEALER socket (stale identity and queued credits die with it),
        and return the jittered backoff to wait before pumping again.
        Budget overflow escalates to a fatal fault like any other kind —
        a permanently partitioned worker must not spin silently."""
        self.continuity.inc("partitions")
        err = TimeoutError(
            f"no traffic on {self._dealer_endpoint} for "
            f"{self.heartbeat.timeout_s:.1f}s")
        self.faults.record(FaultKind.PARTITION, err)
        if self.ledger is not None:
            from dvf_tpu.obs import ledger as ledger_mod

            self.ledger.record(
                ledger_mod.PARTITION, cause=ledger_mod.CAUSE_RECOVERY,
                peer=self._dealer_endpoint, plane="worker",
                attempt=self._reconnect.attempt)
        if (escalate(self._budget, FaultKind.PARTITION,
                     lambda _k: True) == ErrorBudget.FAIL):
            raise FaultError(
                FaultKind.PARTITION,
                f"partition fault budget exhausted (> {self.fault_budget} "
                f"liveness timeouts in {self.fault_window_s:g}s); last: "
                f"{err}", fatal=True)
        self.dealer.close(0)
        self.dealer = self.ctx.socket(self._zmq.DEALER)
        self.dealer.connect(self._dealer_endpoint)
        return self._reconnect.next_delay()

    def _run_loop(self, pid, credits, pending, first_recv_t, max_frames):
        last_rx = time.monotonic()  # liveness clock (any DEALER traffic)
        partitioned = False         # reconnect awaiting confirmation
        while not self._stop.is_set():
            try:
                # Drain any encode batches the codec pool finished while
                # this loop was decoding/computing — non-blocking, so an
                # idle poll cycle still ships completed results promptly.
                self._pump_egress(pid, block=False)
                # Keep batch_size READYs outstanding so the app's ROUTER can
                # stream us frames back-to-back (the reference worker holds
                # exactly one, worker.py:39-46; credits generalize that).
                # Non-blocking sends: with the app down, credit decay would
                # otherwise re-enqueue ~100 READYs/s until the DEALER's
                # SNDHWM fills and send() blocks forever — at which point
                # stop() can no longer interrupt the loop. On a full buffer
                # we just retry next iteration.
                while credits < self.batch_size:
                    try:
                        self.dealer.send(READY, flags=self._zmq.NOBLOCK)
                    except self._zmq.Again:
                        break
                    credits += 1

                if self.dealer.poll(self.poll_ms):
                    parts = self.dealer.recv_multipart()
                    last_rx = time.monotonic()
                    if partitioned:
                        # Traffic after a partition: the reconnect took.
                        partitioned = False
                        self._reconnect.reset()
                        self.continuity.inc("reconnects")
                    if self.chaos is not None:
                        # Injection site "transport": a firing rule
                        # truncates the multipart → malformed reply below.
                        parts = self.chaos.truncate("transport", parts)
                    # Any reply consumes a credit — even a malformed or
                    # control message. Decrementing only on well-formed
                    # frames would leak that credit forever and starve the
                    # READY replenishment loop above.
                    credits = max(0, credits - 1)
                    parsed = parse_frame_reply(parts)
                    if parsed is None:
                        self.errors += 1
                        self.faults.record(
                            FaultKind.TRANSPORT,
                            ValueError(f"malformed frame reply "
                                       f"({len(parts)} parts)"))
                        if (escalate(self._budget, FaultKind.TRANSPORT,
                                     lambda _k: True) == ErrorBudget.FAIL):
                            raise FaultError(
                                FaultKind.TRANSPORT,
                                f"transport fault budget exhausted "
                                f"(> {self.fault_budget} malformed "
                                f"messages in {self.fault_window_s:g}s)",
                                fatal=True)
                    else:
                        idx, payload = parsed
                        if self._ring is not None:
                            self._ring.push(payload, idx, time.time())
                        else:
                            pending.append((idx, payload))
                        if first_recv_t is None:
                            first_recv_t = time.perf_counter()
                else:
                    # Credits DECAY on every poll timeout. The reference
                    # distributor consumes one READY per ~poll iteration
                    # and silently sends no reply whenever it has no fresh
                    # frame (distributor.py:226-244) — the common case
                    # between webcam frames — so outstanding credits are a
                    # claim the server forgets at about one per poll
                    # interval. The reference worker survives by re-sending
                    # READY every poll timeout (worker.py:38); the batched
                    # analog is to decay one credit per quiet poll, which
                    # makes the replenish loop above re-issue one READY at
                    # the same cadence. A fixed long expiry deadlocks
                    # nothing but starves the latest-wins slot: frames get
                    # overwritten while the worker sits on phantom credits.
                    credits = max(0, credits - 1)
                    if (self.heartbeat is not None
                            and (time.monotonic() - last_rx)
                            > self.heartbeat.timeout_s):
                        delay = self._repartition_dealer()
                        partitioned = True
                        credits = 0  # died with the old socket
                        # Next liveness window opens after the backoff:
                        # the reconnect ladder, not the timeout, paces a
                        # persistently dead peer.
                        last_rx = time.monotonic() + delay
                        self._stop.wait(delay)

                n_pending = len(self._ring) if self._ring is not None else len(pending)
                flush = n_pending >= self.batch_size or (
                    n_pending
                    and first_recv_t is not None
                    and time.perf_counter() - first_recv_t > self.assemble_timeout_s
                )
                if not flush:
                    continue

                if self._ring is not None:
                    pending = [(idx, payload) for payload, idx, _ts
                               in self._ring.pop_up_to(self.batch_size)]
                try:
                    self._process_batch(pending, pid)
                finally:
                    pending = []
                    # Leftovers beyond one batch (ring mode) must restart
                    # the flush clock, or a sub-batch remainder strands
                    # until the next arrival happens to reset it.
                    first_recv_t = (
                        time.perf_counter()
                        if self._ring is not None and len(self._ring)
                        else None
                    )
                if max_frames is not None and self.frames_processed >= max_frames:
                    break
            except Exception as e:  # noqa: BLE001 — per-iteration containment
                if isinstance(e, FaultError) and e.fatal:
                    raise  # a budget-exhaustion error escaping containment
                self.errors += 1
                kind = classify(e, site="worker")
                self.faults.record(kind, e)
                if escalate(self._budget, kind,
                            self._lane.degrade) != ErrorBudget.CONTAIN:
                    raise FaultError(
                        kind,
                        f"error budget exhausted for {kind!r} faults "
                        f"(> {self.fault_budget} in {self.fault_window_s:g}s"
                        f", after degradation); last: {e!r}",
                        fatal=True) from e
                print(f"[TpuZmqWorker] {kind} fault (continuing): {e!r}",
                      file=sys.stderr)
                # Drop any half-assembled batch; poison inputs must not wedge
                # the loop by re-raising forever.
                pending = []
                first_recv_t = None
        # Clean exit (stop() or max_frames): flush the codec plane so the
        # tail batches reach the wire before run() returns — async egress
        # must not turn a bounded serve into an at-most-once-minus-tail.
        try:
            self.drain_egress(pid)
        except FaultError as e:
            if e.fatal:
                raise
            self.errors += 1
            self.faults.record(e.kind, e)

    def _degrade_delta(self, kind: str) -> bool:
        """Delta-WIRE degradation, reachable only from delta wire faults
        (``_decode_wire``): fall back to full-frame JPEG on the EGRESS
        side — every frame a keyframe, framed identically, so the peer
        decodes it unchanged at exactly the full-frame codec cost. The
        worker holds no lever over what the PEER sends, so ingest-side
        faults keep being contained per batch inside the fresh budget
        window this degradation buys; a peer that stays corrupt through
        a second window still fails hard — the PR 4 ladder semantics
        (degrade = shrink OUR delta surface, not cure the peer).
        Deliberately NOT part of the lane's ladder: the generic transport
        ladder also counts send and encode failures (dead collector),
        whose overflow must keep FAILING loudly — pessimizing a healthy
        delta wire would be the wrong remedy and would absorb that
        overflow silently."""
        if self.wire == "delta" and not self.codec.full_frames:
            self.codec.full_frames = True
            self._wire_degrade_reason = "delta_fault_budget"
            print("[TpuZmqWorker] repeated delta wire faults: degrading "
                  "to full-frame JPEG (keyframe-only)",
                  file=sys.stderr, flush=True)
            return True
        return False

    def signals(self) -> dict:
        """Flat load-control signal row (registry-conformant keys) — the
        worker's half of the telemetry plane, scraped by the
        ``--metrics-port`` endpoint's provider."""
        out = {
            "frames_total": float(self.frames_processed),
            "batches_total": float(self.batches),
            "errors_total": float(self.errors),
            # Ring transport only: the list-mode backlog lives in the
            # run loop's local `pending`, invisible here — report a GAP
            # (None, dropped by the adapter), never a fake healthy 0.
            "queue_depth": (float(len(self._ring))
                            if self._ring is not None else None),
            "trace_dropped_total": float(self.tracer.dropped),
        }
        out.update(self._lane.signals())
        attr = self.attribution.summary()
        for comp, row in (attr.get("components") or {}).items():
            out[f"attr_{comp}_p99_ms"] = row["p99_ms"]
        if self._wire_in is not None:
            out["audit_wire_verified_total"] = float(
                self._wire_in.verified)
            out["audit_wire_mismatches_total"] = float(
                self._wire_in.mismatches)
            out["audit_wire_stamped_total"] = float(
                self._wire_out.stamped)
        if self.ledger is not None:
            out.update(self.ledger.signals())
        out.update(self.continuity.signals())
        for kind, n in self.faults.summary()["by_kind"].items():
            out[f"fault_{kind}_total"] = float(n)
        return out

    def audit_document(self) -> dict:
        """The worker's ``/audit`` endpoint body: wire-integrity
        counters per hop (the worker runs no shadow replay — its loop
        is batch-synchronous; wire digests are its audit surface)."""
        hops = []
        if self._wire_in is not None:
            hops = [self._wire_in.stats(), self._wire_out.stats()]
        return {
            "label": "worker",
            "wire_enabled": self._wire_in is not None,
            "wire_hops": hops,
            "wire_mismatches_total": sum(h["mismatches_total"]
                                         for h in hops),
        }

    def stats(self) -> dict:
        """Counters for tests/operators (the worker's run loop prints
        nothing on the happy path)."""
        return {
            "frames_processed": self.frames_processed,
            "batches": self.batches,
            "errors": self.errors,
            "wire": self.wire,
            **({"delta": {**self.codec.stats(),
                          "fallback_reason": self._wire_degrade_reason,
                          "device_probe": self._probe is not None,
                          "fused_transform": self._fused is not None,
                          **({"fused_dispatches": self._fused.calls}
                             if self._fused is not None else {})}}
               if self.wire == "delta" else {}),
            "faults": self.faults.summary(),
            "continuity": self.continuity.summary(),
            # Batch-level hop attribution (per-frame lineage is the
            # serve tier's; encode/send costs live in "egress" below —
            # they run asynchronously on the codec plane, so folding
            # them into the batch's additive walls would double-count).
            "attribution": {
                **self.attribution.summary(),
                **({"explain": self.attribution.explain()}
                   if self.attribution.count else {}),
            },
            **self._lane.stats(),
            **({"audit": self.audit_document()}
               if self._wire_in is not None else {}),
            **({"ledger": self.ledger.summary()}
               if self.ledger is not None else {}),
            **({"chaos": self.chaos.summary()}
               if self.chaos is not None else {}),
        }

    def close(self) -> None:
        self._stop.set()
        # Wait for run() to actually exit before freeing native resources:
        # destroying the C++ ring (or the codec pool) under a still-running
        # serve loop is a use-after-free, not an error. If the loop is
        # wedged (e.g. mid-compile) we leak rather than segfault.
        got_lock = self._run_lock.acquire(timeout=10.0)
        try:
            if got_lock:
                # Best-effort flush of the codec plane before the pool is
                # shut down (covers direct _process_batch drivers that
                # never ran the loop's own exit drain).
                try:
                    self.drain_egress()
                except Exception as e:  # noqa: BLE001 — teardown path
                    print(f"[TpuZmqWorker] close(): egress drain failed: "
                          f"{e!r}", file=sys.stderr)
            if self._ring is not None:
                if got_lock:
                    self._ring.close()
                else:
                    print("[TpuZmqWorker] close(): run loop still live after "
                          "10s; leaking ring instead of freeing under it",
                          file=sys.stderr)
            self.codec.close()
        finally:
            if got_lock:
                self._run_lock.release()
        self.dealer.close(0)
        self.push.close(0)
        self.ctx.term()
