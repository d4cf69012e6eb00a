"""Lightweight streaming metrics (fps, latency percentiles).

The reference prints raw FPS every 5 s from three places
(webcam_app.py:88-95, 152-163; distributor.py:152-171); this centralizes the
arithmetic and adds percentiles, which the north-star metric requires
(p50 end-to-end latency, BASELINE.json)."""

from __future__ import annotations

import math
import os
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from dvf_tpu.obs.lineage import BATCH_COMPONENTS, SERVE_COMPONENTS
from dvf_tpu.resilience.faults import FaultStats  # noqa: F401 — re-export:
#   the per-kind fault counters are part of the metrics surface (embedded
#   in pipeline/serve/worker stats and the bench JSON) even though the
#   taxonomy itself lives with the resilience subsystem.


class LatencyStats:
    """Streaming fps + latency percentiles.

    Bounded memory for indefinitely-running live streams: once the sample
    list hits ``max_samples`` it is decimated 2:1 and the recording stride
    doubles — percentiles stay representative at uniform coverage.
    """

    def __init__(self, max_samples: int = 200_000):
        self.max_samples = max_samples
        self.samples_ms: List[float] = []
        self.t0: Optional[float] = None
        self.t1: Optional[float] = None
        self.count = 0
        self._stride = 1

    def record(self, latency_s: float) -> None:
        now = time.perf_counter()
        if self.t0 is None:
            self.t0 = now
        self.t1 = now
        self.count += 1
        if (self.count - 1) % self._stride == 0:
            self.samples_ms.append(latency_s * 1e3)
            if len(self.samples_ms) >= self.max_samples:
                self.samples_ms = self.samples_ms[::2]
                self._stride *= 2

    def fps(self) -> float:
        if self.count < 2 or self.t1 is None or self.t1 == self.t0:
            return 0.0
        return (self.count - 1) / (self.t1 - self.t0)

    def percentiles(self, qs=(50, 90, 99)) -> Dict[str, float]:
        if not self.samples_ms:
            return {f"p{q}_ms": float("nan") for q in qs}
        arr = np.asarray(self.samples_ms)
        return {f"p{q}_ms": float(np.percentile(arr, q)) for q in qs}

    def summary(self) -> Dict[str, float]:
        return {"fps": self.fps(), "count": self.count, **self.percentiles()}

    def snapshot(self) -> Dict[str, object]:
        """One recorder's mergeable export: samples + decimation stride +
        time span, as plain JSON/pickle-safe values. This is what crosses
        a process boundary when a fleet replica ships its latency data to
        the front door (``LatencyStats.merge_snapshots`` on the other
        side) — the object form can't ride an RPC.

        The sample list is read ONCE (list() is atomic under the GIL):
        collect threads append — and decimate, swapping the list and
        doubling ``_stride`` — concurrently with this read. Pairing one
        list snapshot with one stride read keeps samples/weights the same
        length; a stride doubled between the two reads only skews
        weighting transiently, never crashes. ``pid`` tags the time base:
        ``t0``/``t1`` are ``perf_counter`` values, comparable only within
        one process.
        """
        return {
            "samples_ms": list(self.samples_ms),
            "stride": float(self._stride),
            "t0": self.t0,
            "t1": self.t1,
            "count": self.count,
            "pid": os.getpid(),
        }

    @classmethod
    def combined(cls, stats: "list[LatencyStats]") -> Dict[str, object]:
        """Many recorders → ONE snapshot (per-sample ``weights`` carry
        each recorder's stride) — the per-replica half of the fleet
        export: a frontend merges its sessions here, the fleet tier
        merges replicas' combined snapshots with ``merge_snapshots``."""
        stats = [s for s in stats if s.count]
        samples: List[float] = []
        weights: List[float] = []
        for s in stats:
            part = list(s.samples_ms)
            samples.extend(part)
            weights.extend([float(s._stride)] * len(part))
        live = [s for s in stats if s.t0 is not None]
        return {
            "samples_ms": samples,
            "weights": weights,
            "t0": min((s.t0 for s in live), default=None),
            "t1": max((s.t1 for s in live), default=None),
            "count": sum(s.count for s in stats),
            "pid": os.getpid(),
        }

    @classmethod
    def merge_snapshots(cls, snaps: "list[dict]",
                        qs=(50, 90, 99)) -> Dict[str, float]:
        """Weighted summary over :meth:`snapshot`/:meth:`combined`
        exports — the percentile/fps arithmetic behind :meth:`merged`,
        split out so it also works on data that crossed a process
        boundary (fleet replicas).

        Percentiles weight each sample by its recorder's decimation
        stride, so a long-running stream decimated 2:1 still counts each
        surviving sample for the ~stride deliveries it represents. fps
        is total deliveries over the union time span when every snapshot
        shares one time base (same ``pid`` — perf_counter origins are
        per-process); across processes it falls back to total deliveries
        over the LONGEST single span, which is the right wall-clock
        denominator for replicas that ran concurrently.
        """
        snaps = [s for s in snaps if s and s.get("count")]
        if not snaps:
            return {"fps": 0.0, "count": 0,
                    **{f"p{q}_ms": float("nan") for q in qs}}
        count = sum(int(s["count"]) for s in snaps)
        parts = []
        for s in snaps:
            arr = np.asarray(s["samples_ms"], dtype=float)
            if not len(arr):
                continue
            w = (np.asarray(s["weights"], dtype=float)
                 if s.get("weights") is not None
                 else np.full(len(arr), float(s.get("stride", 1.0))))
            parts.append((arr, w))
        if not parts:  # count incremented before the first append landed
            return {"fps": 0.0, "count": count,
                    **{f"p{q}_ms": float("nan") for q in qs}}
        samples = np.concatenate([a for a, _ in parts])
        weights = np.concatenate([w for _, w in parts])
        order = np.argsort(samples)
        cum = np.cumsum(weights[order])
        out: Dict[str, float] = {}
        for q in qs:
            k = int(np.searchsorted(cum, q / 100.0 * cum[-1]))
            out[f"p{q}_ms"] = float(samples[order][min(k, len(samples) - 1)])
        spans = [s for s in snaps
                 if s.get("t0") is not None and s.get("t1") is not None]
        fps = 0.0
        if spans and count > 1:
            if len({s.get("pid") for s in spans}) <= 1:
                dt = (max(s["t1"] for s in spans)
                      - min(s["t0"] for s in spans))
            else:
                dt = max(s["t1"] - s["t0"] for s in spans)
            if dt > 0:
                fps = (count - 1) / dt
        out["fps"] = fps
        out["count"] = count
        return out

    @classmethod
    def merged(cls, stats: "list[LatencyStats]",
               qs=(50, 90, 99)) -> Dict[str, float]:
        """Fleet-level summary across several recorders (the serving
        frontend's per-session stats → one aggregate p50/p99 export).
        Same-process sugar over :meth:`merge_snapshots`."""
        return cls.merge_snapshots(
            [s.snapshot() for s in stats if s.count], qs=qs)


class IngestStats:
    """Streamed-ingest accounting: what one batch's way onto the chip cost
    the thread that staged it, split where the work happens, and how much
    of the H2D the pipeline hid under decode/compute.

    Five cumulative clocks, each taken on ``perf_counter`` inside the call
    that does the work (the serve path's ``assemble_h2d`` interval is
    their sum plus the dispatch loop's own code):

    - ``stage_ms``: the host's copies of frames (and padding rows) into
      the staging slabs (``BatchBuilder.write_row`` / ``finish``);
    - ``put_ms``: time INSIDE the ``device_put`` calls (a host-side
      relayout that ``device_put`` does synchronously shows here);
    - ``wait_ms``: blocked on the depth window (``block_until_ready`` of
      the oldest chunk: the link, or an asynchronous relayout);
    - ``join_ms``: ``finish``, from the last pad copy to the assembled
      array (the on-device chunk concat's dispatch and
      ``make_array_from_single_device_arrays``);
    - ``step_dispatch_ms``: ``DeviceLane.submit``'s ``Engine.submit`` /
      ``submit_resident`` call, row map included (on the monolithic path
      the engine's own blocking ``device_put`` is inside it).

    The row path (PR 47; ``runtime/ingest.py``): a batch that went up
    as B rows from the clients' own arrays books 0 ms of ``stage`` (no
    host copy), its B ``device_put``s under ``put_ms`` and
    ``ingest_join``'s dispatch under ``join_ms`` (where the slab path
    books its concatenate's); ``rows_direct_total`` /
    ``direct_batches`` count its frames and batches, ``rows_staged_total``
    / ``staged_batches`` those that went through the slabs (frames are
    the batch's valid rows either way), and ``row_path`` says the row
    path exists for this signature. ``bytes_total`` stays what
    crossed the link: the whole padded batch of a slab, the valid rows
    of a row-path batch (a padding row is the last valid row's device
    array again).

    Who sums which: ``overlap_efficiency`` below counts ``put + wait`` as
    the exposed H2D; the benchmark's ``ingest_exposed_ms`` reads ``stage +
    wait`` (the copy into the slabs ADDED to the wait for the link, the
    puts left out); ``ingest_stage_ms`` / ``ingest_put_ms`` /
    ``step_dispatch_ms`` read one each, and ``dispatch_thread_pct`` prints
    all five beside what is left of ``assemble_h2d``.

    ``overlap_efficiency`` — the headline number (bench JSON, pipeline
    stats) — is the fraction of the batch's transfer cost hidden from the
    dispatch thread::

        efficiency = (h2d_block_ms − exposed_ms) / h2d_block_ms

    where ``h2d_block_ms`` is the calibrated cost of one BLOCKING
    whole-batch ``device_put`` at this signature (measured once by
    ``Engine.compile`` on its warmup put — the monolithic path's
    serialized transfer), and ``exposed_ms`` is the per-batch average
    host time the streamed path actually spent issuing transfers
    (``put_ms``) plus blocked on the depth window (``wait_ms``). 1.0
    means every transfer microsecond ran under concurrent decode/compute;
    0.0 means streaming hid nothing (e.g. a backend whose ``device_put``
    is synchronous — CPU). Reported as None when no calibration exists
    or the monolithic path ran (nothing is overlapped there by
    construction).
    """

    def __init__(self, requested_mode: str = "streamed", depth: int = 4,
                 h2d_block_ms: Optional[float] = None):
        self.requested_mode = requested_mode
        self.effective_mode = requested_mode
        self.fallback_reason: Optional[str] = None  # why streamed degraded
        #   ("replicated_layout", "cheap_transfer", "unsupported_sharding")
        self.depth = depth
        self.h2d_block_ms = h2d_block_ms
        self.batches = 0
        self.pool_allocs = 0       # staging-pool constructions (the
        #   allocation-regression tests assert this stays at 1 across a
        #   steady-state run: slabs are reused, never reallocated)
        self.stage_ms_total = 0.0
        self.put_ms_total = 0.0
        self.wait_ms_total = 0.0
        self.join_ms_total = 0.0
        self.step_dispatch_ms_total = 0.0  # DeviceLane.submit's own
        self.bytes_total = 0       # bytes staged to the device (what
        #   crossed the link: a slab's whole padded batch, the valid
        #   rows of a row-path batch)
        self.row_path = False      # the row path's program exists
        self.rows_direct_total = 0   # frames put from the client's array
        self.rows_staged_total = 0   # frames copied into a slab
        self.direct_batches = 0
        self.staged_batches = 0

    def record_batch(self, stage_ms: float, put_ms: float,
                     wait_ms: float, nbytes: int = 0,
                     join_ms: float = 0.0, rows: int = 0,
                     direct: bool = False) -> None:
        self.batches += 1
        self.bytes_total += nbytes
        if direct:
            self.rows_direct_total += rows
            self.direct_batches += 1
        else:
            self.rows_staged_total += rows
            self.staged_batches += 1
        self.stage_ms_total += stage_ms
        self.put_ms_total += put_ms
        self.wait_ms_total += wait_ms
        self.join_ms_total += join_ms

    def split_ms(self) -> tuple:
        """The five cumulative clocks, in the docstring's order: a
        caller's per-batch view is the difference of two reads."""
        return (self.stage_ms_total, self.put_ms_total, self.wait_ms_total,
                self.join_ms_total, self.step_dispatch_ms_total)

    def overlap_efficiency(self) -> Optional[float]:
        if (self.effective_mode != "streamed" or self.batches == 0
                or not self.h2d_block_ms):
            return None
        exposed = (self.put_ms_total + self.wait_ms_total) / self.batches
        return max(0.0, min(1.0, (self.h2d_block_ms - exposed)
                            / self.h2d_block_ms))

    def summary(self) -> Dict[str, object]:
        n = max(1, self.batches)
        eff = self.overlap_efficiency()
        return {
            "mode": self.effective_mode,
            "requested_mode": self.requested_mode,
            "fallback_reason": self.fallback_reason,
            "depth": self.depth,
            "batches": self.batches,
            "stage_ms": round(self.stage_ms_total / n, 4),
            "h2d_put_ms": round(self.put_ms_total / n, 4),
            "h2d_wait_ms": round(self.wait_ms_total / n, 4),
            # Cumulative totals beside the lifetime means: a window delta
            # between two reads needs no multiplying back by ``batches``.
            "stage_ms_total": round(self.stage_ms_total, 4),
            "h2d_put_ms_total": round(self.put_ms_total, 4),
            "h2d_wait_ms_total": round(self.wait_ms_total, 4),
            "join_ms_total": round(self.join_ms_total, 4),
            "step_dispatch_ms_total": round(self.step_dispatch_ms_total, 4),
            "bytes_total": self.bytes_total,
            "row_path": self.row_path,
            "rows_direct_total": self.rows_direct_total,
            "rows_staged_total": self.rows_staged_total,
            "direct_batches": self.direct_batches,
            "staged_batches": self.staged_batches,
            "h2d_block_ms": (round(self.h2d_block_ms, 4)
                             if self.h2d_block_ms else None),
            "overlap_efficiency": (round(eff, 4)
                                   if eff is not None else None),
            "pool_allocs": self.pool_allocs,
        }


class EgressStats:
    """Streamed-egress accounting — the delivery-side mirror of
    :class:`IngestStats`: how much D2H cost the collect path actually
    *exposed* vs how much the per-shard ``copy_to_host_async`` issued at
    submit hid under the tail of compute, and how much encode time the
    asynchronous codec plane ran under the next batch's compute.

    ``overlap_efficiency`` mirrors the ingest formula::

        efficiency = (d2h_block_ms − exposed_ms) / d2h_block_ms

    where ``d2h_block_ms`` is the calibrated cost of one BLOCKING
    whole-batch materialization at this signature (measured once by
    ``Engine.compile`` — ``np.asarray`` + copy into a host destination,
    the monolithic collect path's serialized fetch) and ``exposed_ms``
    is the per-batch average the streamed fetch actually spent blocked
    on shard host copies (``d2h_wait_ms``) plus scattering them into the
    output slab (``copy_ms``). None when no calibration exists or the
    monolithic path ran.

    The codec-plane half: ``encode_ms`` is the wall span of one batch's
    encode inside the pool (submit → last future done), ``encode_wait_ms``
    is how long the delivery thread actually *blocked* draining it — a
    wait far below the span is encode running under concurrent
    decode/compute, the "encode_ms no longer additive" evidence.
    """

    def __init__(self, requested_mode: str = "streamed", depth: int = 2,
                 d2h_block_ms: Optional[float] = None):
        self.requested_mode = requested_mode
        self.effective_mode = requested_mode
        self.fallback_reason: Optional[str] = None  # why streamed degraded
        #   ("zero_copy_backend", "cheap_transfer", "unsupported_sharding",
        #   "d2h_fault_budget")
        self.depth = depth               # encode-plane in-flight window
        self.d2h_block_ms = d2h_block_ms
        self.transfer_layout = "plain"   # "u32rows" where the fetcher
        #   packs results into 32-bit words on the device
        #   (runtime.egress.egress_pack) and hands out the landed buffer
        self.batches = 0
        self.packed_batches = 0          # fetched in the packed layout
        self.row_landed_batches = 0      # ... as a host buffer a row
        #   (runtime.egress.LandedRows): nobody copies a row to keep it
        self.rows_landed_total = 0       # rows of those that crossed
        self.rows_skipped_total = 0      # padding rows that never did
        self.pool_allocs = 0             # slab-pool constructions (stays 1
        #   across a steady-state run — the allocation-regression tests;
        #   0 on the packed layout, which has no pool)
        self.prefetch_rows_total = 0     # transfers started at the submit
        self.prefetch_ms_total = 0.0     # ... and what starting them (the
        #   pack's dispatch, the copy_to_host_async calls) cost the
        #   dispatch thread, inside ShardedBatchFetcher.prefetch
        self.d2h_wait_ms_total = 0.0     # blocked on shard host copies
        self.copy_ms_total = 0.0         # scatter into the output slab
        self.bytes_total = 0             # bytes landed on the host, at the
        #   OUTPUT geometry (four for each one staged under a x2 upscale);
        #   a skipped padding row's are not among them
        self.encode_batches = 0
        self.encode_ms_total = 0.0       # in-pool wall span per batch
        self.encode_wait_ms_total = 0.0  # exposed drain wait per batch
        self.entropy_batches = 0
        self.entropy_ms_total = 0.0      # host entropy-coding CPU time
        #   per batch (full-transform assist: the ONLY host codec work —
        #   compare against encode_ms on the host-transform path)
        self.send_batches = 0
        self.send_ms_total = 0.0

    def record_fetch(self, wait_ms: float, copy_ms: float,
                     packed: bool = False, nbytes: int = 0,
                     rows_landed: int = 0, rows_skipped: int = 0) -> None:
        self.batches += 1
        self.packed_batches += packed
        self.row_landed_batches += rows_landed > 0
        self.rows_landed_total += rows_landed
        self.rows_skipped_total += rows_skipped
        self.bytes_total += nbytes
        self.d2h_wait_ms_total += wait_ms
        self.copy_ms_total += copy_ms

    def record_prefetch(self, start_ms: float, transfers: int) -> None:
        self.prefetch_ms_total += start_ms
        self.prefetch_rows_total += transfers

    def record_encode(self, encode_ms: float, wait_ms: float) -> None:
        self.encode_batches += 1
        self.encode_ms_total += encode_ms
        self.encode_wait_ms_total += wait_ms

    def record_entropy(self, entropy_ms: float) -> None:
        """Host entropy-coding time for one batch (full-transform assist:
        the device already did DCT+quant, so this is the whole host-side
        codec cost — the number that replaces ``encode_ms`` as the host
        roofline)."""
        self.entropy_batches += 1
        self.entropy_ms_total += entropy_ms

    def record_send(self, send_ms: float) -> None:
        self.send_batches += 1
        self.send_ms_total += send_ms

    def overlap_efficiency(self) -> Optional[float]:
        if (self.effective_mode != "streamed" or self.batches == 0
                or not self.d2h_block_ms):
            return None
        exposed = (self.d2h_wait_ms_total + self.copy_ms_total) / self.batches
        return max(0.0, min(1.0, (self.d2h_block_ms - exposed)
                            / self.d2h_block_ms))

    def summary(self) -> Dict[str, object]:
        n = max(1, self.batches)
        ne = max(1, self.encode_batches)
        eff = self.overlap_efficiency()
        return {
            "mode": self.effective_mode,
            "requested_mode": self.requested_mode,
            "fallback_reason": self.fallback_reason,
            "depth": self.depth,
            "transfer_layout": self.transfer_layout,
            "batches": self.batches,
            "packed_batches": self.packed_batches,
            "row_landed_batches": self.row_landed_batches,
            "rows_landed_total": self.rows_landed_total,
            "rows_skipped_total": self.rows_skipped_total,
            "prefetch_rows_total": self.prefetch_rows_total,
            "prefetch_ms_total": round(self.prefetch_ms_total, 4),
            "d2h_wait_ms": round(self.d2h_wait_ms_total / n, 4),
            "copy_ms": round(self.copy_ms_total / n, 4),
            # Cumulative totals beside the lifetime means (window deltas).
            "d2h_wait_ms_total": round(self.d2h_wait_ms_total, 4),
            "copy_ms_total": round(self.copy_ms_total, 4),
            "bytes_total": self.bytes_total,
            "encode_ms_total": round(self.encode_ms_total, 4),
            "encode_wait_ms_total": round(self.encode_wait_ms_total, 4),
            "entropy_ms_total": round(self.entropy_ms_total, 4),
            "send_ms_total": round(self.send_ms_total, 4),
            "d2h_block_ms": (round(self.d2h_block_ms, 4)
                             if self.d2h_block_ms else None),
            "overlap_efficiency": (round(eff, 4)
                                   if eff is not None else None),
            "encode_batches": self.encode_batches,
            "encode_ms": round(self.encode_ms_total / ne, 4),
            "encode_wait_ms": round(self.encode_wait_ms_total / ne, 4),
            "entropy_ms": round(self.entropy_ms_total
                                / max(1, self.entropy_batches), 4),
            "send_ms": round(self.send_ms_total
                             / max(1, self.send_batches), 4),
            "pool_allocs": self.pool_allocs,
        }


# ---------------------------------------------------------------------------
# The serve path's stage clock: one set of stamps, always on
# ---------------------------------------------------------------------------

# One histogram geometry for every component, stated once: log-spaced
# edges from 0.1 ms to 100 s, 16 bins a decade (an edge ratio of 1.155),
# plus one bin below and one above. Bin 0 also takes zero and negative
# intervals (a client ``ts`` ahead of the drain).
HIST_LO_MS = 0.1
HIST_DECADES = 6
HIST_PER_DECADE = 16
HIST_BINS = HIST_DECADES * HIST_PER_DECADE + 2


def hist_bin(ms: float) -> int:
    """The cumulative histogram's bin for one interval in ms."""
    if ms < HIST_LO_MS:
        return 0
    i = int(math.log10(ms / HIST_LO_MS) * HIST_PER_DECADE) + 1
    return i if i < HIST_BINS else HIST_BINS - 1


class BatchStamps:
    """One device batch's wall-clock stamps (``time.time()``, the clock
    the Tracer's epoch and ``jax.profiler``'s ``start_trace`` are read
    on), each taken ONCE where the batch crosses the boundary:

    ``t_chosen`` (dispatch: ``select_bucket`` returned) → ``t_permit``
    (in-flight permit acquired) → ``t_submit`` (``Engine.submit``
    returned) → ``t_prefetched`` (``lane.prefetch`` returned: the way
    back is started) → ``t_taken`` (collect: popped off the in-flight
    queue) → ``t_landed`` (the handle's ``wait_landed`` returned: the
    batch's last frame is on the chip) → ``t_ready``
    (``block_until_ready`` returned) → ``t_fetched`` (``fetcher.fetch``
    returned) → ``t_routed`` (``router.route`` returned). ``t_held``:
    where the hold that this batch's binding ended had started (0.0: it
    was bound on the tick that found its frames). ``t_landed`` is
    stamped only where the collect thread SAW the landing (the handle
    read not landed at ``t_taken``); 0.0 where the bytes were there
    before it looked, or the batch has no landing probe: the thread
    observes, and its own lateness is not the link's. It cuts no frame
    component (``device`` stays ``t_taken`` → ``t_ready``) and has no
    cell in ``stages``: its one reader is the ``starved`` ledger
    (:class:`StarvedStats`). Everything that times a batch is a view of
    these: the bucket's :class:`StageStats` and :class:`StarvedStats`,
    ``FrameLineage`` marks, the Tracer's dispatch/collect spans, the
    tick-cost sample. ``stages`` is the bucket's StageStats (None on
    ad-hoc plans: nothing is folded).
    """

    __slots__ = ("stages", "t_held", "t_chosen", "t_permit", "t_submit",
                 "t_prefetched", "t_taken", "t_landed", "t_ready",
                 "t_fetched", "t_routed", "ms", "bins")

    def __init__(self, stages: "Optional[StageStats]" = None,
                 t_chosen: float = 0.0):
        self.stages = stages
        self.t_chosen = t_chosen
        self.t_held = self.t_prefetched = 0.0
        self.t_permit = self.t_submit = self.t_taken = self.t_landed = 0.0
        self.t_ready = self.t_fetched = self.t_routed = 0.0
        self.ms: Optional[tuple] = None    # the five batch-level intervals,
        self.bins: Optional[tuple] = None  # closed once by close_batch()

    def close_batch(self) -> None:
        """Collect thread, after the fetch and before ``route``: the five
        batch-level intervals (``BATCH_COMPONENTS`` order) and their
        histogram bins, computed once for every frame of the batch."""
        ms = ((self.t_permit - self.t_chosen) * 1e3,
              (self.t_submit - self.t_permit) * 1e3,
              (self.t_taken - self.t_submit) * 1e3,
              (self.t_ready - self.t_taken) * 1e3,
              (self.t_fetched - self.t_ready) * 1e3)
        self.ms = ms
        self.bins = tuple(hist_bin(v) for v in ms)

    def marks(self) -> List[tuple]:
        """The batch-level ``(component, wall_ts)`` lineage marks."""
        return list(zip(BATCH_COMPONENTS,
                        (self.t_permit, self.t_submit, self.t_taken,
                         self.t_ready, self.t_fetched)))


class _StageCell:
    """One component's cumulative cell: sum, max, histogram."""

    __slots__ = ("ms_total", "max_ms", "hist", "batches", "batch_ms_total")

    def __init__(self):
        self.ms_total = 0.0
        self.max_ms = 0.0
        self.hist = [0] * HIST_BINS
        self.batches = 0
        self.batch_ms_total = 0.0

    def add(self, ms: float) -> None:
        self.ms_total += ms
        if ms > self.max_ms:
            self.max_ms = ms
        self.hist[hist_bin(ms)] += 1

    def add_batch(self, ms: float) -> None:
        self.batches += 1
        self.batch_ms_total += ms
        if ms > self.max_ms:
            self.max_ms = ms

    def summary(self, frames: Optional[int], batch_level: bool) -> dict:
        row = {"max_ms": round(self.max_ms, 4),
               # sparse: [bin, count] for the occupied bins only
               "hist": [[i, n] for i, n in enumerate(self.hist) if n]}
        if frames is not None:     # a frame component (route, prefetch
            #   are thread states only)
            row["frames"] = frames
            row["ms_total"] = round(self.ms_total, 4)
        if batch_level:
            row["batches"] = self.batches
            row["batch_ms_total"] = round(self.batch_ms_total, 4)
        return row


class StageStats:
    """Always-on per-bucket stage counters: where a delivered frame's
    latency went, and what the two pacing threads did for this bucket.

    Eight frame components in hop order (``obs.lineage.SERVE_COMPONENTS``),
    every one cumulative: ``frames``, ``ms_total`` (frame-weighted: a
    batch-level interval counts once per delivered frame of its batch),
    ``max_ms`` and a histogram on the fixed edges above, so a window
    delta between two reads yields a mean AND percentiles. Beside them
    ``delivered`` and ``latency_ms_total`` (the sum of ``now − ts`` over
    the same frames): the eight ``ms_total`` add up to it, because every
    interval telescopes between the same stamps. Only delivered frames
    are folded, each exactly once, at ``StreamSession.deliver_ready``.

    The batch-level components also carry ``batches`` and
    ``batch_ms_total`` (once per batch, whatever its fill): the pacing
    threads' states for this bucket — dispatch ``{permit_wait,
    assemble_h2d, prefetch}``, collect ``{device, d2h, route}``
    (``prefetch``: submit returned → ``lane.prefetch`` returned, and
    ``route``: fetched → ``router.route`` returned, are thread states and
    no frame components: a frame's ``inflight_wait`` runs from the submit
    and already holds the one, ``deliver`` the frames' share of the other).

    Writers: the dispatch thread writes its three batch cells, the collect
    thread the other four, so those take no lock; the frame fold runs on
    whichever thread delivers (collect, or a finalize) and takes one
    lock per delivery round. Readers see monotone values.
    """

    def __init__(self):
        self.delivered = 0
        self.latency_ms_total = 0.0
        self.cells: Dict[str, _StageCell] = {
            c: _StageCell() for c in SERVE_COMPONENTS}
        self.route = _StageCell()
        self.prefetch = _StageCell()
        self._lock = threading.Lock()
        c = self.cells
        self._frame_cells = (c["queue_ingress"], c["queue_bucket"],
                             c["deliver"])
        self._batch_cells = tuple(c[n] for n in BATCH_COMPONENTS)

    # -- writers ---------------------------------------------------------

    def note_dispatched(self, st: BatchStamps) -> None:
        """Dispatch thread, once ``lane.prefetch`` returned."""
        permit, asm = self._batch_cells[0], self._batch_cells[1]
        permit.add_batch((st.t_permit - st.t_chosen) * 1e3)
        asm.add_batch((st.t_submit - st.t_permit) * 1e3)
        self._add_state(self.prefetch, (st.t_prefetched - st.t_submit) * 1e3)

    @staticmethod
    def _add_state(cell: _StageCell, ms: float) -> None:
        """A thread-state cell (no frame fold): its histogram is per batch."""
        cell.add_batch(ms)
        cell.hist[hist_bin(ms)] += 1

    def note_collected(self, st: BatchStamps) -> None:
        """Collect thread, once ``router.route`` returned (``st`` closed)."""
        ms = st.ms
        cells = self._batch_cells
        cells[2].add_batch(ms[2])
        cells[3].add_batch(ms[3])
        cells[4].add_batch(ms[4])
        self._add_state(self.route, (st.t_routed - st.t_fetched) * 1e3)

    def fold_delivered(self, rows) -> None:
        """Fold one delivery round: ``rows`` = ``[(slot, now), ...]`` of
        frames this bucket served, ``now`` the clock read their latency
        was computed from. One lock round; consecutive frames of one
        batch share its closed intervals."""
        qi, qb, dl = self._frame_cells
        with self._lock:
            cur, n, lat = None, 0, 0.0
            for slot, now in rows:
                st = slot.stamps
                ts = slot.ts
                tp = slot.t_pending
                qi.add((tp - ts) * 1e3)
                qb.add((st.t_chosen - tp) * 1e3)
                dl.add((now - st.t_fetched) * 1e3)
                lat += (now - ts) * 1e3
                if st is not cur:
                    if cur is not None:
                        self._fold_batch_rows(cur, n)
                    cur, n = st, 0
                n += 1
            if cur is not None:
                self._fold_batch_rows(cur, n)
            self.latency_ms_total += lat
            self.delivered += len(rows)

    def _fold_batch_rows(self, st: BatchStamps, n: int) -> None:
        for cell, ms, b in zip(self._batch_cells, st.ms, st.bins):
            cell.ms_total += ms * n
            cell.hist[b] += n

    # -- export ----------------------------------------------------------

    def summary(self) -> dict:
        """``stats()["buckets"][label]["stages"]``. ``t`` is the read's
        own wall time, so two reads carry the window between them."""
        with self._lock:
            frames = self.delivered
            doc = {
                "t": time.time(),
                "delivered": frames,
                "latency_ms_total": round(self.latency_ms_total, 4),
                "hist_lo_ms": HIST_LO_MS,
                "hist_bins_per_decade": HIST_PER_DECADE,
                "hist_bins": HIST_BINS,
                "components": {
                    name: cell.summary(frames, name in BATCH_COMPONENTS)
                    for name, cell in self.cells.items()},
            }
        doc["route"] = self.route.summary(None, True)
        doc["prefetch"] = self.prefetch.summary(None, True)
        return doc


# What the dispatch thread was doing, in a batch's own stamps, between
# the moment the chip ran out of this frontend's work and the moment it
# got more (StarvedStats); each ends at the stamp beside it.
STARVED_STATES = ("idle", "hold", "permit_wait", "assemble_h2d")


class StarvedStats:
    """A bucket's starvation ledger: why the chip had nothing of ours to
    run, asked of the program's own stamps (the bucket row's ``starved``
    block, cumulative ms).

    The chip ran out of work at ``t_ready`` of batch n−1 and got more at
    ``t_submit`` of batch n. Where that interval is positive it is a
    *gap*, cut at batch n's stamps and booked under what the dispatch
    thread was doing in each part: ``idle`` (before the bucket had frames
    to bind: up to ``t_held``, or ``t_chosen`` for a batch that was not
    held), ``hold`` (``t_held`` → ``t_chosen``), ``permit_wait``
    (``t_chosen`` → ``t_permit``), ``assemble_h2d`` (``t_permit`` →
    ``t_submit``). Written by the collect thread, which keeps the
    previous batch's ``t_ready``; the gap belongs to the bucket of batch
    n. A collect thread's first batch (a generation's first, the first
    after a supervised recovery) opens none.

    **The landing (PR 54).** The submit returns when the step is
    dispatched, not when its input is on the chip: a batch's last bytes
    may still be on the link (199 MB a batch in the invert cell). From
    ``max(t_ready(n−1), t_submit(n))`` to ``t_landed(n)`` the chip holds
    a dispatched step of ours and nothing it can run. Where the collect
    thread saw the landing (``st.t_landed`` set) that interval, where
    positive, is ``landing_ms_total``, a fifth state beside the four; the
    four, ``gaps_total`` and ``max_gap_ms`` are what they were, and end
    at the submit. Where the bytes were on the chip before the thread
    looked (``t_landed`` 0.0 on a batch that had a probe), the landing
    is COUNTED and never timed: ``[max(t_ready(n−1), t_submit(n)),
    t_taken(n)]`` where positive goes to ``landing_unseen_ms_total``, an
    upper bound that no share adds in. ``landed_seen_total`` /
    ``landed_unseen_total`` count the batches of each, a collect
    thread's first batch too (a batch without a probe, the slab and
    monolithic paths', is neither); the two ``landing_*_ms_total`` need
    a predecessor, as a gap does.

    Bias, as ``_Bucket.observe_device`` states its own: ``t_ready`` is
    read by the collect thread, so when that thread is behind the device
    the gap reads too SHORT, never too long; ``t_landed`` is read by the
    same thread, so a seen landing reads LATE by at most the thread's
    wake-up (and early by the rows that land behind the batch's last,
    which is the probe: ``runtime/ingest.py::_finish_rows``). What is
    left between this ledger and the device trace's idle share is the
    step's own dispatch-to-start latency, and the landings nobody saw:
    nearly all of them wherever the collect thread reaches a batch after
    its bytes (PERF.md §6, PR 54: every cell but the live one).
    """

    def __init__(self):
        self.ms = dict.fromkeys(STARVED_STATES, 0.0)
        self.gaps = 0
        self.max_gap_ms = 0.0
        self.landing_ms = self.landing_unseen_ms = 0.0
        self.landed_seen = self.landed_unseen = 0

    def note(self, last_ready: float, st: BatchStamps,
             probed: bool = False) -> None:
        """Collect thread, once batch n is ready: ``last_ready`` is batch
        n−1's ``t_ready`` (0.0: there was none on this thread),
        ``probed`` the handle's (the batch had a landing probe)."""
        if probed:
            seen = st.t_landed > 0.0
            # the chip had nothing else of ours from max(...) on, until
            # the bytes were there (seen), or at the latest until the
            # thread looked and found them there (unseen: a bound)
            ms = ((st.t_landed if seen else st.t_taken)
                  - max(last_ready, st.t_submit)) * 1e3
            if not last_ready or ms < 0.0:
                ms = 0.0
            if seen:
                self.landed_seen += 1
                self.landing_ms += ms
            else:
                self.landed_unseen += 1
                self.landing_unseen_ms += ms
        if not last_ready or st.t_submit <= last_ready:
            return
        cur = last_ready
        ends = (st.t_held or st.t_chosen, st.t_chosen, st.t_permit,
                st.t_submit)
        for state, end in zip(STARVED_STATES, ends):
            if end > cur:
                self.ms[state] += (end - cur) * 1e3
                cur = end
        gap_ms = (st.t_submit - last_ready) * 1e3
        self.gaps += 1
        if gap_ms > self.max_gap_ms:
            self.max_gap_ms = gap_ms

    def summary(self) -> dict:
        return {**{f"{k}_ms_total": round(v, 4) for k, v in self.ms.items()},
                "gaps_total": self.gaps,
                "max_gap_ms": round(self.max_gap_ms, 4),
                "landing_ms_total": round(self.landing_ms, 4),
                "landing_unseen_ms_total": round(self.landing_unseen_ms, 4),
                "landed_seen_total": self.landed_seen,
                "landed_unseen_total": self.landed_unseen}


class ThreadClock:
    """One pacing thread's wall-time ledger: every interval of the
    thread's life lands in exactly one state, so the states sum to
    ``accounted_to − started``. ``idle`` is whatever belongs to no
    bucket (no plan and sleeping a tick, control actions, an empty
    in-flight queue, a batch that was shed or whose submit or prefetch
    raised); the named states are the batch intervals the bucket's
    :class:`StageStats` also holds (dispatch ``permit_wait``,
    ``assemble_h2d``, ``prefetch``; collect ``device``, ``d2h``,
    ``route``), and the dispatch thread's ``hold`` (ticks on which a
    bucket's short batch waited for the device's backlog: the bucket
    row's ``hold`` block), summed here over every bucket the frontend ever had. Single writer (the
    thread itself); a replacement thread (supervised recovery) adopts
    its predecessor's clock, so the ledger spans the frontend's life."""

    def __init__(self, states, now: Optional[float] = None):
        self.started = time.time() if now is None else now
        self.mark = self.started      # everything before it is accounted
        self.ms: Dict[str, float] = {"idle": 0.0, **{s: 0.0 for s in states}}

    def spend(self, state: str, until: float) -> float:
        """Everything since the last accounted instant, up to ``until``
        (a stamp the caller already took), was ``state``: the ms booked."""
        ms = (until - self.mark) * 1e3
        self.ms[state] += ms
        self.mark = until
        return ms

    def successor(self) -> "ThreadClock":
        """The ledger a replacement thread carries on from."""
        nxt = ThreadClock((), self.started)
        nxt.ms = dict(self.ms)
        nxt.mark = self.mark
        return nxt

    def summary(self) -> dict:
        mark = self.mark
        return {"started": self.started, "accounted_to": mark,
                "wall_ms": round((mark - self.started) * 1e3, 4),
                **{f"{k}_ms": round(v, 4) for k, v in self.ms.items()}}


class RateLogger:
    """Periodic printer, like the reference's every-5s FPS prints
    (webcam_app.py:88-95).

    When a ``registry`` (obs.registry.MetricsRegistry) is attached, every
    computed rate ALSO lands as the ``rate_fps`` gauge labeled
    ``{stage: name}`` — the every-5s stderr number and the ``/metrics``
    scrape are then the same arithmetic on the same ticks and can never
    disagree. ``quiet`` silences the print only; the gauge keeps
    updating (a quiet server is still scrapeable).
    """

    def __init__(self, name: str, interval_s: float = 5.0,
                 quiet: bool = False, registry=None):
        self.name = name
        self.interval_s = interval_s
        self.quiet = quiet
        self.last_rate: Optional[float] = None
        self._gauge = (registry.gauge("rate_fps")
                       if registry is not None else None)
        self._count = 0
        self._last = time.perf_counter()

    def tick(self, n: int = 1) -> Optional[float]:
        self._count += n
        now = time.perf_counter()
        dt = now - self._last
        if dt >= self.interval_s:
            rate = self._count / dt
            self.last_rate = rate
            if self._gauge is not None:
                self._gauge.set(rate, labels={"stage": self.name})
            if not self.quiet:
                print(f"[{self.name}] {rate:.1f} fps")
            self._count = 0
            self._last = now
            return rate
        return None
