"""The device lane: the one owner of a batch's way onto and off the chip.

    begin → BatchBuilder → submit → device result → prefetch → handle
    (assembler, runtime/ingest.py)  (Engine)       (fetcher, runtime/egress.py)

A caller (a ``ServeFrontend`` bucket, ``Pipeline``, ``TpuZmqWorker``)
gives the engine, its transfer options and how many batches it keeps in
flight. The rest is decided here and nowhere else: which mode each side
runs and why it fell back, how many slots, the calibrations that seed
the two stats blocks, when the assembler and the fetcher are rebuilt,
what a repeated transfer fault degrades to, and when a slab may go
while batches of an older program are still in flight.

Threads: ``begin`` / ``submit`` / ``prefetch`` / ``retarget`` are the
caller's dispatch thread's; an :class:`InflightBatch` goes to whichever
thread collects it; ``degrade``, ``release``, ``slab_bytes``: any thread.
"""

from __future__ import annotations

import sys
import threading
import time
import weakref
from typing import Any, Callable, Optional, Tuple, Union

import numpy as np

from dvf_tpu.obs.metrics import EgressStats, IngestStats
from dvf_tpu.resilience.faults import FaultKind
from dvf_tpu.runtime.egress import (
    EGRESS_MODES,
    ShardedBatchFetcher,
    device_side,
)
from dvf_tpu.runtime.ingest import (
    INGEST_MODES,
    BatchBuilder,
    ShardedBatchAssembler,
)

# Trace tracks of the per-shard transfer spans (ingest_* / egress_d2h),
# clear of the callers' own lanes (0-2).
TRACK_H2D, TRACK_D2H = 3, 4

# The transfer fault kinds a lane can degrade: the side, and the
# fallback reason that side's stats block then reports.
_DEGRADABLE = {FaultKind.H2D: ("ingest", "h2d_fault_budget"),
               FaultKind.D2H: ("egress", "d2h_fault_budget")}


class InflightBatch:
    """One batch between ``prefetch`` and ``fetch``, pinned to the
    fetcher its transfer was issued on: a hot swap or a degradation may
    give the lane another one while this batch is in flight. ``layout``:
    its transfer layout, for the caller's d2h span.

    It can also say where the batch's way UP ended, where the caller gave
    ``prefetch`` the batch's builder and the batch went up on the row
    path: ``landed()`` / ``wait_landed()`` ask the builder's landing probe
    (the batch's last device frame, which no program donates) whether its
    bytes have crossed the link, for the collector's ``t_landed`` stamp.
    ``probed``: there was such a probe (else the batch reads landed: the
    slab and monolithic paths, and every caller but the serve frontend).
    The frame goes once it has answered.

    What ``fetch`` returns, and who may keep a row of it (``out[row]``,
    ``row < valid``, either way):

    - ``egress.LandedRows`` (the packed layout): every row is the host
      buffer it landed in and shares memory with nothing. Keep it as
      long as you like, read-only; it pins that row alone.
    - an ``ndarray``: rows are views of the batch. ``owns(out)`` True:
      a pooled slab, rewritten ``inflight + 1`` batches later, so copy
      what outlives that. False: a fresh per-batch array (monolithic,
      the per-batch fallback) that lives as long as any view of it, so
      copy what may outlive the batch by long (the serve router does).
    """

    __slots__ = ("_lane", "_fetcher", "_payload", "_device", "layout",
                 "_landing", "probed")

    def __init__(self, lane: "DeviceLane", fetcher: ShardedBatchFetcher,
                 payload: Any, landing: Any = None):
        self._lane, self._fetcher, self._payload = lane, fetcher, payload
        self._device, self.layout = device_side(payload)
        self._landing = landing
        self.probed = landing is not None

    def wait(self) -> None:
        """Block until the device is done with this batch (the step, and
        the pack where there is one); the transfer may still be landing."""
        self._device.block_until_ready()

    def is_ready(self) -> bool:
        return self._device.is_ready()

    def landed(self) -> bool:
        """True once the batch's bytes are on the chip (or there is no
        probe to ask). The collecting thread's, like ``wait_landed``."""
        if self._landing is not None and self._landing.is_ready():
            self._landing = None
        return self._landing is None

    def wait_landed(self) -> None:
        """Block until the batch's bytes are on the chip. The landing
        precedes the step's end: this wait and ``wait`` in a row cost
        the thread what ``wait`` alone did."""
        landing, self._landing = self._landing, None
        if landing is not None:
            landing.block_until_ready()

    def fetch(self, seq: int):
        """The batch as host frames, once. ``seq`` is the caller's
        monotone batch number: the delivery slab's slot."""
        try:
            return self._fetcher.fetch(self._payload, seq)
        finally:
            self._lane._fetched(self._fetcher)

    def owns(self, out) -> bool:
        """True when ``out`` is a pooled slab, rewritten ``inflight + 1``
        batches later: rows that outlive that are the caller's to copy."""
        return self._fetcher.owns(out)


class DeviceLane:
    """A batch's round trip through the link, for one engine.

    ``options`` carries the caller's ``ingest``, ``ingest_depth`` and
    ``egress`` (a ``ServeConfig``, a ``PipelineConfig``, the worker) and
    is read whenever a side is (re)built: a planned mode or depth
    written there reaches the lane on its next batch. ``inflight``
    bounds the batches between ``submit`` and the end of their rows'
    use, ``staging_inflight`` (default the same) those between ``begin``
    and the device having consumed them; a side keeps one slot more, so
    a slot being rewritten belongs to a batch that is done.
    ``compile(batch_shape, dtype)`` stands in for
    ``engine.ensure_compiled`` where the caller ledgers or seeds its
    compiles; ``name`` labels a degradation's stderr line."""

    def __init__(self, engine, options, inflight: int, *,
                 staging_inflight: Optional[int] = None,
                 tracer=None, chaos=None,
                 compile: Optional[Callable[[tuple, Any], None]] = None,
                 name: Union[str, Callable[[], str]] = "lane"):
        for side, modes in (("ingest", INGEST_MODES),
                            ("egress", EGRESS_MODES)):
            if getattr(options, side) not in modes:
                raise ValueError(f"{side} must be one of {modes}, got "
                                 f"{getattr(options, side)!r}")
        self.engine = engine
        self.options = options
        self._egress_slots = inflight + 1
        self._staging_slots = 1 + (inflight if staging_inflight is None
                                   else staging_inflight)
        self.tracer, self.chaos = tracer, chaos
        self._compile = compile
        self.name = name
        self.ingest_stats: Optional[IngestStats] = None
        self.egress_stats: Optional[EgressStats] = None
        self._assembler: Optional[ShardedBatchAssembler] = None
        self._fetcher: Optional[ShardedBatchFetcher] = None
        self._degraded: set = set()  # fault kinds already degraded
        self._lock = threading.Lock()  # guards _fetcher swaps and _pending
        self._pending = weakref.WeakKeyDictionary()  # fetcher → batches
        #   prefetched into it and not yet fetched. A key other than
        #   ``_fetcher`` is a parked fetcher (``retarget`` retired it with
        #   batches in flight). Weak: batches shed unfetched (a
        #   supervised recovery) must not pin a parked fetcher's slabs.

    def _mode(self, kind: str) -> Tuple[str, Optional[str]]:
        """(mode to build ``kind``'s side in, fault fallback reason)."""
        side, reason = _DEGRADABLE[kind]
        if kind in self._degraded:
            return "monolithic", reason
        return getattr(self.options, side), None

    # -- onto the chip -----------------------------------------------------

    def begin(self, batch_shape: Tuple[int, ...], dtype,
              seq: int) -> BatchBuilder:
        """Start staging batch number ``seq`` (monotone; its slot). The
        assembler's own builder comes back: ``write_row`` runs once a
        frame on the thread that paces the host-bound cells, or
        ``put_rows`` once a batch where the frames may go up from the
        arrays they are in (the row path, ``runtime/ingest.py``: its
        ``ingest_join`` is compiled here, with the assembler). A new
        batch signature, ingest depth or mode rebuilds the assembler."""
        shape, dtype = tuple(batch_shape), np.dtype(dtype)
        mode, reason = self._mode(FaultKind.H2D)
        depth = self.options.ingest_depth
        asm = self._assembler
        if (asm is None or asm.batch_shape != shape or asm.dtype != dtype
                or asm.depth != depth or asm.mode != mode):
            self.restage()
            # The engine's compiled input sharding defines the shard
            # layout, and its warmup put is the un-overlapped H2D cost
            # that overlap_efficiency is judged against.
            (self._compile or self.engine.ensure_compiled)(shape, dtype)
            self.ingest_stats = IngestStats(
                requested_mode=self.options.ingest, depth=depth,
                h2d_block_ms=self.engine.h2d_block_ms)
            self._assembler = asm = ShardedBatchAssembler(
                shape, dtype, self.engine.input_sharding, mode=mode,
                depth=depth, slots=self._staging_slots, tracer=self.tracer,
                track=TRACK_H2D, stats=self.ingest_stats, chaos=self.chaos)
            if reason is not None:
                self.ingest_stats.fallback_reason = reason
        return asm.begin(seq)

    def submit(self, builder: BatchBuilder, valid: int, rows=None):
        """Pad and flush the staged batch, run the step: the (async)
        device result. ``rows``: a session-state filter's row map. The
        engine call's own time (the jitted step's dispatch on the host)
        is the ingest block's ``step_dispatch_ms``."""
        batch, resident = builder.finish(valid)
        run = (self.engine.submit_resident if resident
               else self.engine.submit)
        t0 = time.perf_counter()
        result = run(batch) if rows is None else run(batch, rows)
        self.ingest_stats.step_dispatch_ms_total += (
            time.perf_counter() - t0) * 1e3
        return result

    # -- off the chip ------------------------------------------------------

    def prefetch(self, result, valid: Optional[int] = None,
                 builder: Optional[BatchBuilder] = None) -> InflightBatch:
        """Start ``result``'s way back now, under the tail of its compute
        and the next batch's staging; the caller keeps the handle in its
        place. ``valid``: the rows that carry a frame (None = all); on
        the packed layout the padding behind them never crosses the
        link. ``builder``: the batch's, from a caller whose collector
        will ask the handle where the batch landed; its landing probe
        changes hands here. A new output signature or mode rebuilds the
        fetcher."""
        shape, dtype = self.engine.out_shape, self.engine.out_dtype
        mode, reason = self._mode(FaultKind.D2H)
        f = self._fetcher
        if (f is None or f.out_shape != shape or f.dtype != dtype
                or f.mode != mode):
            self.egress_stats = None  # a fresh block with the fetcher
            f = ShardedBatchFetcher(  # compiles the pack: not under the lock
                shape, dtype, self.engine.output_sharding, mode=mode,
                slots=self._egress_slots, stats=self.egress_sink(),
                tracer=self.tracer, track=TRACK_D2H, chaos=self.chaos)
            if reason is not None:
                self.egress_stats.fallback_reason = reason
            self._swap_fetcher(f, park=True)
        with self._lock:
            self._pending[f] = self._pending.get(f, 0) + 1
        landing = None
        if builder is not None:
            landing, builder.landing = builder.landing, None
        return InflightBatch(self, f, f.prefetch(result, valid), landing)

    def _swap_fetcher(self, new: Optional[ShardedBatchFetcher],
                      park: bool) -> None:
        """Install ``new``; the old fetcher is freed now, or, parked,
        once the last batch prefetched into it has been fetched."""
        with self._lock:
            old, self._fetcher = self._fetcher, new
            if park and old is not None and self._pending.get(old):
                return
        if old is not None:
            old.release()

    def _fetched(self, f: ShardedBatchFetcher) -> None:
        with self._lock:
            n = self._pending.get(f, 0) - 1
            if n > 0:
                self._pending[f] = n
                return
            self._pending.pop(f, None)
            if f is self._fetcher:
                return
        f.release()  # parked, and that was its last batch

    # -- what the host reports ---------------------------------------------

    def stats(self) -> dict:
        """The host's ``ingest`` / ``egress`` stats blocks, each from
        the first batch that built its side."""
        return {k: s.summary() for k, s in self._sides()}

    def signals(self) -> dict:
        return {f"{k}_overlap_efficiency": s.overlap_efficiency()
                for k, s in self._sides()}

    def _sides(self):
        sides = (("ingest", self.ingest_stats), ("egress", self.egress_stats))
        return [(k, s) for k, s in sides if s is not None]

    def egress_sink(self) -> EgressStats:
        """The egress block for a caller-side stage to report into (the
        worker's codec plane): the fetcher's, or, while nothing was ever
        prefetched (the coefficient wire fetches no pixels), a bare one."""
        if self.egress_stats is None:
            self.egress_stats = EgressStats(
                requested_mode=self.options.egress,
                d2h_block_ms=self.engine.d2h_block_ms)
        return self.egress_stats

    def slab_bytes(self) -> int:
        """Host staging and delivery memory this lane pins right now."""
        a = self._assembler
        with self._lock:
            fetchers = {self._fetcher, *self._pending} - {None}
        return ((a.slab_bytes() if a is not None else 0)
                + sum(f.slab_bytes() for f in fetchers))

    # -- faults, swaps, teardown -------------------------------------------

    def degrade(self, kind: str) -> bool:
        """``resilience.budget.escalate``'s first-overflow degradation:
        repeated ``h2d`` faults put ingest, ``d2h`` faults egress, on
        the monolithic path, the reason in that side's next stats block.
        True if applied: once a kind, and only from a streamed request."""
        side = _DEGRADABLE.get(kind, (None,))[0]
        if (side is None or kind in self._degraded
                or getattr(self.options, side) != "streamed"):
            return False
        self._degraded.add(kind)
        if side == "ingest":
            self.restage()
        else:
            self._swap_fetcher(None, park=False)  # freed now: batches in
            #   flight fall back per batch (the packed ones unpack)
        who = self.name() if callable(self.name) else self.name
        print(f"[{who}] repeated {kind} faults: degrading {side} "
              f"streamed → monolithic", file=sys.stderr, flush=True)
        return True

    def restage(self) -> None:
        """Abandon the staging side, a half-staged batch with it, now: a
        raising frame's traceback would pin the builder (and through it
        every slab) across the caller's retry. Transfers in flight keep
        their own references to the slabs they read."""
        old, self._assembler = self._assembler, None
        if old is not None:
            old.release()

    def retarget(self, engine) -> None:
        """A hot swap committed, or a rebuild replaced the engine: both
        sides re-derive at their next use; batches in flight come back
        through their own fetcher, freed with the last of them."""
        self.engine = engine
        self.restage()
        self._swap_fetcher(None, park=True)

    def release(self) -> None:
        """Free every slab of both sides now, parked fetchers' too
        (teardown; a recovery that shed the window). The next batch
        rebuilds; one still in flight falls back to a per-batch fetch."""
        self.restage()
        with self._lock:
            drop = {self._fetcher, *self._pending} - {None}
            self._fetcher = None
            self._pending.clear()
        for f in drop:
            f.release()
