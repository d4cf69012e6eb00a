"""Streamed shard-level batch ingest: overlap decode, H2D, and compute.

Assembling the ENTIRE batch on the host (decode-all → stage-all) and
then shipping it as one monolithic, serializing ``device_put`` keeps the
device waiting for the whole transfer. This module applies the classic
decoupled access-execute / latency-hiding move (TVM, arXiv:1802.04799):
frames decode directly into *per-device-shard* staging slabs, and each
shard is ``device_put`` the moment its rows fill, so the H2D of shard
*i* overlaps the decode of shard *i+1* and the device compute of batch
*k−1*. The finished batch is assembled with
``jax.make_array_from_single_device_arrays`` and handed to
``Engine.submit_resident`` — the engine's internal ``device_put`` is
skipped entirely.

Timeline, monolithic vs streamed (one batch of 4 shards):

    monolithic   decode ████████████ → H2D ████████ → compute ████
    streamed     decode ███░███░███░███░
                 H2D       ████ ████ ████ ████          (per shard,
                 compute ░░░░ batch k−1 ░░░░░░░         overlapped)

**The row path (PR 47): a submitted frame goes up from the buffer the
client gave.** A ``uint8[B,H,W,3]`` slab costs the host twice: the
``np.copyto`` of every frame into it on the dispatch thread (199 MB a
batch of 32 1080p frames, 25 ms), and the runtime's own de-interleave
behind ``device_put`` (the chip holds such an array channel-planar),
which takes one thread a transfer: four slab chunks stream at 6.0–8.2
GB/s where B frames, a transfer each, stream at 13.5–13.9 (PERF.md §7,
``scripts/h2d_probe.py``: ``S4`` against ``R3``). So where a batch is
one shard on one device, :meth:`BatchBuilder.put_rows` puts each frame
of the plan on the chip *from the client's own array*, as the
``uint8[H,W,C]`` it is: a transfer a frame and no copy of ours on the
host (the runtime's own copy into its transfer buffers, 0.3–0.6 ms a
1080p frame inside the call, is the one pass left), and ``finish``
hands the B device frames to :func:`ingest_join` (module
``jit_ingest_join``, compiled when the assembler is built), the slab
path's concatenate with B operands where it has ``depth``: the
``uint8[B,H,W,C]`` batch the step is compiled for, bit for bit the slab
path's, at the same cost to the chip. A padding row of a short batch is
the last valid row's device array again: padding never crosses the
link. ``IngestStats`` counts ``rows_direct_total`` /
``rows_staged_total`` and the batches of each. Eligible is read off the
input and the plan (:meth:`ShardedBatchAssembler._plan_rows`,
:meth:`BatchBuilder.put_rows`): uint8 frames of three dimensions, every
one C-contiguous at the batch's geometry, the whole batch one shard on
one device, streamed mode; anything else, and the decoders that fill
``window_view`` (``runtime/pipeline.py``, ``transport/zmq_ingress.py``),
keep ``write_row`` and the slabs below byte for byte. Who builds the
assembler (mode, depth, slots, when it is rebuilt, what a repeated fault
degrades to: monolithic, which has no row path) is
:mod:`dvf_tpu.runtime.lane`; no caller constructs one.

Shard granularity follows the engine's input sharding:

- the batch axis is partitioned over devices (data DP) → one slab per
  device batch-shard, sub-chunked up to ``depth`` pieces so transfers
  start before a whole shard decodes (a single-device mesh streams the
  same way: ``depth`` row-chunks concatenated on device — one cheap HBM
  copy buys the host↔device overlap);
- H additionally sharded (space axis) → per-device slabs carry that
  device's H slice; a decoded frame scatters its H slices across slabs;
- any *replicated* placement (batch smaller than the data axis, a model
  axis, an explicitly replicated spec) falls back to the monolithic
  whole-batch ``device_put``: XLA broadcasts a replicated transfer
  device-side, which per-device host puts cannot beat. The effective
  mode is recorded in the ingest stats either way.

Slot discipline is the pipeline's staging-pool contract unchanged: the
caller provides a monotonically increasing slot id per batch and
guarantees (via its in-flight bound) that a slot is only revisited after
its batch has been collected — by which point the device step has
consumed the slabs, so rewriting them is safe even if the backend
aliased host memory.

``depth`` is the dispatch-depth knob (``--ingest-depth``): how many
shard transfers may be in flight before the assembler blocks on the
oldest — bounding both the host memory pinned by outstanding transfers
and the burstiness of the H2D queue.
"""

from __future__ import annotations

import functools
import time
import weakref
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from dvf_tpu.obs.metrics import IngestStats
from dvf_tpu.obs.trace import INGEST_H2D
from dvf_tpu.resilience.faults import FaultError, FaultKind

INGEST_MODES = ("streamed", "monolithic")

# Below this calibrated blocking-put cost (Engine.h2d_block_ms, measured
# at compile), the fixed per-batch streaming overhead — shard-put
# dispatches, the on-device chunk concat, mesh-array assembly — exceeds
# anything overlap can hide, so the assembler stays monolithic. The
# threshold has no on-chip derivation yet (ROADMAP S3); chip_smoke.py
# prints the mode each config's bucket actually took. Tests that
# exercise the streaming machinery at tiny sizes monkeypatch this to 0.
MIN_STREAM_H2D_MS = 2.0

# -- the row path's program ------------------------------------------------

def ingest_join(*frames):
    """B device frames ``uint8[H,W,C]`` → the ``uint8[B,H,W,C]`` batch:
    the slab path's ``jnp.concatenate`` with a frame an operand (1.3–1.9
    ms of the chip for 199–398 MB, as the four-chunk join costs:
    ``scripts/h2d_probe.py``, ``J4``)."""
    import jax.numpy as jnp

    return jnp.stack(frames)


@functools.lru_cache(maxsize=16)
def _compiled_join(batch_shape: Tuple[int, ...], device):
    """:func:`ingest_join` for one batch signature on one device:
    lowered and compiled here, when an assembler is built (through the
    persistent cache), never on a first batch; cached so a hot swap back
    to a known signature and a rebuilt assembler compile nothing. The
    mirror of ``egress._compiled_pack``."""
    import jax
    import jax.numpy as jnp

    frame = jax.ShapeDtypeStruct(
        batch_shape[1:], jnp.uint8,
        sharding=jax.sharding.SingleDeviceSharding(device))
    return jax.jit(ingest_join).lower(*(frame,) * batch_shape[0]).compile()


# Host-slab accounting registry (obs.memory): every live assembler is
# weakly tracked so the scrape-time gauges — and the conftest
# session-end guard asserting zero OCCUPIED slabs once every owner has
# closed — can walk host staging memory without owners wiring anything.
_LIVE_ASSEMBLERS: "weakref.WeakSet" = weakref.WeakSet()


def live_assemblers() -> List["ShardedBatchAssembler"]:
    """Every assembler still referenced anywhere in the process (a
    released one stays listed but reports 0 ``slab_bytes``)."""
    return list(_LIVE_ASSEMBLERS)


def occupied_slab_bytes() -> int:
    """Total host staging bytes currently pinned by live assemblers —
    the ingest half of ``dvf_mem_host_slab_bytes``."""
    return sum(a.slab_bytes() for a in live_assemblers())


def _span(slc: slice, dim: int) -> Tuple[int, int]:
    start, stop, step = slc.indices(dim)
    if step != 1:
        raise ValueError(f"non-unit stride in shard index: {slc}")
    return start, stop


class _Chunk:
    """One contiguous row range of the batch and its per-tail slabs.

    ``tails`` maps a hashable key (the shard's H/W/C index) to the numpy
    slice tuple selecting that portion of a frame; ``targets`` lists the
    (device, tail_key) puts this chunk owes. Slabs live per *slot* (the
    caller's staging-pool index) so an in-flight chunk is never rewritten.
    """

    __slots__ = ("start", "stop", "tails", "targets", "slabs", "frame_like")

    def __init__(self, start: int, stop: int):
        self.start = start
        self.stop = stop
        self.tails: Dict[tuple, tuple] = {}
        self.targets: List[Tuple[Any, tuple]] = []
        self.slabs: List[Dict[tuple, np.ndarray]] = []  # per slot
        self.frame_like = False  # single tail covering the full frame

    @property
    def rows(self) -> int:
        return self.stop - self.start


class ShardedBatchAssembler:
    """Stages batches into per-shard slabs and streams them to devices.

    One assembler per (batch signature, sharding); ``begin(slot)`` yields
    a :class:`BatchBuilder` for one batch. ``mode="monolithic"``: one
    whole-batch host buffer per slot, handed back for the engine's
    classic ``submit`` path.
    """

    def __init__(
        self,
        batch_shape: Tuple[int, ...],
        dtype,
        sharding=None,
        mode: str = "streamed",
        depth: int = 4,
        slots: int = 5,
        tracer=None,
        track: int = 0,
        stats: Optional[IngestStats] = None,
        chaos=None,
    ):
        if mode not in INGEST_MODES:
            raise ValueError(f"ingest mode must be one of {INGEST_MODES}, "
                             f"got {mode!r}")
        if depth < 1:
            raise ValueError("ingest depth must be >= 1")
        self.batch_shape = tuple(batch_shape)
        self.dtype = np.dtype(dtype)
        self.batch_nbytes = (int(np.prod(self.batch_shape))
                             * self.dtype.itemsize)
        self.sharding = sharding
        self.mode = mode
        self.depth = depth
        self.slots = max(1, slots)
        self.tracer = tracer
        self.track = track
        self.wall_offset_s = time.time() - time.perf_counter()  # monotonic
        #   → wall, computed once per assembler: every traced span of this
        #   object lands on the wall clock by the same offset
        self.chaos = chaos  # resilience.chaos.FaultPlan — the "h2d"
        #   injection site fires per shard put when armed (None = zero
        #   overhead)
        self.stats = stats if stats is not None else IngestStats(
            requested_mode=mode, depth=depth)
        self._chunks: List[_Chunk] = []
        self._chunk_of_row: List[int] = []
        self._device_order: List[Any] = []
        self._mono_pool: Optional[List[np.ndarray]] = None
        self._scratch: Optional[np.ndarray] = None  # general-path decode buf
        self._join = None  # ingest_join's executable where the row path
        #   can run; the slabs exist beside it either way
        self.effective_mode = self._plan()
        self.stats.effective_mode = self.effective_mode
        if self.effective_mode == "streamed":
            self._join = self._plan_rows()
        self.stats.row_path = self._join is not None
        self.stats.pool_allocs += 1
        _LIVE_ASSEMBLERS.add(self)

    def slab_bytes(self) -> int:
        """Host staging memory this assembler currently pins (streamed
        shard slabs, the monolithic pool, the decode scratch) — 0 after
        :meth:`release`. The memory-accounting gauge's source."""
        total = 0
        for c in self._chunks:
            for slot in c.slabs:
                total += sum(a.nbytes for a in slot.values())
        if self._mono_pool is not None:
            total += sum(a.nbytes for a in self._mono_pool)
        if self._scratch is not None:
            total += self._scratch.nbytes
        return total

    # -- layout planning -------------------------------------------------

    def _plan(self) -> str:
        """Derive the chunk layout from the sharding; returns the mode
        actually used ("monolithic" when streaming cannot help)."""
        if self.mode == "monolithic" or self.sharding is None:
            return self._plan_monolithic()
        cal = self.stats.h2d_block_ms
        if cal is not None and cal < MIN_STREAM_H2D_MS:
            return self._plan_monolithic(reason="cheap_transfer")
        b = self.batch_shape[0]
        try:
            idx_map = self.sharding.devices_indices_map(self.batch_shape)
        except Exception:  # noqa: BLE001 — exotic sharding: stay correct
            return self._plan_monolithic(reason="unsupported_sharding")
        frame_shape = self.batch_shape[1:]
        groups: Dict[Tuple[int, int], List[tuple]] = {}
        try:
            for dev, idx in idx_map.items():
                b0, b1 = _span(idx[0], b)
                tail = tuple(idx[1:])
                key = tuple(_span(sl, dim)
                            for sl, dim in zip(tail, frame_shape))
                groups.setdefault((b0, b1), []).append((dev, tail, key))
        except ValueError:
            return self._plan_monolithic(reason="unsupported_sharding")
        ranges = sorted(groups)
        # The streamed path needs the device shards to PARTITION the batch
        # axis: contiguous non-overlapping row ranges covering [0, B), and
        # no two devices holding the same (rows, tail) portion. Any
        # replication means device_put's device-side broadcast beats
        # repeated host puts — monolithic wins there.
        if (ranges[0][0] != 0 or ranges[-1][1] != b
                or any(ranges[i][1] != ranges[i + 1][0]
                       for i in range(len(ranges) - 1))):
            return self._plan_monolithic(reason="replicated_layout")
        for members in groups.values():
            keys = [k for _, _, k in members]
            if len(keys) != len(set(keys)):
                return self._plan_monolithic(reason="replicated_layout")
        self._device_order = list(idx_map)
        for b0, b1 in ranges:
            rows = b1 - b0
            n_sub = min(self.depth, rows)
            bounds = [b0 + (rows * i) // n_sub for i in range(n_sub)] + [b1]
            for s, e in zip(bounds, bounds[1:]):
                c = _Chunk(s, e)
                for dev, tail, key in groups[(b0, b1)]:
                    c.tails[key] = tail
                    c.targets.append((dev, key))
                c.frame_like = (
                    len(c.tails) == 1
                    and next(iter(c.tails)) == tuple(
                        (0, d) for d in frame_shape))
                c.slabs = [
                    {key: np.empty(
                        (c.rows,) + tuple(stop - start
                                          for start, stop in key),
                        self.dtype)
                     for key in c.tails}
                    for _ in range(self.slots)
                ]
                self._chunks.append(c)
        self._chunk_of_row = [0] * b
        for i, c in enumerate(self._chunks):
            for r in range(c.start, c.stop):
                self._chunk_of_row[r] = i
        return "streamed"

    def _plan_rows(self):
        """The row path's program where a frame can go up as it is and
        the batch be made on the chip: a uint8 NHWC batch held whole as
        one shard on one device (the one-chip replica). Anything else
        keeps the slabs alone (the mirror of the fetcher's
        ``_plan_pack``)."""
        if (self.dtype != np.uint8 or len(self.batch_shape) != 4
                or len(self.sharding.device_set) != 1):
            return None
        import jax

        try:
            return _compiled_join(self.batch_shape,
                                  next(iter(self.sharding.device_set)))
        except jax.errors.JaxRuntimeError:  # a program the device cannot
            return None  # build or hold: stay correct on the slab path

    def _plan_monolithic(self, reason: Optional[str] = None) -> str:
        self.stats.fallback_reason = reason
        self._mono_pool = [
            np.empty(self.batch_shape, self.dtype) for _ in range(self.slots)
        ]
        return "monolithic"

    def _scratch_for(self, rows: int) -> np.ndarray:
        """Whole-frame decode scratch for the general (H-sharded) path —
        allocated once at the largest chunk size, reused every batch."""
        if self._scratch is None:
            biggest = max(c.rows for c in self._chunks)
            self._scratch = np.empty(
                (biggest,) + self.batch_shape[1:], self.dtype)
        return self._scratch[:rows]

    def begin(self, slot: int) -> "BatchBuilder":
        """Start staging one batch into the given staging-pool slot."""
        return BatchBuilder(self, slot % self.slots)

    def release(self) -> None:
        """Drop every staging buffer reference eagerly.

        For an assembler abandoned mid-batch (the ZMQ worker's geometry
        re-probe), the raising frame's traceback keeps the half-staged
        builder — and through it this assembler and all its slabs —
        alive for the whole retry, doubling peak staging memory until GC.
        Releasing explicitly caps the overlap at zero; in-flight
        ``device_put`` s keep their own references to the individual
        slabs they read, so dropping ours is always safe. The assembler
        is unusable afterwards (callers null their reference).
        """
        for c in self._chunks:
            c.slabs = []
        self._chunks = []
        self._chunk_of_row = []
        self._device_order = []
        self._mono_pool = None
        self._scratch = None
        self._join = None


class BatchBuilder:
    """Mutable per-batch staging state; produced by ``begin``, consumed by
    ``finish``. Rows must be written in increasing order (the pipeline,
    batcher, and decode paths are all naturally monotonic)."""

    def __init__(self, asm: ShardedBatchAssembler, slot: int):
        self.asm = asm
        self.slot = slot
        self._streamed = asm.effective_mode == "streamed"
        self._filled = [0] * len(asm._chunks) if self._streamed else [0]
        self._parts: Dict[Any, List[Any]] = {d: [] for d in asm._device_order}
        self._inflight: List[List[Any]] = []
        self._rows: Optional[List[Any]] = None  # the row path: the
        #   plan's frames on the device, from put_rows to finish
        self._join = None  # ... and the program that takes them
        self._stage_s = 0.0
        self._put_s = 0.0
        self._wait_s = 0.0
        self._join_s = 0.0
        self.direct = False  # put_rows took the batch: it goes up as rows
        self.landing = None  # _finish_rows leaves it: the row batch's
        #   landing probe (its last device frame); None on every other path

    # -- the row path ----------------------------------------------------

    def put_rows(self, frames) -> bool:
        """Put the batch's frames (its valid rows, in order) on the chip
        from the arrays they are in: a transfer a frame (one
        ``device_put`` call for the list: 10–15% cheaper on this thread
        than a call a frame, ``h2d_probe.py``'s ``Rl``), no copy of ours
        on the host, nothing written to a frame. False, with nothing
        done, where the row path cannot take this batch: the assembler
        has none (see ``ShardedBatchAssembler._plan_rows``), or a frame
        is not a C-contiguous uint8 array at the batch's geometry (a
        strided view, another dtype); the caller then stages through
        ``write_row``. A frame must stay as it is until its transfer has
        landed: the caller's contract with its clients (``Session.submit``:
        until the frame's result is delivered)."""
        asm = self.asm
        join = asm._join  # read once: release() may clear it
        if join is None or self.direct or any(self._filled):
            return False
        frame_shape = asm.batch_shape[1:]
        if not frames or len(frames) > asm.batch_shape[0] or not all(
                isinstance(f, np.ndarray) and f.dtype == np.uint8
                and f.shape == frame_shape and f.flags.c_contiguous
                for f in frames):
            return False
        import jax

        if asm.chaos is not None:
            asm.chaos.fire("h2d")  # one batch, one firing (the slab path
            #   fires a chunk)
        dev = next(iter(asm.sharding.device_set))
        t0 = time.perf_counter()
        try:
            rows = jax.device_put(list(frames), dev)
        except Exception as e:  # noqa: BLE001 — as _launch: containment
            # classifies it as h2d and can degrade the lane
            raise FaultError(
                FaultKind.H2D,
                f"row device_put failed for a batch of {len(frames)}: "
                f"{e!r}") from e
        t1 = time.perf_counter()
        self._put_s += t1 - t0
        self._rows, self._join, self.direct = rows, join, True
        tracer = asm.tracer
        if tracer is not None and tracer.enabled:
            off = asm.wall_offset_s
            tracer.complete(INGEST_H2D, t0 + off, t1 + off, asm.track,
                            rows=f"0:{len(rows)}", direct=True,
                            bytes=sum(f.nbytes for f in frames))
        return True

    def _finish_rows(self, valid: int):
        """The row path's ``finish``: ``ingest_join`` over the device
        frames, a padding row being the last valid one's again."""
        import jax

        asm = self.asm
        rows, self._rows = self._rows, None  # the device frees a frame
        #   once the join has read it, not when this builder goes
        if len(rows) != valid:
            raise ValueError(f"valid={valid} but {len(rows)} rows were put")
        t_join = time.perf_counter()
        pad = asm.batch_shape[0] - valid
        # self._join is put_rows' own: a release of the assembler
        # meanwhile takes nothing from a batch under way
        batch = jax.make_array_from_single_device_arrays(
            asm.batch_shape, asm.sharding,
            [self._join(*rows, *(rows[-1],) * pad)])
        self._join_s = time.perf_counter() - t_join
        # The landing probe: a transfer of the batch's own that no program
        # donates (the join does not; the step donates the joined batch),
        # so ``is_ready`` can be asked of it after the submit. ONE frame,
        # the last: rows of one ``device_put(list)`` mostly land in order
        # (``scripts/h2d_probe.py --landing-order``: a row behind the last
        # in 35-58% of batches, by 0.2-0.4 ms in the median), so it reads
        # early by that; holding every row costs this thread and the
        # allocator more than that is worth (PERF.md §6, PR 54). Whoever
        # takes it (``lane.prefetch``) lets it go once it has answered.
        self.landing = rows[-1]
        self._record(valid)
        return batch, True

    # -- row staging -----------------------------------------------------

    def write_row(self, row: int, frame: np.ndarray) -> None:
        """Copy one frame into its shard slab(s); launches a shard's H2D
        the moment its last row lands."""
        t0 = time.perf_counter()
        if not self._streamed:
            np.copyto(self._mono_buf()[row], frame)
            self._stage_s += time.perf_counter() - t0
            return
        ci = self.asm._chunk_of_row[row]
        c = self.asm._chunks[ci]
        local = row - c.start
        slabs = c.slabs[self.slot]
        for key, tail in c.tails.items():
            np.copyto(slabs[key][local], frame[tail])
        self._filled[ci] += 1
        self._stage_s += time.perf_counter() - t0
        if self._filled[ci] == c.rows:
            self._launch(ci)

    def windows(self, k: int) -> List[Tuple[int, int]]:
        """Contiguous row windows covering [0, k) for bulk decode — each
        window is one shard chunk (clipped at k), so committing a window
        launches its transfer while the next window decodes."""
        if not self._streamed:
            return [(0, k)] if k else []
        out = []
        for c in self.asm._chunks:
            if c.start >= k:
                break
            out.append((c.start, min(c.stop, k)))
        return out

    def window_view(self, start: int, stop: int) -> np.ndarray:
        """A (rows, H, W, C) buffer for rows [start, stop): the shard slab
        itself when it holds whole frames (zero-copy decode target), else
        a reused scratch that ``commit_window`` scatters into slabs."""
        if not self._streamed:
            return self._mono_buf()[start:stop]
        c = self.asm._chunks[self.asm._chunk_of_row[start]]
        if c.frame_like:
            key = next(iter(c.tails))
            return c.slabs[self.slot][key][start - c.start:stop - c.start]
        return self.asm._scratch_for(stop - start)

    def commit_window(self, start: int, stop: int) -> None:
        """Mark rows [start, stop) staged (scattering the scratch buffer
        into shard slabs if the fast path was unavailable); launches the
        chunk's transfers when it fills."""
        t0 = time.perf_counter()
        if not self._streamed:
            self._filled[0] = stop
            self._stage_s += time.perf_counter() - t0
            return
        ci = self.asm._chunk_of_row[start]
        c = self.asm._chunks[ci]
        if not c.frame_like:
            scratch = self.asm._scratch_for(stop - start)
            slabs = c.slabs[self.slot]
            for key, tail in c.tails.items():
                for i in range(stop - start):
                    np.copyto(slabs[key][start - c.start + i],
                              scratch[i][tail])
        self._filled[ci] += stop - start
        self._stage_s += time.perf_counter() - t0
        if self._filled[ci] == c.rows:
            self._launch(ci)

    # -- transfers -------------------------------------------------------

    def _launch(self, ci: int) -> None:
        import jax

        c = self.asm._chunks[ci]
        slabs = c.slabs[self.slot]
        if self.asm.chaos is not None:
            # Injection site "h2d": a delay rule stalls this put (models a
            # congested link), a raise rule denies it — either way exactly
            # where a real transfer fault would surface.
            self.asm.chaos.fire("h2d")
        t0 = time.perf_counter()
        arrs = []
        try:
            for dev, key in c.targets:
                arr = jax.device_put(slabs[key], dev)
                self._parts[dev].append(arr)
                arrs.append(arr)
        except Exception as e:  # noqa: BLE001 — carry the fault kind so
            # containment classifies this as h2d (and can escalate to the
            # streamed→monolithic fallback) instead of guessing from site.
            raise FaultError(
                FaultKind.H2D,
                f"shard device_put failed for rows {c.start}:{c.stop}: "
                f"{e!r}") from e
        t1 = time.perf_counter()
        self._put_s += t1 - t0
        tracer = self.asm.tracer
        if tracer is not None and tracer.enabled:
            nbytes = sum(slabs[key].nbytes for _, key in c.targets)
            off = self.asm.wall_offset_s
            tracer.complete(INGEST_H2D, t0 + off, t1 + off, self.asm.track,
                            rows=f"{c.start}:{c.stop}", bytes=nbytes)
        self._inflight.append(arrs)
        if len(self._inflight) > self.asm.depth:
            oldest = self._inflight.pop(0)
            tw = time.perf_counter()
            for a in oldest:
                a.block_until_ready()
            self._wait_s += time.perf_counter() - tw

    def _mono_buf(self) -> np.ndarray:
        return self.asm._mono_pool[self.slot]

    # -- completion ------------------------------------------------------

    def finish(self, valid: int):
        """Pad rows [valid, B) by repeating the last valid row, flush the
        remaining shard transfers, and assemble the batch.

        Returns ``(batch, resident)``: a mesh-sharded ``jax.Array`` with
        ``resident=True`` on the streamed path (feed
        ``Engine.submit_resident``), or the host staging array with
        ``resident=False`` on the monolithic path (feed ``Engine.submit``,
        which owns the transfer exactly as before).
        """
        b = self.asm.batch_shape[0]
        if not (0 < valid <= b):
            raise ValueError(f"valid={valid} out of range for batch {b}")
        if self.direct:
            return self._finish_rows(valid)
        if not self._streamed:
            t0 = time.perf_counter()
            buf = self._mono_buf()
            for row in range(valid, b):
                np.copyto(buf[row], buf[valid - 1])
            self._stage_s += time.perf_counter() - t0
            self._record(valid)
            return buf, False
        # Pad from the already-staged slabs: the source row's chunk may
        # be launched (its slab is only read), the destination rows are
        # by construction in not-yet-launched chunks.
        t0 = time.perf_counter()
        src_c = self.asm._chunks[self.asm._chunk_of_row[valid - 1]]
        src_local = valid - 1 - src_c.start
        for row in range(valid, b):
            ci = self.asm._chunk_of_row[row]
            c = self.asm._chunks[ci]
            slabs = c.slabs[self.slot]
            for key in c.tails:
                np.copyto(slabs[key][row - c.start],
                          src_c.slabs[self.slot][key][src_local])
            self._filled[ci] += 1
            if self._filled[ci] == c.rows:
                self._stage_s += time.perf_counter() - t0
                self._launch(ci)
                t0 = time.perf_counter()
        t_join = time.perf_counter()
        self._stage_s += t_join - t0
        import jax
        import jax.numpy as jnp

        arrs = []
        for dev in self.asm._device_order:
            parts = self._parts[dev]
            arrs.append(parts[0] if len(parts) == 1
                        else jnp.concatenate(parts, axis=0))
        batch = jax.make_array_from_single_device_arrays(
            self.asm.batch_shape, self.asm.sharding, arrs)
        self._join_s = time.perf_counter() - t_join
        self._record(valid)
        return batch, True

    def _record(self, valid: int) -> None:
        asm, direct = self.asm, self.direct
        asm.stats.record_batch(
            stage_ms=self._stage_s * 1e3,
            put_ms=self._put_s * 1e3,
            wait_ms=self._wait_s * 1e3,
            # What crossed the link: a slab's whole padded batch, the
            # valid rows of a row-path batch.
            nbytes=(asm.batch_nbytes // asm.batch_shape[0] * valid
                    if direct else asm.batch_nbytes),
            join_ms=self._join_s * 1e3,
            rows=valid, direct=direct,
        )
