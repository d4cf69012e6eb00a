"""Streamed shard-level egress + asynchronous codec plane.

The symmetric twin of :mod:`dvf_tpu.runtime.ingest`, on the D2H side: a
whole-batch ``np.asarray(result)`` is one serializing fetch that
allocates a fresh host batch, and an encode after it runs serially. The
same operation-overlap discipline, applied at delivery:

- :class:`ShardedBatchFetcher` — per-output-shard ``copy_to_host_async``
  issued the moment the batch is submitted (so D2H runs under the tail
  of compute and the next batch's staging), materialized shard-by-shard
  into a *preallocated* host slab at collect time (no per-batch
  allocation; the copy of shard *i* overlaps the in-flight transfer of
  shard *i+1*). Where the result is one shard on one device (the
  one-chip replica) the fetcher owns the **transfer layout** instead,
  see below;
- :class:`AsyncCodecPlane` — a bounded-window, order-preserving encoder
  over the existing ``JpegCodec``/``NativeJpegCodec`` thread pools
  (``encode_batch_async`` futures): the delivery loop submits a batch's
  rows and returns to decoding/computing the NEXT batch while the pool
  encodes; completed batches drain in submission order.

Who builds a fetcher, with which mode and how many slots, when it is
rebuilt and when its slabs go is :class:`dvf_tpu.runtime.lane.DeviceLane`'s
to decide; no caller constructs one.

Timeline, monolithic vs streamed (worker-style decode→compute→encode):

    monolithic   decode ████ compute ████ fetch ███ encode ██████ send █
    streamed     decode ████ compute ████ fetch ▒█          (prefetch hid
                 encode        ░░ batch k−1 ░░  ██████       most of it)
                 send                            batch k−1 █

The transfer layout (PR 26). A TPU holds a ``uint8[B, H, W, C]`` result
channel-planar (``{2,1,3,0:T(8,128)(4,1)}``) and ``np.asarray`` of it
hands back a numpy array in that order, not C-contiguous: the slab copy
that followed was a byte-granular transposing gather at 0.4–0.5 GB/s on
the collect thread (393 of invert_1080p's 435 ms a batch), and the
transfer itself ran at 1 GB/s. A 32-bit array lands C-contiguous at
3 GB/s. So on the streamed path, for a uint8 NHWC result whose rows are
whole words (``W·C % 4 == 0``) held as one shard on one device:

- the fetcher compiles :func:`egress_pack` (module ``jit_egress_pack``)
  when it is built: the interleave as a permutation on the MXU,
  ``uint8[B,H,W,C]`` → B arrays ``uint32[H,W·C/4]``, one a row, the same
  bytes in row-major order (3–5 ms of device time for 199 MB, 1.1 for
  44 MB);
- ``prefetch(result, valid)`` runs it, starts ``copy_to_host_async`` on
  each of the first ``valid`` rows and returns a :class:`PackedBatch`,
  which the lane's in-flight handle carries in the result's place (the
  device frees the result once the pack has read it). A padding row
  (``row >= valid``) is dropped there: it never crosses the link;
- ``fetch(packed, slot)`` waits for those transfers row by row and
  returns :class:`LandedRows`: each row the buffer it landed in, viewed
  as ``uint8[H,W,C]``: read-only, one pass over the bytes (the
  runtime's), no slab, no copy (``copy_ms`` is 0), no pool allocated.

**A delivered frame is the buffer it landed in (PR 39).** A row of
:class:`LandedRows` shares memory with nothing: it may sit in a reorder
buffer, an out queue or a replay ring for as long as it likes and keeps
1/B of the batch alive on the host and nothing on the device (the
device rows go with the :class:`PackedBatch`), so nobody copies it: as
views of one landed ``uint32[B,H,W·C/4]`` buffer the rows had to be
copied out by the serve router, 100–199 MB a batch on the collect
thread, and B transfers land at 9–13 GB/s on this host where one lands
at 3 (``scripts/d2h_probe.py``, PERF.md §6). Every other path hands out
one ``ndarray`` a batch, whose rows are views of it; those are copied
by whoever keeps them beyond the batch (``serve/router.py::route``
tells the two apart by what ``fetch`` returned, nothing else).

``EgressStats.transfer_layout`` (``"u32rows"`` / ``"plain"``),
``packed_batches`` / ``row_landed_batches`` and ``rows_landed_total`` /
``rows_skipped_total`` say that it engaged; the ``egress_d2h`` and
``collect:d2h`` spans carry ``layout=``. No flag: a result sharded over
several devices, another dtype or rank, rows that are no whole words, a
batch of another geometry, monolithic mode and a released fetcher keep
the paths below byte for byte.

Which path runs where (ledger, PRs 26–37): all five benchmark cells
(``invert_1080p.bulk``, ``style_720p.bulk`` / ``.live``,
``flow_720p.bulk``, ``sr2x_540p.bulk``) serve one chip and run the
packed layout (``packed_batches == row_landed_batches == batches``);
the layout took invert from 72.4 to 346.0 frames/s and tied in the
style and flow cells. The per-shard slab path is what a result sharded
over several devices gets; its copy is the transposing gather above,
and no cell runs it yet (the four-chip cell, PERF.md §7a, decides:
pack per shard there, or delete: ROADMAP D3b).
Monolithic is the CPU backend's path, the degrade target and the tests'
reference.

Fallbacks mirror the ingest assembler, recorded in the stats either way:

- ``mode="monolithic"`` and results that are not shard-addressable keep
  the classic ``np.asarray`` fetch;
- a CPU-backend result's ``np.asarray`` is already a zero-copy view of
  the runtime buffer, so any slab copy is pure added work
  (``fallback_reason="zero_copy_backend"``; tests monkeypatch
  ``STREAM_ON_CPU`` to exercise the machinery);
- a calibrated blocking fetch (``Engine.d2h_block_ms``) below the fixed
  streaming overhead stays monolithic (``"cheap_transfer"``, the mirror
  of ingest's ``MIN_STREAM_H2D_MS`` guard);
- repeated d2h faults degrade streamed → monolithic through the error
  budget (``"d2h_fault_budget"``: ``DeviceLane.degrade``).

Slot discipline is the staging-pool contract unchanged: the caller
provides a monotonically increasing slot id per batch and guarantees
(via its in-flight bound / encode window) that a slab is only revisited
after its rows have been copied onward or sent.
"""

from __future__ import annotations

import functools
import threading
import time
import weakref
from collections import deque
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from dvf_tpu.obs.metrics import EgressStats
from dvf_tpu.obs.trace import EGRESS_D2H, EGRESS_ENCODE

EGRESS_MODES = ("streamed", "monolithic")

# Below this calibrated blocking-fetch cost (Engine.d2h_block_ms,
# measured at compile), the fixed per-batch streaming overhead — shard
# iteration, slab scatter — exceeds anything overlap can hide, so the
# fetcher stays monolithic. Mirror of ingest.MIN_STREAM_H2D_MS; tests
# that exercise the streaming machinery at tiny sizes monkeypatch to 0.
MIN_STREAM_D2H_MS = 2.0

# On the CPU backend ``np.asarray(result)`` of a single-device result is
# a zero-copy view — the monolithic path costs literally nothing, and a
# slab copy would be a pure regression. Tests monkeypatch True to run
# the streamed machinery on the CPU test backend.
STREAM_ON_CPU = False

# Host-slab accounting registry (obs.memory) — the egress mirror of
# runtime.ingest._LIVE_ASSEMBLERS: scrape-time gauges and the conftest
# session-end leak guard walk it; a released fetcher reports 0.
_LIVE_FETCHERS: "weakref.WeakSet" = weakref.WeakSet()


# -- the transfer layout --------------------------------------------------

# Pixels per MXU chunk of the pack program. 512 pixels of C channels are
# 128·C whole 32-bit words, so every chunk boundary is lane-aligned
# (multiples of 128) on the input side (pixels) and the output side
# (words) alike; only a row's last chunk may be shorter.
PACK_CHUNK_PX = 512

TRANSFER_PLAIN = "plain"        # the result as the step program left it
TRANSFER_PACKED = "u32rows"     # egress_pack's words, one row of W·C/4


def pack_table(width: int, channels: int) -> np.ndarray:
    """The pack program's permutation for one chunk, ``float32[C, P,
    2·Pw]`` with ``P = min(width, PACK_CHUNK_PX)`` pixels and ``Pw =
    P·C/4`` words: byte ``k`` of word ``j`` of the interleaved stream is
    channel ``c`` of pixel ``p`` where ``p·C + c == 4·j + k``; column
    ``j`` (low half-words) or ``Pw + j`` (high half-words) of row ``p``
    of plane ``c`` holds 1 for an even ``k`` and 256 for an odd one. The
    pattern repeats every four pixels, so one table serves every chunk
    and a short last chunk reads the prefix of each half."""
    p = min(width, PACK_CHUNK_PX)
    pw = p * channels // 4
    byte = np.arange(p * channels)
    word, k = byte // 4, byte % 4
    table = np.zeros((channels, p, 2 * pw), np.float32)
    table[byte % channels, byte // channels, (k // 2) * pw + word] = \
        np.where(k % 2, 256.0, 1.0)
    return table


def _pack_chunks(y, table):
    """The words of ``uint8[B, H, W, C]`` in row-major (interleaved)
    order, as one ``uint32[B, H, n·C/4]`` array per chunk of ``n <=
    PACK_CHUNK_PX`` pixels along W.

    XLA's own reshape of the channel-planar bytes goes through a 42×
    padded intermediate (C on the lanes) and does not compile at 1080p,
    so the interleave is a permutation on the MXU: each plane's pixels,
    exact in bfloat16, times :func:`pack_table` give the low and the
    high 16 bits of every word (two terms per column, exact in the
    float32 accumulator)."""
    import jax.numpy as jnp
    from jax import lax

    _, _, width, channels = y.shape
    p = min(width, PACK_CHUNK_PX)
    pw = p * channels // 4
    words = []
    for w0 in range(0, width, p):
        n = min(p, width - w0)
        nw = n * channels // 4
        acc = None
        for c in range(channels):
            t = table[c] if n == p else jnp.concatenate(
                [table[c, :n, :nw], table[c, :n, pw:pw + nw]], axis=1)
            d = lax.dot_general(
                y[:, :, w0:w0 + n, c].astype(jnp.bfloat16), t,
                (((2,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc = d if acc is None else acc + d
        words.append(acc[..., :nw].astype(jnp.uint32)
                     | (acc[..., nw:].astype(jnp.uint32) << 16))
    return words


def _join(chunks):
    import jax.numpy as jnp

    return chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks, axis=-1)


def pack_words(y, table):
    """``uint8[B, H, W, C]`` → ``uint32[B, H, W·C/4]`` holding the same
    bytes in row-major (interleaved) order, computed on the device (why:
    the module docstring; how: :func:`_pack_chunks`)."""
    return _join(_pack_chunks(y, table))


def egress_pack(y, table):
    """:func:`pack_words` as B arrays ``uint32[H, W·C/4]``, one a batch
    row, each joined from its own slices of the chunks (no whole-batch
    array is built on the way): each row gets a device buffer and, once
    fetched, a host buffer of its own, so a frame that outlives its
    batch keeps nothing else alive (``scripts/d2h_probe.py`` variant
    ``R``: what the rows cost on the device and on the link against the
    one array)."""
    chunks = _pack_chunks(y, table)
    return tuple(_join([c[i] for c in chunks]) for i in range(y.shape[0]))


@functools.lru_cache(maxsize=16)
def _compiled_pack(out_shape: Tuple[int, ...], device):
    """(executable, device-resident table) for one output signature on
    one device — lowered and compiled here, when a fetcher is built,
    never on the first batch; cached so a hot swap back to a known
    signature and a rebuilt fetcher compile nothing."""
    import jax
    import jax.numpy as jnp

    where = jax.sharding.SingleDeviceSharding(device)
    table = jax.device_put(
        jnp.asarray(pack_table(out_shape[2], out_shape[3]), jnp.bfloat16),
        where)
    spec = jax.ShapeDtypeStruct(out_shape, jnp.uint8, sharding=where)
    return jax.jit(egress_pack).lower(spec, table).compile(), table


class PackedBatch:
    """One batch in the packed transfer layout, in flight: the rows of
    the pack program's words that are on their way to the host (the
    batch's valid rows; a padding row was dropped at the prefetch) and
    the batch shape they belong to. What ``prefetch`` returns in the
    result's place on that layout; any fetcher unpacks it, whatever
    became of the one that packed it (released, degraded)."""

    __slots__ = ("rows", "out_shape")

    def __init__(self, rows: Tuple[Any, ...], out_shape: Tuple[int, ...]):
        self.rows = rows
        self.out_shape = out_shape


class LandedRows(tuple):
    """A fetched batch whose rows each own the host buffer they landed
    in (the packed layout's ``fetch``): ``out[row]`` for ``row < valid``
    as an ``ndarray`` batch gives it, but no row shares memory with
    another, so one kept beyond the batch pins that row alone and a
    keeper need not copy it. Rows are read-only."""

    __slots__ = ()


def device_side(payload: Any) -> Tuple[Any, str]:
    """(the device array whose readiness is the batch's, its transfer
    layout) for what ``prefetch`` returned: what the lane's in-flight
    handle waits on, and the ``layout=`` of the collect span."""
    if isinstance(payload, PackedBatch):
        # One executable wrote every row: any of them is ready when
        # the pack is done.
        return payload.rows[0], TRANSFER_PACKED
    return payload, TRANSFER_PLAIN


def live_fetchers() -> List["ShardedBatchFetcher"]:
    return list(_LIVE_FETCHERS)


def occupied_slab_bytes() -> int:
    """Total host delivery-slab bytes currently pinned by live fetchers
    — the egress half of ``dvf_mem_host_slab_bytes``."""
    return sum(f.slab_bytes() for f in live_fetchers())


class ShardedBatchFetcher:
    """Fetches engine results into preallocated host slabs, per shard,
    or, where it owns the transfer layout, as the landed buffer itself.

    One fetcher per (output signature, sharding); ``handle =
    prefetch(result)`` belongs right after ``Engine.submit`` (it issues
    the per-shard ``copy_to_host_async``, or the pack and its transfer,
    so the transfer overlaps the tail of compute), ``fetch(handle,
    slot)`` belongs in the collect path (it materializes into the
    slot's slab and only *waits*, never initiates).

    The returned array is the slab itself on the streamed slab path —
    valid until the slot is revisited (the caller's in-flight bound), so
    consumers that hold rows longer (reorder buffers) must copy them;
    ``owns(out)`` says so. The monolithic path and the per-batch
    fallback return a fresh array that lives as long as a view of it
    does; the packed layout returns :class:`LandedRows`, a buffer a row.
    """

    def __init__(
        self,
        out_shape: Tuple[int, ...],
        dtype,
        sharding=None,
        mode: str = "streamed",
        slots: int = 5,
        stats: Optional[EgressStats] = None,
        tracer=None,
        track: int = 0,
        chaos=None,
    ):
        if mode not in EGRESS_MODES:
            raise ValueError(f"egress mode must be one of {EGRESS_MODES}, "
                             f"got {mode!r}")
        self.out_shape = tuple(out_shape)
        self.dtype = np.dtype(dtype)
        self.sharding = sharding
        self.mode = mode
        self.slots = max(1, slots)
        self.tracer = tracer
        self.track = track
        self.wall_offset_s = time.time() - time.perf_counter()  # monotonic
        #   → wall, once per fetcher (not per shard span)
        self.chaos = chaos  # resilience.chaos.FaultPlan — the "d2h"
        #   injection site fires per shard fetch when armed
        self.stats = stats if stats is not None else EgressStats(
            requested_mode=mode)
        self._pool: Optional[List[np.ndarray]] = None
        self._pack = None  # (executable, table) while the transfer
        #   layout is TRANSFER_PACKED; no slab pool exists then
        self.effective_mode = self._plan()
        self.stats.effective_mode = self.effective_mode
        _LIVE_FETCHERS.add(self)

    def slab_bytes(self) -> int:
        """Host delivery-slab memory this fetcher currently pins — 0
        after :meth:`release` (and always 0 on the monolithic path,
        which allocates per batch instead of pooling)."""
        if self._pool is None:
            return 0
        return sum(a.nbytes for a in self._pool)

    def _plan(self) -> str:
        if self.mode == "monolithic" or self.sharding is None:
            return "monolithic"
        try:
            dev = next(iter(self.sharding.device_set))
            if dev.platform == "cpu" and not STREAM_ON_CPU:
                self.stats.fallback_reason = "zero_copy_backend"
                return "monolithic"
        except Exception:  # noqa: BLE001 — exotic sharding: stay correct
            self.stats.fallback_reason = "unsupported_sharding"
            return "monolithic"
        cal = self.stats.d2h_block_ms
        if cal is not None and cal < MIN_STREAM_D2H_MS:
            self.stats.fallback_reason = "cheap_transfer"
            return "monolithic"
        self._pack = self._plan_pack()
        if self._pack is not None:
            self.stats.transfer_layout = TRANSFER_PACKED
        else:
            self._pool = [np.empty(self.out_shape, self.dtype)
                          for _ in range(self.slots)]
            self.stats.pool_allocs += 1
        return "streamed"

    def _plan_pack(self):
        """The pack program where the transfer layout can help and the
        landed buffer can be handed out whole: a uint8 NHWC result whose
        rows are whole 32-bit words, held as one shard on one device
        (the one-chip replica). Anything else keeps the slab path."""
        shape = self.out_shape
        if (self.dtype != np.uint8 or len(shape) != 4
                or (shape[2] * shape[3]) % 4
                or len(self.sharding.device_set) != 1):
            return None
        import jax

        try:
            return _compiled_pack(shape, next(iter(self.sharding.device_set)))
        except jax.errors.JaxRuntimeError:  # a program the device cannot
            return None  # build or hold: stay correct on the slab path

    # -- submit side ----------------------------------------------------

    def prefetch(self, result: Any, valid: Optional[int] = None) -> Any:
        """Start the D2H now, overlapped with the next batch's staging and
        the tail of this batch's compute; ``fetch`` then only waits for
        completion instead of initiating the copy. Returns what to hand
        ``fetch``: on the packed layout the pack program's first
        ``valid`` rows, each with its own transfer started (the caller
        drops ``result``, which the device frees once the pack has read
        it; the padding rows past ``valid``, None = none, are freed here
        and never transferred), otherwise ``result`` itself, its
        transfer started per shard on the streamed path so each shard's
        copy is independently in flight."""
        t0 = time.perf_counter()
        handle, started = self._start_transfers(result, valid)
        self.stats.record_prefetch((time.perf_counter() - t0) * 1e3, started)
        return handle

    def _start_transfers(self, result: Any, valid: Optional[int]):
        """``prefetch``'s work: (what to hand ``fetch``, transfers started)."""
        pack = self._pack  # read once: release() may clear it
        if (pack is not None
                and getattr(result, "dtype", None) == self.dtype
                and tuple(result.shape) == self.out_shape
                and result.sharding.device_set == self.sharding.device_set):
            run, table = pack
            rows = tuple(run(result, table))[:valid]
            for row in rows:
                row.copy_to_host_async()
            return PackedBatch(rows, self.out_shape), len(rows)
        started = 0
        try:
            if self.effective_mode == "streamed":
                seen = set()
                for sh in result.addressable_shards:
                    # Same dedupe as fetch(): replicated placements hold
                    # identical bytes on every device — starting N
                    # identical transfers would waste N−1 batches of
                    # link bandwidth on the submit hot path.
                    key = tuple((sl.start, sl.stop, sl.step)
                                for sl in sh.index)
                    if key in seen:
                        continue
                    seen.add(key)
                    sh.data.copy_to_host_async()
                    started += 1
            else:
                result.copy_to_host_async()
                started = 1
        except AttributeError:
            pass  # non-jax results (tests/fakes) have nothing to prefetch
        return result, started

    # -- collect side ---------------------------------------------------

    def _streamable(self, result: Any) -> bool:
        return (self.effective_mode == "streamed"
                and self._pool is not None  # released mid-flight (egress
                #   degradation, hot swap): a plan-pinned fetcher must
                #   fall back per batch, not scatter into freed slabs
                and hasattr(result, "addressable_shards")
                and getattr(result, "is_fully_addressable", True)
                and tuple(result.shape) == self.out_shape)

    def fetch(self, result: Any, slot: int):
        """Materialize one batch; blocks until the device is done (like
        the ``np.asarray`` it replaces) but scatters shard host copies
        into the slot's preallocated slab as each one lands. An
        ``ndarray`` whose rows are views of it, or, on the packed
        layout, :class:`LandedRows`."""
        if isinstance(result, PackedBatch):
            return self._fetch_packed(result)
        if not self._streamable(result):
            # A mid-stream geometry change can hand this fetcher a batch
            # compiled at another signature — fall back per batch rather
            # than corrupt the slab. (Intentional monolithic mode and
            # non-jax results land here too: the classic fetch.)
            out = np.asarray(result)
            self.stats.record_fetch(wait_ms=0.0, copy_ms=0.0,
                                    nbytes=out.nbytes)
            return out
        # Compute wait is not D2H: exclude it from the exposed-transfer
        # clock so overlap_efficiency judges the fetch, not the device.
        try:
            result.block_until_ready()
        except AttributeError:
            pass
        slab = self._pool[slot % self.slots]
        wait_s = 0.0
        copy_s = 0.0
        seen = set()
        tracer = self.tracer
        for sh in result.addressable_shards:
            # Replicated output placements hold identical bytes on every
            # device — one host copy per distinct index range is enough.
            key = tuple((sl.start, sl.stop, sl.step) for sl in sh.index)
            if key in seen:
                continue
            seen.add(key)
            if self.chaos is not None:
                # Injection site "d2h": a delay rule stalls this shard's
                # fetch (models a congested link), a raise rule denies it
                # — exactly where a real transfer fault would surface.
                self.chaos.fire("d2h")
            t0 = time.perf_counter()
            host = np.asarray(sh.data)  # waits on THIS shard's copy only
            t1 = time.perf_counter()
            np.copyto(slab[sh.index], host)
            t2 = time.perf_counter()
            wait_s += t1 - t0
            copy_s += t2 - t1
            if tracer is not None and tracer.enabled:
                off = self.wall_offset_s
                b0 = sh.index[0]
                tracer.complete(
                    EGRESS_D2H, t0 + off, t2 + off, self.track,
                    rows=f"{b0.start or 0}:{b0.stop}", bytes=host.nbytes,
                    layout=TRANSFER_PLAIN)
        self.stats.record_fetch(wait_ms=wait_s * 1e3, copy_ms=copy_s * 1e3,
                                nbytes=slab.nbytes)
        return slab

    def _fetch_packed(self, packed: PackedBatch) -> LandedRows:
        """The packed layout's fetch: wait, row by row, for the
        transfers ``prefetch`` started and hand out the buffers they
        landed in, each viewed as a frame. One pass over the bytes, the
        runtime's; no slab, no copy (``copy_ms`` is 0 by construction).
        A row is read-only, shares memory with no other, and holds
        nothing of the device: the device rows go with ``packed``."""
        packed.rows[0].block_until_ready()  # the step's and the pack's
        #   device time are not D2H (see fetch)
        if self.chaos is not None and self._pack is not None:
            self.chaos.fire("d2h")  # one batch, one firing; a released
            #   fetcher (degraded to monolithic, torn down) has left the
            #   site, as its slab fetch has, also for a batch packed
            #   before that
        frame = packed.out_shape[1:]
        t0 = time.perf_counter()
        out = LandedRows(np.asarray(row).view(np.uint8).reshape(frame)
                         for row in packed.rows)
        t1 = time.perf_counter()
        nbytes = sum(row.nbytes for row in out)
        tracer = self.tracer
        if tracer is not None and tracer.enabled:
            off = self.wall_offset_s
            tracer.complete(
                EGRESS_D2H, t0 + off, t1 + off, self.track,
                rows=f"0:{len(out)}", bytes=nbytes, layout=TRANSFER_PACKED)
        self.stats.record_fetch(
            wait_ms=(t1 - t0) * 1e3, copy_ms=0.0, packed=True, nbytes=nbytes,
            rows_landed=len(out),
            rows_skipped=packed.out_shape[0] - len(out))
        return out

    def owns(self, out) -> bool:
        """True when ``out`` is one of this fetcher's pooled slabs — i.e.
        it will be rewritten once the slot cycles, so rows that outlive
        the caller's collect step must be copied. The monolithic and
        per-batch-fallback paths return fresh arrays, the packed layout
        rows of their own, and stay False."""
        return self._pool is not None and any(out is s for s in self._pool)

    def release(self) -> None:
        """Drop the slab pool eagerly (geometry re-probe / degradation:
        same rationale as ``ShardedBatchAssembler.release``)."""
        self._pool = None
        self._pack = None  # a PackedBatch in flight unpacks regardless


class _EncodeEntry:
    __slots__ = ("metas", "futures", "payloads", "t_submit", "t_done",
                 "_remaining", "_lock")

    def __init__(self, metas, futures, payloads, t_submit):
        self.metas = metas
        self.futures = futures      # None on the raw (no-encode) path
        self.payloads = payloads    # raw path: zero-copy memoryviews
        self.t_submit = t_submit
        self.t_done = t_submit
        self._remaining = len(futures) if futures else 0
        self._lock = threading.Lock()

    def mark_done(self) -> None:
        """Done-callback (pool thread): stamps the batch's encode span
        end when its last future completes."""
        with self._lock:
            self._remaining -= 1
            if self._remaining == 0:
                self.t_done = time.perf_counter()

    def done(self) -> bool:
        if self.futures is None:
            return True
        return all(f.done() for f in self.futures)

    def collect(self) -> List[Tuple[Any, Any, Optional[BaseException]]]:
        """(meta, payload, error) per row, in submission order; a failed
        encode surfaces as its row's error instead of poisoning the
        batch."""
        if self.futures is None:
            return [(m, p, None) for m, p in zip(self.metas, self.payloads)]
        out = []
        for meta, fut in zip(self.metas, self.futures):
            try:
                out.append((meta, fut.result(), None))
            except Exception as e:  # noqa: BLE001 — per-row containment
                out.append((meta, None, e))
        return out


class AsyncCodecPlane:
    """Bounded-window, order-preserving async encode over a codec pool.

    ``submit(rows, metas)`` hands one batch's rows to the codec's thread
    pool (``encode_batch_async``) and returns immediately; ``ready()``
    drains *completed head* batches — delivery order is submission order,
    never completion order. ``ready(block=True)`` (or ``flush``) waits
    for the head, which is how callers enforce the in-flight window:

        plane.submit(rows, metas)
        for batch in plane.ready(block=len(plane) > plane.depth):
            for meta, payload, err in batch: …send…

    The raw (``jpeg=False``) path skips the pool entirely and carries
    each row as a zero-copy memoryview over the caller's slab — valid
    until the slab slot is reused, which the window bound guarantees
    happens only after the send (zmq copies at send time).

    Thread contract: ``submit``/``ready``/``flush`` are called from one
    delivery thread; only the future done-callbacks run in pool threads.
    """

    def __init__(self, codec, jpeg: bool = True, depth: int = 2,
                 stats: Optional[EgressStats] = None, tracer=None,
                 track: int = 0):
        if depth < 1:
            raise ValueError("encode depth must be >= 1")
        self.codec = codec
        self.jpeg = jpeg
        self.depth = depth
        self.stats = stats
        self.tracer = tracer
        self.track = track
        self.wall_offset_s = time.time() - time.perf_counter()
        self._pending: "deque[_EncodeEntry]" = deque()

    def __len__(self) -> int:
        return len(self._pending)

    def submit(self, rows: Sequence[np.ndarray], metas: Sequence[Any],
               bitmaps: Optional[Sequence[np.ndarray]] = None,
               coeffs: Optional[Sequence[Any]] = None) -> None:
        """``bitmaps`` (delta wire only): per-row device-computed dirty-
        tile reductions (runtime.codec_assist.DeviceDeltaProbe), handed
        through to ``DeltaCodec.encode_batch_async`` so the host skips
        its own change-detection pass. Ignored by full-frame codecs.

        ``coeffs`` (full-transform assist): per-row
        ``transport.codec.CoefficientFrame`` handles from the fused
        device pass — the codec entropy-codes device-quantized blocks
        and never touches pixels, so ``rows`` may be ``[None, ...]``."""
        t0 = time.perf_counter()
        if self.jpeg:
            if coeffs is not None:
                futures = self.codec.encode_batch_async(
                    rows, bitmaps=bitmaps, coeffs=coeffs)
            elif bitmaps is not None:
                futures = self.codec.encode_batch_async(rows,
                                                        bitmaps=bitmaps)
            else:
                futures = self.codec.encode_batch_async(rows)
            entry = _EncodeEntry(list(metas), futures, None, t0)
            for f in futures:
                f.add_done_callback(lambda _f, e=entry: e.mark_done())
        else:
            # Raw wire: zero-copy memoryviews over the staged slab rows
            # (flattened — the wire carries bytes, not shapes).
            payloads = [row.reshape(-1).data for row in rows]
            entry = _EncodeEntry(list(metas), None, payloads, t0)
        self._pending.append(entry)

    def ready(self, block: bool = False) -> List[list]:
        """Completed head batches, each a list of (meta, payload, error)
        rows. ``block=True`` waits for at least the head batch (the
        window-bound path); completed non-head batches always wait their
        turn — ordered delivery is the contract."""
        out = []
        while self._pending:
            entry = self._pending[0]
            if not entry.done():
                if not block:
                    break
                tw = time.perf_counter()
                if entry.futures is not None:
                    for f in entry.futures:
                        try:
                            f.exception()  # waits; result errors surface
                        except Exception:  # noqa: BLE001 — in collect()
                            pass
                wait_ms = (time.perf_counter() - tw) * 1e3
            else:
                wait_ms = 0.0
            self._pending.popleft()
            block = False  # only the head is owed a wait
            # Future.done() flips before done-callbacks run, so the batch
            # can be observed complete with t_done not yet stamped by
            # mark_done — stamp it here rather than record a 0 ms span.
            t_done = entry.t_done
            if entry.futures is not None and t_done <= entry.t_submit:
                entry.t_done = t_done = time.perf_counter()
            if self.stats is not None:
                self.stats.record_encode(
                    encode_ms=(t_done - entry.t_submit) * 1e3,
                    wait_ms=wait_ms)
                # Full-transform assist: drain the host entropy-coding
                # time the codec accumulated for this batch — on that
                # path it is the entire host codec cost (encode_ms wall
                # span still includes pool queueing / drain overlap).
                take = getattr(self.codec, "take_entropy_ms", None)
                if take is not None:
                    ms = take()
                    if ms > 0.0:
                        self.stats.record_entropy(ms)
            tracer = self.tracer
            if tracer is not None and tracer.enabled and entry.futures:
                off = self.wall_offset_s
                tracer.complete(EGRESS_ENCODE, entry.t_submit + off,
                                max(entry.t_done, entry.t_submit) + off,
                                self.track, rows=len(entry.metas))
            out.append(entry.collect())
        return out

    def flush(self) -> List[list]:
        """Drain everything, blocking until the pool finishes."""
        out = []
        while self._pending:
            out.extend(self.ready(block=True))
        return out
