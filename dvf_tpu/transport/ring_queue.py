"""RingFrameQueue — the native C++ ring as the pipeline's ingest queue.

The reference's transport *is* its hot path: every frame crosses libzmq
between the capture thread and the workers (distributor.py:27-35,
worker.py:17-25). The TPU framework's equivalent hot path is
source → ingest queue → batch assembler, and this adapter puts the native
SPSC ring (ring.cpp) on it, drop-in compatible with the Python
``DropOldestQueue`` surface the :class:`~dvf_tpu.runtime.pipeline.Pipeline`
uses (``put`` / ``pop_up_to`` / ``__len__`` / ``dropped`` / ``put_total``).

Three wire formats — the reference's ``use_jpeg`` switch
(webcam_app.py:109-113) plus the temporal-delta wire:

- **raw** — ``frame.tobytes()``; zero codec cost, ring capacity sized in
  whole frames.
- **jpeg** — encoded on ``put`` (the capture side, like webcam_app.py:110)
  through the full-frame codec, decoded on the assembler side by
  ``decode_batch(out=staging)`` straight into the dispatch staging buffer
  that feeds ``device_put`` — no intermediate stack/copy.
- **delta** — :class:`~dvf_tpu.transport.codec.DeltaCodec` over the JPEG
  codec: ``put`` encodes only the tiles that changed since the last
  shipped state (keyframe every N / scene cut), the assembler side
  composites onto its cached previous frame. For low-motion streams this
  removes almost the entire host codec cycle from the hot path — the
  same-codec head-to-head attack (ROADMAP open item 3).

Delta resync under drop-oldest: evicting ring records loses delta frames
the decoder never saw. The PRODUCER observes every eviction (``push``
returns the count) and forces the next encode to be a keyframe; the
consumer side runs the decoder in tolerant (``on_gap="composite"``) mode
— absolute tiles composite onto the stale reference with bounded
staleness (counted in ``resyncs``) until that keyframe lands, preserving
drop-oldest's freshness-over-completeness contract instead of killing
the stream.

When to use which (measured, 1080p invert e2e on CPU, inline collect):
in-process Python queue 139 fps (frames pass as zero-copy views);
ring/raw 75 fps (one serialize + one deserialize memcpy per frame buys
cross-process shm capability and byte-bounded freshness); ring/jpeg
16 fps (the ~60 ms/frame 1080p encode in the capture thread dominates —
the codec-throughput wall SURVEY §7 hard part 3 predicts; JPEG pays off
when the wire is a network, not shm, or at the reference's 512² geometry
where encode is ~5-10 ms); ring/delta scales those codec costs by the
stream's dirty ratio.

Differences from the Python queue, by design:

- The bound is **bytes**, not frames (``capacity_frames`` is converted
  using the raw frame size at construction). Drop-oldest semantics are
  identical: a full ring evicts oldest records until the new one fits
  (distributor.py:193-203 behavior, enforced in native code).
- ``pop_up_to`` returns ``(index, payload_bytes, timestamp)`` tuples;
  the pipeline detects the adapter via :meth:`decode_into` and routes
  payload decoding into its staging buffer instead of row-copying arrays.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from dvf_tpu.transport.codec import WIRE_MODES, make_wire_codec
from dvf_tpu.transport.ring import FrameRing

# Native per-record overhead: RecordHeader (24 B) rounded up to 8-byte
# alignment, matching ring.cpp's align_up(sizeof(RecordHeader) + len).
_RECORD_OVERHEAD = 32


class RingFrameQueue:
    """Drop-oldest ingest queue backed by the native shared-memory ring."""

    def __init__(
        self,
        frame_shape: Tuple[int, int, int],
        capacity_frames: int = 10,
        jpeg: bool = False,
        jpeg_quality: int = 90,
        codec_threads: int = 4,
        shm_name: Optional[str] = None,
        create: bool = True,
        wire: Optional[str] = None,
        delta_tile: int = 32,
        delta_keyframe_interval: int = 48,
        delta_threshold: int = 0,
        codec_assist: str = "none",
        audit_wire: bool = False,
        chaos=None,
    ):
        if wire is None:
            wire = "jpeg" if jpeg else "raw"
        if wire not in WIRE_MODES:
            raise ValueError(f"wire must be one of {WIRE_MODES}, got {wire!r}")
        self.frame_shape = tuple(frame_shape)
        self.frame_dtype = np.dtype(np.uint8)
        self._frame_bytes = int(np.prod(self.frame_shape))
        self.wire = wire
        self.jpeg = wire != "raw"  # legacy flag: "payloads need a codec"
        # Exposed so serve's wire-budget check budgets against the pool
        # the pipeline actually runs, not the host's total core count.
        self.codec_pool_threads = codec_threads
        self.codec = None
        self._dec_codec = None
        # ``codec_assist`` here is PROVENANCE, not behavior: the serve
        # tier's ring is an ingest-side host wire (source → pipeline), so
        # the device transform cannot feed it — the stamp makes bench
        # rows attributable to the assist tier the run requested (the
        # worker tier is where "full" changes the dataflow).
        if wire == "jpeg":
            self.codec = make_wire_codec("jpeg", quality=jpeg_quality,
                                         threads=codec_threads,
                                         assist=codec_assist)
            self._dec_codec = self.codec  # stateless: one instance, both ends
        elif wire == "delta":
            # Distinct encoder/decoder instances — DeltaCodec keeps
            # independent state per direction anyway, but producer and
            # consumer run on different threads and the ring is the
            # process boundary this queue may one day straddle (shm).
            def _delta():
                return make_wire_codec(
                    "delta", quality=jpeg_quality, threads=codec_threads,
                    assist=codec_assist,
                    tile=delta_tile,
                    keyframe_interval=delta_keyframe_interval,
                    delta_threshold=delta_threshold,
                    on_gap="composite")

            self.codec = _delta()
            self._dec_codec = _delta()
        # Sized for capacity_frames RAW frames (a JPEG ring then holds more
        # — the bound is freshness in bytes, the stronger guarantee). The
        # per-record cap leaves 2× slack: JPEG is *larger* than raw for
        # noise-like content (worst case ~1.5×), and an oversized record
        # must fail loudly at push, never at pop. The delta header +
        # bitmap add at most a few KB on top of a raw-sized payload.
        # Wire-integrity audit (obs.audit): every payload is wrapped in
        # a digest-stamped envelope at put and verified+stripped at
        # decode_into — a flipped bit between the two (the native ring,
        # shm, a future network hop) raises WireIntegrityError into the
        # pipeline's containment as an ``integrity`` fault instead of
        # delivering wrong pixels. ``chaos`` arms the post-encode
        # ``corrupt_wire`` flip on the stamp side. ~11 ns/KB of blake2b
        # per direction; off by default.
        self._wire_audit = None
        if audit_wire:
            from dvf_tpu.obs.audit import WireAudit

            self._wire_audit = WireAudit("ring", chaos=chaos)
        # First eviction re-keys immediately; the cooldown only
        # rate-limits re-keying under SUSTAINED overload.
        self._force_cooldown = max(4, delta_keyframe_interval // 2)
        self._puts_since_forced = self._force_cooldown
        cap = max(1, capacity_frames) * (self._frame_bytes + _RECORD_OVERHEAD)
        self.ring = FrameRing(
            capacity_bytes=cap,
            shm_name=shm_name,
            create=create,
            max_frame_bytes=2 * self._frame_bytes + _RECORD_OVERHEAD + 8192,
        )

    # -- producer side (pipeline._ingest) -------------------------------

    def put(self, item: Tuple[int, np.ndarray, float]) -> Optional[int]:
        """Enqueue; returns the eviction count if frames were displaced
        (the pipeline's pacing only checks ``is not None``), else None."""
        idx, frame, ts = item
        if isinstance(frame, np.ndarray) and frame.shape != self.frame_shape:
            raise ValueError(
                f"ring transport carries fixed {self.frame_shape} frames; "
                f"source yielded {frame.shape} (pass the source's real "
                f"geometry when constructing RingFrameQueue)"
            )
        if self.wire == "raw":
            payload = frame.tobytes() if isinstance(frame, np.ndarray) else frame
        else:
            payload = self.codec.encode(frame)
        if self._wire_audit is not None:
            payload = self._wire_audit.stamp(payload)
        evicted = self.ring.push(payload, idx, ts)
        self._puts_since_forced += 1
        if (evicted > 0 and self.wire == "delta"
                and self._puts_since_forced >= self._force_cooldown):
            # Evicted records are delta frames the consumer will never
            # composite — its reference is now stale. The producer is the
            # only side that SEES the eviction, so the keyframe request
            # lives here: the next put re-keys the stream. COOLDOWN: an
            # unthrottled source under drop-oldest evicts on nearly every
            # put, and re-keying every time turns sustained overload into
            # a keyframe storm (keyframes are the big payloads, which
            # fills the ring faster — a vicious cycle). One forced key
            # per half keyframe-interval bounds clean-tile staleness at
            # interval/2 frames (the dirty tiles are absolute and always
            # current), which is the drop-oldest freshness contract.
            self.codec.force_keyframe()
            self._puts_since_forced = 0
        return evicted if evicted > 0 else None

    # -- consumer side (pipeline._assemble/_dispatch) --------------------

    def pop_up_to(self, n: int) -> List[Tuple[int, bytes, float]]:
        return [(idx, payload, ts)
                for payload, idx, ts in self.ring.pop_up_to(n)]

    def decode_into(self, items: List[Tuple[int, bytes, float]],
                    staging: np.ndarray) -> None:
        """Decode popped payloads into rows [0, len(items)) of the dispatch
        staging buffer (the §2b 'decode into staging feeding device_put'
        path — JPEG batches go through the threaded codec; delta batches
        composite sequentially, their per-frame cost scaled by the dirty
        ratio)."""
        k = len(items)
        if self._wire_audit is not None:
            # Verify + strip every envelope BEFORE any pixel decode: a
            # digest mismatch raises here (integrity fault) instead of
            # compositing corrupt bytes into the staging batch.
            items = [(idx, self._wire_audit.verify(payload), ts)
                     for idx, payload, ts in items]
        if self.wire == "raw":
            for row, (_, payload, _) in enumerate(items):
                staging[row] = np.frombuffer(
                    payload, np.uint8).reshape(self.frame_shape)
        else:
            self._dec_codec.decode_batch([p for _, p, _ in items],
                                         out=staging[:k])

    # -- stats / lifecycle ----------------------------------------------

    @property
    def dropped(self) -> int:
        if self._closed_counts is not None:
            return self._closed_counts[0]
        return self.ring.dropped

    @property
    def put_total(self) -> int:
        if self._closed_counts is not None:
            return self._closed_counts[1]
        return self.ring.pushed

    def wire_stats(self) -> dict:
        """Wire provenance + delta accounting for bench JSON (dirty
        ratio, keyframes, resyncs — ``DeltaCodec.stats``)."""
        out = {"wire": self.wire}
        if self.wire == "delta":
            out["encode"] = self.codec.stats()
            out["decode"] = self._dec_codec.stats()
            out["codec"] = self.codec.config()
        elif self.codec is not None:
            out["codec"] = self.codec.config()
        if self._wire_audit is not None:
            out["audit"] = self._wire_audit.stats()
        return out

    def __len__(self) -> int:
        return 0 if self._closed_counts is not None else len(self.ring)

    _closed_counts: Optional[Tuple[int, int]] = None

    def close(self) -> None:
        if self._closed_counts is not None:
            return
        # Snapshot the native counters first: stats() is routinely read
        # after the pipeline shuts the transport down, and poking a
        # destroyed ring is a use-after-free.
        self._closed_counts = (self.ring.dropped, self.ring.pushed)
        if self.codec is not None:
            self.codec.close()
        if self._dec_codec is not None and self._dec_codec is not self.codec:
            self._dec_codec.close()
        self.ring.close()
