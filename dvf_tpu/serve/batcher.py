"""Continuous cross-session batcher.

The single-stream pipeline fills each device batch from ONE queue
(`runtime.pipeline._assemble`); when that stream is slow, the batch pads
and TPU utilization collapses. This batcher generalizes the assembler
across tenants: every tick it drains ready frames from *all* sessions and
packs them into one fixed-signature device batch — slots tagged
``(session_id, frame_index)``, short batches padded with a repeat of the
last valid row exactly like the single-stream assembler (static shapes →
one compilation; the ``valid`` count drops padded outputs on the way
back).

Scheduling policy (the genuinely new multi-tenant part):

- **EDF across sessions.** Candidate slots are ordered by SLO deadline
  (submit ts + the session's latency budget) and the earliest deadlines
  win the batch. With equal SLOs this degrades to global FIFO by arrival
  — fair by construction; a tighter-SLO stream gets priority exactly
  proportional to how much less slack it has. Deadlines are monotonic
  within a stream, so EDF always picks a per-session *prefix* and
  per-session ordering is preserved end to end.
- **Shed by SLO headroom when oversubscribed.** Losing slots stay queued
  and age; once a frame's deadline passes before it reaches a device
  slot it is shed (counted per session) rather than processed — device
  time is never spent on a result the client's latency budget has
  already written off. Undersubscribed systems never shed: every frame
  makes the next batch.
- **EDF/cost across buckets.** A multi-signature frontend groups
  sessions into signature buckets, each with its own compiled program;
  one tick serves ONE bucket (one program launch). ``select_bucket``
  scores every bucket with pending work by *deadline headroom ÷
  measured per-bucket tick cost* and serves the lowest score: a bucket
  whose earliest deadline is closest relative to how long its program
  takes to run is the one most at risk of shedding. Costs are
  MEASURED, never guessed (TVM's measured-stage discipline): the
  compile-time ``Engine.step_block_ms`` calibration seeds the estimate
  and an EWMA over observed batch wall times keeps it current — a
  starved small bucket's headroom shrinks every tick while the big
  bucket's stays refreshed, so the small bucket always wins before its
  deadline passes (fairness pinned in tests/test_multitenant.py).
- **A short batch waits for the device, not in it.** The program costs
  the same step whether one row or all of them are real, and the chip
  runs batches in submit order: dispatching a batch earlier never makes
  its frames START earlier, it only freezes who rides together and puts
  a whole padded step in front of every later frame. So while the
  device still has submitted work to run (``may_go_short=False``: the
  caller's newest in-flight batch is not ready), ``select_bucket`` binds
  the picked bucket only if it fills a batch; fewer frames stay in
  ``pending``, where they age and shed as ever and later arrivals join
  them. Once the backlog has run out whatever is there goes at once (an
  idle chip never waits for company), and a full batch always goes, as
  deep as the in-flight window allows. A held bucket costs no device
  time, so no other bucket is promoted past it. "Run out" is read one
  staging ahead (:class:`DeviceBacklog`): the newest batch started when
  the one before it was seen ready, its program's last batch says how
  long it takes, so the held set is bound when the device is due to
  fall idle by the time it is staged, not after.
- **Temporal state follows the session, not the batch.** For a filter
  with per-session state each plan carries a row map (``BatchPlan.rows``):
  the state row of the session each batch row belongs to, -1 on pad
  rows, and a fresh mark on a session's first frame since its row was
  bound. EDF picks per-session prefixes in index order, so within a
  batch a session's rows are in stream order and a row's predecessor is
  the same session's previous row in that batch, else the state its
  session carried in. A frame that was shed, dropped or discarded
  before submit never reached the device: it is skipped, and the next
  frame's predecessor is the last frame of that session that DID reach
  the device (tests/test_session_state.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from dvf_tpu.obs.metrics import BatchStamps
from dvf_tpu.serve.session import Slot, StreamSession


@dataclasses.dataclass
class BatchPlan:
    """One tick's device batch: how many rows are real, the
    (session, frame_index) tag per valid row, and — on the monolithic
    staging path only — the staged host array (the streamed ingest path
    stages straight into per-shard slabs, so ``batch`` is None there and
    the router never needs it)."""

    batch: Optional[np.ndarray]
    valid: int
    slots: List[Slot]
    dead: bool = False  # set by supervisor recovery (or a discard) when
    #   the plan's claims were already released — a late result/second
    #   discard for a dead plan must not double-account the sessions
    bucket: Any = None  # the signature bucket this batch belongs to
    #   (serve.server._Bucket): the collect side attributes tick cost /
    #   faults to it;
    #   None on the legacy single-signature paths (tests, ad-hoc plans)
    cost_sample: bool = True  # False when other batches were in flight
    #   at submit: the submit→materialize wall then includes queue wait
    #   behind THEIR device time, which would contaminate the bucket's
    #   per-program tick-cost EWMA (the EDF/cost denominator) toward the
    #   shared pipeline latency instead of this program's cost
    stamps: BatchStamps = dataclasses.field(default_factory=BatchStamps)
    #   the batch's wall-clock stamps, always carried and each taken
    #   once (obs.metrics.BatchStamps): chosen / permit / submit on the
    #   dispatch thread, taken / ready / fetched / routed on the collect
    #   thread. The bucket's always-on stage counters, the lineage marks
    #   the router fans out, the Tracer's dispatch/collect spans and the
    #   tick-cost sample are all views of these.
    audit_rows: Any = None  # audit-armed frontends (obs.audit): rows
    #   the shadow-replay sampler picked this tick — [(row, input-copy,
    #   session_id, frame_index, lineage), ...]; the collect side pairs
    #   each with its DELIVERED output and hands the pair to the replay
    #   worker. None = audit off or nothing sampled (zero cost).
    rows: Optional[np.ndarray] = None  # session-state filters: the
    #   int32 [2, batch] row map Engine.submit takes (runtime.engine.
    #   device_row_map) — state row per batch row (-1 = pad), fresh
    #   mark per row. None for every other filter.


def _seen_ready(handle) -> bool:
    try:
        return handle.is_ready()
    except Exception:  # noqa: BLE001 — a poisoned batch holds nothing
        return True    # up: the collect side's containment takes it


class DeviceBacklog:
    """What a dispatch thread can tell of the device's queue from the
    batches it put there: ``select_bucket``'s ``may_go_short``.

    The chip runs batches in submit order, so it has work left iff the
    newest batch queued is not ready (``handle.is_ready()``, the lane's
    ``InflightBatch``; one that raises counts as ready). That batch
    started when the one before it was seen ready, or at its own submit
    onto an idle device; with what its program's last batch took of the
    device (``device_ms``: a measurement, or None while there is none)
    that says when the backlog is due to run out, so a batch may be
    bound one staging before that and be staged under the step's tail
    instead of leaving the device idle meanwhile. Every start is an
    observation, never a forecast built on a forecast: an error does
    not outlive its batch, and binding a little early costs nothing but
    the freeze. ``generation`` is whatever the caller's batches live and
    die with (the frontend's permit semaphore: a supervised recovery
    replaces it and writes the window off): batches of another one hold
    nothing up. Single-threaded: the dispatch thread's.
    """

    def __init__(self):
        self._staging_s = 0.0   # the last batch's, permit to submit
        self._generation = None
        self.clear()

    def clear(self) -> None:
        """Nothing of ours is on the device (or it was all written off)."""
        self._newest = self._before = None
        self._free_at = self._device_s = float("inf")

    def queued(self, handle, generation, t_permit: float, t_submit: float,
               device_ms: Optional[float]) -> None:
        """``handle``'s batch was staged from ``t_permit`` and submitted
        at ``t_submit``; its program's last batch took ``device_ms``."""
        self._generation = generation
        self._staging_s = t_submit - t_permit
        self._before, self._newest = self._newest, handle
        self._device_s = device_ms / 1e3 if device_ms else float("inf")
        self._free_at = (t_submit + self._device_s
                         if self._before is None else float("inf"))

    def may_go_short(self, now: float, generation=None) -> bool:
        """One poll a tick. True when the device has nothing left to
        run, or will have nothing by the time a batch bound ``now`` is
        staged (it takes what the last one took)."""
        if generation is not self._generation:
            self.clear()
        if self._newest is None:
            return True
        if _seen_ready(self._newest):
            self.clear()
            return True
        if self._before is not None and _seen_ready(self._before):
            self._before = None     # the newest one runs from now
            self._free_at = now + self._device_s
        return now + self._staging_s >= self._free_at


class ContinuousBatcher:
    """Drains ready frames across sessions into fixed-signature batches."""

    def __init__(self, batch_size: int, staging_pool: int = 2):
        if batch_size <= 0:
            raise ValueError("batch_size must be positive")
        self.batch_size = batch_size
        # Bounded internal staging ring for plan() callers that pass no
        # buffer: a fresh multi-MB np.empty per tick put the allocator on
        # the serving hot path. Cycled like the pipeline's per-slot pool;
        # callers that hold a plan across more than ``staging_pool``
        # ticks must pass their own staging (the frontend does).
        self._staging_pool = max(1, staging_pool)
        self._staging: Optional[List[np.ndarray]] = None
        self._staging_seq = 0

    def select(self, sessions: Sequence[StreamSession],
               now: float, pre_drained: bool = False,
               limit: Optional[int] = None) -> Optional[List[Slot]]:
        """Tier-then-EDF slot selection for one batch; None = nothing
        to do.

        Drains every session's ingress, sheds blown deadlines, picks the
        ``batch_size`` earliest-deadline slots, and claims them in-flight
        — everything plan() does except touching frame bytes, so the
        streamed assembler can stage the chosen frames straight into its
        per-shard slabs. Dispatch-thread only: touches the sessions'
        scheduler-owned ``pending`` staging. ``pre_drained`` skips the
        drain/shed pass (select_bucket already ran it this tick);
        ``limit`` overrides ``batch_size`` for this pick (the control
        plane's per-bucket batch sizing).
        """
        candidates: List[Slot] = []
        for s in sessions:
            if not pre_drained:
                s.drain_ingress()
                s.shed_expired(now)  # counted on the session (stats() sums)
            candidates.extend(s.pending)
        if not candidates:
            return None
        # Priority tier first, then EDF within a tier: with spare slots
        # every queued frame makes the batch regardless of tier, so this
        # only bites when OVERSUBSCRIBED — then lower-priority (higher
        # tier value) frames lose the slot race, age, and shed first;
        # paid/interactive sessions shed last by construction. Stable
        # sort + per-session monotonic deadlines (a hard guarantee —
        # submit clamps each deadline to at least the previous one,
        # whatever client ts says) + per-session constant tier ⇒ the
        # chosen set is a prefix of each session's pending deque, so
        # popleft below removes exactly the chosen slots.
        candidates.sort(
            key=lambda slot: (slot.session.config.tier, slot.deadline))
        chosen = candidates[: (limit if limit is not None
                               else self.batch_size)]
        taken_per_session: dict = {}
        for slot in chosen:
            taken_per_session[slot.session] = (
                taken_per_session.get(slot.session, 0) + 1)
        for s, n in taken_per_session.items():
            for _ in range(n):
                s.pending.popleft()
            s.claim_inflight(n)
        return chosen

    def select_bucket(
        self,
        bucket_sessions: Sequence[Tuple[Any, Sequence[StreamSession]]],
        now: float,
        may_go_short: bool = True,
    ) -> Tuple[Any, Optional[List[Slot]]]:
        """EDF/cost-aware bucket pick for one tick; ``(None, None)`` =
        nothing to do anywhere, ``(bucket, None)`` = the pick's frames
        are held: they do not fill a batch and ``may_go_short`` is False
        (the device has a backlog to run first; module docstring).

        ``bucket_sessions``: ``[(bucket, sessions)]`` where ``bucket``
        exposes ``tick_cost_estimate() -> ms`` (a MEASURED per-batch
        cost — Engine.step_block_ms seed + live EWMA). Every bucket's
        ingress is drained and its blown deadlines shed each tick (a
        losing bucket must still age and shed); then buckets with
        pending work are picked by ``(best pending tier, (earliest
        deadline − now) ÷ tick cost)``: priority tier first — a bucket
        holding a tier-0 frame beats any bucket whose best is tier 1+,
        else the within-bucket tier-EDF guarantee silently dissolves
        the moment sessions span buckets (exactly what the quality
        controller's downshift buckets create: under a re-admission
        flood, cost-weighted EDF alone serves interactive only once its
        frames have burned down to the flood's headroom-per-cost) —
        then lowest score wins within a tier: least headroom per unit
        of program time is the bucket most at risk. The winner's slots
        are then claimed by the ordinary within-bucket EDF
        :meth:`select`.
        """
        best = None
        best_key = None
        best_sessions: Optional[Sequence[StreamSession]] = None
        for bucket, sessions in bucket_sessions:
            earliest = None
            tier = None
            for s in sessions:
                s.drain_ingress()
                s.shed_expired(now)
                if s.pending:
                    d = s.pending[0].deadline
                    earliest = d if earliest is None else min(earliest, d)
                    t = s.config.tier
                    tier = t if tier is None else min(tier, t)
            if earliest is None:
                continue
            cost_ms = max(float(bucket.tick_cost_estimate()), 1e-3)
            key = (tier, (earliest - now) * 1e3 / cost_ms)
            if best_key is None or key < best_key:
                best, best_key, best_sessions = bucket, key, sessions
        if best is None:
            return None, None
        # Per-bucket batch size (control plane autotune): a small bucket
        # runs small batches instead of inheriting the frontend-wide
        # batch_size and padding the difference with repeated rows.
        limit = getattr(best, "batch_size", None)
        if not may_go_short:
            want = limit if limit is not None else self.batch_size
            if sum(len(s.pending) for s in best_sessions) < want:
                return best, None
        return best, self.select(best_sessions, now, pre_drained=True,
                                 limit=limit)

    @staticmethod
    def row_map(slots: Sequence[Slot], batch_size: int
                ) -> Optional[np.ndarray]:
        """The plan's session-state row map (``BatchPlan.rows``), or
        None when the chosen sessions hold no state row. Reads each
        session's fresh mark without clearing it: the caller clears the
        marks (:meth:`mark_reached_device`) only once the batch was
        submitted, so a plan discarded before submit leaves them set."""
        if not slots or slots[0].session.state_row is None:
            return None
        rows = np.zeros((2, batch_size), np.int32)
        rows[0] = -1
        marked = set()
        for i, slot in enumerate(slots):
            s = slot.session
            rows[0, i] = s.state_row
            if s.state_fresh and s not in marked:
                rows[1, i] = 1
                marked.add(s)
        return rows

    @staticmethod
    def mark_reached_device(slots: Sequence[Slot], depth: int = 1) -> int:
        """The batch was submitted: its sessions' rows now hold state.
        Returns how many of the rows were warm-up: served with fewer
        than ``depth`` predecessors since their session's row restarted
        (``StreamSession.state_depth``, capped at ``depth``)."""
        warm = 0
        for slot in slots:
            s = slot.session
            if s.state_fresh:
                s.state_fresh, s.state_depth = False, 0
            warm += s.state_depth < depth
            s.state_depth = min(s.state_depth + 1, depth)
        return warm

    def _pool_staging(self, frame: np.ndarray) -> np.ndarray:
        shape = (self.batch_size, *frame.shape)
        if self._staging is None or self._staging[0].shape != shape \
                or self._staging[0].dtype != frame.dtype:
            self._staging = [np.empty(shape, dtype=frame.dtype)
                             for _ in range(self._staging_pool)]
        self._staging_seq += 1
        return self._staging[self._staging_seq % len(self._staging)]

    def plan(
        self,
        sessions: Sequence[StreamSession],
        now: float,
        staging: Optional[np.ndarray] = None,
    ) -> Optional[BatchPlan]:
        """Assemble one monolithic batch from everything ready; None =
        nothing to do.

        ``staging``: preallocated (batch_size, H, W, C) buffer to fill
        (the frontend's per-inflight-slot pool); the batcher's own
        bounded ring is used when omitted (tests, ad-hoc callers).
        """
        chosen = self.select(sessions, now)
        if chosen is None:
            return None
        valid = len(chosen)
        if staging is None:
            staging = self._pool_staging(chosen[0].frame)
        for row, slot in enumerate(chosen):
            np.copyto(staging[row], slot.frame)
            slot.frame = None  # drop the client's buffer reference
        for row in range(valid, self.batch_size):
            np.copyto(staging[row], staging[valid - 1])
        return BatchPlan(batch=staging, valid=valid, slots=chosen,
                         rows=self.row_map(chosen, self.batch_size))
