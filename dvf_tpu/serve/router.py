"""Result router: demultiplex shared-engine batches back to sessions.

The collect side of the serving frontend. A completed device batch
carries frames from several tenants interleaved in slot order; the
router walks the plan's ``(session, frame_index)`` tags, feeds each valid
row to its session's reorder buffer, advances that session's display
cursor, and emits whatever became ready to the session's out queue or
sink. The padded tail rows (``row >= valid``) are dropped exactly like
the single-stream collect path.

Observability stays session-local here: every delivered frame is
recorded in its session's ``LatencyStats``; the frontend-wide p50/p99
export merges those per-stream samples on demand
(``LatencyStats.merged``), so nothing is recorded twice.
"""

from __future__ import annotations

import threading

from dvf_tpu.runtime.egress import LandedRows
from dvf_tpu.serve.batcher import BatchPlan


class ResultRouter:
    """Collect-thread component: batches in, per-session deliveries out."""

    def __init__(self):
        self.batches = 0
        self.frames = 0
        self.rows_handed_total = 0  # rows delivered as the buffer they
        #   landed in (egress.LandedRows)
        self.rows_copied_total = 0  # rows copied out of a batch array
        self.late_after_close = 0  # results for hard-closed sessions
        self.late_after_recovery = 0  # results for plans the supervisor
        #   already wrote off (their sessions' claims were released at
        #   recovery; routing them now would double-account)
        self._dead_lock = threading.Lock()  # makes the plan.dead
        #   check-then-set atomic: recovery (supervisor thread) and a
        #   waking superseded collect thread may discard the same plan
        #   concurrently, and a double discard_inflight would drive
        #   session.inflight negative

    def route(self, plan: BatchPlan, out) -> int:
        """Demux one completed batch; returns frames delivered.

        The invariant: a delivery that sits unpolled (out queue, replay
        ring, a slow client) keeps ONE frame's bytes alive, never its
        batch — else a slow-polling client could pin out_queue_size
        full batches (batch_size× amplification) instead of
        out_queue_size frames. How each shape of ``out`` keeps it:

        - ``egress.LandedRows`` (the packed layout: one device, uint8
          NHWC): every row is a host buffer of its own, handed to the
          session as it is, read-only; nothing here touches its bytes;
        - an ``ndarray`` (a pooled slab of a result sharded over several
          devices, the monolithic fetch of the CPU backend or a degraded
          lane, the per-batch fallback): rows are views of the batch,
          so each is copied out.
        """
        with self._dead_lock:
            if plan.dead:
                self.late_after_recovery += 1
                return 0
            plan.dead = True  # consumed — a recovery discard racing this
            #   route (the plan was still in the supervisor window) must
            #   become a no-op, not a second release of the same claims
        touched = []
        delivered = 0
        st = plan.stamps
        marks = None
        own_rows = isinstance(out, LandedRows)
        if own_rows:
            self.rows_handed_total += plan.valid
        else:
            self.rows_copied_total += plan.valid
        for row, slot in enumerate(plan.slots[: plan.valid]):
            s = slot.session
            # The batch's stamps ride each slot to its delivery — the one
            # place every routed row already passes: deliver_ready folds
            # the always-on stage counters from them.
            slot.stamps = st
            if slot.lin is not None and st.t_fetched:
                # Lineage view (armed frontends): the frame's marks are
                # the slot's own t_pending, then the batch's stamps; no
                # clock is read for them. deliver_ready closes the trail.
                if marks is None:
                    marks = st.marks()
                slot.lin.marks = [("queue_ingress", slot.t_pending),
                                  ("queue_bucket", st.t_chosen), *marks]
            s.complete(slot, out[row] if own_rows else out[row].copy())
            if s.state == "closed":
                self.late_after_close += 1
                continue
            if s not in touched:
                touched.append(s)
            if len(s.reorder) >= s.config.reorder_capacity:
                # One batch can hold more of a stream's rows than its
                # reorder buffer holds frames (batch 64, one tenant,
                # capacity 50): drain now, or the capacity cap evicts
                # frames nobody has been offered yet — lost to every
                # counter (PR 21's four-chip smoke: 100 of 128 delivered).
                delivered += s.deliver_ready()
        for s in touched:
            delivered += s.deliver_ready()
        self.batches += 1
        self.frames += plan.valid
        if plan.bucket is not None:
            # Lifetime per-bucket row counter, maintained HERE (the one
            # place every routed row passes) so the bucket's export
            # stays monotone across session retirement — a per-session
            # sum would shrink when a tenant retires, which a counter
            # consumer reads as a reset.
            plan.bucket.routed_frames += plan.valid
        return delivered

    def discard(self, plan: BatchPlan, kind: str = None) -> None:
        """A device batch failed; release its sessions' in-flight claims
        so a closing session can still finalize. ``kind`` (a FaultKind)
        attributes the loss in each session's per-kind fault counters;
        None for non-fault discards (shutdown). Idempotent: a plan
        already written off (supervisor recovery) is skipped."""
        with self._dead_lock:
            if plan.dead:
                return
            plan.dead = True
        per_session = {}
        for slot in plan.slots[: plan.valid]:
            per_session[slot.session] = per_session.get(slot.session, 0) + 1
        for s, n in per_session.items():
            s.discard_inflight(n, kind=kind)

    def stats(self) -> dict:
        return {
            "batches": self.batches,
            "frames": self.frames,
            "rows_handed_total": self.rows_handed_total,
            "rows_copied_total": self.rows_copied_total,
            "late_after_close": self.late_after_close,
            "late_after_recovery": self.late_after_recovery,
        }
