"""Fleet-level stats: merge per-replica exports into one view.

Every replica already computes its own half — ``ServeFrontend.stats()``
(per-session rows + per-replica aggregate), ``latency_snapshot()``
(mergeable weighted samples), ``faults.summary()`` (per-kind counters,
replica-attributed via ``ServeConfig.replica_label``). This module does
the other half: the front door pulls those exports (in-process reads or
one ``stats`` RPC per process replica) and folds them into fleet-wide
latency percentiles (``LatencyStats.merge_snapshots`` — weighted raw
samples, never averaged percentiles) and a fleet fault table with
``by_replica`` attribution.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional

from dvf_tpu.obs.metrics import LatencyStats
from dvf_tpu.resilience.faults import FaultStats


DOOR_KEYS = ("submit_calls_total", "submit_us_total", "poll_calls_total",
             "poll_us_total", "deliveries_total")
_DOOR_ZERO = (0, 0.0, 0, 0.0, 0)


class DoorStats:
    """The front door's own clock (always on, as the stage clock is):
    what ``FleetFrontend.submit`` and ``poll`` cost their caller, entry
    to return on ``time.perf_counter``, cumulative and booked under the
    replica the call's session is bound to when it returns.

    ``deliveries_total`` counts the deliveries ``poll`` handed out, so
    ``(submit_us_total + poll_us_total) / deliveries_total`` over a window
    is the front door's cost of one served frame, empty polls included.
    Writers are the clients' threads (one lock, two adds a call); a
    replica that left the fleet keeps its row, so the total is monotone.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._rows: Dict[str, list] = {}    # rid -> [DOOR_KEYS order]

    def _row_locked(self, rid: str) -> list:
        row = self._rows.get(rid)
        if row is None:
            row = self._rows[rid] = list(_DOOR_ZERO)
        return row

    def note_submit(self, rid: str, seconds: float) -> None:
        with self._lock:
            row = self._row_locked(rid)
            row[0] += 1
            row[1] += seconds * 1e6

    def note_poll(self, rid: str, seconds: float, deliveries: int) -> None:
        with self._lock:
            row = self._row_locked(rid)
            row[2] += 1
            row[3] += seconds * 1e6
            row[4] += deliveries

    @staticmethod
    def _block(vals) -> dict:
        return {k: (round(v, 1) if isinstance(v, float) else v)
                for k, v in zip(DOOR_KEYS, vals)}

    def row(self, rid: str) -> dict:
        """One replica's block: what rides its bucket rows (``replica``
        says whose it is, so a reader that meets it on several rows
        counts it once)."""
        with self._lock:
            vals = list(self._rows.get(rid) or _DOOR_ZERO)
        return {"replica": rid, **self._block(vals)}

    def summary(self) -> dict:
        """``stats()["door"]``: the fleet's total beside every replica's
        block."""
        with self._lock:
            rows = {rid: list(v) for rid, v in sorted(self._rows.items())}
        total = [sum(col) for col in zip(*rows.values())] or _DOOR_ZERO
        return {**self._block(total),
                "by_replica": {rid: {"replica": rid, **self._block(v)}
                               for rid, v in rows.items()}}


def merge_fault_summaries(
    fleet_own: dict,
    per_replica: Dict[str, Optional[dict]],
) -> dict:
    """The fleet fault table: the router's own faults (``replica``
    losses it observed, attributed to the replica that died) plus every
    reachable replica's summary. Unreachable replicas contribute nothing
    — their loss is already counted on the fleet side."""
    merged = FaultStats()
    merged.absorb_summary(fleet_own)
    for rid, summary in per_replica.items():
        if summary:
            merged.absorb_summary(summary, replica=rid)
    return merged.summary()


def merge_latency_snapshots(per_replica: Dict[str, Optional[dict]]) -> dict:
    """Fleet p50/p99/fps over replicas' weighted sample snapshots."""
    return LatencyStats.merge_snapshots(
        [s for s in per_replica.values() if s])


def replica_row(handle, export: Optional[dict], sessions: int) -> dict:
    """One replica's row in the fleet stats table: lifecycle + the
    headline numbers from its export (None when unreachable)."""
    row = {
        "state": handle.state,
        "restarts": handle.restarts,
        "sessions": sessions,
    }
    if export is not None:
        st = export.get("stats", {})
        row.update(
            engine_batches=st.get("engine_batches"),
            engine_frames=st.get("engine_frames"),
            open_sessions=st.get("open_sessions"),
            queue_depth=st.get("queue_depth"),
            # The replica's MONOTONE lifetime counter (signals() carries
            # the evicted-session floor) — the scrape's counter source;
            # the windowed aggregate.count beside it is NOT monotone.
            delivered_total=(export.get("signals") or {}).get(
                "delivered_total"),
            errors=st.get("errors"),
            recoveries=st.get("recoveries"),
            faults=st.get("faults", {}).get("by_kind", {}),
            aggregate=st.get("aggregate"),
            # The replica's bucket rows as its own stats() has them
            # (stages, ingest, egress, starved, hold, ...): what a reader
            # of the fleet needs of a replica without reaching past
            # stats() into the handles.
            buckets=st.get("buckets"),
        )
        attr = st.get("attribution")
        if attr is not None:
            # Lineage-armed replicas: the per-replica latency
            # attribution rides the same stats RPC — the fleet-wide
            # half of "where did my p99 go" (explain() fans this out).
            row["attribution"] = attr
    return row
