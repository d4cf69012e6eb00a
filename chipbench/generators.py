"""The two generator kinds a mix selects: ``closed`` and ``open``.

One thread submits and polls for every session (load from one process with
few threads). It does nothing else: no counter, memory or trace call runs
on it, least of all at the window's edges (run.py reads those after the
tail, or from a helper thread in a traced run). The phases, on the host
clock:

  closed  fill -> ramp (one turnover; takes in the compile or cache load;
          then a seeded fraction of one batch period, so that the window's
          edges are not tied to a batch's delivery)
          -> window -> drain (sending stops; outstanding frames come back)
  open    warm (frames through every program, before any schedule)
          -> ramp (the schedule at the cell's rate, nothing attempted)
          -> window -> tail (the schedule RUNS ON until every attempted
          frame is resolved, so none meets an idling batcher)
          -> rest (sending stops; what the tail sent comes back)

Every delivery is checked for order as it is polled; a seeded sample of the
window's deliveries is kept for the comparison with the reference, which
runs after everything here.
"""

import contextlib
import time

import numpy as np

from chipbench.frames import pool_index


class Record:
    """What a run observed, before any reduction to metrics
    (run.account reduces it)."""

    def __init__(self):
        self.t0 = self.t1 = None          # the window [t0, t1), time.time()
        self.deliveries = []              # poll time of every delivery, whatever it answers
        self.transit = []                 # (due or sent, polled) of attempted frames delivered
        self.attempted = 0                # open: due in the window; closed: set at the end
        self.unresolved = 0               # attempted, not back when the tail or drain ended
        self.left_inside = 0              # any frame not back when the generator stopped
        self.late_ms = []                 # generator lateness: actual submit - due
        self.order_violations = 0         # a delivery backwards or twice within a session
        self.samples = []                 # (session_k, index, frame)
        self.submitted = {}               # sid -> count
        self.polled = {}                  # sid -> count
        self.health_error = None          # set by the caller after the run
        self.poll_lumps = 0               # polls that returned something
        self.tail_s = 0.0                 # how long the tail or drain took
        self.stalls = []                  # the loop's 3 longest turns: (ms, s from t0)
        self._last_turn = None

    def turn(self, now):
        """Once per loop turn: keeps the three longest gaps between turns
        (a turn's own work included), so a disturbed run can be seen."""
        if self._last_turn is not None and self.t0 is not None:
            self.stalls = sorted(self.stalls + [((now - self._last_turn) * 1e3,
                                                 self._last_turn - self.t0)])[-3:]
        self._last_turn = now


class _Span:
    """A host span of the generator, logged on the host clock (traced runs
    only): what the host was doing, for labelling the device's idle gaps.
    The profiler's own host tracer is off: on this runtime it records some
    thirteen million layout-conversion events in five seconds and slows
    the run it traces by a third."""

    def __init__(self, log, name):
        self.log, self.name = log, name

    def __enter__(self):
        self.t = time.time()

    def __exit__(self, *exc):
        self.log.append((self.name, self.t, time.time()))


class _Base:
    def __init__(self, cell, front, pool, seed, seconds, spans=False):
        self.cell, self.front, self.pool = cell, front, pool
        self.seconds = float(seconds)
        self.rng = np.random.default_rng(int(seed) + 1)
        self.rec = Record()
        self.span_log = [] if spans else None
        self.sids = []
        self.next_expected = []
        self.t_opened = None              # sessions open (open loop: and warm)
        self._sample_cap = int(cell.mix["sample_frames"])

    def _span(self, name):
        if self.span_log is None:
            return contextlib.nullcontext()
        return _Span(self.span_log, name)

    def open_sessions(self, n):
        for _ in range(n):
            self.sids.append(self.front.open_stream(self.cell.slo_ms))
            self.next_expected.append(0)
        self.t_opened = time.time()
        self.rec.submitted = {s: 0 for s in self.sids}
        self.rec.polled = {s: 0 for s in self.sids}
        # seeded choice of which (session, index) pairs are kept for the
        # reference: about sample_frames of the expected deliveries
        self._salt = int(self.rng.integers(1, 2 ** 31 - 1))

    def _keep(self, k, index, expected_total):
        if len(self.rec.samples) >= self._sample_cap:
            return False
        every = max(1, int(expected_total // self._sample_cap))
        return ((k * 2654435761 + index * 40503 + self._salt) % every) == 0

    def _submit(self, k, index, ts):
        sid = self.sids[k]
        frame = self.pool[pool_index(k, index, len(self.pool))]
        got = self.front.submit(sid, frame, ts)
        if got != index:
            raise RuntimeError(f"session {sid}: submit returned index {got}, "
                               f"the generator counted {index}")
        self.rec.submitted[sid] += 1

    def _on_delivery(self, k, d, t, sample, expected_total):
        sid = self.sids[k]
        self.rec.polled[sid] += 1
        self.rec.deliveries.append(t)
        if d.index < self.next_expected[k]:
            self.rec.order_violations += 1      # delivered twice or backwards
        self.next_expected[k] = max(self.next_expected[k], d.index + 1)
        if sample and self._keep(k, d.index, expected_total):
            self.rec.samples.append((k, d.index, d.frame))


class ClosedLoop(_Base):
    """Each session keeps ``window`` frames outstanding and sends one more
    for each one it reads back."""

    def run(self):
        cell, mix, rec = self.cell, self.cell.mix, self.rec
        win = int(mix["window"])
        batches = cell.config["serve"]["max_inflight"] + int(mix["batches_beyond_inflight"])
        n_sessions = -(-batches * cell.batch_size * self.front.replicas() // win)
        self.open_sessions(n_sessions)
        sent = [0] * n_sessions
        sent_at = [dict() for _ in range(n_sessions)]
        for i in range(win):                       # fill every session's window
            for k in range(n_sessions):
                ts = time.time()
                self._submit(k, sent[k], ts)
                sent_at[k][sent[k]] = ts
                sent[k] += 1
        ramp_frames = int(mix["ramp_turnovers"] * n_sessions * win)
        total_polled = 0
        phase = "ramp"
        t_open = t1 = drain_deadline = expected_total = None
        while True:
            now = time.time()
            if phase == "ramp" and t_open is None and total_polled >= ramp_frames:
                # Deliveries come a batch at a time, and this turn has just
                # polled one. A window opened here would count the whole
                # batches of floor(seconds / batch period), always under the
                # rate by up to one batch (1.1% in invert_1080p). It opens a
                # seeded fraction of one batch period later instead, so that
                # the count's mean over seeds is the rate.
                ramp_rate = total_polled / max(now - self.t_opened, 1e-6)
                expected_total = max(1.0, ramp_rate * self.seconds)
                t_open = now + float(self.rng.random()) * cell.batch_size / ramp_rate
            if phase == "ramp" and t_open is not None and now >= t_open:
                phase, t1 = "window", now + self.seconds
                rec.t1, rec.t0 = t1, now           # t0 last: a helper thread waits on it
            elif phase == "window" and now >= t1:
                phase = "drain"
                drain_deadline = now + float(mix["drain_s"])
            rec.turn(now)
            moved = 0
            with self._span("chipbench.poll"):
                for k in range(n_sessions):
                    got = self.front.poll(self.sids[k])
                    if not got:
                        continue
                    t = time.time()
                    in_window = phase == "window" and t < t1
                    for d in got:
                        self._on_delivery(k, d, t, in_window, expected_total)
                        ts = sent_at[k].pop(d.index, None)
                        if in_window and ts is not None:
                            rec.transit.append((ts, t))
                        if phase != "drain":
                            with self._span("chipbench.submit"):
                                ts2 = time.time()
                                self._submit(k, sent[k], ts2)
                                sent_at[k][sent[k]] = ts2
                                sent[k] += 1
                    moved += len(got)
                    rec.poll_lumps += 1
            total_polled += moved
            if phase == "drain":
                rec.unresolved = sum(len(s) for s in sent_at)
                if rec.unresolved == 0 or now > drain_deadline:
                    rec.tail_s = now - t1
                    break
            if not moved:
                with self._span("chipbench.idle_wait"):
                    time.sleep(0.001)
        rec.left_inside = rec.unresolved
        # attempted: the answers that came in the window, and the frames
        # that were outstanding at its close and never came
        rec.attempted = sum(1 for t in rec.deliveries if rec.t0 <= t < rec.t1) + rec.unresolved
        return rec


class OpenLoop(_Base):
    """Sessions send on a schedule whatever the service does. Every seed
    has the same set of intervals and phases, in another order."""

    WARM_TIMEOUT_S = 900.0

    def _program_resolved_all(self):
        """Whether the program's own per-session counters say that every
        frame sent is delivered, shed, dropped or failed, and every
        delivery has been polled (so nothing is queued or in flight)."""
        rows = self.front.counters()["sessions"].values()
        done = sum(int(r.get(c) or 0) for r in rows for c in
                   ("delivered", "shed", "dropped_at_ingress", "failed"))
        delivered = sum(int(r.get("delivered") or 0) - int(r.get("dropped_unpolled") or 0)
                        for r in rows)
        return (done == sum(self.rec.submitted.values())
                and delivered == sum(self.rec.polled.values()))

    def _settle(self, give_up, what, forget=None):
        """Polls until the program has resolved every frame sent; returns
        how many deliveries came back meanwhile. ``forget(k, index)``
        takes a delivered frame off the caller's books."""
        back, last_look = 0, 0.0
        while True:
            now = time.time()
            if now > give_up:
                raise RuntimeError(f"{what}: frames still inside the service at the time limit")
            for k in range(len(self.sids)):
                for d in self.front.poll(self.sids[k]):
                    self._on_delivery(k, d, now, False, 1)
                    if forget is not None:
                        forget(k, d.index)
                    back += 1
            if now - last_look >= 0.1:
                last_look = now
                if self._program_resolved_all():
                    return back
            time.sleep(0.002)

    def _warm(self, n_sessions):
        """One frame a session (a whole batch) through the service, round
        after round until a round comes back whole, before any schedule
        starts: the first batch compiles the step program or loads it from
        the cache, and with it runs every program the window will run
        (the step, and the helpers that join and split a batch's chunks).
        A paced schedule would not wait for that as the closed loop's ramp
        does. Frames that wait out their SLO behind a compile are shed by
        the service: they are warm-up frames and are attempted by nobody.
        Returns how many frames each session has sent."""
        give_up = time.time() + self.WARM_TIMEOUT_S
        sent = [0] * n_sessions
        while True:
            for k in range(n_sessions):
                self._submit(k, sent[k], time.time())
                sent[k] += 1
            if self._settle(give_up, "warm-up") == n_sessions:
                return sent

    def run(self):
        mix, rec = self.cell.mix, self.rec
        n_sessions = int(mix["sessions"])
        self.open_sessions(n_sessions)
        base = self._warm(n_sessions)
        self.t_opened = time.time()
        period = n_sessions / float(mix["offered_fps"])
        ramp_s, tail_s, jitter = float(mix["ramp_s"]), float(mix["tail_s"]), float(mix["jitter"])
        t_close = ramp_s + self.seconds               # offsets from the schedule's start
        horizon = t_close + tail_s
        n_iv = int(horizon / (period * (1 - jitter))) + 2
        phases = self.rng.permutation(n_sessions) / n_sessions * period
        events = []                                   # (due offset, session, index)
        for k in range(n_sessions):
            steps = period * (1 + jitter * np.linspace(-1, 1, n_iv))
            due = phases[k] + np.concatenate([[0.0], np.cumsum(self.rng.permutation(steps))])
            events += [(float(t), k, i) for i, t in enumerate(due) if t < horizon]
        events.sort()
        expected_total = sum(1 for t, _, _ in events if ramp_s <= t < t_close)
        start = time.time() + 0.05
        t0, t1 = start + ramp_s, start + t_close
        rec.t1, rec.t0 = t1, t0
        due_at = [dict() for _ in range(n_sessions)]  # sent and not back: index -> (due, attempted)
        open_attempts = 0                             # attempted and not back
        nxt = 0
        while True:
            now = time.time()
            rec.turn(now)
            while nxt < len(events) and start + events[nxt][0] <= now:
                off, k, i = events[nxt]
                due = start + off
                with self._span("chipbench.submit"):
                    self._submit(k, base[k] + i, due)       # stamped with its due time
                attempted = t0 <= due < t1
                due_at[k][base[k] + i] = (due, attempted)
                if attempted:
                    rec.attempted += 1
                    open_attempts += 1
                    rec.late_ms.append((time.time() - due) * 1e3)
                nxt += 1
            moved = 0
            with self._span("chipbench.poll"):
                for k in range(n_sessions):
                    got = self.front.poll(self.sids[k])
                    if not got:
                        continue
                    t = time.time()
                    for d in got:
                        due, attempted = due_at[k].pop(d.index, (None, False))
                        self._on_delivery(k, d, t, attempted, expected_total)
                        if attempted:
                            rec.transit.append((due, t))
                            open_attempts -= 1
                    moved += len(got)
                    rec.poll_lumps += 1
            if now >= t1 and (open_attempts == 0 or now >= t1 + tail_s):
                break                    # every attempted frame is back, or the tail is over
            if not moved:
                wait = 0.001
                if nxt < len(events):
                    wait = min(wait, max(0.0, start + events[nxt][0] - time.time()))
                with self._span("chipbench.idle_wait"):
                    time.sleep(wait)
        rec.unresolved = open_attempts
        rec.tail_s = time.time() - t1
        # The tail's own frames decide nothing; they come back (or not) with
        # sending stopped, so that the program's counters add up at the end.
        try:
            self._settle(time.time() + float(mix["rest_s"]), "rest",
                         forget=lambda k, index: due_at[k].pop(index, None))
        except RuntimeError:
            pass                          # run.py's accounting shows what is left inside
        rec.left_inside = sum(len(s) for s in due_at)
        return rec


KINDS = {"closed": ClosedLoop, "open": OpenLoop}


def build(cell, front, pool, seed, seconds, spans=False):
    kind = cell.mix["kind"]
    if kind not in KINDS:
        raise SystemExit(f"chipbench: generator kind {kind!r} (known: {sorted(KINDS)})")
    return KINDS[kind](cell, front, pool, seed, seconds, spans)
