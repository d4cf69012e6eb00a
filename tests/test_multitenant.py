"""Multi-signature serving: bucketed batching, program pool, AOT warm-start.

The acceptance surface of the multi-tenant frontend on CPU: a session
mix with ≥3 distinct (op_chain, geometry, dtype) signatures runs
concurrently on ONE frontend with per-session outputs bit-identical to
dedicated single-signature runs (zero cross-bucket leakage), the
compiled-program pool LRU-evicts and re-admits correctly (recompile
through the cache, outputs unchanged), the EDF/cost bucket scheduler
never starves a small tight-SLO bucket behind a big busy one, a chaos
``compute`` fault in one bucket leaves the other buckets' sessions
untouched (budgets attribute per bucket), signature keys canonicalize
(``u8`` ≡ ``uint8``, list ≡ tuple, kwarg order irrelevant), precompile
manifests warm the pool, and every pool engine frees its device buffers
at frontend close.
"""

import time

import numpy as np
import pytest

from dvf_tpu.ops import get_filter
from dvf_tpu.runtime.engine import live_pool_engines
from dvf_tpu.runtime.signature import (
    canonical_op_chain,
    make_key,
    parse_manifest,
)
from dvf_tpu.serve import AdmissionError, ServeConfig, ServeFrontend

pytestmark = pytest.mark.multitenant

H, W = 16, 24


def cfg(**kw) -> ServeConfig:
    base = dict(batch_size=4, queue_size=1000, out_queue_size=1000,
                slo_ms=60_000.0)
    base.update(kw)
    return ServeConfig(**base)


def frames_for(shape, dtype, n, seed):
    rng = np.random.default_rng(seed)
    if np.dtype(dtype) == np.uint8:
        return [rng.integers(0, 255, shape, dtype=np.uint8)
                for _ in range(n)]
    return [rng.random(shape, dtype=np.float32).astype(dtype)
            for _ in range(n)]


def drain_session(fe, sid, want, deadline_s=60.0):
    got = []
    deadline = time.time() + deadline_s
    while len(got) < want and time.time() < deadline:
        got.extend(fe.poll(sid))
        time.sleep(0.002)
    got.extend(fe.poll(sid))
    return got


# ------------------------------------------------- signature canonicalization


class TestSignatureKey:
    """Satellite: equal signatures can't miss the pool/cache by
    spelling — dtype aliases, geometry container type, kwarg order and
    whitespace all normalize to ONE key."""

    def test_dtype_spellings_equal(self):
        ref = make_key("invert", (4, 4, 3), "uint8")
        for spelling in ("u8", "uint8", "byte", np.uint8,
                         np.dtype("uint8")):
            assert make_key("invert", (4, 4, 3), spelling) == ref
        assert make_key("invert", (4, 4, 3), "f32") == \
            make_key("invert", (4, 4, 3), np.float32)
        # "u8" is the ML spelling (8 bits), NOT numpy's 8-byte code.
        assert make_key("invert", (4, 4, 3), "u8").dtype == "uint8"
        assert make_key("invert", (4, 4, 3), "u8") != \
            make_key("invert", (4, 4, 3), "uint16")

    def test_geometry_container_types_equal(self):
        a = make_key("invert", (4, 8, 3), "u8")
        assert make_key("invert", [4, 8, 3], "u8") == a
        assert make_key("invert", np.zeros((4, 8, 3)).shape, "u8") == a
        with pytest.raises(ValueError):
            make_key("invert", (0, 8, 3), "u8")

    def test_op_chain_kwarg_order_whitespace_and_numerics(self):
        a = canonical_op_chain("gaussian_blur(ksize=9, sigma=2.0)")
        b = canonical_op_chain("gaussian_blur( sigma=2,ksize=9 )")
        assert a == b == "gaussian_blur(ksize=9,sigma=2)"
        assert canonical_op_chain(" grayscale | invert ") == \
            canonical_op_chain("grayscale|invert")
        with pytest.raises(ValueError):
            canonical_op_chain("not a name!(")

    def test_engine_signature_key_is_canonical(self):
        from dvf_tpu.runtime.engine import Engine

        e = Engine(get_filter("invert"))
        assert e.signature_key is None
        e.compile((2, H, W, 3), np.uint8)
        assert e.signature_key == make_key("invert", (H, W, 3), "u8")
        assert e.signature_key.render() == f"invert|{H}x{W}x3|uint8"
        e.free()

    def test_manifest_parses_and_canonicalizes(self):
        entries = parse_manifest({"signatures": [
            {"op_chain": "grayscale |invert", "frame_shape": [H, W, 3],
             "dtype": "u8"}]})
        assert entries[0]["key"] == make_key("grayscale|invert",
                                             (H, W, 3), "uint8")
        with pytest.raises(ValueError):
            parse_manifest([{"op_chain": "invert"}])


# ------------------------------------------------------- mixed-signature runs


class TestMixedSignatures:
    def test_three_signatures_concurrent_bit_identical(self):
        """Acceptance: ≥3 distinct (op_chain, geometry, dtype)
        signatures on ONE frontend, every session's output bit-identical
        to a dedicated single-signature frontend fed the same frames —
        bucket isolation with zero cross-bucket index or pixel leakage."""
        n = 12
        specs = [
            ("invert", (H, W, 3), np.uint8),          # default bucket
            ("grayscale|invert", (H + 8, W, 3), np.uint8),
            ("invert", (H, W + 8, 3), np.uint8),      # same op, new geometry
        ]
        frames = {i: frames_for(shape, dt, n, seed=10 + i)
                  for i, (_, shape, dt) in enumerate(specs)}

        # Dedicated single-signature runs first: the golden outputs.
        golden = {}
        for i, (chain, shape, dt) in enumerate(specs):
            from dvf_tpu.runtime.signature import build_filter

            fe = ServeFrontend(build_filter(chain), cfg())
            with fe:
                sid = fe.open_stream()
                for f in frames[i]:
                    fe.submit(sid, f)
                golden[i] = [d.frame for d in drain_session(fe, sid, n)]
            assert len(golden[i]) == n

        # The mixed run: all three signatures interleaved on one
        # frontend, one device.
        fe = ServeFrontend(get_filter("invert"), cfg(max_buckets=4))
        with fe:
            # Declared → pins the default bucket (opened FIRST, so the
            # later invert-at-new-geometry declaration forks a bucket
            # instead of claiming the unpinned default).
            sids = [fe.open_stream(frame_shape=specs[0][1])]
            for chain, shape, dt in specs[1:]:
                sids.append(fe.open_stream(op_chain=chain,
                                           frame_shape=shape,
                                           frame_dtype=dt))
            for j in range(n):  # round-robin interleave across buckets
                for i, sid in enumerate(sids):
                    fe.submit(sid, frames[i][j])
            got = {i: drain_session(fe, sid, n)
                   for i, sid in enumerate(sids)}
            stats = fe.stats()

        assert stats["open_buckets"] == 3
        assert len(stats["buckets"]) == 3
        for i in range(len(specs)):
            assert [d.index for d in got[i]] == list(range(n)), (
                f"signature {i}: wrong indices")
            for j, d in enumerate(got[i]):
                np.testing.assert_array_equal(
                    d.frame, golden[i][j],
                    err_msg=f"signature {i} frame {j}: differs from the "
                            f"dedicated single-signature run "
                            f"(cross-bucket leakage?)")

    def test_configured_filter_routes_new_geometry(self):
        """Regression (review finding): a CONFIGURED filter's display
        name (e.g. the measured-default gaussian resolved to its impl,
        with renamed kwargs) is not a buildable registry spec — routing
        a second geometry of the default chain must reuse the live
        Filter object, not round-trip through build_filter."""
        n = 4
        fe = ServeFrontend(get_filter("gaussian_blur", ksize=5),
                           cfg(batch_size=2))
        with fe:
            a = fe.open_stream(frame_shape=(H, W, 3))
            b = fe.open_stream(frame_shape=(H + 8, W, 3))  # same chain,
            #   new geometry → new bucket, same Filter object
            for j in range(n):
                fe.submit(a, frames_for((H, W, 3), np.uint8, 1, j)[0])
                fe.submit(b, frames_for((H + 8, W, 3), np.uint8, 1, j)[0])
            got_a = drain_session(fe, a, n)
            got_b = drain_session(fe, b, n)
            st = fe.stats()
        assert len(got_a) == n and len(got_b) == n
        assert st["open_buckets"] == 2
        labels = sorted(st["buckets"])
        assert len(labels) == 2

    def test_pool_eviction_and_readmission_recompile(self):
        """LRU eviction frees the program's device buffers; re-admitting
        the signature recompiles (a fresh pool miss) and serves
        bit-identical output."""
        n = 4
        gray_frames = frames_for((H, W, 3), np.uint8, n, seed=3)
        fe = ServeFrontend(get_filter("invert"),
                           cfg(batch_size=2, max_buckets=2,
                               pool_capacity=1))
        with fe:
            a = fe.open_stream()
            fe.submit(a, gray_frames[0])
            drain_session(fe, a, 1)  # default bucket compiled + pooled

            b = fe.open_stream(op_chain="grayscale", frame_shape=(H, W, 3))
            assert fe.stats()["pool"]["misses"] == 1
            for f in gray_frames:
                fe.submit(b, f)
            first = [d.frame for d in drain_session(fe, b, n)]
            assert len(first) == n

            # Retire the grayscale bucket (close + a new signature at
            # the bucket cap evicts the idle one); pool_capacity=1 then
            # frees the un-leased grayscale program.
            fe.close(b, drain=True)
            deadline = time.time() + 20
            while fe.open_count() > 1 and time.time() < deadline:
                time.sleep(0.005)
            c = fe.open_stream(frame_shape=(H + 8, W, 3))  # third signature
            st = fe.stats()
            assert st["pool"]["misses"] == 2
            assert st["pool"]["evictions"] >= 1
            fe.close(c, drain=False)
            deadline = time.time() + 20
            while fe.open_count() > 1 and time.time() < deadline:
                time.sleep(0.005)

            # Re-admission: the evicted signature compiles AGAIN (pool
            # miss, not a stale hit) and its output is unchanged.
            b2 = fe.open_stream(op_chain="grayscale",
                                frame_shape=(H, W, 3))
            assert fe.stats()["pool"]["misses"] == 3
            for f in gray_frames:
                fe.submit(b2, f)
            second = [d.frame for d in drain_session(fe, b2, n)]
        assert len(second) == n
        for x, y in zip(first, second):
            np.testing.assert_array_equal(x, y)

    def test_returning_signature_is_a_pool_hit_and_a_join_compiles_nothing(
            self):
        """The admission ladder in counts: a second session of a live
        signature routes to its bucket (no pool traffic, no XLA
        compile), and a signature whose bucket retired while its
        program stayed in the pool comes back as a pool hit, again with
        no XLA compile, serving the same bytes."""
        from dvf_tpu.obs.ledger import XLA_COMPILES

        shape = (H + 2, W, 3)
        frames = frames_for(shape, np.uint8, 4, seed=11)
        fe = ServeFrontend(get_filter("invert"),
                           cfg(batch_size=2, max_buckets=2,
                               pool_capacity=8))

        def serve(sid):
            for f in frames:
                fe.submit(sid, f)
            got = drain_session(fe, sid, len(frames))
            assert len(got) == len(frames)
            return [d.frame.tobytes() for d in got]

        def leave(*sids):
            for sid in sids:
                fe.close(sid, drain=True)
            deadline = time.time() + 20
            while fe.open_count() > 0 and time.time() < deadline:
                time.sleep(0.005)

        def counts():
            pool = fe.stats()["pool"]
            return (pool["hits"], pool["misses"],
                    XLA_COMPILES.totals()[0])

        with fe:
            a = fe.open_stream(op_chain="grayscale|invert",
                               frame_shape=shape)
            first = serve(a)       # two batches: the step's own jit too
            hits, misses, compiles = counts()
            assert (hits, misses) == (0, 1)
            joined = fe.open_stream(op_chain="grayscale|invert",
                                    frame_shape=shape)
            assert serve(joined) == first
            assert counts() == (0, 1, compiles)
            leave(a, joined)
            # A second signature at the bucket cap retires the idle one.
            b = fe.open_stream(op_chain="grayscale", frame_shape=shape)
            serve(b)
            leave(b)
            hits, misses, compiles = counts()
            assert (hits, misses) == (0, 2)
            back = fe.open_stream(op_chain="grayscale|invert",
                                  frame_shape=shape)
            assert serve(back) == first
            assert counts() == (1, 2, compiles)

    def test_edf_cost_scheduler_never_starves_small_bucket(self):
        """A big, continuously-loaded bucket on a slowed engine vs a
        small tight-SLO bucket: the EDF-headroom ÷ tick-cost score must
        keep serving the small bucket before its deadlines blow — zero
        shed, everything delivered."""
        fe = ServeFrontend(get_filter("invert"),
                           cfg(batch_size=4, max_inflight=1))
        small_n = 15
        with fe:
            big = [fe.open_stream(frame_shape=(H, W, 3))
                   for _ in range(2)]
            small = fe.open_stream(op_chain="grayscale",
                                   frame_shape=(H, W, 3), slo_ms=2000.0)
            # Prime both buckets (compile before the clock matters).
            for sid in (*big, small):
                fe.submit(sid, np.zeros((H, W, 3), np.uint8))
            deadline = time.time() + 30
            while time.time() < deadline:
                st = fe.stats()["sessions"]
                if all(st[s]["delivered"] == 1 for s in (*big, small)):
                    break
                time.sleep(0.005)
            # Slow the BIG bucket's engine only: each of its batches now
            # costs ~10 ms, so a naive biggest-queue scheduler would sit
            # on big batches while the small bucket's deadlines expire.
            big_engine = fe._session(big[0]).bucket.engine
            orig = big_engine.submit_resident

            def slow_submit(batch):
                time.sleep(0.01)
                return orig(batch)

            big_engine.submit_resident = slow_submit
            big_engine.submit = slow_submit
            stop = time.time() + 3.0
            rng = np.random.default_rng(0)
            sent_small = 0
            frame = rng.integers(0, 255, (H, W, 3), np.uint8)
            while time.time() < stop:
                for sid in big:  # saturate the big bucket
                    for _ in range(4):
                        fe.submit(sid, frame)
                if sent_small < small_n:
                    fe.submit(small, frame)
                    sent_small += 1
                time.sleep(0.01)
            got = drain_session(fe, small, sent_small + 1)
            st = fe.stats()
        s = st["sessions"][small]
        assert s["shed"] == 0, (
            f"small bucket shed {s['shed']} frames behind the big one")
        assert s["delivered"] == sent_small + 1
        assert len(got) == sent_small + 1

    def test_compute_chaos_in_one_bucket_leaves_others_unharmed(self):
        """Chaos ``compute`` faults armed on ONE bucket's engine: that
        bucket's sessions absorb the (attributed, budgeted) failures;
        the other bucket's stream is bit-identical to fault-free — and
        the faulted bucket's budget, not the frontend's, absorbed it."""
        from dvf_tpu.resilience import FaultPlan

        n = 10
        inv_frames = frames_for((H, W, 3), np.uint8, n, seed=4)
        fe = ServeFrontend(get_filter("invert"),
                           cfg(batch_size=2, fault_budget=16,
                               stall_timeout_s=0.0))
        with fe:
            a = fe.open_stream()                       # default: invert
            b = fe.open_stream(op_chain="grayscale",
                               frame_shape=(H, W, 3))
            # One clean frame each (compile both programs) …
            fe.submit(a, inv_frames[0])
            fe.submit(b, inv_frames[0])
            deadline = time.time() + 30
            while time.time() < deadline:
                st = fe.stats()["sessions"]
                if st[a]["delivered"] == 1 and st[b]["delivered"] == 1:
                    break
                time.sleep(0.005)
            # … then arm chaos on the GRAYSCALE bucket's engine only.
            bucket_b = fe._session(b).bucket
            bucket_b.engine.chaos = FaultPlan(seed=7).add(
                "compute", every=1, count=3)
            got_a, got_b = [], []
            for j in range(1, n):
                fe.submit(a, inv_frames[j])
                fe.submit(b, inv_frames[j])
                time.sleep(0.01)
            got_a = drain_session(fe, a, n)
            deadline = time.time() + 30
            while time.time() < deadline:
                sb = fe.stats()["sessions"][b]
                if sb["delivered"] + sb["failed"] + sb["shed"] \
                        + sb["dropped_at_ingress"] >= n:
                    break
                time.sleep(0.005)
            got_b = drain_session(fe, b, 0, deadline_s=0.1)
            stats = fe.stats()

        # The healthy bucket: complete, ordered, bit-exact.
        assert [d.index for d in got_a] == list(range(n))
        for j, d in enumerate(got_a):
            np.testing.assert_array_equal(d.frame, 255 - inv_frames[j])
        # The chaos bucket: exactly 3 injected fault EVENTS (each may
        # fail 1-2 frames when a batch carried two of b's frames), all
        # attributed to ITS sessions/bucket — not the healthy one.
        sb = stats["sessions"][b]
        assert 3 <= sb["failed"] <= 6
        assert sb["faults"] == {"compute": sb["failed"]}
        sa = stats["sessions"][a]
        assert sa["failed"] == 0 and sa["faults"] == {}
        rows = stats["buckets"]
        b_row = rows[bucket_b.label()]
        assert b_row["faults"] == {"compute": 3}
        a_label = [k for k in rows if k != bucket_b.label()][0]
        assert rows[a_label]["faults"] == {}
        # Contained within the bucket's budget: no recovery, no error.
        assert stats["recoveries"] == 0
        del got_b  # b's exact delivery count is timing-dependent; the
        # session counters reconcile exactly instead:
        assert sb["submitted"] == sb["delivered"] + sb["shed"] \
            + sb["failed"] + sb["dropped_at_ingress"]


# --------------------------------------------------- warm-start + lifecycle


class TestWarmStart:
    def test_precompile_manifest_warms_pool(self):
        fe = ServeFrontend(get_filter("invert"), cfg(batch_size=2))
        manifest = [{"op_chain": "grayscale",
                     "frame_shape": [H, W, 3], "dtype": "u8"}]
        with fe:
            warmed = fe.precompile(manifest)
            assert warmed == [f"grayscale|{H}x{W}x3|uint8"]
            st = fe.stats()
            assert st["pool"]["misses"] == 1 and st["pool"]["size"] == 1
            # The real admission is now a pool hit — and it serves.
            sid = fe.open_stream(op_chain="grayscale",
                                 frame_shape=(H, W, 3))
            assert fe.stats()["pool"]["hits"] == 1
            f = np.full((H, W, 3), 9, np.uint8)
            fe.submit(sid, f)
            got = drain_session(fe, sid, 1)
            assert len(got) == 1
        assert fe.health()["warm_signatures"]  # still enumerable

    def test_open_stream_canonicalizes_dtype_spelling(self):
        """Regression (caught driving the live surface): "u8" declared
        at open_stream must mean uint8 (the ML spelling), not numpy's
        8-byte uint64 — pre-fix the first uint8 submit was refused
        against a bogus uint64 pin."""
        fe = ServeFrontend(get_filter("invert"), cfg(batch_size=2))
        with fe:
            sid = fe.open_stream(frame_shape=(H, W, 3), frame_dtype="u8")
            f = np.full((H, W, 3), 5, np.uint8)
            fe.submit(sid, f)
            got = drain_session(fe, sid, 1)
            assert len(got) == 1
            np.testing.assert_array_equal(got[0].frame, 255 - f)

    def test_warm_signatures_in_health_and_rejection(self):
        fe = ServeFrontend(get_filter("invert"),
                           cfg(batch_size=2, max_buckets=1))
        with fe:
            fe.open_stream(frame_shape=(H, W, 3))
            assert f"invert|{H}x{W}x3|uint8" in \
                fe.health()["warm_signatures"]
            with pytest.raises(AdmissionError, match="warm signatures"):
                fe.open_stream(op_chain="grayscale",
                               frame_shape=(H, W, 3))

    def test_stop_frees_every_pool_engine(self):
        """Satellite: no pool engine may keep device buffers past
        frontend close (the conftest session-end guard's per-test
        twin)."""
        fe = ServeFrontend(get_filter("invert"), cfg(batch_size=2))
        with fe:
            a = fe.open_stream(frame_shape=(H, W, 3))
            b = fe.open_stream(op_chain="grayscale",
                               frame_shape=(H + 8, W, 3))
            fe.submit(a, np.zeros((H, W, 3), np.uint8))
            fe.submit(b, np.zeros((H + 8, W, 3), np.uint8))
            drain_session(fe, a, 1)
            drain_session(fe, b, 1)
            assert len(live_pool_engines()) >= 2
        assert live_pool_engines() == []

    def test_freed_engine_refuses_submit(self):
        from dvf_tpu.runtime.engine import Engine

        e = Engine(get_filter("invert"))
        e.compile((2, H, W, 3), np.uint8)
        e.free()
        with pytest.raises(RuntimeError, match="freed"):
            e.submit(np.zeros((2, H, W, 3), np.uint8))
        e.free()  # idempotent


class TestPoolAndRetireHardening:
    """Review-pass regressions: pool.replace racing close/retire, and
    retired buckets releasing their host staging slabs."""

    def test_pool_replace_on_closed_pool_frees_and_raises(self):
        """A supervised recovery whose rebuilt engine lands after the
        owner's stop() swept the pool must not insert a live program
        nothing will ever free — replace() frees it and raises, like
        acquire()/adopt()."""
        from dvf_tpu.runtime.engine import Engine, ProgramPool

        pool = ProgramPool(capacity=2)
        key = ("invert", (H, W, 3), "uint8")
        pool.acquire(key, lambda: _compiled_engine())
        pool.close()
        assert live_pool_engines() == []
        rebuilt = _compiled_engine()
        with pytest.raises(RuntimeError, match="closed"):
            pool.replace(key, rebuilt)
        assert live_pool_engines() == []
        with pytest.raises(RuntimeError, match="freed"):
            rebuilt.submit(np.zeros((2, H, W, 3), np.uint8))

    def test_pool_replace_absent_key_enters_warm_not_leased(self):
        """A key retired (lease dropped + evicted) while its bucket was
        mid-recovery re-enters WARM: lease count 0, so capacity
        pressure can still evict it — pre-fix it re-entered with a
        lease nobody would ever release, pinning the program forever."""
        from dvf_tpu.runtime.engine import ProgramPool

        pool = ProgramPool(capacity=1)
        key_a, key_b = ("a",), ("b",)
        pool.replace(key_a, _compiled_engine())  # absent key → warm
        assert pool.warm_keys() == [key_a]
        # A later acquire of another key must be able to evict it.
        pool.acquire(key_b, lambda: _compiled_engine())
        assert pool.evictions == 1
        assert key_a not in pool.warm_keys()
        pool.close()
        assert live_pool_engines() == []

    def test_retired_bucket_releases_staging_slabs(self):
        """Bucket churn through a small max_buckets cap must not pin
        the retired buckets' assembler/fetcher host slabs: retired
        sessions keep a .bucket reference for tail drains, so the slabs
        (unlike the pool-warm program) must be dropped at retire."""
        fe = ServeFrontend(get_filter("invert"),
                           cfg(batch_size=2, max_buckets=2))
        with fe:
            a = fe.open_stream(op_chain="grayscale",
                               frame_shape=(H, W, 3))
            fe.submit(a, np.zeros((H, W, 3), np.uint8))
            assert len(drain_session(fe, a, 1)) == 1
            bucket = fe._session(a).bucket
            assert bucket.lane._assembler is not None
            assert bucket.lane.slab_bytes() > 0
            fe.close(a, drain=True)
            deadline = time.time() + 20
            while fe.open_count() > 0 and time.time() < deadline:
                time.sleep(0.005)
            # A new signature at the cap retires the idle bucket.
            b = fe.open_stream(op_chain="grayscale",
                               frame_shape=(H + 8, W, 3))
            assert fe._session(b).bucket is not bucket
            assert bucket.lane._assembler is None
            assert bucket.lane._fetcher is None
            assert bucket.lane.slab_bytes() == 0
            # The retired session still drains through its reference.
            assert fe.poll(a) == []


def _compiled_engine():
    from dvf_tpu.runtime.engine import Engine

    e = Engine(get_filter("invert"))
    e.compile((2, H, W, 3), np.uint8)
    return e


# ------------------------------------- a short batch waits for the device


class _Bucket:
    """What ``select_bucket`` asks of a bucket."""

    def __init__(self, batch_size, cost_ms=1.0):
        self.batch_size = batch_size
        self.cost_ms = cost_ms

    def tick_cost_estimate(self):
        return self.cost_ms


def _queued(name, n, slo_ms, ts):
    from dvf_tpu.serve.session import SessionConfig, StreamSession

    s = StreamSession(name, SessionConfig(queue_size=64, slo_ms=slo_ms))
    for j in range(n):
        s.submit(np.zeros((2, 2, 3), np.uint8), ts=ts + j * 1e-4)
    return s


class TestShortBatchWaitsAcrossBuckets:
    """serve/batcher.py, "A short batch waits for the device, not in
    it", at the bucket pick: the rule applies to the bucket the EDF/cost
    score leads with."""

    @pytest.mark.parametrize("have,may_go_short,bound", [
        (1, False, 0), (3, False, 0), (4, False, 4), (6, False, 4),
        (1, True, 1), (3, True, 3), (4, True, 4), (6, True, 4),
    ])
    def test_fill_and_backlog_decide_the_binding(self, have, may_go_short,
                                                 bound):
        from dvf_tpu.serve.batcher import ContinuousBatcher

        now = time.time()
        bucket = _Bucket(4)
        s = _queued("s", have, 60_000.0, now)
        pick, chosen = ContinuousBatcher(8).select_bucket(
            [(bucket, [s])], now, may_go_short=may_go_short)
        assert pick is bucket
        assert len(chosen or ()) == bound
        assert (chosen is None) == (bound == 0)
        # what was not bound waits where later arrivals join it
        assert len(s.pending) == have - bound and s.inflight == bound

    def test_a_held_pick_promotes_no_other_bucket_and_still_sheds(self):
        from dvf_tpu.serve.batcher import ContinuousBatcher

        batcher = ContinuousBatcher(4)
        now = time.time()
        tight, loose = _Bucket(4), _Bucket(4)
        a = _queued("a", 2, 50.0, now)          # leads: least headroom
        b = _queued("b", 4, 60_000.0, now)      # a full batch, behind it
        both = [(loose, [b]), (tight, [a])]
        assert batcher.select_bucket(both, now, may_go_short=False) == (
            tight, None)
        assert (len(a.pending), len(b.pending)) == (2, 4)
        assert a.inflight == b.inflight == 0
        # two more arrive while the device works: the four leave together
        for j in (2, 3):
            a.submit(np.zeros((2, 2, 3), np.uint8), ts=now + j * 1e-4)
        pick, chosen = batcher.select_bucket(both, now, may_go_short=False)
        assert pick is tight
        assert [(sl.session.id, sl.index) for sl in chosen] == [
            ("a", j) for j in range(4)]
        # held frames age like any other: past the deadline they are shed
        c = _queued("c", 3, 50.0, now)
        pick, chosen = batcher.select_bucket(
            [(tight, [c])], now + 1.0, may_go_short=False)
        assert (pick, chosen) == (None, None) and c.shed == 3

    def test_two_signatures_through_holds_every_frame_accounted(
            self, device_gate):
        """Two buckets on one frontend while the device's backlog comes
        and goes: each session's frames come back once, in order,
        bit-identical to its own filter."""
        fe = ServeFrontend(get_filter("invert"), cfg(batch_size=4))
        n = 12
        frames = frames_for((H, W, 3), np.uint8, n, seed=5)
        with fe:
            inv = fe.open_stream(frame_shape=(H, W, 3))
            gray = fe.open_stream(op_chain="grayscale",
                                  frame_shape=(H, W, 3))
            for sid in (inv, gray):             # compile both programs
                fe.submit(sid, frames[0])
            want_gray = drain_session(fe, gray, 1)[0].frame
            assert len(drain_session(fe, inv, 1)) == 1
            for j in range(1, n):
                device_gate.busy = j % 3 != 0   # held two rounds in three
                fe.submit(inv, frames[j])
                fe.submit(gray, frames[j])
                time.sleep(0.004)
            device_gate.busy = False
            got_inv = drain_session(fe, inv, n - 1)
            got_gray = drain_session(fe, gray, n - 1)
            st = fe.stats()
        assert [d.index for d in got_inv] == list(range(1, n))
        assert [d.index for d in got_gray] == list(range(1, n))
        for d in got_inv:
            np.testing.assert_array_equal(d.frame, 255 - frames[d.index])
        assert want_gray.shape == got_gray[0].frame.shape
        for sid in (inv, gray):
            row = st["sessions"][sid]
            assert row["submitted"] == row["delivered"] == n
            assert row["shed"] == row["failed"] == 0
        holds = [r["hold"] for r in st["buckets"].values()]
        assert sum(h["held_batches_total"] for h in holds) >= 1
        assert sum(h["hold_ms_total"] for h in holds) == pytest.approx(
            st["threads"]["dispatch"]["hold_ms"], abs=0.01)
        for r in st["buckets"].values():
            assert (r["hold"]["short_batches_total"]
                    + r["hold"]["full_batches_total"]) == r["batches"]


class _Handle:
    def __init__(self, ready=False, poisoned=False):
        self.ready, self.poisoned = ready, poisoned

    def is_ready(self):
        if self.poisoned:
            raise RuntimeError("poisoned")
        return self.ready


class TestDeviceBacklog:
    """serve/batcher.py::DeviceBacklog: what ``may_go_short`` is read
    from. Times in seconds; one step "took" 100 ms, staging 10 ms."""

    def _one_onto_an_idle_device(self, staging_s=0.010):
        from dvf_tpu.serve.batcher import DeviceBacklog

        b, h = DeviceBacklog(), _Handle()
        assert b.may_go_short(0.0)                  # nothing of ours there
        b.queued(h, None, -staging_s, 0.0, 100.0)
        return b, h

    @pytest.mark.parametrize("now,staging_s,go", [
        (0.050, 0.010, False),      # the device still has half a step
        (0.089, 0.010, False),
        (0.091, 0.010, True),       # staged by the time it runs out
        (0.050, 0.060, True),       # a long staging starts sooner
        (5.000, 0.000, True),       # overdue: not held on a stale handle
    ])
    def test_released_one_staging_before_the_backlog_is_due(
            self, now, staging_s, go):
        b, _ = self._one_onto_an_idle_device(staging_s)
        assert b.may_go_short(now) is go

    @pytest.mark.parametrize("how", ["ready", "poisoned"])
    def test_a_ready_or_raising_handle_ends_the_backlog(self, how):
        b, h = self._one_onto_an_idle_device()
        setattr(h, how, True)
        assert b.may_go_short(0.001)
        h.ready = h.poisoned = False                # forgotten: not asked again
        assert b.may_go_short(0.002)

    def test_without_a_measurement_only_readiness_counts(self):
        from dvf_tpu.serve.batcher import DeviceBacklog

        b, h = DeviceBacklog(), _Handle()
        b.queued(h, None, -1.0, 0.0, None)
        assert not b.may_go_short(3600.0)
        h.ready = True
        assert b.may_go_short(3600.0)

    def test_a_queued_batch_starts_when_the_one_before_is_seen_ready(self):
        """No forecast is built on a forecast: behind an unready batch
        nothing is due until that one has been SEEN ready."""
        b, first = self._one_onto_an_idle_device()
        second = _Handle()
        b.queued(second, None, 0.085, 0.095, 100.0)  # bound a staging early
        assert not b.may_go_short(0.150)
        assert not b.may_go_short(9.000)            # whatever the clock says
        first.ready = True                          # seen at 9.002: the
        assert not b.may_go_short(9.002)            # second runs from there
        assert not b.may_go_short(9.090)
        assert b.may_go_short(9.093)
        second.ready = True
        assert b.may_go_short(9.094)

    def test_batches_of_another_generation_hold_nothing_up(self):
        """A supervised recovery wrote the window off and replaced the
        permits: what was queued under the old ones is not waited for."""
        b, _ = self._one_onto_an_idle_device()
        old, new = object(), object()
        b.queued(_Handle(), old, 0.0, 0.010, 100.0)
        assert not b.may_go_short(0.020, old)
        assert b.may_go_short(0.020, new)
        b.queued(_Handle(), new, 0.020, 0.030, 100.0)
        assert not b.may_go_short(0.040, new)
