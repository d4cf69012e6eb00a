"""Resilience: the long-running loops must survive what the reference
survives — idle gaps with silently-consumed READYs (distributor.py:226-244),
malformed messages, poison frames, raising filters (worker.py:71-76,
distributor.py:249-251) — and expose the --delay fault-injection knob."""

import threading
import time

import numpy as np
import pytest

pytest.importorskip("zmq")


class _Sockets:
    """App-side ROUTER + PULL pair on random ports."""

    def __init__(self):
        import zmq

        self.ctx = zmq.Context()
        self.router = self.ctx.socket(zmq.ROUTER)
        self.dist_port = self.router.bind_to_random_port("tcp://127.0.0.1")
        self.pull = self.ctx.socket(zmq.PULL)
        self.coll_port = self.pull.bind_to_random_port("tcp://127.0.0.1")

    def close(self):
        self.router.close(0)
        self.pull.close(0)
        self.ctx.term()


def _mk_worker(app, **kw):
    from dvf_tpu.ops import get_filter
    from dvf_tpu.transport.zmq_ingress import TpuZmqWorker

    defaults = dict(
        host="127.0.0.1",
        distribute_port=app.dist_port,
        collect_port=app.coll_port,
        batch_size=4,
        use_jpeg=False,
        raw_size=16,
    )
    defaults.update(kw)
    return TpuZmqWorker(get_filter("invert"), **defaults)


def test_credit_expiry_survives_silent_ready_consumption(rng):
    """The reference distributor consumes a READY and replies with NOTHING
    whenever it has no fresh frame (distributor.py:226-244) — the common
    case between webcam frames. Credits must expire and be re-issued or the
    worker deadlocks after one idle gap (it would hold batch_size
    'outstanding' credits forever while the server has already forgotten
    them)."""
    app = _Sockets()
    worker = _mk_worker(app)
    t = threading.Thread(target=worker.run, kwargs={"max_frames": 4}, daemon=True)
    t.start()

    # Phase 1 (idle gap): consume every READY for 0.3 s, reply nothing.
    deadline = time.time() + 0.3
    consumed = 0
    while time.time() < deadline:
        if app.router.poll(10):
            app.router.recv_multipart()
            consumed += 1
    assert consumed >= 4  # the worker's entire initial credit window was eaten

    # Phase 2: serve frames. A deadlocked worker never sends READY again.
    frames = [rng.integers(0, 255, (16, 16, 3), np.uint8) for _ in range(4)]
    sent, results = 0, {}
    deadline = time.time() + 15
    while len(results) < 4 and time.time() < deadline:
        if sent < 4 and app.router.poll(5):
            client = app.router.recv_multipart()[0]
            app.router.send_multipart(
                [client, str(sent).encode(), frames[sent].tobytes()]
            )
            sent += 1
        if app.pull.poll(5):
            parts = app.pull.recv_multipart()
            results[int(parts[0])] = parts[4]

    worker.stop()
    t.join(timeout=5)
    assert len(results) == 4, "worker deadlocked after silent READY consumption"
    for i in range(4):
        out = np.frombuffer(results[i], np.uint8).reshape(16, 16, 3)
        np.testing.assert_array_equal(out, 255 - frames[i])
    worker.close()
    app.close()


def test_worker_survives_malformed_and_poison_messages(rng):
    """worker.py:71-76 semantics: a malformed message or an undecodable
    frame is dropped and counted; the worker keeps serving."""
    app = _Sockets()
    worker = _mk_worker(app)
    t = threading.Thread(target=worker.run, kwargs={"max_frames": 8}, daemon=True)
    t.start()

    def await_ready(timeout=10.0):
        deadline = time.time() + timeout
        while time.time() < deadline:
            if app.router.poll(10):
                return app.router.recv_multipart()[0]
        raise TimeoutError("worker never sent READY")

    # 1. Malformed: 3-part reply, then a non-integer frame index.
    client = await_ready()
    app.router.send_multipart([client, b"a", b"b", b"c"])
    client = await_ready()
    app.router.send_multipart([client, b"notanint", b"payload"])
    # 2. Poison frame: valid index, wrong-size payload (reshape blows up in
    #    the decode step). Let it flush as its own batch.
    client = await_ready()
    app.router.send_multipart([client, b"0", b"short"])
    time.sleep(0.1)  # > assemble_timeout_s: poison batch flushes alone

    # 3. Good frames — all must still be served.
    frames = [rng.integers(0, 255, (16, 16, 3), np.uint8) for _ in range(8)]
    sent, results = 0, {}
    deadline = time.time() + 15
    while len(results) < 8 and time.time() < deadline:
        if sent < 8 and app.router.poll(5):
            client = app.router.recv_multipart()[0]
            app.router.send_multipart(
                [client, str(10 + sent).encode(), frames[sent].tobytes()]
            )
            sent += 1
        if app.pull.poll(5):
            parts = app.pull.recv_multipart()
            results[int(parts[0])] = parts[4]

    worker.stop()
    t.join(timeout=5)
    assert len(results) == 8, "worker died after malformed/poison input"
    for i in range(8):
        out = np.frombuffer(results[10 + i], np.uint8).reshape(16, 16, 3)
        np.testing.assert_array_equal(out, 255 - frames[i])
    assert worker.errors >= 3
    worker.close()
    app.close()


def test_worker_delay_fault_injection(rng):
    """--delay knob (inverter.py:37-38,55-56): injected latency slows
    batches down without breaking the protocol."""
    app = _Sockets()
    worker = _mk_worker(app, delay_s=0.05, batch_size=2)
    t = threading.Thread(target=worker.run, kwargs={"max_frames": 2}, daemon=True)
    t.start()

    frames = [rng.integers(0, 255, (16, 16, 3), np.uint8) for _ in range(2)]
    sent, results = 0, {}
    t0 = time.time()
    deadline = t0 + 15
    while len(results) < 2 and time.time() < deadline:
        if sent < 2 and app.router.poll(5):
            client = app.router.recv_multipart()[0]
            app.router.send_multipart(
                [client, str(sent).encode(), frames[sent].tobytes()]
            )
            sent += 1
        if app.pull.poll(5):
            parts = app.pull.recv_multipart()
            results[int(parts[0])] = (float(parts[2]), float(parts[3]), parts[4])
    worker.stop()
    t.join(timeout=5)
    assert len(results) == 2
    # The injected delay shows up in the worker's self-reported timing span
    # (the same place the reference's --delay lands, worker.py:47,59).
    t_start, t_end, payload = results[0]
    assert t_end - t_start >= 0.05
    np.testing.assert_array_equal(
        np.frombuffer(payload, np.uint8).reshape(16, 16, 3), 255 - frames[0]
    )
    worker.close()
    app.close()


def test_stateful_pad_unsafe_filter_rejected():
    """A stateful filter that is not pad-safe must be refused by the worker
    (repeat-last padding would corrupt its temporal state)."""
    import jax.numpy as jnp

    from dvf_tpu.api.filter import Filter

    running_mean = Filter(
        name="running_mean",
        fn=lambda b, s: (b, s + jnp.mean(b)),
        init_state=lambda shape, dtype: jnp.zeros((), dtype=jnp.float32),
        pad_safe=False,
    )
    app = _Sockets()
    from dvf_tpu.transport.zmq_ingress import TpuZmqWorker

    with pytest.raises(ValueError, match="pad-safe"):
        TpuZmqWorker(
            running_mean,
            host="127.0.0.1",
            distribute_port=app.dist_port,
            collect_port=app.coll_port,
        )
    app.close()


def test_geometry_reprobe_releases_slabs_and_counts_fault(rng):
    """Mid-stream geometry change (the app restarted with a new
    target_size): the worker re-probes and keeps serving; the abandoned
    half-staged assembler's slabs are released eagerly (not left to GC)
    and the event lands under the `geometry` fault kind."""
    from dvf_tpu.transport.codec import make_codec
    from dvf_tpu.transport.zmq_ingress import TpuZmqWorker

    app = _Sockets()
    worker = _mk_worker(app, use_jpeg=True, batch_size=2)
    codec = make_codec()
    small = [rng.integers(0, 255, (16, 16, 3), np.uint8) for _ in range(2)]
    large = [rng.integers(0, 255, (24, 24, 3), np.uint8) for _ in range(2)]
    payloads = codec.encode_batch(small) + codec.encode_batch(large)

    t = threading.Thread(target=worker.run, kwargs={"max_frames": 4},
                         daemon=True)
    t.start()

    def serve(lo, hi):
        sent, got = lo, 0
        deadline = time.time() + 30
        while got < hi - lo and time.time() < deadline:
            if sent < hi and app.router.poll(5):
                client = app.router.recv_multipart()[0]
                app.router.send_multipart(
                    [client, str(sent).encode(), payloads[sent]])
                sent += 1
            if app.pull.poll(5):
                parts = app.pull.recv_multipart()
                results[int(parts[0])] = parts[4]
                got += 1
        return got

    results: dict = {}
    # Phase 1: the 16x16 stream — pins the first assembler geometry.
    assert serve(0, 2) == 2
    old_asm = worker._lane._assembler  # the 16x16-geometry assembler
    # Phase 2: the stream switches to 24x24 → JpegGeometryError → re-probe.
    assert serve(2, 4) == 2
    worker.stop()
    t.join(timeout=10)

    assert sorted(results) == [0, 1, 2, 3], "re-probe lost frames"
    # The geometry flip was classified, not silently absorbed …
    assert worker.faults.summary()["by_kind"] == {"geometry": 1}
    assert worker.errors == 0  # successful containment, not an error
    # … and the abandoned assembler's staging buffers were freed eagerly.
    assert old_asm is not None and old_asm is not worker._lane._assembler
    assert old_asm._chunks == [] and old_asm._mono_pool is None
    assert worker._lane._assembler.batch_shape == (2, 24, 24, 3)
    # Numerics survive the re-probe: results decode to the inverted input.
    for i, frame in enumerate(small + large):
        h, w = codec.probe(results[i])
        out = np.empty((h, w, 3), np.uint8)
        codec.decode_batch([results[i]], out=out[None])
        assert (h, w) == frame.shape[:2]
    codec.close()
    worker.close()
    app.close()


def test_shm_ring_source_detects_producer_death():
    """io/sources.py ShmRingSource: a producer that dies without pushing
    the EOF sentinel must end the stream via the idle timeout — served
    frames intact, no hang (the previously-untested containment branch)."""
    import os

    pytest.importorskip("numpy")
    try:
        from dvf_tpu.transport.ring import FrameRing
    except Exception as e:  # noqa: BLE001 — native shim unavailable
        pytest.skip(f"native ring shim unavailable: {e}")
    from dvf_tpu.io.sources import ShmRingSource

    name = f"dvf_test_pdeath_{os.getpid()}"
    frame = (np.arange(16 * 16 * 3, dtype=np.uint32) % 251).astype(np.uint8)
    frame = frame.reshape(16, 16, 3)
    ring = FrameRing(capacity_bytes=1 << 20, shm_name=name, create=True,
                     max_frame_bytes=16 * 16 * 3 + 64)
    try:
        ring.push(frame.tobytes(), 0, time.time())
        # No EOF sentinel is ever pushed — the producer "died" here.
        src = ShmRingSource(name, (16, 16, 3), attach_timeout_s=5.0,
                            idle_timeout_s=0.3)
        got = []
        t0 = time.time()
        for f, _ts in src:
            if f is None:
                break
            got.append(np.array(f))
        wall = time.time() - t0
        assert len(got) == 1
        np.testing.assert_array_equal(got[0], frame)
        assert wall < 5.0, "producer-death detection hung"
    finally:
        ring.close()


# ---------------------------------------------------- pipeline resilience


def test_pipeline_resilient_survives_engine_errors(rng):
    """resilient=True: a failing device submission drops that batch and the
    stream continues (distributor.py:249-251 semantics); fail-fast mode
    (default) re-raises — both from the same pipeline."""
    from dvf_tpu.io.sinks import NullSink
    from dvf_tpu.io.sources import SyntheticSource
    from dvf_tpu.ops import get_filter
    from dvf_tpu.runtime.pipeline import Pipeline, PipelineConfig

    def build(resilient):
        pipe = Pipeline(
            SyntheticSource(height=16, width=16, n_frames=32, rate=0.0),
            get_filter("invert"),
            NullSink(),
            # queue_size ≥ n_frames: no drop-oldest at ingest while the
            # first batch compiles, so the delivered count is deterministic.
            PipelineConfig(batch_size=4, frame_delay=0, queue_size=64,
                           resilient=resilient),
        )
        real_submit = pipe.engine.submit
        calls = {"n": 0}

        def flaky_submit(batch):
            calls["n"] += 1
            if calls["n"] == 2:
                raise RuntimeError("injected device error")
            return real_submit(batch)

        pipe.engine.submit = flaky_submit
        return pipe

    pipe = build(resilient=True)
    stats = pipe.run()
    assert stats["errors"] == 1
    # One batch of 4 dropped; everything else delivered.
    assert stats["delivered"] == 32 - 4

    with pytest.raises(RuntimeError, match="injected"):
        build(resilient=False).run()


def test_pipeline_resilient_survives_bad_source_frames():
    """A source that raises on some reads keeps streaming the good ones."""
    from dvf_tpu.io.sinks import NullSink
    from dvf_tpu.ops import get_filter
    from dvf_tpu.runtime.pipeline import Pipeline, PipelineConfig

    class FlakySource:
        """Raises on reads 3, 8, 13, 18 but recovers — like a camera that
        drops a read. (Not a generator: a generator would die on first
        raise; the containment contract is about sources that can keep
        going.)"""

        def __init__(self):
            self.i = 0

        def __iter__(self):
            return self

        def __next__(self):
            i = self.i
            if i >= 20:
                raise StopIteration
            self.i += 1
            if i % 5 == 3:
                raise OSError(f"camera glitch at {i}")
            return np.full((16, 16, 3), i, np.uint8), time.time()

    pipe = Pipeline(
        FlakySource(),
        get_filter("invert"),
        NullSink(),
        PipelineConfig(batch_size=4, frame_delay=0, queue_size=64, resilient=True),
    )
    stats = pipe.run()
    assert stats["errors"] == 4  # i = 3, 8, 13, 18
    assert stats["delivered"] == 16
