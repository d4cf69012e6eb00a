"""THE compatibility proof: the reference's own Distributor (imported
from /root/reference at test time — never copied) drives our TPU worker
over its real sockets, and the processed frames come back through its real
reorder buffer.

This is the north-star integration ("webcam_app.py is untouched and picks
CPU-worker vs TPU-worker via a --backend flag", BASELINE.json): everything
the app side does — ROUTER fan-out, latest-wins slot, PULL collection,
display-cursor reorder — is the reference's unmodified code; only the
worker process is ours.
"""

import os
import threading
import time

import numpy as np
import pytest

pytest.importorskip("zmq")

REF = "/root/reference/distributor.py"


def load_reference_module(filename: str, ref_dir: str = os.path.dirname(REF)):
    """Import one of the reference's modules from its read-only checkout
    (never copied). Returns the loaded module."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "ref_" + filename.removesuffix(".py"),
        os.path.join(ref_dir, filename))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_reference_distributor():
    return load_reference_module("distributor.py").Distributor


def _free_port():
    from _util import free_port

    return free_port()


@pytest.mark.skipif(not os.path.exists(REF), reason="reference not present")
@pytest.mark.parametrize("transport", ["list", "ring"])
def test_reference_distributor_drives_tpu_worker(rng, transport):
    from dvf_tpu.ops import get_filter
    from dvf_tpu.transport.zmq_ingress import TpuZmqWorker

    Distributor = _load_reference_distributor()
    p_dist, p_coll = _free_port(), _free_port()
    dist = Distributor(distribute_port=p_dist, collect_port=p_coll, frame_delay=0)
    dist.start()

    worker = TpuZmqWorker(
        get_filter("invert"),
        host="127.0.0.1",
        distribute_port=p_dist,
        collect_port=p_coll,
        batch_size=4,
        # Wide assembly window: frames arrive ~15 ms apart (feed loop
        # below), so a 60 ms window deterministically accumulates 2-4
        # frames per batch — the batching proof can't depend on compile
        # stalls happening to back frames up.
        assemble_timeout_s=0.06,
        use_jpeg=False,
        raw_size=16,
        transport=transport,  # "ring" stages recv'd payloads in the C++ ring
    )
    wt = threading.Thread(target=worker.run, daemon=True)
    wt.start()

    n = 30
    frames = {}
    got = {}

    display_hits = set()

    def poll_display():
        # The reference's draw-loop pair (webcam_app.py:135-137): advance
        # the cursor, fetch whatever frame it points at.
        dist.update_display_frame()
        shown = dist.get_frame_to_display()
        idx = dist.current_display_frame
        if shown is not None and idx is not None:
            display_hits.add(idx)
            if idx not in got:
                got[idx] = np.frombuffer(shown, np.uint8).reshape(16, 16, 3)
        # Batched completion makes the display cursor leapfrog intermediate
        # results (it tracks latest_received), so also sweep the reorder
        # buffer itself — n=30 < the 50-entry cap (distributor.py:23), so
        # every collected frame is still in it.
        for idx, entry in list(dist.received_frames.items()):
            if idx not in got:
                got[idx] = np.frombuffer(entry["frame_data"], np.uint8).reshape(16, 16, 3)

    try:
        # Feed like a ~60fps camera and poll the display path *while*
        # feeding, like the real app's 60Hz on_draw — the cursor tracks
        # latest_received, so polling only afterwards would see just the
        # final frames.
        for i in range(n):
            f = rng.integers(0, 255, (16, 16, 3), np.uint8)
            frames[i] = f
            dist.add_frame_for_distribution(f.tobytes(), time.time())
            end = time.perf_counter() + 0.015
            while time.perf_counter() < end:
                poll_display()
                time.sleep(0.002)
        deadline = time.time() + 10
        while time.time() < deadline and dist.latest_received_frame < n - 1:
            poll_display()
            time.sleep(0.002)
        poll_display()
    finally:
        worker.stop()
        wt.join(timeout=5)
        worker.close()
        dist.cleanup()

    # The latest-wins slot may legitimately skip frames under load; require
    # real throughput (most frames served) and exact numerics on every one.
    assert len(got) >= n // 2, f"only {len(got)}/{n} frames came back"
    assert display_hits, "display path never surfaced a frame"
    for idx, out in got.items():
        np.testing.assert_array_equal(out, 255 - frames[idx])
    # The worker really batched (not one frame per roundtrip like the
    # reference's own workers).
    assert worker.batches < worker.frames_processed


@pytest.mark.skipif(not os.path.exists(REF), reason="reference not present")
def test_reference_distributor_drives_tpu_worker_jpeg(rng):
    """The reference app's DEFAULT wire (use_jpeg=True, webcam_app.py:203
    footgun: JPEG effectively always on) against our JPEG-mode worker:
    the reference's own Distributor fans out JPEG frames, the worker
    decodes through the native C shim, inverts on device, re-encodes,
    and the display path serves bytes that decode to the inverse."""
    from dvf_tpu.ops import get_filter
    from dvf_tpu.transport.codec import NativeJpegCodec
    from dvf_tpu.transport.zmq_ingress import TpuZmqWorker

    try:
        codec = NativeJpegCodec(quality=95)
    except RuntimeError as e:
        pytest.skip(f"native jpeg shim unavailable: {e}")

    Distributor = _load_reference_distributor()
    p_dist, p_coll = _free_port(), _free_port()
    dist = Distributor(distribute_port=p_dist, collect_port=p_coll, frame_delay=0)
    dist.start()

    worker = TpuZmqWorker(
        get_filter("invert"),
        host="127.0.0.1",
        distribute_port=p_dist,
        collect_port=p_coll,
        batch_size=4,
        assemble_timeout_s=0.06,
        use_jpeg=True,
    )
    wt = threading.Thread(target=worker.run, daemon=True)
    wt.start()

    n = 24
    # Smooth frames: JPEG loss stays small enough to assert the inverse.
    y, x = np.mgrid[0:32, 0:32]
    frames = {}
    got = {}
    try:
        for i in range(n):
            f = np.stack([(x * 3 + i) % 256, (y * 3) % 256, (x + y) % 256],
                         -1).astype(np.uint8)
            frames[i] = f
            dist.add_frame_for_distribution(codec.encode(f), time.time())
            time.sleep(0.015)
        deadline = time.time() + 15
        while time.time() < deadline and dist.latest_received_frame < n - 1:
            time.sleep(0.01)
        for idx, entry in list(dist.received_frames.items()):
            got[idx] = codec.decode(entry["frame_data"])
    finally:
        worker.stop()
        wt.join(timeout=5)
        worker.close()
        dist.cleanup()

    assert len(got) >= n // 2, f"only {len(got)}/{n} frames came back"
    for idx, out in got.items():
        err = np.abs(out.astype(int) - (255 - frames[idx]).astype(int)).mean()
        assert err < 8, (idx, err)  # two JPEG round-trips of loss


# The reference's own InverterWorker, unmodified, as a process of its own
# (its topology). It imports ``turbojpeg`` (PyTurboJPEG), which this image
# does not have: an API-compatible stand-in over the in-repo libjpeg-turbo
# codec goes into ``sys.modules`` before the reference is loaded.
_REFERENCE_WORKER = """
import os, sys, types
tests_dir, ref_dir, p_dist, p_coll = sys.argv[1:3] + [int(a) for a in sys.argv[3:5]]
sys.path.insert(0, tests_dir)
from dvf_tpu.transport.codec import make_codec
from test_reference_compat import load_reference_module

codec = make_codec()

class TurboJPEG:
    def __init__(self, lib_path=None):
        pass
    def encode(self, frame, quality=90):
        return codec.encode(frame)
    def decode(self, data):
        return codec.decode(data)

shim = types.ModuleType("turbojpeg")
shim.TurboJPEG = TurboJPEG
sys.modules["turbojpeg"] = shim
sys.path.insert(0, ref_dir)           # inverter.py: from worker import Worker
sys.stdout = open(os.devnull, "w")    # its print a frame
ref = load_reference_module("inverter.py", ref_dir)
ref.InverterWorker("localhost", p_dist, p_coll).start()
"""


@pytest.mark.skipif(not os.path.exists(REF), reason="reference not present")
def test_reference_worker_and_tpu_worker_share_one_distributor():
    """The parity baseline's mechanics without its clock: the reference's
    Distributor fans JPEG frames out to its own InverterWorker (a process)
    and to our worker at once. Every index that comes back is one that was
    sent, comes back once (the reorder buffer is keyed by index and the
    cursor never steps back), and decodes to the inverse of its frame,
    whichever worker served it."""
    import subprocess
    import sys

    from dvf_tpu.ops import get_filter
    from dvf_tpu.transport.codec import NativeJpegCodec
    from dvf_tpu.transport.zmq_ingress import TpuZmqWorker

    try:
        codec = NativeJpegCodec(quality=95)
    except RuntimeError as e:
        pytest.skip(f"native jpeg shim unavailable: {e}")

    Distributor = _load_reference_distributor()
    p_dist, p_coll = _free_port(), _free_port()
    dist = Distributor(distribute_port=p_dist, collect_port=p_coll, frame_delay=0)
    dist.start()
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    ref_worker = subprocess.Popen(
        [sys.executable, "-c", _REFERENCE_WORKER, tests_dir,
         os.path.dirname(REF), str(p_dist), str(p_coll)],
        cwd=os.path.dirname(tests_dir), stdout=subprocess.DEVNULL,
        stderr=subprocess.PIPE)
    worker = TpuZmqWorker(
        get_filter("invert"), host="127.0.0.1", distribute_port=p_dist,
        collect_port=p_coll, batch_size=4, assemble_timeout_s=0.06,
        use_jpeg=True)
    wt = threading.Thread(target=worker.run, daemon=True)
    wt.start()

    n = 40
    y, x = np.mgrid[0:32, 0:32]
    frames, got, cursor = {}, {}, []
    try:
        for i in range(n):
            f = np.stack([(x * 3 + i) % 256, (y * 3) % 256, (x + y) % 256],
                         -1).astype(np.uint8)
            frames[i] = f
            dist.add_frame_for_distribution(codec.encode(f), time.time())
            dist.update_display_frame()
            if dist.current_display_frame is not None:
                cursor.append(dist.current_display_frame)
            time.sleep(0.015)
        deadline = time.time() + 15
        while time.time() < deadline and dist.latest_received_frame < n - 1:
            time.sleep(0.01)
        assert ref_worker.poll() is None, ref_worker.stderr.read()[-800:]
        for idx, entry in list(dist.received_frames.items()):
            got[idx] = codec.decode(entry["frame_data"])
    finally:
        worker.stop()
        wt.join(timeout=5)
        worker.close()
        ref_worker.terminate()
        try:
            ref_worker.wait(timeout=5)
        except subprocess.TimeoutExpired:
            ref_worker.kill()
        dist.cleanup()

    assert len(got) >= n // 2, f"only {len(got)}/{n} frames came back"
    assert set(got) <= set(frames)
    assert cursor == sorted(cursor)
    for idx, out in got.items():
        err = np.abs(out.astype(int) - (255 - frames[idx]).astype(int)).mean()
        assert err < 8, (idx, err)  # two JPEG round-trips of loss
