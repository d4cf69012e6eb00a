"""Test env: run everything on CPU with 8 virtual devices.

Mesh/sharding logic is testable without a TPU by forcing the host platform
to expose 8 devices (SURVEY.md §4). Must run before jax initializes, hence
module level in conftest.
"""

import os

# The suite runs on the CPU with 8 virtual devices: it exercises mesh
# logic without hardware (the chip is reached through chip_smoke.py and
# the benchmark only). Set before jax initializes a backend; child processes
# (fleet replicas, CLI subprocesses) inherit the same platform. Override
# with DVF_TEST_PLATFORM to run on an accelerator.
_platform = os.environ.get("DVF_TEST_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = _platform
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", _platform)
if _platform == "cpu":
    jax.config.update("jax_num_cpu_devices", 8)

import faulthandler  # noqa: E402
import signal  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402


# Every test's time limit (setup, call and teardown together), unless it
# carries ``@pytest.mark.time_limit(seconds)``: five times the slowest
# test on an idle machine, and small enough that a hang or two still let a
# loaded run of the suite end inside the driver's 1470 s. A test that
# waits for ever costs itself and this long, not the rest of its file and
# the run's clock.
TEST_LIMIT_S = 300

_limit = {"stderr": None, "deadline": 0.0, "seconds": 0.0}


def _time_limit_reached(signum, frame):
    """SIGALRM in the main thread: fail the test that is running, with
    every thread's stack in the failure (who waited, and on whom)."""
    with tempfile.TemporaryFile("w+") as f:
        faulthandler.dump_traceback(file=f, all_threads=True)
        f.seek(0)
        stacks = f.read()
    pytest.fail(f"time limit of {_limit['seconds']:g} s reached "
                f"(tests/conftest.py TEST_LIMIT_S, or the test's "
                f"time_limit marker); every thread's stack:\n{stacks}",
                pytrace=False)


@pytest.hookimpl(wrapper=True, tryfirst=True)
def pytest_runtest_protocol(item):
    """One deadline for the test's whole protocol, and behind it the
    backstop for a main thread no signal reaches (stuck in XLA or other
    native code): a fifth of the limit later, and never less than 5 s
    later, the process writes its stacks to stderr and exits, and xdist
    names the test its worker went down in and starts another worker."""
    marker = item.get_closest_marker("time_limit")
    seconds = float(marker.args[0]) if marker else TEST_LIMIT_S
    _limit.update(seconds=seconds, deadline=time.monotonic() + seconds)
    faulthandler.dump_traceback_later(
        seconds + max(seconds / 5, 5.0), file=_limit["stderr"], exit=True)
    previous = signal.signal(signal.SIGALRM, _time_limit_reached)
    try:
        return (yield)
    finally:
        signal.signal(signal.SIGALRM, previous)
        faulthandler.cancel_dump_traceback_later()


def _alarm_while_phase_runs():
    """The alarm is armed only while setup, call or teardown runs: what it
    raises there is the phase's own failure (pytest's CallInfo catches
    it), never an error inside pytest's or xdist's reporting between
    phases. A phase that starts past the deadline (teardown after the
    limit was reached) runs under the backstop alone."""
    left = _limit["deadline"] - time.monotonic()
    if left > 0:
        signal.setitimer(signal.ITIMER_REAL, left)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)


pytest_runtest_setup = pytest.hookimpl(wrapper=True, tryfirst=True)(
    _alarm_while_phase_runs)
pytest_runtest_call = pytest.hookimpl(wrapper=True, tryfirst=True)(
    _alarm_while_phase_runs)
pytest_runtest_teardown = pytest.hookimpl(wrapper=True, tryfirst=True)(
    _alarm_while_phase_runs)


def _codec_threads():
    return {t for t in threading.enumerate()
            if t.name.startswith("dvf-jpeg") and t.is_alive()}


@pytest.fixture(scope="session", autouse=True)
def _codec_pools_joined_on_close():
    """Codec pools must be joined on close (codec.close → pool.shutdown
    wait=True): a leaked dvf-jpeg worker thread at session end means some
    codec was never closed, or close() stopped joining — a long-lived
    server churning codecs would accumulate threads forever. The
    ``dvf-jpeg`` prefix match covers every pool family: the per-codec
    encode/decode pools (``dvf-jpeg``), DeltaCodec's ordered encode
    worker (``dvf-jpeg-delta``), and the host-wide refcounted entropy
    pool of the full-transform assist (``dvf-jpeg-entropy``,
    transport.codec.EntropyPool — released when the last DeltaCodec that
    acquired it closes). Session scope (not per-test): module-scoped
    codec fixtures legitimately keep a pool open across tests, but every
    pool must be gone once all fixtures have finalized. A short grace
    window absorbs shutdown latency; test_egress_stream pins the
    prompt-join property directly."""
    yield
    leaked = _codec_threads()
    deadline = time.time() + 5.0
    while leaked and time.time() < deadline:
        time.sleep(0.05)
        leaked = {t for t in leaked if t.is_alive()}
    assert not leaked, (
        f"codec pool threads leaked (close() not called, or no longer "
        f"joining?): {sorted(t.name for t in leaked)}")


def pytest_configure(config):
    # Output capture is suspended while plugins are configured, so this is
    # the run's real stderr (a test's own fd 2 is the capture's file).
    _limit["stderr"] = os.dup(2)
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from tier-1 "
                   "(-m 'not slow')")
    config.addinivalue_line(
        "markers", "time_limit(seconds): this test's time limit for "
                   "setup, call and teardown together, in place of "
                   "TEST_LIMIT_S (tests/conftest.py)")
    config.addinivalue_line(
        "markers", "chaos: deterministic fault-injection tests (seeded "
                   "FaultPlans, CPU backend, bounded wall time — run in "
                   "tier-1; select with -m chaos)")
    config.addinivalue_line(
        "markers", "delta: temporal-delta wire + on-device codec assist "
                   "tests (CPU backend, seeded streams, bounded wall time "
                   "— run in tier-1; select with -m delta)")
    config.addinivalue_line(
        "markers", "fleet: multi-replica serving tier tests (CPU backend, "
                   "bounded timeouts; some spawn replica worker "
                   "subprocesses — run in tier-1, select with -m fleet; "
                   "capacity-gated scaling assertions skip cleanly where "
                   "the host can't express real parallelism)")
    config.addinivalue_line(
        "markers", "multitenant: multi-signature serving tests (signature "
                   "buckets, compiled-program pool, AOT warm-start — CPU "
                   "backend, bounded wall time; run in tier-1, select "
                   "with -m multitenant)")
    config.addinivalue_line(
        "markers", "control: load-adaptive control plane tests (seeded, "
                   "CPU backend, deterministic controller replay, quality "
                   "downshift/recovery, priority tiers — run in tier-1; "
                   "select with -m control)")
    config.addinivalue_line(
        "markers", "lineage: frame-lineage tracing & latency attribution "
                   "tests (additive decomposition, exemplar capture, "
                   "stage-cost profiles, trace-view — CPU backend, "
                   "bounded wall time; run in tier-1, select with "
                   "-m lineage)")
    config.addinivalue_line(
        "markers", "ledger: compile/reconfiguration ledger and memory "
                   "accounting tests (bounded event ring, measured "
                   "bucket stalls, dvf_mem_* gauges — CPU backend, "
                   "bounded wall time; run in tier-1, select with "
                   "-m ledger)")
    config.addinivalue_line(
        "markers", "elastic: controller-driven fleet autoscaling tests "
                   "(deterministic scale-decision replay, warm standby "
                   "pool, spawn/retire actuators, SIGKILL-during-scale-in "
                   "chaos — CPU backend, bounded wall time; run in "
                   "tier-1, select with -m elastic)")
    config.addinivalue_line(
        "markers", "audit: audit-plane tests (obs.audit — wire-integrity "
                   "digests across raw/jpeg/delta, sampled shadow replay "
                   "vs the golden un-jitted path, program-swap "
                   "equivalence guard, cross-replica divergence, "
                   "corrupt_wire/corrupt_device chaos acceptance — CPU "
                   "backend, bounded wall time; run in tier-1, select "
                   "with -m audit)")
    config.addinivalue_line(
        "markers", "broadcast: broadcast-plane tests (encode-once tiered "
                   "fan-out, per-subscriber isolation, late-join "
                   "keyframe rate limiting, relay-only egress replicas, "
                   "ZMQ gate — CPU backend, bounded wall time; run in "
                   "tier-1, select with -m broadcast)")
    config.addinivalue_line(
        "markers", "swap: live-reconfiguration tests (compile-aside "
                   "program double-buffering, atomic hot swap, "
                   "mid-stream filter morph, chaos-injected swap "
                   "aborts — CPU backend, bounded wall time; run in "
                   "tier-1, select with -m swap)")


@pytest.fixture(scope="session", autouse=True)
def _fleet_resources_released():
    """Fleet tests must not leak replica worker subprocesses or fleet
    service threads past the suite: a leaked worker pins a whole jax
    runtime (and its sockets) beyond session end. Checked at session
    scope with a grace window, like the codec-pool guard below; only
    consults the fleet registry when fleet code was actually imported."""
    yield
    import sys as _sys

    mod = _sys.modules.get("dvf_tpu.fleet.replica")
    deadline = time.time() + 10.0
    if mod is not None:
        leaked = mod.live_worker_processes()
        while leaked and time.time() < deadline:
            time.sleep(0.1)
            leaked = mod.live_worker_processes()
        assert not leaked, (
            f"fleet worker processes leaked (FleetFrontend.stop not "
            f"called?): pids {[p.pid for p in leaked]}")
    # Standby-pool workers are replicas that exist BEFORE any session
    # does (pre-forked, AOT-warm): one outliving FleetFrontend.stop()
    # is a leaked child the process guard above may miss in local mode
    # (a local standby is a live frontend + engine, not a subprocess).
    mod_el = _sys.modules.get("dvf_tpu.fleet.elastic")
    if mod_el is not None:
        standby = mod_el.live_standby_handles()
        while standby and time.time() < deadline:
            time.sleep(0.1)
            standby = mod_el.live_standby_handles()
        assert not standby, (
            f"warm standby replicas leaked (StandbyPool.stop not called "
            f"— FleetFrontend.stop sweeps its pool?): "
            f"{[h.id for h in standby]}")
    fleet_threads = {t for t in threading.enumerate()
                    if t.name.startswith("dvf-fleet") and t.is_alive()}
    while fleet_threads and time.time() < deadline:
        time.sleep(0.05)
        fleet_threads = {t for t in fleet_threads if t.is_alive()}
    assert not fleet_threads, (
        f"fleet threads leaked: {sorted(t.name for t in fleet_threads)}")


@pytest.fixture(scope="session", autouse=True)
def _broadcast_resources_released():
    """Broadcast tests must not leak fan-out workers, relay pumps, or
    gate sockets past the suite: a leaked ``dvf-bcast*`` thread means
    some Channel/RelayNode/gate was never closed (or a plane's stop()
    stopped sweeping them) — a long-lived publisher churning channels
    would accumulate one worker per channel forever. Fleet publish
    pumps (``dvf-fleet-bcast*``) ride the fleet guard's prefix; this
    one covers the serve tier and bare-plane tests. Registry checks
    are import-gated like the sibling guards."""
    yield
    import sys as _sys

    deadline = time.time() + 10.0
    mod_p = _sys.modules.get("dvf_tpu.broadcast.plane")
    if mod_p is not None:
        gates = mod_p.live_broadcast_sockets()
        while gates and time.time() < deadline:
            time.sleep(0.1)
            gates = mod_p.live_broadcast_sockets()
        assert not gates, (
            f"broadcast gate sockets leaked (ZmqBroadcastGate.close not "
            f"called?): {[g.endpoint for g in gates]}")
    mod_r = _sys.modules.get("dvf_tpu.broadcast.relay")
    if mod_r is not None:
        relays = mod_r.live_relay_nodes()
        while relays and time.time() < deadline:
            time.sleep(0.1)
            relays = mod_r.live_relay_nodes()
        assert not relays, (
            f"relay nodes leaked (RelayNode.close / plane retire_relay "
            f"not called?): {[r.id for r in relays]}")
    bcast_threads = {t for t in threading.enumerate()
                     if t.name.startswith("dvf-bcast") and t.is_alive()}
    while bcast_threads and time.time() < deadline:
        time.sleep(0.05)
        bcast_threads = {t for t in bcast_threads if t.is_alive()}
    assert not bcast_threads, (
        f"broadcast threads leaked (Channel/plane close not called?): "
        f"{sorted(t.name for t in bcast_threads)}")


@pytest.fixture(scope="session", autouse=True)
def _pool_engines_freed_on_close():
    """Every pool-managed compiled program must release its device
    buffers when its frontend closes (ServeFrontend.stop → pool.close /
    engine.free): a pool engine still live at session end means some
    stop path stopped freeing — a long-lived multi-tenant server
    churning signatures would leak one compiled program (plus device
    state) per signature forever. Only consults the registry when the
    engine module was actually imported; a short grace window absorbs
    teardown latency (the fleet guard's discipline)."""
    yield
    import sys as _sys

    mod = _sys.modules.get("dvf_tpu.runtime.engine")
    if mod is None:
        return
    deadline = time.time() + 5.0
    leaked = mod.live_pool_engines()
    while leaked and time.time() < deadline:
        time.sleep(0.05)
        leaked = mod.live_pool_engines()
    assert not leaked, (
        f"program-pool engines leaked (frontend stop() not called, or no "
        f"longer freeing?): "
        f"{[getattr(e, 'op_chain', '?') for e in leaked]}")


@pytest.fixture(scope="session", autouse=True)
def _memory_accounting_clean_at_session_end():
    """The obs.memory accounting must read ZERO once every owner has
    closed: no residual pool-engine device state, no occupied host
    staging/delivery slabs. Extends the pool-engine guard above with
    the PR-13 memory plane — a stop path that stops releasing slabs
    (or an engine whose free() stops dropping state) fails the build
    here instead of growing a long-lived server's RSS forever. Only
    consults registries for modules actually imported; gc first (test-
    local frontends may still be reachable from frame locals until
    collection), then a grace window like the sibling guards."""
    yield
    import gc
    import sys as _sys

    ing = _sys.modules.get("dvf_tpu.runtime.ingest")
    egr = _sys.modules.get("dvf_tpu.runtime.egress")
    eng = _sys.modules.get("dvf_tpu.runtime.engine")
    if ing is None and egr is None and eng is None:
        return
    gc.collect()

    def residual():
        out = {}
        if ing is not None:
            b = ing.occupied_slab_bytes()
            if b:
                out["ingest_slab_bytes"] = b
        if egr is not None:
            b = egr.occupied_slab_bytes()
            if b:
                out["egress_slab_bytes"] = b
        if eng is not None:
            b = sum(getattr(e, "state_bytes", 0) or 0
                    for e in eng.live_pool_engines())
            if b:
                out["pool_device_state_bytes"] = b
        return out

    deadline = time.time() + 5.0
    leaked = residual()
    while leaked and time.time() < deadline:
        time.sleep(0.1)
        gc.collect()
        leaked = residual()
    assert not leaked, (
        f"memory accounting reads nonzero at session end (a stop() path "
        f"stopped releasing slabs / freeing device state?): {leaked}")


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def frame_u8(rng):
    """A smooth-ish random 64x48 RGB uint8 frame."""
    base = rng.integers(0, 255, size=(48, 64, 3), dtype=np.uint8)
    try:
        import cv2

        return cv2.GaussianBlur(base, (5, 5), 1.5)
    except ImportError:
        return base


@pytest.fixture
def batch_f32(rng):
    """(4, 48, 64, 3) float batch in [0,1]."""
    return rng.random((4, 48, 64, 3), dtype=np.float32)


class DeviceGate:
    """A test's hand on what the serve dispatch thread reads of the
    device (``runtime.lane.InflightBatch``): while ``busy`` every
    in-flight batch reads not ready, whatever the CPU backend has done
    with it (a backlog that lasts as long as the test says); ``fail``
    makes the read raise (a poisoned handle); with ``collect`` cleared
    the collect thread's ``wait`` blocks, so nothing comes back and no
    permit is released. ``device_ms`` is what every bucket is told its
    last batch took of the device, in place of the CPU's few ms: None
    (no estimate, so no hold ends before the handle reads ready) unless
    the test says otherwise."""

    def __init__(self):
        self.busy = False
        self.fail = False
        self.device_ms = None
        self.collect = threading.Event()
        self.collect.set()

    @staticmethod
    def until(cond, what="condition", deadline_s=30.0):
        """Poll ``cond`` a tick at a time; fail loudly at the deadline."""
        deadline = time.time() + deadline_s
        while not cond():
            assert time.time() < deadline, f"timed out waiting for {what}"
            time.sleep(0.002)


@pytest.fixture
def device_gate(monkeypatch):
    from dvf_tpu.runtime import lane

    gate = DeviceGate()
    real_ready, real_wait = lane.InflightBatch.is_ready, lane.InflightBatch.wait

    def is_ready(self):
        if gate.fail:
            raise RuntimeError("device_gate: poisoned handle")
        return not gate.busy and real_ready(self)

    def wait(self):
        assert gate.collect.wait(timeout=60.0), "device_gate never released"
        real_wait(self)

    from dvf_tpu.serve import server

    monkeypatch.setattr(
        server._Bucket, "observe_device",
        lambda self, ms: setattr(self, "device_ms", gate.device_ms))
    monkeypatch.setattr(lane.InflightBatch, "is_ready", is_ready)
    monkeypatch.setattr(lane.InflightBatch, "wait", wait)
    yield gate
    gate.busy = gate.fail = False
    gate.collect.set()
