"""chip_smoke.py, the compile-cache rule, and the no-chip refusals.

The smoke itself runs on the chip (through the chip tool); here its
``--cpu-tiny`` dry run keeps the control flow honest, and the rules PR 21
set are pinned: no TPU means a non-zero exit naming what was found, and
one resolver decides where compiled programs persist.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run_smoke(*flags, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = ""   # one CPU device: the smoke sizes its own meshes
    return subprocess.run([sys.executable, SMOKE, *flags], env=env,
                          stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                          text=True, timeout=timeout)


def test_cpu_tiny_smoke_passes():
    """Every leg of the smoke, at toy sizes with Pallas interpreted: the
    last stdout line is the contract's JSON, labelled with the CPU."""
    p = _run_smoke("--cpu-tiny")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-1500:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": 1}}
    # every line it logs names the platform it ran on
    logged = [ln for ln in p.stdout.splitlines() if ln.startswith("[smoke")]
    assert logged and all(ln.startswith("[smoke cpu]") for ln in logged)
    for name in ("serve:invert_1080p", "serve:flow_720p", "serve:sr2x_540p",
                 "mixed", "cli", "kernel:dct8x8_quant", "kernel:flow_warp"):
        assert any(f"ok {name}" in ln for ln in logged), name


def test_smoke_without_a_chip_fails_and_names_what_it_found():
    """No TPU and no --cpu-tiny: non-zero, no result line, the platform
    it found named on stderr. A failed init never reaches the CPU path."""
    p = _run_smoke()
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "'cpu'" in p.stderr and "not a tpu" in p.stderr


def test_compile_cache_resolver(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins when set; unset, the directory is
    <checkout>/.jax_compile_cache whatever the working directory."""
    from dvf_tpu.runtime.engine import resolve_compile_cache_dir

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x/cache")
    assert resolve_compile_cache_dir() == "/x/cache"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    seen = set()
    for cwd in (tmp_path, REPO):
        monkeypatch.chdir(cwd)
        seen.add(resolve_compile_cache_dir())
    assert seen == {os.path.join(REPO, ".jax_compile_cache")}


def test_compile_cache_flag_only_names_the_dir_when_env_is_unset(
        monkeypatch, tmp_path):
    """--compile-cache-dir DIR sets the variable when it is unset and
    loses to it when it is set; either way one resolver is consulted."""
    import types

    import jax

    from dvf_tpu import cli

    before = jax.config.jax_compilation_cache_dir
    env_dir, flag_dir = str(tmp_path / "env"), str(tmp_path / "flag")
    args = types.SimpleNamespace(compile_cache_dir=flag_dir)
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert cli._arm_compile_cache(args) == env_dir
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert cli._arm_compile_cache(args) == flag_dir
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == flag_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def test_process_fleet_refuses_a_tpu_host(monkeypatch):
    """fleet --mode process never puts a replica on the CPU unasked: with
    the platform unset and a TPU as the default backend it refuses and
    points at --mode local; an explicit JAX_PLATFORMS=cpu is allowed."""
    import jax

    from dvf_tpu.fleet.replica import refuse_process_replicas_on_tpu
    from dvf_tpu.serve import ServeError

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(ServeError, match="--mode local"):
        refuse_process_replicas_on_tpu({})
    refuse_process_replicas_on_tpu({"JAX_PLATFORMS": "cpu"})
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    refuse_process_replicas_on_tpu({})


def test_replicated_batch_is_counted_and_said(capsys):
    """A batch that does not divide the data axis stays correct but every
    device computes all of it: stderr says so and stats count it."""
    import numpy as np

    from dvf_tpu.ops import get_filter
    from dvf_tpu.parallel.mesh import MeshConfig, make_mesh
    from dvf_tpu.runtime.engine import Engine

    eng = Engine(get_filter("invert"), mesh=make_mesh(MeshConfig(data=4)))
    x = np.zeros((2, 8, 8, 3), np.uint8)
    assert np.array_equal(np.asarray(eng.submit(x)), 255 - x)
    assert eng.stats.replicated_batches == 1
    assert "does not divide the data axis (4)" in capsys.readouterr().err
    eng.submit(np.zeros((4, 8, 8, 3), np.uint8))
    assert eng.stats.replicated_batches == 1   # 4 divides: sharded


def test_mosaic_filters_are_partitioned_by_hand_on_a_multi_device_mesh(capsys):
    """GSPMD cannot partition a Mosaic call, so on a >1-device mesh a
    filter that contains a pallas_call runs under an explicit shard_map
    (on the CPU the same routing is taken with the kernel interpreted):
    stateless ones keep their batch sharding, a stateful one has every
    device compute the whole batch, said and counted."""
    import jax
    import numpy as np

    from dvf_tpu.ops import get_filter
    from dvf_tpu.parallel.mesh import MeshConfig, make_mesh
    from dvf_tpu.runtime.engine import Engine

    mesh4 = make_mesh(MeshConfig(data=4))
    one = make_mesh(MeshConfig(), devices=jax.devices()[:1])
    x = np.random.default_rng(0).integers(0, 256, (4, 32, 48, 3),
                                          dtype=np.uint8)
    for name, kw, spec, replicated in (
            ("sobel_bilateral", {"impl": "pallas"}, "data", 0),
            ("flow_warp", {"warp_impl": "pallas", "levels": 1,
                           "win_size": 7, "n_iters": 1}, None, 2),
            ("invert", {}, "data", 0)):
        sharded = Engine(get_filter(name, **kw), mesh=mesh4)
        single = Engine(get_filter(name, **kw), mesh=one)
        for shift in (0, 1):
            frames = np.roll(x, shift, axis=2)
            got = np.asarray(sharded.submit(frames))
            want = np.asarray(single.submit(frames))
            assert np.abs(got.astype(int) - want.astype(int)).max() <= 1
        manual = sharded._exec_filter.name.startswith("manual(")
        assert manual == (name != "invert"), sharded._exec_filter.name
        assert single._exec_filter.name == get_filter(name, **kw).name
        in_spec = sharded.input_sharding.spec
        assert (in_spec[0] if len(in_spec) else None) == spec
        assert sharded.stats.replicated_batches == replicated
    assert "holds state and a Mosaic kernel" in capsys.readouterr().err
