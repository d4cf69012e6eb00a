"""Layer pipeline parallelism (parallel/pp.py + style_transfer parallel='pp').

SURVEY §2c's layer-PP row: a GPipe schedule over a homogeneous layer
stack, each device owning a contiguous stage, activations hopping via
ppermute. Goldens: plain sequential application of the same stack.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from dvf_tpu.parallel.mesh import MeshConfig, make_mesh
from dvf_tpu.parallel.pp import (
    pipeline_apply,
    pipeline_stage_specs,
    stack_layer_params,
)


def _layers(rng, n, f):
    return [
        {"w": jnp.asarray(rng.normal(size=(f, f), scale=0.3).astype(np.float32)),
         "b": jnp.asarray(rng.normal(size=(f,)).astype(np.float32))}
        for _ in range(n)
    ]


def _layer_fn(p, h):
    return jnp.tanh(h @ p["w"] + p["b"])


def _sequential(layers, x):
    for p in layers:
        x = _layer_fn(p, x)
    return x


def _run_pp(layers, x, mesh, n_microbatches=0):
    stacked = stack_layer_params(layers)
    inner = lambda sp, xx: pipeline_apply(  # noqa: E731
        _layer_fn, sp, xx, axis="model", n_microbatches=n_microbatches)
    return jax.jit(jax.shard_map(
        inner, mesh=mesh,
        in_specs=(pipeline_stage_specs("model", stacked), P("data")),
        out_specs=P("data"), check_vma=False,
    ))(stacked, x)


@pytest.mark.parametrize("n_micro", [0, 2, 4])  # per-DATA-shard batch is 4
def test_pipeline_matches_sequential(rng, n_micro):
    layers = _layers(rng, 8, 16)
    x = jnp.asarray(rng.normal(size=(8, 16)).astype(np.float32))
    mesh = make_mesh(MeshConfig(data=2, model=4))
    got = _run_pp(layers, x, mesh, n_microbatches=n_micro)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_sequential(layers, x)), atol=1e-5)


def test_pipeline_batch_smaller_than_stages(rng):
    """B=2 over 4 stages: microbatches auto-clamp to B."""
    layers = _layers(rng, 4, 8)
    x = jnp.asarray(rng.normal(size=(2, 8)).astype(np.float32))
    mesh = make_mesh(MeshConfig(data=1, model=4))
    got = _run_pp(layers, x, mesh)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_sequential(layers, x)), atol=1e-5)


def test_pipeline_bad_microbatch_raises(rng):
    layers = _layers(rng, 4, 8)
    x = jnp.asarray(rng.normal(size=(8, 8)).astype(np.float32))
    mesh = make_mesh(MeshConfig(data=1, model=4))
    with pytest.raises(ValueError, match="divide"):
        _run_pp(layers, x, mesh, n_microbatches=3)


def test_style_pp_engine_matches_single_device(rng):
    from dvf_tpu.ops import get_filter
    from dvf_tpu.runtime.engine import Engine

    batch = rng.integers(0, 255, (4, 32, 32, 3), np.uint8)
    want = np.asarray(Engine(
        get_filter("style_transfer", base_channels=8, n_residual=4, parallel="pp"),
        mesh=make_mesh(MeshConfig(data=1)),
    ).submit(batch))
    got = np.asarray(Engine(
        get_filter("style_transfer", base_channels=8, n_residual=4, parallel="pp"),
        mesh=make_mesh(MeshConfig(data=2, model=4)),
    ).submit(batch))
    assert np.abs(got.astype(int) - want.astype(int)).max() <= 1


def test_style_pp_matches_tp(rng):
    """Same seed → PP and TP are two schedules of the same math."""
    from dvf_tpu.ops import get_filter
    from dvf_tpu.runtime.engine import Engine

    mesh = make_mesh(MeshConfig(data=2, model=4))
    batch = rng.integers(0, 255, (4, 32, 32, 3), np.uint8)
    pp = np.asarray(Engine(
        get_filter("style_transfer", base_channels=8, n_residual=4, parallel="pp"),
        mesh=mesh).submit(batch))
    tp = np.asarray(Engine(
        get_filter("style_transfer", base_channels=8, n_residual=4, parallel="tp"),
        mesh=mesh).submit(batch))
    # bf16 compute with different reduction orders (psum vs sequential
    # scan): a few uint8 steps of drift is expected, equality is not.
    assert np.abs(pp.astype(int) - tp.astype(int)).max() <= 4


def test_style_pp_indivisible_falls_back(rng, capsys):
    """model axis 4, n_residual 3: warns and runs unspecialized, still
    numerically correct vs single device."""
    from dvf_tpu.ops import get_filter
    from dvf_tpu.runtime.engine import Engine

    batch = rng.integers(0, 255, (4, 32, 32, 3), np.uint8)
    want = np.asarray(Engine(
        get_filter("style_transfer", base_channels=8, n_residual=3, parallel="pp"),
        mesh=make_mesh(MeshConfig(data=1)),
    ).submit(batch))
    got = np.asarray(Engine(
        get_filter("style_transfer", base_channels=8, n_residual=3, parallel="pp"),
        mesh=make_mesh(MeshConfig(data=2, model=4)),
    ).submit(batch))
    assert np.abs(got.astype(int) - want.astype(int)).max() <= 1


def test_style_pp_rejects_bad_parallel():
    from dvf_tpu.ops import get_filter

    with pytest.raises(ValueError, match="parallel"):
        get_filter("style_transfer", parallel="zz")
