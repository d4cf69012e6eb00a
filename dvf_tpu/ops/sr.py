"""Super-resolution filter op — the second neural entry in the registry.

Wraps :mod:`dvf_tpu.models.espcn` as a registered stateful filter: like
``style_transfer``, the network params ARE the filter state (device-
resident across batches, never baked into the program as constants).

This is the one registered filter whose OUTPUT GEOMETRY differs from its
input ((H, W) → (H·r, W·r)): the runtime carries whatever the jitted step
returns, the reorder/sink path is geometry-agnostic, and the display sink
letterboxes — so SR slots into the same serve pipeline as every other op.

Reference counterpart: none — the reference's only op is invert
(inverter.py:41).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dvf_tpu.api.filter import Filter
from dvf_tpu.models.espcn import (
    EspcnConfig,
    apply_espcn,
    init_espcn,
    param_pspecs,
    tp_inner_apply,
)
from dvf_tpu.ops.registry import measured_default_for, register_filter


@register_filter("upscale")
def upscale(scale: int = 2, method: str = "nearest") -> Filter:
    """Stateless geometry-restoring upscale — the quality controller's
    return path (dvf_tpu.control): a session downshifted to 1/``scale``
    resolution under load appends this stage to its op chain, so the
    device program's OUTPUT is full client-visible resolution and the
    delivery path never knows the session was downshifted. Like
    ``super_resolution`` this changes output geometry ((H, W) →
    (H·scale, W·scale)); unlike it, it is stateless (no params), so the
    multi-tenant frontend can serve it, and cheap (one VPU
    repeat/resize, not a conv net — degradation must cost less than it
    saves).

    ``method``: ``nearest`` (exact pixel replication, dtype-preserving —
    works on the uint8 passthrough path) or ``linear``
    (``jax.image.resize`` bilinear, float path only).
    """
    s = int(scale)
    if s < 1:
        raise ValueError(f"scale must be >= 1, got {scale}")
    if method not in ("nearest", "linear"):
        raise ValueError(f"method must be 'nearest' or 'linear', "
                         f"got {method!r}")

    def fn(batch: jnp.ndarray) -> jnp.ndarray:
        if s == 1:
            return batch
        if method == "nearest":
            return jnp.repeat(jnp.repeat(batch, s, axis=1), s, axis=2)
        b, h, w, c = batch.shape
        return jax.image.resize(batch, (b, h * s, w * s, c),
                                method="linear")

    from dvf_tpu.api.filter import stateless

    # halo=None (unknown), not 0: the output pixel grid is a different
    # geometry, so the pointwise H-sharding contract does not apply —
    # a space-sharded mesh conservatively replicates H through this
    # stage instead of trusting GSPMD across the geometry change.
    return stateless(f"upscale(scale={s})", fn,
                     uint8_ok=(method == "nearest"), halo=None)


@register_filter("super_resolution")
def super_resolution(
    params: Optional[Any] = None,
    scale: int = 2,
    seed: int = 0,
    fast_convs: Optional[bool] = None,
    dtype: Optional[str] = None,
) -> Filter:
    """``params=None`` → seeded random init (benchmark weights); pass a
    trained param pytree for real upscaling. ``specialize`` swaps in the
    Megatron-TP shard_map body when the mesh has a model axis > 1 (same
    scheme as ``style_transfer``; see models.espcn.param_pspecs).

    ``fast_convs=None`` resolves the space-to-depth conv rewrite from the
    measured sr_fast_540p A/B winner (MEASURED_DEFAULTS; "ref" until one
    is committed); ``dtype`` pins the compute dtype as in style_transfer."""
    if fast_convs is None:
        fast_convs = measured_default_for("espcn_fast") == "fast"
    if dtype is None:
        dtype = "bfloat16"
    if dtype not in ("bfloat16", "float32"):
        raise ValueError(
            f"dtype must be 'bfloat16' or 'float32', got {dtype!r}")
    config = EspcnConfig(scale=scale, compute_dtype=jnp.dtype(dtype),
                         fast_convs=bool(fast_convs))

    def fn(batch: jnp.ndarray, state: Any) -> Tuple[jnp.ndarray, Any]:
        return apply_espcn(state, batch, config), state

    def init_state(batch_shape, dtype):
        if params is not None:
            return params
        return init_espcn(jax.random.PRNGKey(seed), config)

    name = f"super_resolution(x{scale})"

    def specialize(mesh, batch_shape) -> Optional[Filter]:
        axes = dict(zip(mesh.axis_names, mesh.devices.shape))
        if axes.get("model", 1) <= 1:
            return None  # generic body; params replicate over size-1 axis
        inner = tp_inner_apply(config)
        specs = param_pspecs(config)
        # Fold batch over (data, space) when divisible, degrading like
        # ops.style does — shard_map needs exact divisibility on dim 0.
        b = batch_shape[0]
        d, s = axes.get("data", 1), axes.get("space", 1)
        if b % (d * s) == 0:
            batch_spec = P(("data", "space"))
        elif b % d == 0:
            batch_spec = P("data")
        else:
            batch_spec = P(None)

        def sharded_fn(batch: jnp.ndarray, state: Any) -> Tuple[jnp.ndarray, Any]:
            sharded = jax.shard_map(
                inner,
                mesh=mesh,
                in_specs=(specs, batch_spec),
                out_specs=batch_spec,
                check_vma=False,
            )
            return sharded(state, batch), state

        return Filter(
            name=f"tp({name})",
            fn=sharded_fn,
            init_state=init_state,
        constant_state=True,  # the state is the weights
            compute_dtype=jnp.float32,
            state_pspecs=lambda: specs,
        )

    return Filter(
        name=name,
        fn=fn,
        init_state=init_state,
        constant_state=True,  # the state is the weights
        compute_dtype=jnp.float32,
        state_pspecs=lambda: param_pspecs(config),
        specialize=specialize,
    )
