"""Layer pipeline parallelism (GPipe schedule) over a homogeneous stack.

SURVEY.md §2c marks layer-PP as the optional deep-filter strategy; this
module implements it the TPU way (the scaling-book pipelining recipe): an
all-manual ``shard_map`` where each device along the mesh axis holds a
contiguous slice of a homogeneous layer stack, activations hop stage→stage
with a single ``ppermute`` per tick, and microbatches keep every stage busy
outside the (S-1)-tick fill/drain bubble. Control flow is a ``lax.scan``
over ticks — static shapes, no Python loops in the hot path, one compiled
program.

Schedule (S stages, M microbatches, T = M + S - 1 ticks):

    tick t:  stage 0 injects microbatch t (t < M, else don't-care zeros)
             every stage applies its L/S resident layers (inner lax.scan)
             activations ppermute to the next stage
             stage S-1's result for microbatch t-(S-1) lands in the output

The output is assembled with a masked ``psum`` (only stage S-1 contributes)
so every shard returns the full result — one extra all-reduce of the output,
the price of keeping the call signature mesh-transparent.

This is deliberately *parameter-partitioned* pipelining: each device ever
holds only its own L/S layers' weights — the memory win that motivates PP —
while the schedule overlaps stages' compute. Heterogeneous prologs/epilogs
(a net's stem/decoder) stay outside the pipelined stack (see
models.style_transfer's ``parallel="pp"`` wiring).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax



def stack_layer_params(params_list) -> Any:
    """Stack per-layer pytrees (same structure) along a new leading axis:
    L pytrees → one pytree whose leaves have leading dim L."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *params_list)


def pipeline_stage_specs(pspec_axis: str, params_stacked: Any):
    """PartitionSpec tree placing the stacked-layer leading dim on
    ``pspec_axis`` (each device holds its stage's contiguous layer slice)."""
    from jax.sharding import PartitionSpec as P

    return jax.tree.map(
        lambda x: P(pspec_axis, *([None] * (x.ndim - 1))), params_stacked
    )


def pipeline_apply(
    layer_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stage_params: Any,
    x: jnp.ndarray,
    axis: str = "model",
    n_microbatches: int = 0,
) -> jnp.ndarray:
    """Apply L stacked layers to ``x`` with a pipeline schedule.

    FOR USE INSIDE an all-manual ``shard_map`` region (like
    ``tp_inner_apply``): ``stage_params`` is this shard's slice of the
    stacked params — leaves of shape (L/S, ...) — and ``x`` is this
    shard's full activation batch (B, ...). Returns layer_fn composed L
    times over x, identical on every shard.

    ``n_microbatches``: 0/1 → auto: min(B, S) (enough to fill the
    pipeline); otherwise must divide B.
    """
    s = jax.lax.axis_size(axis)
    stage = lax.axis_index(axis)
    b = x.shape[0]
    if n_microbatches and n_microbatches > 1:
        m = n_microbatches
        if b % m != 0:
            raise ValueError(f"microbatches {m} must divide batch {b}")
    else:
        # Auto: the largest divisor of b not exceeding S — enough to fill
        # the pipeline when b allows, and always legal (b=6 over S=4 picks
        # m=3 rather than crashing on min(b, s)=4).
        m = next(d for d in range(min(b, s), 0, -1) if b % d == 0)
    if s == 1:
        # Degenerate single-stage mesh: plain sequential scan.
        out, _ = lax.scan(lambda c, p: (layer_fn(p, c), None), x, stage_params)
        return out

    mb = b // m
    x_stack = x.reshape(m, mb, *x.shape[1:])
    ticks = m + s - 1

    def run_stage(act):
        out, _ = lax.scan(lambda c, p: (layer_fn(p, c), None), act, stage_params)
        return out

    fwd = [(i, (i + 1) % s) for i in range(s)]  # stage i → i+1 ring

    def tick(carry, t):
        buf, out_stack = carry
        # Inject microbatch t at stage 0 (zeros-fed past the end: the
        # bubble; those results are masked out of the output below).
        inj = lax.dynamic_index_in_dim(
            x_stack, jnp.minimum(t, m - 1), axis=0, keepdims=False)
        act = jnp.where(stage == 0, inj, buf)
        act = run_stage(act)
        # Last stage's result for microbatch t-(s-1); write when valid.
        widx = t - (s - 1)
        valid = jnp.logical_and(stage == s - 1, widx >= 0)
        out_stack = lax.dynamic_update_index_in_dim(
            out_stack,
            jnp.where(valid, act, lax.dynamic_index_in_dim(
                out_stack, jnp.maximum(widx, 0), axis=0, keepdims=False)),
            jnp.maximum(widx, 0),
            axis=0,
        )
        # Hand activations to the next stage for the coming tick.
        buf = lax.ppermute(act, axis, fwd)
        return (buf, out_stack), None

    buf0 = jnp.zeros_like(x_stack[0])
    out0 = jnp.zeros_like(x_stack)
    (_, out_stack), _ = lax.scan(tick, (buf0, out0), jnp.arange(ticks))
    # Only stage S-1 holds real results; the masked psum replicates them.
    out_stack = jnp.where(stage == s - 1, out_stack, jnp.zeros_like(out_stack))
    out_stack = lax.psum(out_stack, axis)
    return out_stack.reshape(b, *x.shape[1:])
