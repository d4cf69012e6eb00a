"""Spatial parallelism with explicit halo exchange — the framework's
"ring attention" analog (SURVEY.md §2c, §5.7).

The reference never scales *within* a frame — its unit of parallelism is a
whole frame shipped to one worker (worker.py:50-57). For the 1080p stencil
configs (BASELINE.json configs[1-2]) one frame is sharded across devices on
the H axis instead, and each stencil op needs its neighbors' boundary rows:
the halo. That exchange is written EXPLICITLY here as a `shard_map` ring —
`lax.ppermute` shifts of the boundary rows over the mesh 'space' axis,
riding ICI — rather than relying on GSPMD's automatic spatial partitioner
(which miscompiles convs when spatial and feature dims are both sharded on
this toolchain; see train.style.make_train_step).

Overlap-and-discard scheme: each shard receives ``r`` rows from each
neighbor, runs the unmodified filter body on the extended slab, and
discards the outer ``r`` output rows. The filter's own internal
reflect-padding only ever touches rows that get discarded, so any
stencil filter of radius ≤ r composes with this wrapper unchanged. The
global top/bottom shards substitute reflect-101 rows (cv2's default
border, matching the unsharded ops) for the missing neighbor.

Chains: for a FilterChain, halos are exchanged **per stage** (one
``ppermute`` pair per member, inside a single shard_map). A single
summed-radius exchange around the fused chain is NOT exact at the global
top/bottom border: edge shards would compute stage2(stage1(reflect(x)))
where the unsharded chain computes stage2(reflect(stage1(x))) — these
differ whenever a stage's intermediate is not reflection-symmetric (e.g.
a directional gradient). Per-stage exchange reproduces the unsharded
border semantics exactly; pass ``per_stage=False`` to get the cheaper
fused exchange when you know every intermediate is symmetric.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from dvf_tpu.api.filter import Filter


def halo_exchange_rows(x: jnp.ndarray, r: int, axis_name: str = "space") -> jnp.ndarray:
    """Extend a (B, H_local, W, C) slab by r rows from each ring neighbor.

    Must run inside a shard_map manual over ``axis_name``. The first/last
    shards use reflect-101 of their own edge instead of the ring wrap, so
    the assembled result matches reflect-padded single-device semantics.
    """
    n = jax.lax.axis_size(axis_name)
    if x.shape[1] <= r:
        raise ValueError(
            f"local slab has {x.shape[1]} rows but the stencil radius is {r}; "
            f"use fewer 'space' shards (or taller frames) so each shard owns "
            f"more than r rows"
        )
    if n == 1:
        return jnp.pad(x, ((0, 0), (r, r), (0, 0), (0, 0)), mode="reflect")
    idx = lax.axis_index(axis_name)
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]
    # My bottom rows become my successor's top halo, and vice versa.
    top_halo = lax.ppermute(x[:, -r:], axis_name, fwd)
    bot_halo = lax.ppermute(x[:, :r], axis_name, bwd)
    # reflect-101: rows 1..r mirrored (edge row not repeated).
    top_reflect = x[:, 1 : r + 1][:, ::-1]
    bot_reflect = x[:, -r - 1 : -1][:, ::-1]
    top = jnp.where(idx == 0, top_reflect, top_halo)
    bot = jnp.where(idx == n - 1, bot_reflect, bot_halo)
    return jnp.concatenate([top, x, bot], axis=1)


def _stage_apply(x: jnp.ndarray, f: Filter) -> jnp.ndarray:
    """One overlap-and-discard stage on a local slab (inside shard_map)."""
    r = f.halo
    if r is None:
        raise ValueError(f"chain member {f.name!r} has no halo radius")
    if r > 0:
        ext = halo_exchange_rows(x, r, "space")
        y, _ = f.fn(ext, None)
        return y[:, r:-r]
    y, _ = f.fn(x, None)
    return y


def spatial_filter(
    filt: Filter,
    mesh: Mesh,
    halo: Optional[int] = None,
    data_sharded: bool = True,
    per_stage: Optional[bool] = None,
) -> Filter:
    """Wrap a stateless stencil filter for H-sharded execution.

    The returned Filter's fn is a shard_map over ('data', 'space'): B is
    sharded over 'data' (unless ``data_sharded=False``, e.g. the batch
    doesn't divide the data axis), H over 'space'; each shard
    halo-exchanges ``r`` rows, applies the original filter body to the
    extended slab, and drops the halo rows of the output. Requires
    ``filt.halo`` (stencil radius in rows) or an explicit ``halo=``;
    stateful filters are not supported (state row-sharding is
    filter-specific).

    ``per_stage`` (default: auto — on when the filter is a chain with
    per-member halos): exchange halos per chain member for exact global-
    border semantics (module docstring). ``False`` forces one fused
    summed-radius exchange (cheaper, assumes reflection-symmetric
    intermediates).
    """
    if filt.stateful:
        raise ValueError("spatial_filter supports stateless filters only")

    members = filt.members
    if per_stage is None:
        per_stage = (
            members is not None
            and all(not m.stateful and m.halo is not None for m in members)
        )
    r = halo if halo is not None else filt.halo
    if r is None:
        raise ValueError(
            f"filter {filt.name!r} has no halo radius; pass halo= explicitly"
        )

    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_space = axes.get("space", 1)

    if n_space == 1:
        return Filter(
            name=f"spatial({filt.name})",
            fn=filt.fn,
            compute_dtype=filt.compute_dtype,
            uint8_ok=filt.uint8_ok,
            halo=filt.halo,
        )

    if per_stage:
        def local_fn(x: jnp.ndarray) -> jnp.ndarray:
            for m in members:
                x = _stage_apply(x, m)
            return x
    else:
        def local_fn(x: jnp.ndarray) -> jnp.ndarray:
            if r > 0:
                ext = halo_exchange_rows(x, r, "space")
                y, _ = filt.fn(ext, None)
                return y[:, r:-r]
            y, _ = filt.fn(x, None)
            return y

    spec = P("data" if data_sharded else None, "space")

    def fn(batch: jnp.ndarray, state):
        sharded = jax.shard_map(
            local_fn,
            mesh=mesh,
            in_specs=spec,
            out_specs=spec,
            check_vma=False,
        )
        return sharded(batch), state

    return Filter(
        name=f"spatial({filt.name})",
        fn=fn,
        compute_dtype=filt.compute_dtype,
        uint8_ok=filt.uint8_ok,
        halo=filt.halo,
    )
