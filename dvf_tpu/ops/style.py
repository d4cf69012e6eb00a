"""Style-transfer filter op — the neural entry in the filter registry.

Wraps :mod:`dvf_tpu.models.style_transfer` as a registered, *stateful*
filter: the network params ARE the filter state, so they live on device and
thread through the engine's jitted step (never baked into the program as
constants, never copied back to host). The state is returned unchanged each
batch — inference only; training lives in :mod:`dvf_tpu.train`.

Reference counterpart: none — the reference's only op is invert
(inverter.py:41); this covers BASELINE.json configs[4].
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dvf_tpu.api.filter import Filter
from dvf_tpu.models.style_transfer import (
    StyleNetConfig,
    apply_style_net,
    init_style_net,
    param_pspecs,
    pp_inner_apply,
    pp_param_pspecs,
    pp_sequential_apply,
    to_pp_params,
    tp_inner_apply,
)
from dvf_tpu.ops.registry import register_filter


@register_filter("style_transfer")
def style_transfer(
    params: Optional[Any] = None,
    base_channels: int = 32,
    n_residual: int = 5,
    seed: int = 0,
    parallel: str = "tp",
    dtype: Optional[str] = None,
) -> Filter:
    """``params=None`` → seeded random init (demo/benchmark weights);
    pass a trained param pytree for real stylization.

    ``dtype`` pins the model compute dtype ("bfloat16" default —
    MXU-native — or "float32"). The form each stage runs in (the
    full-resolution stages on phase tensors, dense on the 128 lanes) is a
    function of the batch's shape: ``models.style_transfer.stage_forms``.

    ``parallel`` picks the model-axis strategy the ``specialize`` hook
    compiles when the mesh's model axis > 1:

    - ``"tp"`` — Megatron column/row tensor parallelism with explicit
      psums (models.style_transfer.tp_inner_apply), the same all-manual
      formulation the train step uses (GSPMD-auto conv partitioning is
      distrusted on this toolchain, see train.style.make_train_step).
      Covers configs[4] when one chip can't hold the activation footprint.
    - ``"pp"`` — layer pipeline parallelism over the residual trunk
      (models.style_transfer.pp_inner_apply / parallel.pp): each device
      owns n_residual/S contiguous blocks, activations hop stages via
      ppermute on a GPipe schedule — SURVEY §2c's optional layer-PP for
      deep filters (raise n_residual and the trunk dominates). Requires
      model-axis size to divide n_residual.
    """
    if parallel not in ("tp", "pp"):
        raise ValueError(f"parallel must be 'tp' or 'pp', got {parallel!r}")
    if dtype is None:
        dtype = "bfloat16"
    if dtype not in ("bfloat16", "float32"):
        raise ValueError(
            f"dtype must be 'bfloat16' or 'float32', got {dtype!r}")
    config = StyleNetConfig(
        base_channels=base_channels, n_residual=n_residual,
        compute_dtype=jnp.dtype(dtype))

    if parallel == "pp":
        _seq_apply = pp_sequential_apply(config)

        def fn(batch: jnp.ndarray, state: Any) -> Tuple[jnp.ndarray, Any]:
            return _seq_apply(state, batch), state

        def init_state(batch_shape, dtype):
            flat = params if params is not None else init_style_net(
                jax.random.PRNGKey(seed), config)
            return to_pp_params(flat, config)
    else:
        def fn(batch: jnp.ndarray, state: Any) -> Tuple[jnp.ndarray, Any]:
            return apply_style_net(state, batch, config), state

        def init_state(batch_shape, dtype):
            if params is not None:
                return params
            return init_style_net(jax.random.PRNGKey(seed), config)

    name = f"style_transfer(c={base_channels},r={n_residual},{parallel})"

    def specialize(mesh, batch_shape) -> Optional[Filter]:
        axes = dict(zip(mesh.axis_names, mesh.devices.shape))
        n_model = axes.get("model", 1)
        if n_model <= 1:
            return None  # generic body; params replicate over size-1 axis
        if parallel == "pp":
            if config.n_residual % n_model != 0:
                import sys

                print(
                    f"[style_transfer] pp needs model axis ({n_model}) to "
                    f"divide n_residual ({config.n_residual}); running "
                    f"unspecialized (replicated params)",
                    file=sys.stderr,
                )
                return None
            inner = pp_inner_apply(config)
            specs = pp_param_pspecs(config)
        else:
            inner = tp_inner_apply(config)
            specs = param_pspecs(config)
        # Batch folded over (data, space) on dim 0 — mirrors
        # train.style.train_batch_sharding. The model axis replicates the
        # batch and owns param shards. shard_map requires dim 0 to divide
        # the named axes exactly, which the Engine never guarantees —
        # degrade the fold (data+space → data → replicated) to whatever
        # the actual batch divides.
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
            name=f"{parallel}({name})",
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
        # TP specs are safe on any mesh (a size-1 model axis replicates);
        # PP's trunk specs are NOT — an indivisible model axis must fall
        # back to full replication, so the base PP filter replicates and
        # only the specialized filter (which checked divisibility) carries
        # the stage-sharded specs.
        state_pspecs=(None if parallel == "pp"
                      else (lambda: param_pspecs(config))),
        specialize=specialize,
    )
