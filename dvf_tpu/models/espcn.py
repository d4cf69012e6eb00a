"""ESPCN super-resolution — the framework's second neural model family.

Efficient Sub-Pixel CNN (Shi et al. 2016): all convs run at LOW (input)
resolution and a final zero-FLOP subpixel rearrange produces the ×r
output — the architecture was designed for exactly the property TPUs
want: every FLOP is a dense low-res conv (MXU matmul in bfloat16), and
the upscale itself is a reshape XLA folds away.

Reference counterpart: none — the reference's only op is invert
(inverter.py:41); this widens the neural filter families the framework
ships (style transfer = artistic, ESPCN = enhancement), demonstrating the
same params-in-state + explicit-TP machinery on a second architecture.

Tensor parallelism mirrors models.style_transfer: Megatron column/row
with ONE hand-placed psum per col→row pair, applied inside an all-manual
shard_map (GSPMD-auto conv partitioning is distrusted on this toolchain,
see train.style.make_train_step). The head conv (32 → 3r², a few percent
of total FLOPs) runs replicated after the psum — sharding 12 output
channels would buy nothing and cost a gather before depth_to_space.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from dvf_tpu.models.layers import (
    Params,
    conv2d_nb,
    conv2d_s2d,
    conv_init,
    depth_to_space,
)


@dataclasses.dataclass(frozen=True)
class EspcnConfig:
    scale: int = 2
    c1: int = 64                     # feature widths from the paper
    c2: int = 32
    compute_dtype: Any = jnp.bfloat16
    # Space-to-depth conv rewrite (models.layers.conv2d_s2d): every ESPCN
    # conv is stride-1 with lane-starved Cout (64/32/12 of 128 lanes), so
    # the phase decomposition raises MXU utilization 2-3x per layer
    # (models.analysis). Exact; opt-in pending the sr_fast_540p A/B.
    fast_convs: bool = False


def init_espcn(rng: jax.Array, config: EspcnConfig = EspcnConfig()) -> Params:
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "feat": conv_init(k1, 5, 3, config.c1),
        "map": conv_init(k2, 3, config.c1, config.c2),
        "head": conv_init(k3, 3, config.c2, 3 * config.scale**2),
    }


def _forward(params: Params, batch: jnp.ndarray, config: EspcnConfig,
             row_reduce) -> jnp.ndarray:
    """Shared body; ``row_reduce`` is identity when unsharded, psum('model')
    under TP (runs on map's pre-bias partial sums — the one collective)."""
    cd = config.compute_dtype

    def cv(name, x, reduce=None):
        p = params[name]
        if config.fast_convs:
            y = conv2d_s2d(p, x, compute_dtype=cd)  # SAME zero-pad, exact
        else:
            y = conv2d_nb(p, x, compute_dtype=cd)
        if reduce is not None:
            y = reduce(y)
        return y + p["b"].astype(cd)

    # Every stage carries a ``jax.named_scope``, as the style net's do, so
    # the compiled HLO's ``op_name`` says whose each fusion is
    # (scripts/style_step_probe.py --model espcn).
    x = batch.astype(cd)
    with jax.named_scope("feat"):
        x = jax.nn.relu(cv("feat", x))
    with jax.named_scope("map"):
        x = jax.nn.relu(cv("map", x, reduce=row_reduce))
    with jax.named_scope("head"):
        x = cv("head", x)
    with jax.named_scope("shuffle"):
        y = depth_to_space(x.astype(jnp.float32), config.scale)
        return jnp.clip(y, 0.0, 1.0).astype(batch.dtype)


def apply_espcn(params: Params, batch: jnp.ndarray,
                config: EspcnConfig = EspcnConfig()) -> jnp.ndarray:
    """(B, H, W, 3) in [0, 1] → (B, H·r, W·r, 3). Single-shard version."""
    return _forward(params, batch, config, row_reduce=None)


def tp_inner_apply(config: EspcnConfig):
    """Per-shard apply for INSIDE an all-manual shard_map: feat is
    column-parallel (activations leave C-sharded), map is row-parallel and
    reduces with an explicit psum over 'model', head runs replicated."""
    return lambda params, batch: _forward(
        params, batch, config, row_reduce=lambda y: lax.psum(y, "model")
    )


def param_pspecs(config: EspcnConfig = EspcnConfig()) -> Dict[str, Any]:
    """PartitionSpec tree for TP over the ``model`` axis: feat=col
    (output channels sharded), map=row (input channels sharded, one psum),
    head replicated. Size-1 model axes degrade to replication, so this one
    tree serves every mesh."""
    return {
        "feat": {"w": P(None, None, None, "model"), "b": P("model")},
        "map": {"w": P(None, None, "model", None), "b": P()},
        "head": {"w": P(), "b": P()},
    }
