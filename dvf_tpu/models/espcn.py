"""ESPCN super-resolution — the framework's second neural model family.

Efficient Sub-Pixel CNN (Shi et al. 2016): all convs run at LOW (input)
resolution and a final zero-FLOP subpixel rearrange produces the ×r
output. Every FLOP is a dense low-res conv (MXU matmul in bfloat16), but
none of its widths (64, 32, 3r²) fills the TPU's 128 lanes or the MXU's
128 columns, and the rearrange is not free: on a v5e the plain body took
34.6 ms for 16 540p frames, 12.6 of them ``map`` at 12% of the MXU's peak,
3.6 a layout copy of ``head``'s 12 channels and 5.6 the shuffle as two
uint8 copies (PERF.md §5, PR 37). So where the batch's shape allows
(:func:`stage_phases`) the forward runs in the **carried phase form**, as
the style net's sub-128-channel stages do: each conv emits as many phases
of its result as fill the 128 lanes (``feat`` 2, ``map`` 4, ``head`` 8:
128, 128 and 96 dense columns), the activation stays that
``space_to_depth`` image into the conv that reads it, and ``head`` emits
its sub-pixels in the order that leaves shuffle + un-phasing ONE
``depth_to_space`` — 14.9 ms for the same batch, ``map`` and ``head`` at
the MXU's pass limit for their kernels (PERF.md §5, PR 41).

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
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from dvf_tpu.models.layers import (
    Params,
    conv2d_nb,
    conv2d_s2d,
    conv2d_zero_phase,
    conv_init,
    depth_to_space,
    subpixel_order,
)


@dataclasses.dataclass(frozen=True)
class EspcnConfig:
    scale: int = 2
    c1: int = 64                     # feature widths from the paper
    c2: int = 32
    compute_dtype: Any = jnp.bfloat16
    # The per-layer space-to-depth rewrite (models.layers.conv2d_s2d: to
    # phases AND BACK around every conv). Exact, and slower than the plain
    # convs it replaces: 78.31 ms a step of 16 540p frames against 34.64 on
    # a v5e (PERF.md §7, PR 37) — the relayouts cost more than the convs
    # save. True keeps that body; False takes the carried form where
    # stage_phases admits the geometry (14.9 ms). Its retirement is
    # ROADMAP D4's, with the benchmark edit that needs.
    fast_convs: bool = False


def init_espcn(rng: jax.Array, config: EspcnConfig = EspcnConfig()) -> Params:
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "feat": conv_init(k1, 5, 3, config.c1),
        "map": conv_init(k2, 3, config.c1, config.c2),
        "head": conv_init(k3, 3, config.c2, 3 * config.scale**2),
    }


LANES = 128     # the TPU's lane width: a tensor with fewer channels is stored padded to it
PLAIN = (1, 1)


def stage_phases(config: EspcnConfig, batch_shape) -> Dict[str, Tuple[int, int]]:
    """The (H, W) phase factors of the tensor each conv of :func:`_forward`
    MAKES for an NHWC batch of this shape: it is the ``space_to_depth(·,
    (fh, fw))`` image of the conv's full-resolution result (models.layers,
    "The phase domain"); (1, 1) is the result itself. A function of the
    shapes alone.

    A stage emits the most phases that fit the 128 lanes (a power of two,
    W taking the odd factor of two): ``feat`` 2·64 = 128 columns at (1, 2),
    ``map`` 4·32 = 128 at (2, 2), ``head`` 8·3r² = 96 at (2, 4) — on a v5e
    (2, 2) throughout read 20.1 ms a step where these read 14.9, and H
    first or 16 phases of ``head`` read slower (PERF.md §6, PR 41). A conv
    emits a multiple of the phases it reads and no tensor goes back, so
    from the last conv to the first each stage's factors are capped by its
    reader's, and ``head``'s by what divides H and W. The carried form
    needs even H and W (a W that is no multiple of 4 leaves ``head`` at
    (2, 2)); any other geometry, and ``fast_convs=True`` (the per-layer
    round trip), keep the plain body."""
    _, h, w, _ = batch_shape
    if h % 2 or w % 2 or config.fast_convs:
        cap = PLAIN
    else:
        cap = (h & -h, w & -w)          # the largest powers of two dividing H and W
    phases = {}
    for name, channels in (("head", 3 * config.scale**2), ("map", config.c2),
                           ("feat", config.c1)):
        log2 = max(LANES // channels, 1).bit_length() - 1
        cap = (min(1 << (log2 // 2), cap[0]), min(1 << ((log2 + 1) // 2), cap[1]))
        phases[name] = cap
    return dict(reversed(phases.items()))        # in the net's order


def stage_forms(config: EspcnConfig, batch_shape) -> Dict[str, str]:
    """The form each stage of :func:`_forward` runs in for an NHWC batch of
    this shape, as the style net's: ``"phase"`` (it makes a phase image,
    :func:`stage_phases` says at which factors) or ``"plain"``;
    ``shuffle``'s form is ``head``'s (one ``depth_to_space`` either way).
    ``_forward`` branches on :func:`stage_phases` and on nothing else."""
    phases = stage_phases(config, batch_shape)
    forms = {name: "plain" if f == PLAIN else "phase" for name, f in phases.items()}
    return {**forms, "shuffle": forms["head"]}


def _forward(params: Params, batch: jnp.ndarray, config: EspcnConfig,
             row_reduce) -> jnp.ndarray:
    """Shared body; ``row_reduce`` is identity when unsharded, psum('model')
    under TP (runs on map's pre-bias partial sums — the one collective).

    Where :func:`stage_phases` says so an activation is made, biased,
    rectified and read as its phase image and never exists at (B, H, W, c);
    ``head`` then emits its sub-pixels in the order that makes shuffle +
    un-phasing one ``depth_to_space``."""
    cd = config.compute_dtype
    phases = stage_phases(config, batch.shape)

    def cv(name, x, fi, reduce=None, cols=None):
        """Conv ``name`` + bias of ``x`` (a phase image at ``fi``), making
        the image at ``phases[name]``; the bias is tiled over the emitted
        phases after the row-parallel reduce."""
        p, b, fo = params[name], params[name]["b"], phases[name]
        if fo != PLAIN:
            y = conv2d_zero_phase(p, x, fi, fo, cols, compute_dtype=cd)
            b = jnp.tile(b, fo[0] * fo[1])
            b = b if cols is None else b[cols]
        elif config.fast_convs:
            y = conv2d_s2d(p, x, compute_dtype=cd)  # SAME zero-pad, exact
        else:
            y = conv2d_nb(p, x, compute_dtype=cd)
        if reduce is not None:
            y = reduce(y)
        return y + b.astype(cd)

    # Every stage carries a ``jax.named_scope``, as the style net's do, so
    # the compiled HLO's ``op_name`` says whose each fusion is
    # (scripts/style_step_probe.py --model espcn).
    x = batch.astype(cd)
    with jax.named_scope("feat"):
        x = jax.nn.relu(cv("feat", x, PLAIN))
    with jax.named_scope("map"):
        x = jax.nn.relu(cv("map", x, phases["feat"], reduce=row_reduce))
    with jax.named_scope("head"):
        fh, fw = phases["head"]
        x = cv("head", x, phases["map"], cols=subpixel_order((fh, fw), config.scale, 3))
    with jax.named_scope("shuffle"):
        y = depth_to_space(x.astype(jnp.float32), (fh * config.scale, fw * config.scale))
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
