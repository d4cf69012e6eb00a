"""Fast neural style transfer — the framework's flagship neural filter.

Covers BASELINE.json configs[4] ("fast neural style-transfer (small VGG
encoder), 720p, batch=8"). Architecture follows the Johnson et al. (2016)
feed-forward transformer net: 9×9 stem conv → two stride-2 downsampling
convs → N residual blocks at ¼ resolution → two ×2 resize-convs → 9×9 output
conv, instance norm + ReLU throughout, scaled-tanh output.

TPU-first choices:
- all heavy convs run at ¼ spatial resolution in bfloat16 (MXU-native);
- tensor parallelism is **explicit** (Megatron column/row alternation with
  hand-placed psums, :func:`param_pspecs` + :func:`tp_inner_apply`), run
  inside an all-manual shard_map — GSPMD-auto conv partitioning is
  deliberately avoided (it miscompiles spatial×feature sharded convs on
  this toolchain; see train.style.make_train_step);
- resize-conv (nearest upsample + conv) instead of transposed conv: fewer
  artifacts;
- the sub-128-channel stages live in the **phase domain**: a (B, H, W, c)
  activation with c under the 128 lanes is made, normalized and read as its
  space_to_depth image (B, H/2, W/2, 4c) — dense on the lanes, never stored
  4x padded — from ``stem`` into ``down1`` and from ``up2`` into ``out``
  (whose 3-channel result alone is brought back to the frame), and the
  decoder's upsample+conv pairs run as low-res convs emitting the phases.
  Which form a stage takes is a function of its shape
  (:func:`stage_forms`); every stage carries a ``jax.named_scope`` so a
  device trace's ``op_name`` says which layer an op is.

The net is exposed as a registered filter (``style_transfer``) whose params
ride in the filter *state* pytree, so weights live on device across batches
instead of being baked into the jitted program as constants.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from dvf_tpu.models.layers import (
    Params,
    conv2d_nb,
    conv2d_phase,
    conv_init,
    conv_norm,
    depth_to_space,
    instance_norm_init,
    space_to_depth,
    upsample2_conv_phase,
    upsample_nearest,
)

LANES = 128     # the TPU's lane width: a tensor with fewer channels is stored padded to it

_reflect_conv = functools.partial(conv2d_nb, reflect=True)


@dataclasses.dataclass(frozen=True)
class StyleNetConfig:
    base_channels: int = 32          # stem width; doubles at each downsample
    n_residual: int = 5
    compute_dtype: Any = jnp.bfloat16

    @property
    def widths(self):
        c = self.base_channels
        return (c, c * 2, c * 4)     # stem, down1, down2/residual trunk


def init_style_net(rng: jax.Array, config: StyleNetConfig = StyleNetConfig()) -> Params:
    c1, c2, c3 = config.widths
    keys = iter(jax.random.split(rng, 8 + 2 * config.n_residual))
    p: Dict[str, Params] = {
        "stem": conv_init(next(keys), 9, 3, c1),
        "stem_norm": instance_norm_init(c1),
        "down1": conv_init(next(keys), 3, c1, c2),
        "down1_norm": instance_norm_init(c2),
        "down2": conv_init(next(keys), 3, c2, c3),
        "down2_norm": instance_norm_init(c3),
    }
    for i in range(config.n_residual):
        p[f"res{i}_a"] = conv_init(next(keys), 3, c3, c3)
        p[f"res{i}_an"] = instance_norm_init(c3)
        p[f"res{i}_b"] = conv_init(next(keys), 3, c3, c3)
        p[f"res{i}_bn"] = instance_norm_init(c3)
    p["up1"] = conv_init(next(keys), 3, c3, c2)
    p["up1_norm"] = instance_norm_init(c2)
    p["up2"] = conv_init(next(keys), 3, c2, c1)
    p["up2_norm"] = instance_norm_init(c1)
    p["out"] = conv_init(next(keys), 9, c1, 3)
    return p


def apply_style_net(
    params: Params,
    batch: jnp.ndarray,
    config: StyleNetConfig = StyleNetConfig(),
) -> jnp.ndarray:
    """Apply the transformer net to a float NHWC batch in [0, 1]
    (single-shard version; for tensor parallelism use :func:`tp_inner_apply`
    inside an all-manual shard_map, as train.style.make_train_step does)."""
    return _forward(params, batch, config, lambda y: y)


def _conv_modes(config: StyleNetConfig) -> Dict[str, str]:
    """Which convs are column- vs row-parallel (see param_pspecs)."""
    modes = {
        "stem": "col", "down1": "row", "down2": "col",
        "up1": "row", "up2": "col", "out": "row",
    }
    for i in range(config.n_residual):
        modes[f"res{i}_a"] = "row"
        modes[f"res{i}_b"] = "col"
    return modes


def stage_forms(config: StyleNetConfig, batch_shape) -> Dict[str, str]:
    """The form each stage of :func:`_forward` runs in for an NHWC batch of
    this shape — ``"phase"``: on the ``space_to_depth(·, 2)`` image of its
    full-resolution tensor (models.layers, "The phase domain"), ``"plain"``:
    on the tensor itself. A function of the shapes alone: the full-
    resolution stages (stem → down1, up2 → out) hold ``base_channels``
    channels at H×W and up1's output twice that at H/2×W/2; each takes the
    phase form when its channels are under the 128 lanes and H, W are even
    (odd geometry keeps the plain path throughout). down1's and the
    trunk's convs already emit 128 channels. ``_forward`` branches on this
    and nothing else. What each form costs on a v5e: PERF.md §5."""
    _, h, w, _ = batch_shape
    c1, c2, _ = config.widths
    even = h % 2 == 0 and w % 2 == 0
    full = "phase" if even and c1 < LANES else "plain"
    half = "phase" if even and c2 < LANES else "plain"
    return {"stem": full, "down1": full, "down2": "plain", "trunk": "plain",
            "up1": half, "up2": full, "out": full}


def _forward(params: Params, batch: jnp.ndarray, config: StyleNetConfig,
             row_reduce, trunk_fn=None) -> jnp.ndarray:
    """Shared forward body for ALL schedules. ``row_reduce`` runs on each
    row-parallel conv's pre-bias output (identity when unsharded,
    psum('model') under TP). ``trunk_fn(params, x)`` replaces the default
    flat residual loop (the PP grouping passes its scan/pipeline here) —
    one copy of the stem/decoder wiring, however the trunk executes.

    Between ``stem`` and ``down1`` and between ``up2`` and ``out`` the
    activation is a phase tensor where :func:`stage_forms` says so: it is
    made, normalized and read as (B, H/2, W/2, 4·c) and never exists at
    (B, H, W, c)."""
    cd = config.compute_dtype
    modes = _conv_modes(config)
    phase = {k: v == "phase" for k, v in stage_forms(config, batch.shape).items()}

    def finish(name, y, phases=1):
        """Row-parallel reduce, then the bias (once per output phase)."""
        if modes.get(name) == "row":
            y = row_reduce(y)
        return y + jnp.tile(params[name]["b"], phases).astype(cd)

    def conv(name, fn=_reflect_conv, phases=1, **kw):
        """Conv ``name`` with its bias, as a function of its input."""
        return lambda x: finish(
            name, fn(params[name], x, compute_dtype=cd, **kw), phases)

    def norm(name, conv, x, phased=False, relu=True):
        y = conv_norm(params[name], conv, x, phased)
        return jax.nn.relu(y) if relu else y

    x = batch.astype(cd)
    with jax.named_scope("stem"):
        if phase["stem"]:
            x = norm("stem_norm", conv("stem", conv2d_phase, 4),
                     space_to_depth(x, 2), phased=True)
        else:
            x = norm("stem_norm", conv("stem"), x)
    with jax.named_scope("down1"):
        if phase["down1"]:
            # 3x3 stride 2 on a phase tensor: a 2x2 conv, plain output.
            x = norm("down1_norm", conv("down1", conv2d_phase, stride=2), x)
        else:
            x = norm("down1_norm", conv("down1", stride=2), x)
    with jax.named_scope("down2"):
        x = norm("down2_norm", conv("down2", stride=2), x)
    with jax.named_scope("trunk"):
        if trunk_fn is not None:
            x = trunk_fn(params, x)
        else:
            for i in range(config.n_residual):
                h = norm(f"res{i}_an", conv(f"res{i}_a"), x)
                x = x + norm(f"res{i}_bn", conv(f"res{i}_b"), h, relu=False)
    with jax.named_scope("up1"):
        if phase["up1"]:
            # Made and normalized as phases (dense), then brought to the
            # half-resolution tensor up2's low-res conv reads.
            x = depth_to_space(norm(
                "up1_norm", conv("up1", upsample2_conv_phase, 4), x,
                phased=True), 2)
        else:
            x = norm("up1_norm", conv("up1"), upsample_nearest(x, 2))
    with jax.named_scope("up2"):
        if phase["up2"]:
            # nearest-x2 + 3x3 as one low-res conv emitting the 4 phases.
            x = norm("up2_norm", conv("up2", upsample2_conv_phase, 4), x,
                     phased=True)
        else:
            x = norm("up2_norm", conv("up2"), upsample_nearest(x, 2))
    with jax.named_scope("out"):
        if phase["out"]:
            fold = _out_fold(x.shape)
            y = conv("out", conv2d_phase, 4 * fold * fold, fold=fold)(x)
            y = 0.5 * (jnp.tanh(y.astype(jnp.float32)) + 1.0)
            y = depth_to_space(y, 2 * fold)
        else:
            y = 0.5 * (jnp.tanh(conv("out")(x).astype(jnp.float32)) + 1.0)
    return y.astype(batch.dtype)


def _out_fold(phase_shape) -> int:
    """Phase factor of the out conv, as a fold of its factor-2 input: Cout
    is 3, so at factor 2 the conv fills 12 of the MXU's 128 columns; at
    factor 4 (fold 2) 48, with 2.8x fewer padded multiply-adds — taken
    where the phase tensor's own H and W are even (on a v5e: 98 ms a step
    at fold 1, 84 at fold 2; PERF.md §6, PR 28)."""
    _, h2, w2, _ = phase_shape
    return 2 if h2 % 2 == 0 and w2 % 2 == 0 else 1


def tp_inner_apply(config: StyleNetConfig) -> Any:
    """Per-shard apply for use INSIDE an all-manual shard_map region:
    row-parallel convs reduce with an explicit psum over 'model'. With a
    size-1 model axis the psum is an identity collective."""
    return lambda params, batch: _forward(
        params, batch, config, lambda y: lax.psum(y, "model")
    )


# ---------------------------------------------------------------------------
# Layer pipeline parallelism over the residual trunk (SURVEY §2c layer-PP)
# ---------------------------------------------------------------------------

def to_pp_params(flat: Params, config: StyleNetConfig) -> Params:
    """Regroup the flat param dict for pipelining: stem/down/up/out stay
    flat (replicated), the N homogeneous residual blocks stack into a
    'trunk' pytree with leading dim N — the axis PP shards over stages."""
    from dvf_tpu.parallel.pp import stack_layer_params

    enc_dec = {k: v for k, v in flat.items() if not k.startswith("res")}
    blocks = [
        {"a": flat[f"res{i}_a"], "an": flat[f"res{i}_an"],
         "b": flat[f"res{i}_b"], "bn": flat[f"res{i}_bn"]}
        for i in range(config.n_residual)
    ]
    return {**enc_dec, "trunk": stack_layer_params(blocks)}


def pp_param_pspecs(config: StyleNetConfig = StyleNetConfig()) -> Dict[str, Any]:
    """PartitionSpecs for the PP grouping: trunk layer-dim on 'model'
    (each device owns N/S contiguous blocks — the PP memory win), the
    non-repeated stem/decoder replicated. Built structurally — no params
    are materialized (cf. param_pspecs)."""
    conv_r = {"w": P(), "b": P()}
    norm_r = {"scale": P(), "bias": P()}
    specs: Dict[str, Any] = {
        "stem": conv_r, "stem_norm": norm_r,
        "down1": conv_r, "down1_norm": norm_r,
        "down2": conv_r, "down2_norm": norm_r,
        "up1": conv_r, "up1_norm": norm_r,
        "up2": conv_r, "up2_norm": norm_r,
        "out": conv_r,
    }
    # Stacked leaves: conv w (L,kh,kw,cin,cout) / b (L,c); norm (L,c).
    conv_s = {"w": P("model", None, None, None, None), "b": P("model", None)}
    norm_s = {"scale": P("model", None), "bias": P("model", None)}
    specs["trunk"] = {"a": conv_s, "an": norm_s, "b": conv_s, "bn": norm_s}
    return specs


def _pp_res_block(config: StyleNetConfig):
    cd = config.compute_dtype

    def norm(pn, pc, x):
        return conv_norm(pn, lambda x: _reflect_conv(
            pc, x, compute_dtype=cd) + pc["b"].astype(cd), x)

    def res_block(p, x):
        h = jax.nn.relu(norm(p["an"], p["a"], x))
        return x + norm(p["bn"], p["b"], h)

    return res_block


def pp_sequential_apply(config: StyleNetConfig) -> Any:
    """Single-shard apply over PP-grouped params (the un-specialized
    engine path): the trunk is a plain lax.scan over the stacked blocks —
    numerically identical to apply_style_net on the flat params."""
    block = _pp_res_block(config)

    def trunk(params, x):
        out, _ = lax.scan(lambda c, p: (block(p, c), None), x, params["trunk"])
        return out

    return lambda params, batch: _forward(
        params, batch, config, lambda y: y, trunk_fn=trunk)


def pp_inner_apply(config: StyleNetConfig, n_microbatches: int = 0) -> Any:
    """Per-shard apply for ``parallel='pp'`` INSIDE an all-manual
    shard_map: stem/down and up/out run replicated on every model-rank
    (they are the non-repeated layers), the residual trunk runs as a
    GPipe pipeline over 'model' (parallel.pp.pipeline_apply) with the
    activations hopping stages via ppermute."""
    from dvf_tpu.parallel.pp import pipeline_apply

    block = _pp_res_block(config)

    def trunk(params, x):
        return pipeline_apply(block, params["trunk"], x, axis="model",
                              n_microbatches=n_microbatches)

    return lambda params, batch: _forward(
        params, batch, config, lambda y: y, trunk_fn=trunk)


def param_pspecs(config: StyleNetConfig = StyleNetConfig()) -> Dict[str, Any]:
    """PartitionSpec tree for tensor parallelism over the ``model`` axis.

    Megatron-style alternation: **column-parallel** convs shard output
    channels (activations leave C-sharded), the following **row-parallel**
    conv shards input channels (each shard consumes the channels it owns;
    GSPMD inserts one reduce for the output sum). Collectives therefore
    appear once per col→row pair instead of per layer. Instance norms
    normalize over (H, W) per channel, so a norm after a column conv simply
    shards its scale/bias with the channels; after a row conv it replicates.

    Alternation map (activations C-sharded after stem, down2, res*_b, up2):
    stem=col → down1=row → down2=col → [res_a=row, res_b=col]* →
    up1=row → up2=col → out=row.
    """
    def col():
        return {"w": P(None, None, None, "model"), "b": P("model")}

    def row():
        return {"w": P(None, None, "model", None), "b": P()}

    def norm_spec(sharded: bool):
        s = P("model") if sharded else P()
        return {"scale": s, "bias": s}

    specs: Dict[str, Any] = {
        "stem": col(),
        "stem_norm": norm_spec(True),
        "down1": row(),
        "down1_norm": norm_spec(False),
        "down2": col(),
        "down2_norm": norm_spec(True),
        "up1": row(),
        "up1_norm": norm_spec(False),
        "up2": col(),
        "up2_norm": norm_spec(True),
        "out": row(),
    }
    for i in range(config.n_residual):
        specs[f"res{i}_a"] = row()
        specs[f"res{i}_an"] = norm_spec(False)
        specs[f"res{i}_b"] = col()
        specs[f"res{i}_bn"] = norm_spec(True)
    return specs
