"""Perceptual-loss training for the style-transfer net, sharded over a mesh.

Loss = content (VGG feature MSE vs the input) + style (Gram-matrix MSE vs a
fixed style image's Grams) + total-variation smoothness — the Johnson et al.
recipe, computed entirely on device.

Sharding design — **explicit SPMD**, not GSPMD-auto: the whole train step
is one all-manual ``jax.shard_map`` over the mesh (see make_train_step for
the full rationale, including the XLA bugs that rule out the auto path on
this toolchain):
- batch: dim 0 sharded over 'data' AND 'space' folded together
  (``train_batch_sharding``) — both axes act as data parallelism here;
- net/VGG params + adam moments: Megatron column/row tensor-parallel specs
  over 'model' (``state_pspecs``), with explicit psum/all_gather
  collectives inside the forward (models.*.tp_inner_*);
- gradients: explicit ``lax.pmean`` over ('data', 'space').

The shard_map is jitted with donated state — zero steady-state allocation.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


from dvf_tpu.models.layers import gram_matrix
from dvf_tpu.models.style_transfer import (
    StyleNetConfig,
    apply_style_net,
    init_style_net,
    param_pspecs,
    tp_inner_apply,
)
from dvf_tpu.models.vgg import (
    VGGConfig,
    init_vgg,
    tp_inner_features,
    vgg_features,
    vgg_param_pspecs,
)


@dataclasses.dataclass(frozen=True)
class StyleTrainConfig:
    net: StyleNetConfig = StyleNetConfig()
    vgg: VGGConfig = VGGConfig()
    content_weight: float = 1.0
    style_weight: float = 10.0
    tv_weight: float = 1e-4
    learning_rate: float = 1e-3


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    vgg_params: Any          # frozen perceptual encoder
    style_grams: List[jnp.ndarray]   # target Grams, one per VGG block
    step: jnp.ndarray


def _tv_loss(img: jnp.ndarray) -> jnp.ndarray:
    dh = img[:, 1:, :, :] - img[:, :-1, :, :]
    dw = img[:, :, 1:, :] - img[:, :, :-1, :]
    return jnp.mean(dh.astype(jnp.float32) ** 2) + jnp.mean(dw.astype(jnp.float32) ** 2)


def style_loss_fn(
    params: Any,
    batch: jnp.ndarray,
    vgg_params: Any,
    style_grams: List[jnp.ndarray],
    config: StyleTrainConfig,
    apply_fn=None,
    features_fn=None,
) -> Tuple[jnp.ndarray, dict]:
    """``apply_fn``/``features_fn`` default to the single-shard model fns;
    make_train_step passes the per-shard TP versions (tp_inner_apply /
    tp_inner_features) since it calls this inside an all-manual shard_map."""
    apply_fn = apply_fn or (lambda p, b: apply_style_net(p, b, config.net))
    features_fn = features_fn or (lambda p, b: vgg_features(p, b, config.vgg))
    out = apply_fn(params, batch)
    out_feats = features_fn(vgg_params, out)
    content_feats = features_fn(vgg_params, batch)
    content = sum(
        jnp.mean((a.astype(jnp.float32) - b.astype(jnp.float32)) ** 2)
        for a, b in zip(out_feats, content_feats)
    ) / len(out_feats)
    # Per-layer RELATIVE Gram error: raw Gram MSE scales with 1/(H·W·C)²
    # and sits orders of magnitude below the content term (measured ~1e-6
    # vs ~1e-2 at 64², which made the style term invisible at any sane
    # weight and trained nets that just desaturated). Dividing by the
    # target Gram's energy makes every layer O(1) and resolution-free.
    style = sum(
        jnp.mean((gram_matrix(f) - g[None]) ** 2)
        / (jnp.mean(g.astype(jnp.float32) ** 2) + 1e-12)
        for f, g in zip(out_feats, style_grams)
    ) / len(out_feats)
    tv = _tv_loss(out)
    loss = (
        config.content_weight * content
        + config.style_weight * style
        + config.tv_weight * tv
    )
    return loss, {"loss": loss, "content": content, "style": style, "tv": tv}


def make_optimizer(config: StyleTrainConfig) -> optax.GradientTransformation:
    return optax.adam(config.learning_rate)


def init_train_state(
    rng: jax.Array,
    style_image: jnp.ndarray,
    config: StyleTrainConfig = StyleTrainConfig(),
) -> TrainState:
    """Build params + opt state + precomputed style-target Grams.

    ``style_image``: (1, H, W, 3) float in [0, 1].
    """
    net_key, vgg_key = jax.random.split(rng)
    params = init_style_net(net_key, config.net)
    vgg_params = init_vgg(vgg_key, config.vgg)
    opt_state = make_optimizer(config).init(params)
    grams = [gram_matrix(f)[0] for f in vgg_features(vgg_params, style_image, config.vgg)]
    return TrainState(
        params=params,
        opt_state=opt_state,
        vgg_params=vgg_params,
        style_grams=grams,
        step=jnp.zeros((), jnp.int32),
    )


def state_pspecs(state: TrainState, config: StyleTrainConfig) -> TrainState:
    """PartitionSpec tree mirroring a TrainState (TP over 'model').

    Optimizer moments (adam mu/nu) mirror the param layout leaf-for-leaf:
    each opt-state leaf whose dict path resolves inside the param spec tree
    inherits that spec; scalars (step counts) replicate.
    """
    p_specs = param_pspecs(config.net)
    v_specs = vgg_param_pspecs(config.vgg)

    def opt_spec(path, _leaf):
        keys = [k.key for k in path if isinstance(k, jax.tree_util.DictKey)]
        node: Any = p_specs
        for k in keys:
            if not isinstance(node, dict) or k not in node:
                return P()
            node = node[k]
        return node if isinstance(node, P) else P()

    opt_specs = jax.tree_util.tree_map_with_path(opt_spec, state.opt_state)
    return TrainState(
        params=p_specs,
        opt_state=opt_specs,
        vgg_params=v_specs,
        style_grams=[P() for _ in state.style_grams],
        step=P(),
    )


def shard_train_state(state: TrainState, mesh: Mesh, config: StyleTrainConfig) -> TrainState:
    """Place a host TrainState onto the mesh per the TP layout."""
    specs = state_pspecs(state, config)

    def put(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))

    return TrainState(
        params=jax.tree.map(put, state.params, specs.params),
        opt_state=jax.tree.map(put, state.opt_state, specs.opt_state),
        vgg_params=jax.tree.map(put, state.vgg_params, specs.vgg_params),
        style_grams=[put(g, s) for g, s in zip(state.style_grams, specs.style_grams)],
        step=put(state.step, specs.step),
    )


def train_batch_sharding(mesh: Mesh) -> NamedSharding:
    """Canonical batch sharding for training: DP over data×space combined
    (see the batch-layout note in make_train_step)."""
    return NamedSharding(mesh, P(("data", "space")))


def make_train_step(
    mesh: Mesh,
    config: StyleTrainConfig = StyleTrainConfig(),
    state_template: TrainState = None,
    donate: bool = True,
) -> Callable[[TrainState, jnp.ndarray], Tuple[TrainState, dict]]:
    """Build the jitted, mesh-sharded train step.

    The whole step is ONE all-manual ``shard_map`` over the mesh — the
    explicit-SPMD formulation (scaling-book style): every device runs the
    per-shard program below and all cross-device movement is an explicit
    named-axis collective:

    - dp (``data`` and ``space``, folded together on the batch dim):
      per-shard grads from the local micro-batch, then ``pmean`` over both
      axes. Spatially partitioning the conv net's H axis is deliberately
      NOT done here — GSPMD's spatial conv partitioner miscompiles when
      combined with TP on this toolchain (wrong halo values; and
      differentiating a mixed manual/auto shard_map crashes the XLA SPMD
      pass with "Invalid binary instruction opcode copy"). True spatial
      parallelism with hand-written halo exchange lives in the stencil
      filter path (dvf_tpu.parallel.halo).
    - tp (``model``): Megatron column/row convs with explicit ``psum``
      inside the forward (models.style_transfer.tp_inner_apply /
      models.vgg.tp_inner_features); grads of the psum are handled by AD.
    - adam runs per-shard on locally-owned param slices; (data, space)
      replicas compute identical updates deterministically.

    ``state_template`` provides the opt-state tree structure for the spec
    derivation (any TrainState from init_train_state).

    The returned fn maps ``(state, batch) -> (state, metrics)`` with batch
    sharded per :func:`train_batch_sharding` and state per ``state_pspecs``.
    """
    optimizer = make_optimizer(config)
    apply_fn = tp_inner_apply(config.net)
    features_fn = tp_inner_features(config.vgg)
    if state_template is None:
        raise ValueError("make_train_step needs a state_template TrainState")
    specs = state_pspecs(state_template, config)
    dp_axes = ("data", "space")

    def local_step(state: TrainState, batch: jnp.ndarray):
        grads, metrics = jax.grad(style_loss_fn, has_aux=True)(
            state.params, batch, state.vgg_params, state.style_grams, config,
            apply_fn, features_fn,
        )
        grads = lax.pmean(grads, dp_axes)
        metrics = lax.pmean(metrics, dp_axes)
        updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        new_state = TrainState(
            params=params,
            opt_state=opt_state,
            vgg_params=state.vgg_params,
            style_grams=state.style_grams,
            step=state.step + 1,
        )
        return new_state, metrics

    batch_spec = P(dp_axes)
    sharded = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(specs, batch_spec),
        out_specs=(specs, P()),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())
