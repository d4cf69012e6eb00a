"""FastDVDnet — video denoising without flow estimation (Tassano, Delon,
Veit, CVPR 2020, arXiv:1907.01361; the authors' ``models.py``).

Two stages of one block. A ``DenBlock`` takes three frames and a noise
map and returns its centre frame less a residual that a three-scale
U-Net (widths 32-64-128, additive skips, stride-2 convolutions down,
``conv → PixelShuffle(2)`` up) computes from them; stage 1 runs it on
each of a five-frame window's three triplets (shared weights), stage 2
on the three results. All convolutions are 3×3, zero padding 1, no
bias; ``BN`` is inference batch norm, a per-channel affine:

    CvBlock(c)   [conv c→c, BN, ReLU] × 2
    Input        (f0, m, f1, m, f2, m) → 12 channels; conv 12→90 in three
                 groups (each frame's 4 → 30), BN, ReLU; conv 90→32, BN, ReLU
    Down(a→b)    conv a→b stride 2, BN, ReLU; CvBlock(b)
    Up(a→b)      CvBlock(a); conv a→4b; PixelShuffle(2)
    Output       conv 32→32, BN, ReLU; conv 32→3
    DenBlock     x0 = Input(f0, f1, f2, m); x1 = Down(32→64)(x0);
                 x2 = Up(128→64)(Down(64→128)(x1)); x1 = Up(64→32)(x1 + x2);
                 f1 − Output(x0 + x1)

1,237,320 convolution weights a block. The parameter tree keeps what the
authors' checkpoint keeps (the grouped kernel as (3, 3, 4, 90), batch
norm as γ, β, running mean and variance); :func:`apply_denblock` folds
each norm to a scale and a shift and applies them in float32 to the
convolution's float32 result (bfloat16 operands on the MXU). Three
rewrites, all exact re-indexings of weights: the grouped convolution is
one dense one with a block-diagonal kernel (one pass of the MXU's rows
either way); an ``Up``'s last convolution emits its columns in
(dy, dx, c) order, PyTorch's being ``c·4 + 2·dy + dx``; and every
activation is carried as the phase image that fills the 128 lanes
(``FULL``, ``HALF``, ``PLAIN`` below; ``layers.conv2d_zero_phase``).

The streamed form that serves it (a stage-1 result computed once and
kept, two blocks a delivered frame) is ``dvf_tpu/ops/denoise.py``;
:func:`apply_fastdvdnet` here is the window at once, four blocks.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dvf_tpu.models.layers import conv2d_zero_phase, depth_to_space

Params = Dict[str, Any]

WIDTHS = (32, 64, 128)      # the U-Net's three scales
FRAME_FEATURES = 30         # the grouped first convolution's columns a frame
DENBLOCK_WEIGHTS = 1_237_320    # convolution weights a block (conv_weights of one)
BN_EPS = 1e-5               # torch.nn.BatchNorm2d's default
_DN = ("NHWC", "HWIO", "NHWC")


@dataclasses.dataclass(frozen=True)
class FastDvdConfig:
    sigma: float = 25.0 / 255.0      # the noise map's constant, in [0, 1] units
    compute_dtype: Any = jnp.bfloat16


def _conv_init(key, cin: int, cout: int) -> jnp.ndarray:
    return jax.random.normal(key, (3, 3, cin, cout), jnp.float32) * np.sqrt(2.0 / (9 * cin))


def _bn_init(key, c: int) -> Params:
    kg, kb, km, kv = jax.random.split(key, 4)
    return {"gamma": 1.0 + 0.1 * jax.random.normal(kg, (c,), jnp.float32),
            "beta": 0.1 * jax.random.normal(kb, (c,), jnp.float32),
            "mean": 0.1 * jax.random.normal(km, (c,), jnp.float32),
            "var": jnp.exp(0.2 * jax.random.normal(kv, (c,), jnp.float32))}


def _layers(names_shapes, key) -> Params:
    """{name: conv kernel or norm}: an int is a norm's channels, a pair a
    convolution's (cin, cout)."""
    out = {}
    for i, (name, what) in enumerate(names_shapes):
        k = jax.random.fold_in(key, i)
        out[name] = _bn_init(k, what) if isinstance(what, int) else _conv_init(k, *what)
    return out


def _cvblock(key, c: int) -> Params:
    return _layers([("conv0", (c, c)), ("bn0", c), ("conv1", (c, c)), ("bn1", c)], key)


def init_denblock(key) -> Params:
    c0, c1, c2 = WIDTHS
    wide = 3 * FRAME_FEATURES
    ks = jax.random.split(key, 10)
    return {
        "inc": _layers([("conv0", (4, wide)), ("bn0", wide), ("conv1", (wide, c0)), ("bn1", c0)], ks[0]),
        "down0": {**_layers([("conv", (c0, c1)), ("bn", c1)], ks[1]), "cv": _cvblock(ks[2], c1)},
        "down1": {**_layers([("conv", (c1, c2)), ("bn", c2)], ks[3]), "cv": _cvblock(ks[4], c2)},
        "up2": {"cv": _cvblock(ks[5], c2), **_layers([("conv", (c2, 4 * c1))], ks[6])},
        "up1": {"cv": _cvblock(ks[7], c1), **_layers([("conv", (c1, 4 * c0))], ks[8])},
        # the last convolution's scale is cut: the residual of a seeded net stays a
        # fraction of the frame, so that results are not mostly clipped
        "out": {**_layers([("conv0", (c0, c0)), ("bn0", c0)], ks[9]),
                "conv1": 0.06 * _conv_init(jax.random.fold_in(ks[9], 7), c0, 3)},
    }


def init_fastdvdnet(key) -> Params:
    """Seeded weights (He's scale, seeded norm statistics): benchmark
    weights, as ``init_style_net``'s. A trained tree has the same keys."""
    k1, k2 = jax.random.split(key)
    return {"stage1": init_denblock(k1), "stage2": init_denblock(k2)}


def conv_weights(params: Params) -> int:
    """Convolution weights in a tree (1,237,320 a DenBlock)."""
    return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params) if len(a.shape) == 4)


# The forms the activations are carried in (models/layers.py, "The phase
# domain"): each scale's tensor as the space_to_depth image that fills the
# 128 lanes, made by the conv that makes it and read by the conv that reads
# it, never as the plain (B, H, W, c < 128) tensor, which a TPU stores and
# moves 128 / c times padded (at 32 frames of 540p the plain 32-channel
# float32 tensor alone is 7.9 GB). H and W divide by 4.
FULL = (2, 2)     # 540 x 960: 32 channels as (270, 480, 128), the 90 as (270, 480, 360)
HALF = (1, 2)     # 270 x 480: 64 channels as (270, 240, 128)
PLAIN = (1, 1)    # 135 x 240: 128 channels


def _conv(w, x, config, fi, fo, stride=1, cols=None):
    return conv2d_zero_phase({"w": w}, x, fi, fo, cols=cols, stride=stride,
                             compute_dtype=config.compute_dtype, out_dtype=jnp.float32)


def _bn_relu(bn, y, config, phases=PLAIN):
    """The folded norm and the ReLU on a conv's float32 result, a channel's
    terms tiled over its phases; handed on in the operand dtype of the conv
    that reads it."""
    scale = bn["gamma"] * lax.rsqrt(bn["var"] + BN_EPS)
    shift = bn["beta"] - bn["mean"] * scale
    n = phases[0] * phases[1]
    return jax.nn.relu(y * jnp.tile(scale, n) + jnp.tile(shift, n)).astype(config.compute_dtype)


def _cv(p, x, config, form):
    x = _bn_relu(p["bn0"], _conv(p["conv0"], x, config, form, form), config, form)
    return _bn_relu(p["bn1"], _conv(p["conv1"], x, config, form, form), config, form)


def _grouped_dense(w):
    """The (3, 3, 4, 90) kernel of the three-group convolution as the
    block-diagonal (3, 3, 12, 90) one of a dense convolution."""
    groups = w.shape[3] // FRAME_FEATURES
    cols = jnp.arange(w.shape[3]) // FRAME_FEATURES
    blocks = [jnp.where(cols == g, w, 0.0) for g in range(groups)]
    return jnp.concatenate(blocks, axis=2)


def _shuffle_cols(cout4: int) -> np.ndarray:
    """Column j = (2·dy + dx)·C + c of the re-ordered kernel is PyTorch's
    column c·4 + 2·dy + dx."""
    c = cout4 // 4
    return (np.arange(c)[None, :] * 4 + np.arange(4)[:, None]).reshape(-1)


def _phase_cols(form, cols: np.ndarray) -> np.ndarray:
    """``cols`` (a permutation of a conv's columns) applied within each of
    the phases a conv emitting at ``form`` lays its columns out in."""
    n = form[0] * form[1]
    return (np.arange(n)[:, None] * len(cols) + cols[None, :]).reshape(-1)


def apply_denblock(p: Params, f0, f1, f2, config: FastDvdConfig = FastDvdConfig()):
    """``DenBlock(f0, f1, f2, m)``: float NHWC frames in (H and W multiples
    of 4), ``f1`` less the net's residual out, float32 and unclipped.

    A ``conv → PixelShuffle(2)`` is a conv that emits its result's phases:
    with its columns in (dy, dx, c) order (:func:`_shuffle_cols`) the conv's
    result at one scale IS the (2, 2) phase image of the shuffled tensor at
    the next, so ``up1`` hands ``out`` its tensor with no rearrangement
    (its columns carry the W phase first: a reshape) and ``up2``'s is one
    interleave of rows (``depth_to_space`` by (2, 1))."""
    noise = jnp.full(f1.shape[:3] + (1,), config.sigma, f1.dtype)
    x = jnp.concatenate([f0, noise, f1, noise, f2, noise], axis=-1)
    inc, out = p["inc"], p["out"]
    x = _bn_relu(inc["bn0"], _conv(_grouped_dense(inc["conv0"]), x, config, PLAIN, FULL), config, FULL)
    x0 = _bn_relu(inc["bn1"], _conv(inc["conv1"], x, config, FULL, FULL), config, FULL)

    def down(q, t, fi, fo):
        t = _bn_relu(q["bn"], _conv(q["conv"], t, config, fi, fo, stride=2), config, fo)
        return _cv(q["cv"], t, config, fo)

    def up(q, t, form):
        w = q["conv"]
        return _conv(w, _cv(q["cv"], t, config, form), config, form, form,
                     cols=_phase_cols(form, _shuffle_cols(w.shape[3]))).astype(config.compute_dtype)

    x1 = down(p["down0"], x0, FULL, HALF)
    x2 = depth_to_space(up(p["up2"], down(p["down1"], x1, HALF, PLAIN), PLAIN), (2, 1))
    x1 = up(p["up1"], x1 + x2, HALF)
    b, h2, w4, c = x1.shape                   # (W phase, dy, dx, c) columns: the W phase into W
    x1 = x1.reshape(b, h2, 2 * w4, c // 2)
    x = _bn_relu(out["bn0"], _conv(out["conv0"], x0 + x1, config, FULL, FULL), config, FULL)
    return f1.astype(jnp.float32) - depth_to_space(_conv(out["conv1"], x, config, FULL, FULL), 2)


def apply_fastdvdnet(params: Params, window, config: FastDvdConfig = FastDvdConfig()):
    """The five-frame window at once, as published: ``window`` is
    (B, 5, H, W, 3) float frames in [0, 1]; returns the denoised centre
    (B, H, W, 3), float32 and unclipped. Four blocks a window."""
    d = [apply_denblock(params["stage1"], window[:, k], window[:, k + 1], window[:, k + 2], config)
         for k in range(3)]
    return apply_denblock(params["stage2"], *d, config)
