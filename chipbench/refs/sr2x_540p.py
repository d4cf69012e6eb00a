"""Plain reference for sr2x_540p: the efficient sub-pixel CNN of Shi et al.
(CVPR 2016), l = 3 layers, widths 64 and 32, upscale r, in straightforward
jax.numpy float32 at matmul precision "highest". The result is r times
the input's height and width: (B, H, W, 3) uint8 in, (B, rH, rW, 3) uint8
out.

Imports nothing of the program and takes nothing the program made: the
weights are made here, on the device, in one jitted call from the seed
(``make_params``), and handed to the program as its ``params``. Biases are
random too (the program's own init has them at 0, which would hide a
dropped bias); the head's are centred on mid-grey so that the result is
not mostly clipped.

Layer equations (NHWC, weights HWIO, C = 3 colour channels, r = scale):
  x  = uint8 / 255
  cv(name, x) = conv_SAME_zero_pad(x, w) + b
  x = relu(cv(feat 5x5 3->64))
  x = relu(cv(map  3x3 64->32))
  x = cv(head 3x3 32->3 r^2)
  sub-pixel shuffle, DCR order:
      y[b, h r + i, w r + j, c] = x[b, h, w, (i r + j) 3 + c]
  out = round(clip(y, 0, 1) * 255)

Departures of the served network (dvf_tpu/models/espcn.py, which this
follows) from the paper: three RGB channels where the paper upscales the
luminance channel alone; ReLU where the paper has tanh; r = 2 where the
paper reports r = 3 and 4.

``control`` is the same net with every convolution's operands rounded to
float8_e4m3fn under per-tensor amax scaling (the best case of an fp8 path,
the precision one step below the configuration's bfloat16), accumulated in
float32.
"""

import functools

import numpy as np

CHANNELS = 3


def _layers(config):
    r = int(config["filter"]["kwargs"]["scale"])
    return [("feat", 5, CHANNELS, 64), ("map", 3, 64, 32), ("head", 3, 32, CHANNELS * r * r)]


def make_params(seed, config):
    """The weight pytree, float32 (the type the program is served them
    in; it casts per convolution), made on the device in one jitted call."""
    import jax
    import jax.numpy as jnp

    convs = _layers(config)

    @jax.jit
    def build(key):
        p = {}
        for i, (name, k, cin, cout) in enumerate(convs):
            kw_, kb = jax.random.split(jax.random.fold_in(key, i))
            head = name == "head"
            # He's scale through the ReLU layers; the head's is cut so that
            # its sums about the mid-grey bias span most of [0, 1] and clip
            # little of it.
            std = (2.0 / (k * k * cin)) ** 0.5 * (0.25 if head else 1.0)
            p[name] = {"w": jax.random.normal(kw_, (k, k, cin, cout), jnp.float32) * std,
                       "b": (0.5 if head else 0.0)
                       + 0.1 * jax.random.normal(kb, (cout,), jnp.float32)}
        return p

    return build(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))


def shuffle(x, r, order="ij"):
    """The sub-pixel shuffle. ``order="ji"`` is the transposed one (J for I):
    what the tests put in the program's place to see it read not correct."""
    import jax.numpy as jnp

    b, h, w, crr = x.shape
    c = crr // (r * r)
    x = x.reshape(b, h, w, r, r, c)               # [..., i, j, c]
    if order == "ji":
        x = jnp.swapaxes(x, 3, 4)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(b, h * r, w * r, c)


def _forward(params, frames_u8, config, precision):
    import jax
    import jax.numpy as jnp
    from jax import lax

    r = int(config["filter"]["kwargs"]["scale"])

    def q8(t):
        s = jnp.maximum(jnp.max(jnp.abs(t)), 1e-12) / 448.0
        return (t / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s

    def cv(name, x):
        w, b = params[name]["w"], params[name]["b"]
        if precision == "fp8":
            x, w = q8(x), q8(w)
        elif precision == "bfloat16":
            x, w = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
        y = lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.float32)
        return y + b

    x = frames_u8.astype(jnp.float32) * (1.0 / 255.0)
    x = jax.nn.relu(cv("feat", x))
    x = jax.nn.relu(cv("map", x))
    y = shuffle(cv("head", x), r)
    return jnp.round(jnp.clip(y, 0.0, 1.0) * 255.0).astype(jnp.uint8)


@functools.lru_cache(maxsize=None)
def _jitted(config_key, precision):
    import json

    import jax

    config = json.loads(config_key)
    return jax.jit(lambda p, x: _forward(p, x, config, precision))


def _run(frames, config, params, precision):
    """One frame at a time, one compiled program for all of them."""
    import json

    import jax

    fn = _jitted(json.dumps({"filter": config["filter"]}, sort_keys=True), precision)
    out = []
    with jax.default_matmul_precision("highest"):
        for f in frames:
            out.append(np.asarray(fn(params, np.asarray(f)[None]))[0])
    return out


def reference(frames, config, params):
    return _run(frames, config, params, "float32")


def control(frames, config, params):
    return _run(frames, config, params, "fp8")


def bfloat16_run(frames, config, params):
    """The reference with bfloat16 convolution operands: what a sound
    program's rounding looks like, for tests that have no program."""
    return _run(frames, config, params, "bfloat16")
