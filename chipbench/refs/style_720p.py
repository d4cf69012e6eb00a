"""Plain reference for style_720p: the Johnson et al. (2016) feed-forward
style net at widths c, 2c, 4c with n residual blocks, in straightforward
jax.numpy float32 at matmul precision "highest".

Imports nothing of the program and takes nothing the program made: the
weights are made here, on the device, in one jitted call from the seed
(``make_params``), and handed to the program as its ``params``. Biases and
norm scales are random too (the program's own init has them at 0 and 1,
which would hide a dropped bias).

Layer equations (NHWC, weights HWIO):
  x  = uint8 / 255
  cv(name, x, stride) = conv(reflect_pad(x, k//2), w, stride, VALID) + b
  inorm(name, y) = (y - mean_hw) * rsqrt(var_hw + 1e-5) * scale + bias
  x = relu(inorm(cv(stem 9x9 3->c)))
  x = relu(inorm(cv(down1 3x3 s2 c->2c))); x = relu(inorm(cv(down2 3x3 s2 2c->4c)))
  n times: h = relu(inorm(cv(res_a 3x3))); h = inorm(cv(res_b 3x3)); x = x + h
  x = relu(inorm(cv(up1 3x3 4c->2c, nearest_x2(x)))); likewise up2 2c->c
  y = 0.5 * (tanh(cv(out 9x9 c->3)) + 1);  out = round(clip(y, 0, 1) * 255)

``control`` is the same net with every convolution's operands rounded to
float8_e4m3fn under per-tensor amax scaling (the best case of an fp8 path,
the precision one step below the configuration's bfloat16), accumulated in
float32.
"""

import functools

import numpy as np


def _layers(config):
    kw = config["filter"]["kwargs"]
    c, n = kw["base_channels"], kw["n_residual"]
    convs = [("stem", 9, 3, c), ("down1", 3, c, 2 * c), ("down2", 3, 2 * c, 4 * c)]
    for i in range(n):
        convs += [(f"res{i}_a", 3, 4 * c, 4 * c), (f"res{i}_b", 3, 4 * c, 4 * c)]
    convs += [("up1", 3, 4 * c, 2 * c), ("up2", 3, 2 * c, c), ("out", 9, c, 3)]
    return convs


def _norm_name(conv_name):
    if conv_name.startswith("res"):
        return conv_name + "n"          # res0_a -> res0_an
    return conv_name + "_norm"


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
            kw_, kb, ks, kn = jax.random.split(jax.random.fold_in(key, i), 4)
            std = (2.0 / (k * k * cin)) ** 0.5
            p[name] = {"w": jax.random.normal(kw_, (k, k, cin, cout), jnp.float32) * std,
                       "b": jax.random.normal(kb, (cout,), jnp.float32) * 0.1}
            if name != "out":
                p[_norm_name(name)] = {
                    "scale": 1.0 + 0.1 * jax.random.normal(ks, (cout,), jnp.float32),
                    "bias": 0.1 * jax.random.normal(kn, (cout,), jnp.float32)}
        return p

    return build(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))


def _forward(params, frames_u8, config, precision):
    import jax
    import jax.numpy as jnp
    from jax import lax

    n_res = config["filter"]["kwargs"]["n_residual"]

    def q8(t):
        s = jnp.maximum(jnp.max(jnp.abs(t)), 1e-12) / 448.0
        return (t / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s

    def cv(name, x, stride=1):
        w, b = params[name]["w"], params[name]["b"]
        r = w.shape[0] // 2
        x = jnp.pad(x, ((0, 0), (r, r), (r, r), (0, 0)), mode="reflect")
        if precision == "fp8":
            x, w = q8(x), q8(w)
        elif precision == "bfloat16":
            x, w = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
        y = lax.conv_general_dilated(
            x, w, (stride, stride), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.float32)
        return y + b

    def inorm(name, y):
        p = params[_norm_name(name)]
        mean = jnp.mean(y, axis=(1, 2), keepdims=True)
        var = jnp.mean((y - mean) ** 2, axis=(1, 2), keepdims=True)
        return (y - mean) * lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]

    def up2(x):
        return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)

    x = frames_u8.astype(jnp.float32) * (1.0 / 255.0)
    x = jax.nn.relu(inorm("stem", cv("stem", x)))
    x = jax.nn.relu(inorm("down1", cv("down1", x, 2)))
    x = jax.nn.relu(inorm("down2", cv("down2", x, 2)))
    for i in range(n_res):
        h = jax.nn.relu(inorm(f"res{i}_a", cv(f"res{i}_a", x)))
        h = inorm(f"res{i}_b", cv(f"res{i}_b", h))
        x = x + h
    x = jax.nn.relu(inorm("up1", cv("up1", up2(x))))
    x = jax.nn.relu(inorm("up2", cv("up2", up2(x))))
    y = 0.5 * (jnp.tanh(cv("out", x)) + 1.0)
    return jnp.round(jnp.clip(y, 0.0, 1.0) * 255.0).astype(jnp.uint8)


@functools.lru_cache(maxsize=None)
def _jitted(config_key, precision):
    import json

    import jax

    config = json.loads(config_key)
    return jax.jit(lambda p, x: _forward(p, x, config, precision))


def _run(frames, config, params, precision):
    """One frame at a time (the float32 activations of one 720p frame are
    about a gigabyte), one compiled program for all of them."""
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
