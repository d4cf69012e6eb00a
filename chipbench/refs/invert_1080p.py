"""Plain reference for the invert configurations: 255 - x, on the host.

Imports nothing of the program. ``control`` is the guarantee-breaker the
contract asks for where a configuration states exact integer arithmetic:
the same filter computed on a float path one step below float32
(bfloat16: x/255 -> 1 - x -> *255 -> round), which no longer returns every
byte exactly.
"""

import numpy as np


def make_params(seed, config):
    return None


def reference(frames, config, params=None):
    return [255 - f for f in frames]


def control(frames, config, params=None):
    import jax.numpy as jnp

    out = []
    for f in frames:
        x = jnp.asarray(f).astype(jnp.bfloat16) * jnp.bfloat16(1.0 / 255.0)
        y = (jnp.bfloat16(1.0) - x) * jnp.bfloat16(255.0)
        out.append(np.asarray(jnp.round(jnp.clip(y, 0, 255)).astype(jnp.uint8)))
    return out
