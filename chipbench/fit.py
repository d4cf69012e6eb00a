"""Compile-only memory rehearsal: each configuration's step program,
compiled for a described (not attached) v5e chip, with XLA's
memory_analysis(), and from it an estimate of what the cell holds on the
chip: the program's scratch, and the batches the frontend keeps there
(``max_inflight`` in flight, one being staged, one being fetched). Costs no
chip time and is never a chip run: the estimate is arithmetic on a compile,
the chip's own reading is ``memory_peak_bytes`` of a run (the estimate read
4.82 and 7.32 GiB where the chip read 4.82 and 7.39; ledger, PR 23). Exits
1 when a configuration's estimate is under the floor a cell has to hold.

    JAX_PLATFORMS=cpu python3 -m chipbench.fit [config ...]
"""

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import spec  # noqa: E402


FLOOR_GIB = 4.0          # 25% of a v5e chip's 16 GiB: a cell under it is refused


def fit(config_name):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from dvf_tpu.ops import get_filter
    from dvf_tpu.utils.image import to_float, to_uint8

    config = spec.load_json("configs", config_name + ".json")
    g = config["geometry"]
    shape = (config["serve"]["batch_size"], g["height"], g["width"], g["channels"])
    filt = get_filter(config["filter"]["name"], **config["filter"]["kwargs"])
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def step(batch, state):            # the body of Engine._build_step
        x = batch if filt.uint8_ok else to_float(batch, filt.compute_dtype)
        y, new_state = filt.fn(x, state)
        return (y if y.dtype == jnp.uint8 else to_uint8(y)), new_state

    state = None
    if filt.init_state is not None:
        state = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            jax.eval_shape(lambda: filt.init_state(shape, jnp.float32)))
    batch = jax.ShapeDtypeStruct(shape, jnp.uint8, sharding=one)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(batch, state).compile()
    m = compiled.memory_analysis()
    gib = 2.0 ** 30
    print(f"[fit compile-only v5e:2x2, one chip] {config_name} batch {shape[0]}: "
          f"temp {m.temp_size_in_bytes / gib:.2f} GiB, arguments "
          f"{m.argument_size_in_bytes / gib:.2f} GiB, output "
          f"{m.output_size_in_bytes / gib:.2f} GiB, aliased "
          f"{m.alias_size_in_bytes / gib:.2f} GiB", flush=True)
    depth = config["serve"]["max_inflight"] + 2
    held = (m.temp_size_in_bytes
            + depth * max(m.argument_size_in_bytes, m.output_size_in_bytes)) / gib
    print(f"[fit compile-only v5e:2x2, one chip] {config_name}: estimate held on the chip "
          f"= temp + (max_inflight + 2 = {depth}) batches = {held:.2f} GiB "
          f"({'at or over' if held >= FLOOR_GIB else 'UNDER'} the {FLOOR_GIB:.0f} GiB floor)",
          flush=True)
    return held >= FLOOR_GIB


def main(argv):
    jax_platforms = os.environ.get("JAX_PLATFORMS", "")
    if jax_platforms != "cpu":
        sys.stderr.write("chipbench.fit: run with JAX_PLATFORMS=cpu (it compiles for a "
                         "described chip and must not take an attached one)\n")
        return 2
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    names = argv or sorted({c["name"] for c in spec.benchmark()["configs"]})
    return 0 if all([fit(name) for name in names]) else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
