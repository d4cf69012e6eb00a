"""Compile-only memory rehearsal: each configuration's step program as the
frontend serves it, compiled for a described (not attached) v5e chip, with
XLA's memory_analysis(), and from it an estimate of what the cell holds on
the chip: the program's scratch, its state, and the batches the frontend
keeps there (``max_inflight`` in flight, one being staged, one being
fetched, and since PR 26 the packed words of each result, with the pack
program's scratch). It assumes the in-flight window full: a cell whose
host threads keep fewer batches in flight holds less (invert_1080p.bulk
since PR 26, PERF.md section 7j). A filter with per-session state
(``Filter.session_state``) is served through the engine's table body over
``max_sessions`` rows (``runtime/engine.py::Engine._table_body``): that body
and its table are what is compiled, not the one-session ``Filter.fn``.
Costs no chip time and is never a chip run: the estimate is arithmetic on a
compile, the chip's own reading is ``memory_peak_bytes`` of a run (the
estimate reads 5.45 and 2.23 GiB where the chip read 5.56 and 2.31 for
flow_720p.bulk and style_720p.bulk; PERF.md section 4).

A new cell is held to 25% of a chip's memory, or to 12.5% where the device
is busy at least 75% of the traced window. A compile cannot know the busy
share, so every estimate is printed against both floors, and the exit code
is 1 only when a configuration's estimate is under the lower one.

    JAX_PLATFORMS=cpu python3 -m chipbench.fit [config ...]
"""

import math
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import spec  # noqa: E402


FLOOR_GIB = 4.0          # 25% of a v5e chip's 16 GiB
BUSY_FLOOR_GIB = 2.0     # 12.5%: where busy_s >= 75% of window_s in the traced run


def served_step(filt, shape, max_sessions):
    """(step, abstract state, abstract extra operands) of the program the
    frontend's engine runs for ``filt`` at ``shape``: the body of
    ``Engine._build_step``. For a session-state filter the body is the
    engine's own table body over ``max_sessions`` rows, the state its
    table, and the step takes the batch's row map."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dvf_tpu.runtime.engine import Engine
    from dvf_tpu.utils.image import to_float, to_uint8

    body, state, extra = filt.fn, None, ()
    if filt.session_state:
        engine = Engine(filt, state_rows=max_sessions)
        engine._tabled = True                  # what compile() finds for such a filter
        body = engine._table_body(shape, np.uint8)
        state = jax.eval_shape(lambda: engine._fresh_state(shape, np.uint8))
        extra = (engine._row_map_aval(shape[0]),)
    elif filt.init_state is not None:
        state = jax.eval_shape(lambda: filt.init_state(shape, jnp.float32))

    def step(batch, state, *row_map):
        x = batch if filt.uint8_ok else to_float(batch, filt.compute_dtype)
        y, new_state = body(x, state, *row_map)
        return (y if y.dtype == jnp.uint8 else to_uint8(y)), new_state

    return step, state, extra


def fit(config_name):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from dvf_tpu.ops import get_filter

    config = spec.load_json("configs", config_name + ".json")
    g, serve = config["geometry"], config["serve"]
    shape = (serve["batch_size"], g["height"], g["width"], g["channels"])
    filt = get_filter(config["filter"]["name"], **config["filter"]["kwargs"])
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)

    step, state, extra = served_step(filt, shape, serve.get("max_sessions") or 1)
    batch = jax.ShapeDtypeStruct(shape, jnp.uint8, sharding=one)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        batch, on_chip(state), *on_chip(extra)).compile()
    m = compiled.memory_analysis()
    gib = 2.0 ** 30
    what = (f"table body over {serve['max_sessions']} session rows" if filt.session_state
            else "Filter.fn")
    print(f"[fit compile-only v5e:2x2, one chip] {config_name} batch {shape[0]} ({what}): "
          f"temp {m.temp_size_in_bytes / gib:.2f} GiB, arguments "
          f"{m.argument_size_in_bytes / gib:.2f} GiB, output "
          f"{m.output_size_in_bytes / gib:.2f} GiB, aliased "
          f"{m.alias_size_in_bytes / gib:.2f} GiB", flush=True)
    # arguments = one batch + the state (weights or the session table); the
    # state is held once, a batch max_inflight + 2 times
    one_batch = math.prod(shape)               # uint8
    state_bytes = max(0, m.argument_size_in_bytes - one_batch)
    depth = serve["max_inflight"] + 2
    held = (m.temp_size_in_bytes + state_bytes
            + depth * max(one_batch, m.output_size_in_bytes - state_bytes))
    # Since PR 26 a uint8 result on one device is packed into 32-bit words on
    # the chip (runtime/egress.py::egress_pack, a program of its own, run
    # behind the step): its scratch, and the words of every batch in flight
    # and of the one being fetched.
    out_aval = jax.eval_shape(step, batch, state, *extra)[0]
    pack_note = "result not packed"
    if out_aval.dtype == jnp.uint8 and (out_aval.shape[2] * out_aval.shape[3]) % 4 == 0:
        from dvf_tpu.runtime.egress import egress_pack, pack_table

        table = pack_table(out_aval.shape[2], out_aval.shape[3])
        p = jax.jit(egress_pack).lower(
            jax.ShapeDtypeStruct(out_aval.shape, jnp.uint8, sharding=one),
            jax.ShapeDtypeStruct(table.shape, jnp.bfloat16, sharding=one)
        ).compile().memory_analysis()
        held += p.temp_size_in_bytes + (depth - 1) * p.output_size_in_bytes
        pack_note = (f"pack temp {p.temp_size_in_bytes / gib:.2f} + {depth - 1} x words "
                     f"{p.output_size_in_bytes / gib:.2f}")
    held /= gib
    verdict = ("at or over both floors" if held >= FLOOR_GIB
               else f"UNDER the {FLOOR_GIB:.0f} GiB floor, at or over the {BUSY_FLOOR_GIB:.0f} "
                    f"GiB one that holds where the device is busy >= 75% of the window"
               if held >= BUSY_FLOOR_GIB else "UNDER both floors")
    print(f"[fit compile-only v5e:2x2, one chip] {config_name}: estimate held on the chip "
          f"= temp + state {state_bytes / gib:.2f} + (max_inflight + 2 = {depth}) batches + "
          f"{pack_note} = {held:.2f} GiB with the in-flight window full, against "
          f"{FLOOR_GIB:.0f} GiB (25%) and {BUSY_FLOOR_GIB:.0f} GiB (12.5%, busy >= 75%): {verdict}",
          flush=True)
    return held >= BUSY_FLOOR_GIB


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
