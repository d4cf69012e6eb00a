"""Device engine: traced, batched, mesh-sharded filter execution.

This replaces the distributed hot path of the reference end-to-end
(SURVEY.md §3.3): everything between "ROUTER.send frame to worker" and
"PULL.recv result" (distributor.py:236-238 → worker.py:35-67 →
distributor.py:258-264) becomes

    device_put(batch)  →  one jitted sharded program  →  async fetch

Key TPU-first choices:
- **uint8 on the wire, both directions.** Frames cross host↔device as
  uint8 NHWC (¼ the bytes of float32 — PCIe/ICI bandwidth is the scarce
  resource, SURVEY.md §7 hard part 1). The cast to the filter's compute
  dtype happens on device, fused into the filter program.
- **Donation.** The input batch and filter state are donated, so steady
  state allocates nothing.
- **Async dispatch.** `submit` returns un-materialized `jax.Array`s; JAX's
  async dispatch pipelines host staging of batch k+1 under device compute
  of batch k — the double-buffering the reference approximates with
  threads+queues falls out of the runtime.
- **Static shapes.** One (batch, H, W, C) signature = one compilation;
  the assembler pads short batches (`valid` mask) rather than re-tracing.
- **Temporal state is a table of sessions.** A filter with per-session
  state (``Filter.session_state``) gets one state row per session,
  ``[state_rows, …]`` per leaf, donated through every step like any
  state. The step takes the frames and a small int32 row map saying
  which table row each batch row continues; which sessions share a
  batch, and with how many rows each, is data and never shape.
"""

from __future__ import annotations

import collections
import dataclasses
import os
import re
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dvf_tpu.api.filter import Filter, session_leaves
from dvf_tpu.parallel.halo import spatial_filter
from dvf_tpu.parallel.mesh import batch_pspec, batch_sharding, make_mesh, replicated
from dvf_tpu.utils.image import to_float, to_uint8


# compile()-time D2H and step calibrations are skipped above this batch
# size: each is one more blocking whole-batch pass inside compile(). The
# value has not been re-derived on a real host↔device link; at it,
# invert_1080p at batch 64 (398 MB) gets neither calibration (ROADMAP S3).
_D2H_CALIBRATION_CAP_BYTES = 128 * 1024 * 1024


def _body_dtype(filt: Filter, in_dtype):
    """The dtype a filter's body (and its state) sees for a batch of
    ``in_dtype``: uint8 frames are cast to ``compute_dtype`` on device
    unless the filter consumes uint8 directly."""
    if np.dtype(in_dtype) == np.uint8 and not filt.uint8_ok:
        return filt.compute_dtype
    return in_dtype


def session_rows(batch: int, state_rows: int) -> int:
    """How many session states one step gathers out of the table (and
    writes back): a batch cannot hold more sessions than rows, nor than
    the table has."""
    return min(int(batch), int(state_rows))


def device_row_map(rows: Optional[np.ndarray], batch: int,
                   state_rows: int) -> np.ndarray:
    """The step's row map, from the serve path's per-batch-row form.

    ``rows`` is int32 ``[2, batch]``: ``rows[0, i]`` the state row (the
    session) batch row i belongs to, -1 for a pad row; ``rows[1, i]`` 1
    on a session's first frame since its row was bound (the row restarts
    from ``init_state`` there). None is one stream's consecutive frames
    in state row 0, every row valid — what the single-stream executors
    submit.

    Returned: int32 ``[batch + 3 T]``, T = :func:`session_rows`:
    ``pred[batch]`` as ``Filter.rows`` reads it, then per gathered
    session ``t``: its table row (-1 = entry unused: read row 0, write
    nothing), its fresh mark, and the batch row after which its state is
    stored. A pad row reads entry 0 and is nobody's predecessor.
    """
    t_n = session_rows(batch, state_rows)
    m = np.zeros(batch + 3 * t_n, np.int32)
    pred, trow = m[:batch], m[batch:batch + t_n]
    tfresh, tlast = m[batch + t_n:batch + 2 * t_n], m[batch + 2 * t_n:]
    trow[:] = -1
    if rows is None:
        pred[1:] = np.arange(t_n, t_n + batch - 1)
        trow[0], tlast[0] = 0, batch - 1
        return m
    entry: Dict[int, int] = {}
    last: Dict[int, int] = {}
    for i in range(batch):
        r = int(rows[0, i])
        if r < 0:
            continue
        if r >= state_rows:   # the device would drop the write in silence
            raise ValueError(f"state row {r} of batch row {i} is outside "
                             f"the table's {state_rows} rows")
        j = last.get(r)
        if j is None:
            t = entry[r] = len(entry)
            trow[t], tfresh[t], pred[i] = r, rows[1, i], t
        else:
            pred[i] = t_n + j
        last[r] = i
    for r, t in entry.items():
        tlast[t] = last[r]
    return m


@dataclasses.dataclass
class EngineStats:
    batches: int = 0
    frames: int = 0
    compile_count: int = 0
    replicated_batches: int = 0  # batches every device of a data>1 mesh
    #   computed WHOLE because the batch size did not divide the data
    #   axis (parallel.mesh.batch_pspec keeps that correct, not fast)


class Engine:
    """Compiles and runs one filter over one mesh at one batch signature."""

    def __init__(
        self,
        filt: Filter,
        mesh: Optional[Mesh] = None,
        out_uint8: bool = True,
        chaos=None,
        op_chain: Optional[str] = None,
        calibration_seed: Optional[dict] = None,
        state_rows: int = 1,
    ):
        self.filter = filt
        if state_rows < 1:
            raise ValueError("state_rows must be >= 1")
        self.state_rows = int(state_rows)  # sessions whose temporal state
        #   the device table holds (Filter.session_state filters only;
        #   the serve frontend sizes it with max_sessions). 1 = the
        #   single-stream executors: one stream, table row 0.
        self.mesh = mesh if mesh is not None else make_mesh()
        self.out_uint8 = out_uint8
        self.op_chain = op_chain if op_chain is not None else filt.name
        #   the signature-key spelling of what this engine computes
        #   (runtime.signature.canonical_op_chain where parseable) —
        #   what the compiled-program pool and the multi-signature
        #   frontend key this engine by
        self.freed = False  # set by free(): device buffers released,
        #   submit is a programming error afterwards
        self.chaos = chaos  # resilience.chaos.FaultPlan; armed test/replay
        #   runs only — submit paths fire the "oom"/"compute" injection
        #   sites through it (zero overhead when None)
        self.stats = EngineStats()
        self._exec_filter = filt   # possibly halo-wrapped in compile()
        self._step = None
        self._tabled = False  # compile() found a session-state filter:
        #   _state is the session table and the step takes a row map
        self._signature: Optional[Tuple] = None
        self._state: Any = None
        self._sharding = None  # chosen per batch signature in compile()
        self._batch_replicated = False  # compile() found the batch does
        #   not divide the mesh's data axis (see EngineStats)
        self._replicated = replicated(self.mesh)
        self.calibration_seed = calibration_seed  # optional persisted
        #   {h2d_block_ms, d2h_block_ms, step_block_ms} triple (plan
        #   cache, keyed per backend+topology — control.plan_cache):
        #   when present AND it carries real h2d+step numbers, compile()
        #   adopts it and SKIPS the blocking re-measurement passes — a
        #   warm restart pays trace+compile+warmup only. d2h may be
        #   None in a valid seed (measured above the calibration cap).
        self.calibration_seeded = False  # did the last compile() adopt
        #   the seed (vs measure)? — what the ledger's compile events
        #   record so warm-start behavior is auditable
        self.h2d_block_ms: Optional[float] = None  # calibrated blocking
        #   whole-batch device_put at the compiled signature (measured on
        #   compile()'s warmup put) — the un-overlapped transfer cost the
        #   streamed ingest path's overlap_efficiency is judged against
        #   (obs.metrics.IngestStats)
        self.d2h_block_ms: Optional[float] = None  # the egress mirror:
        #   one blocking whole-batch materialization (np.asarray + copy
        #   into a host destination) of the warmup output — the
        #   serialized fetch cost the streamed egress path's
        #   overlap_efficiency is judged against (obs.metrics.EgressStats)
        self.step_block_ms: Optional[float] = None  # calibrated blocking
        #   execution of ONE compiled step at the signature (measured on
        #   a post-warmup run in compile(), so trace/compile time stays
        #   out of it) — the MEASURED per-batch tick cost the bucket
        #   scheduler's EDF/cost score starts from before it has live
        #   samples (TVM's measured-stage discipline: pick costs from
        #   measurements, not guesses). Skipped (None) above the
        #   calibration size cap.
        self.out_shape: Optional[Tuple[int, ...]] = None  # compiled output
        self.out_dtype = None                             # signature — what
        #   the egress fetcher sizes its host slabs from (set by compile())
        self.step_donates_input: Optional[bool] = None  # whether the
        #   compiled step aliases its input batch: False where the result
        #   has another geometry or dtype (set by _build_step)
        self.kernel_plan: Optional[dict] = None  # which kernel of the
        #   repo's own the compiled step runs and how it tiled it, as the
        #   filter states it (Filter.kernel_plan) for the shape one device
        #   sees; None for XLA's own ops (set by _build_step)
        self._out_sharding = None
        self.last_compile_ms: Optional[float] = None  # wall duration of
        #   the most recent compile() (trace + XLA compile + warmup +
        #   calibrations — the whole admission-visible cost): what the
        #   reconfiguration ledger's compile events and the
        #   dvf_compile_ms histogram record
        self.step_conv_ops: Optional[List[str]] = None  # the compiled
        #   step's convolutions by instruction name (conv_op_names), for
        #   a filter that states its network; None otherwise
        self.state_bytes: int = 0  # measured device residency of the
        #   filter state (summed leaf nbytes at compile) — the per-
        #   engine half of the memory accounting; free() folds it into
        #   the process-wide freed counter
        # Double-buffered program swap (stall-free reconfiguration):
        # prepare_swap() compiles a successor engine ASIDE (background
        # thread, nothing blocked), commit_swap() adopts its program
        # fields in place between ticks. The lock serializes staging
        # bookkeeping and the commit's field swing against run_probe
        # (the audit worker must never read a half-adopted program).
        self._swap_lock = threading.RLock()
        self._staged: Optional["Engine"] = None
        self._preparing: Dict[Tuple, threading.Event] = {}
        self.swap_count = 0
        self.last_swap: Optional[dict] = None

    # ------------------------------------------------------------------

    def _pick_exec_filter(self, filt: Filter, batch_shape,
                          in_dtype) -> "Filter":
        """Choose the executed filter + H-axis sharding for this signature.

        GSPMD's automatic spatial partitioning of stencil ops is distrusted
        on this toolchain (wrong halo values in some conv layouts), so an
        H-sharded mesh routes stencil filters through the EXPLICIT
        ppermute halo exchange (parallel.halo.spatial_filter). Pointwise
        filters (halo == 0) have no halo traffic and stay on plain GSPMD
        sharding. Filters that can't halo-exchange (stateful, unknown
        radius, slab thinner than the radius, indivisible H) keep H
        replicated — correct first, the inefficiency is logged. Whatever
        stays on GSPMD and contains a Mosaic kernel is partitioned by
        hand (:meth:`_manual_if_mosaic`).
        """
        pspec = batch_pspec(self.mesh, batch_shape)
        if pspec[1] == "space" and filt.halo != 0:
            # (A pointwise filter needs no halo exchange even when
            # stateful — state placement is already handled by
            # state_pspecs / replication — so statefulness alone must not
            # cost it H-axis parallelism or spam the warning below.)
            n_space = dict(zip(self.mesh.axis_names,
                               self.mesh.devices.shape))["space"]
            can_halo = (
                not filt.stateful
                and filt.halo is not None
                and batch_shape[1] // n_space > filt.halo
            )
            if can_halo:
                return spatial_filter(
                    filt, self.mesh, data_sharded=(pspec[0] == "data")
                )
            # Fall back to replicating H (shard batch only).
            print(
                f"[engine] filter {filt.name!r} can't halo-shard H "
                f"(stateful={filt.stateful}, halo={filt.halo}, "
                f"H={batch_shape[1]}, space={n_space}); replicating H",
                file=sys.stderr,
            )
            self._sharding = NamedSharding(
                self.mesh, P(pspec[0], None, None, None))
        return self._manual_if_mosaic(filt, batch_shape, in_dtype)

    def _manual_if_mosaic(self, filt: Filter, batch_shape,
                          in_dtype) -> "Filter":
        """GSPMD cannot partition a Mosaic custom call ("Mosaic kernels
        cannot be automatically partitioned" — every Pallas-default filter
        failed to compile on the four-chip data mesh in PR 21's chip run;
        interpret mode on the CPU has no such call, so the virtual-device
        suite never saw it). On a multi-device mesh a filter whose body
        contains a ``pallas_call`` therefore runs under an explicit
        ``shard_map``: a stateless one on the batch sharding already
        chosen (rows of a batch are independent — the contract the
        multi-tenant batcher already relies on), a stateful one (flow: a
        frame needs its predecessor, which may live on another shard)
        with every device computing the whole batch, said and counted
        like any replicated batch."""
        if self.mesh.devices.size == 1:
            return filt
        x_dtype = _body_dtype(filt, in_dtype)
        state = (jax.eval_shape(lambda: filt.init_state(batch_shape, x_dtype))
                 if filt.stateful else None)
        jaxpr = str(jax.make_jaxpr(filt.fn)(
            jax.ShapeDtypeStruct(tuple(batch_shape), x_dtype), state))
        if "pallas_call" not in jaxpr or "shard_map" in jaxpr:
            return filt
        if filt.stateful:
            self._sharding = self._replicated
            self._batch_replicated = True
            print(f"[engine] filter {filt.name!r} holds state and a Mosaic "
                  f"kernel: every device computes the whole batch (counted "
                  f"as replicated_batches)", file=sys.stderr)
            spec = P()
            fn = jax.shard_map(filt.fn, mesh=self.mesh, in_specs=(spec, spec),
                               out_specs=(spec, spec), check_vma=False)
            if filt.rows is not None:
                return dataclasses.replace(
                    filt, name=f"manual({filt.name})", fn=fn, specialize=None,
                    rows=jax.shard_map(
                        filt.rows, mesh=self.mesh, in_specs=(spec,) * 3,
                        out_specs=(spec, spec), check_vma=False))
        else:
            spec = self._sharding.spec
            rows = jax.shard_map(lambda batch: filt.fn(batch, None)[0],
                                 mesh=self.mesh, in_specs=spec,
                                 out_specs=spec, check_vma=False)

            def fn(batch, state):
                return rows(batch), state
        return dataclasses.replace(filt, name=f"manual({filt.name})", fn=fn,
                                   specialize=None)

    def _build_step(self, batch_shape, in_dtype):
        filt = self._exec_filter
        out_uint8 = self.out_uint8
        # A session-state filter's body also takes the batch's row map.
        body, map_avals = ((self._table_body(batch_shape, in_dtype),
                            (self._row_map_aval(batch_shape[0]),))
                           if self._tabled else (filt.fn, ()))

        def step(batch, state, *row_map):
            if batch.dtype == jnp.uint8 and not filt.uint8_ok:
                x = to_float(batch, filt.compute_dtype)
            else:
                x = batch
            y, new_state = body(x, state, *row_map)
            if out_uint8 and y.dtype != jnp.uint8:
                y = to_uint8(y)
            return y, new_state

        # State placement: the filter's declared PartitionSpecs (neural
        # filters shard their weight pytree over 'model' — tensor
        # parallelism), else replicate (temporal state is small).
        state_shardings = self._state_shardings() if filt.stateful else None
        # Donate the input batch only when the output can actually reuse
        # its buffer — a geometry-changing filter (super_resolution) can't,
        # and XLA would warn "donated buffers were not usable" every run.
        out_aval = jax.eval_shape(
            step,
            jax.ShapeDtypeStruct(tuple(batch_shape), np.dtype(in_dtype)),
            self._state,  # built just before _build_step in compile()
            *map_avals,
        )[0]
        self.step_donates_input = (
            out_aval.shape == tuple(batch_shape)
            and out_aval.dtype == np.dtype(in_dtype))
        donate = (0, 1) if self.step_donates_input else (1,)
        self.kernel_plan = (
            filt.kernel_plan(self._sharding.shard_shape(tuple(batch_shape)))
            if filt.kernel_plan is not None else None)
        return jax.jit(
            step,
            in_shardings=(self._sharding, state_shardings)
            + (self._replicated,) * len(map_avals),
            out_shardings=(self._sharding, state_shardings),
            donate_argnums=donate,
        )

    def _row_map_aval(self, batch: int):
        return jax.ShapeDtypeStruct(
            (batch + 3 * session_rows(batch, self.state_rows),), np.int32)

    def _table_body(self, batch_shape, in_dtype):
        """The body of a session-state filter's step: gather the batch's
        sessions out of the table, run ``Filter.rows``, store each
        session's state after its last row. Everything about who is in
        the batch arrives in ``row_map`` (:func:`device_row_map`)."""
        filt = self._exec_filter
        bsz = int(batch_shape[0])
        t_n = session_rows(bsz, self.state_rows)
        n_rows = self.state_rows
        state_dtype = _body_dtype(filt, in_dtype)

        def body(x, table, row_map):
            pred = row_map[:bsz]
            trow = row_map[bsz:bsz + t_n]
            fresh = row_map[bsz + t_n:bsz + 2 * t_n] > 0
            tlast = row_map[bsz + 2 * t_n:]

            def gathered(leaf, init, per_session):
                if not per_session:   # stored once (a chain's weights)
                    return leaf
                got = jnp.take(leaf, jnp.maximum(trow, 0), axis=0)
                mark = fresh.reshape((t_n,) + (1,) * (got.ndim - 1))
                return jnp.where(mark, jnp.asarray(init)[None], got)

            init = filt.init_state(batch_shape, state_dtype)
            tabled = session_leaves(filt, init)
            with jax.named_scope("state_table"):
                prev = jax.tree.map(gathered, table, init, tabled)
            y, row_states = filt.rows(x, prev, pred)
            # Unused entries point past the table, each at an index of
            # its own (the scatter is told they are unique): dropped.
            with jax.named_scope("state_table"):
                dst = jnp.where(trow >= 0, trow,
                                n_rows + jnp.arange(t_n, dtype=trow.dtype))
                new_table = jax.tree.map(
                    lambda leaf, rs, per_session: leaf.at[dst].set(
                        jnp.take(rs, tlast, axis=0).astype(leaf.dtype),
                        mode="drop", unique_indices=True)
                    if per_session else leaf,
                    table, row_states, tabled)
            return y, new_table

        return body

    def _run_step(self, batch, rows: Optional[np.ndarray] = None):
        """One step on a device-resident batch, the state threaded."""
        if self._tabled:
            y, self._state = self._step(
                batch, self._state,
                device_row_map(rows, batch.shape[0], self.state_rows))
        else:
            y, self._state = self._step(batch, self._state)
        return y

    def compiled_step(self):
        """The step's executable (its text, cost and memory analyses):
        the one the engine calls where ``compile`` built it ahead of
        time, else one more lowering beside the jit's own, which the
        persistent cache answers for any program worth the wait."""
        if isinstance(self._step, jax.stages.Compiled):
            return self._step
        return self._step.lower(*self.step_operands()).compile()

    def step_operands(self) -> Tuple:
        """Abstract operands of the compiled step, for ``lower()``."""
        shape, dtype = self._signature
        ops = (jax.ShapeDtypeStruct(shape, dtype), self._state)
        return ops + ((self._row_map_aval(shape[0]),) if self._tabled else ())

    def _fresh_state(self, batch_shape, dtype):
        """The filter's initial state on the device; for a session-state
        filter, its per-session leaves (``session_leaves``) stacked
        ``state_rows`` times into the table."""
        ef = self._exec_filter
        if not ef.stateful:
            return None
        state = ef.init_state(batch_shape, _body_dtype(ef, dtype))
        if self._tabled:
            n = self.state_rows
            state = jax.tree.map(
                lambda a, per_session: jnp.broadcast_to(
                    jnp.asarray(a)[None], (n,) + jnp.shape(a))
                if per_session else a, state, session_leaves(ef, state))
        return jax.device_put(state, self._state_shardings())

    def state_row_bytes(self) -> int:
        """Bytes one session's row of the state table holds (its
        per-session leaves, ``session_leaves``); 0 without a table. What
        a step reads and writes back for each session in its batch."""
        if not self._tabled or self._state is None:
            return 0
        mine = session_leaves(self._exec_filter, self._state)
        return sum(
            int(np.prod(leaf.shape[1:])) * np.dtype(leaf.dtype).itemsize
            for leaf, per_session in zip(jax.tree.leaves(self._state),
                                         jax.tree.leaves(mine))
            if per_session)

    def _state_shardings(self):
        """Sharding (tree or single) for the state pytree; also valid as a
        jit in/out_shardings prefix and a device_put target."""
        if self._exec_filter.state_pspecs is not None:
            return jax.tree.map(
                lambda s: NamedSharding(self.mesh, s),
                self._exec_filter.state_pspecs(),
                is_leaf=lambda x: isinstance(x, P),
            )
        return self._replicated

    def compile(self, batch_shape: Tuple[int, ...], dtype=np.uint8) -> None:
        """Trace + compile for a fixed (B,H,W,C) signature; builds state."""
        sig = (tuple(batch_shape), np.dtype(dtype))
        if sig == self._signature:
            return
        t_compile0 = time.perf_counter()
        self._sharding = batch_sharding(self.mesh, batch_shape)
        n_data = dict(zip(self.mesh.axis_names,
                          self.mesh.devices.shape)).get("data", 1)
        self._batch_replicated = (n_data > 1
                                  and self._sharding.spec[0] is None)
        if self._batch_replicated:
            print(f"[engine] batch {batch_shape[0]} does not divide the "
                  f"data axis ({n_data}): every device computes the whole "
                  f"batch (counted as replicated_batches)", file=sys.stderr)
        # Mesh-aware body swap first (e.g. style transfer → shard_map'd
        # Megatron TP forward when the mesh has a model axis) …
        base = self.filter
        if base.specialize is not None:
            specialized = base.specialize(self.mesh, tuple(batch_shape))
            if specialized is not None:
                base = specialized
        # … then the H-axis halo routing — see _pick_exec_filter.
        self._exec_filter = self._pick_exec_filter(base, batch_shape, dtype)
        self._tabled = self._exec_filter.session_state
        if self._exec_filter.temporal and not self._tabled \
                and self.state_rows > 1:
            raise ValueError(
                f"filter {self._exec_filter.name!r} carries temporal state "
                f"but defines no many-session body (Filter.rows): it can "
                f"only run with state_rows=1")

        def fresh_state():
            return self._fresh_state(batch_shape, dtype)

        self._state = fresh_state()
        self._step = self._build_step(batch_shape, dtype)
        self._signature = sig
        self.stats.compile_count += 1
        # A filter that states its network (Filter.model) has its step
        # compiled ahead of time: the executable the engine calls is the
        # one whose text names its convolutions for a trace's reader
        # (the bucket row's ``model.conv_ops``), and nothing is lowered
        # or compiled twice.
        self.step_conv_ops = None
        if self._exec_filter.model is not None:
            self._step = self.compiled_step()
            self.step_conv_ops = conv_op_names(self._step.as_text())
        # Warm the compile cache so the first real batch doesn't eat compile
        # time; the warmup consumes (donates) the state, so rebuild it —
        # stateful filters must still see a pristine first batch. A second
        # put at the same signature is the H2D calibration sample: one
        # blocking whole-batch transfer, measured AFTER the first put has
        # paid any backend/allocator warmup (timing the first put
        # over-reports the steady-state cost by an order of magnitude on
        # some backends, which would mislead the streamed-ingest
        # cheap-transfer fallback).
        zeros = np.zeros(batch_shape, dtype=dtype)
        warm = jax.device_put(zeros, self._sharding)
        jax.block_until_ready(warm)
        # Persisted-calibration fast path (auto-plan plane): a seed with
        # real h2d+step numbers — measured earlier on this same
        # backend+topology and loaded from the plan cache — replaces
        # every timed pass below. The warmup put and warmup step still
        # run (they ARE the compile warm + output-signature discovery);
        # what a warm restart skips is the blocking measurement choreo:
        # the second put, the whole-batch D2H copy, and the extra
        # donated step with its two state rebuilds.
        seed = self.calibration_seed
        seeded = (isinstance(seed, dict)
                  and isinstance(seed.get("h2d_block_ms"), (int, float))
                  and isinstance(seed.get("step_block_ms"), (int, float)))
        self.calibration_seeded = seeded
        if seeded:
            self.h2d_block_ms = float(seed["h2d_block_ms"])
            dummy = warm
        else:
            del warm
            t0 = time.perf_counter()
            dummy = jax.device_put(zeros, self._sharding)
            jax.block_until_ready(dummy)
            self.h2d_block_ms = (time.perf_counter() - t0) * 1e3
        out = self._run_step(dummy)
        out.block_until_ready()
        # Output signature + sharding: what the egress fetcher lays its
        # per-shard host slabs out from (the mirror of input_sharding).
        self.out_shape = tuple(out.shape)
        self.out_dtype = np.dtype(out.dtype)
        self._out_sharding = out.sharding
        # D2H calibration: one blocking materialize-and-copy of the warmup
        # output — the serialized fetch the monolithic collect path pays
        # per batch. Unlike H2D there is no second-sample dance (jax
        # caches the first np.asarray, so a re-measure would clock a
        # cached view); the host destination is pre-touched so allocator
        # warmup stays out of the number. Skipped above the size cap
        # (_D2H_CALIBRATION_CAP_BYTES): d2h_block_ms stays None there.
        if seeded:
            # d2h may legitimately be None in a valid seed (the original
            # measurement was above the calibration cap) — reproduce it.
            d2h = seed.get("d2h_block_ms")
            self.d2h_block_ms = (float(d2h)
                                 if isinstance(d2h, (int, float)) else None)
        elif out.nbytes <= _D2H_CALIBRATION_CAP_BYTES:
            dst = np.empty(out.shape, out.dtype)
            dst.fill(0)
            t0 = time.perf_counter()
            np.copyto(dst, np.asarray(out))
            self.d2h_block_ms = (time.perf_counter() - t0) * 1e3
            del dst
        else:
            self.d2h_block_ms = None
        self._state = fresh_state()
        # Tick-cost calibration: one more blocking step, AFTER the warmup
        # compiled it — a measured per-batch execution cost for the
        # multi-signature bucket scheduler (its EDF/cost score needs a
        # starting estimate before live ticks arrive; guessing would let
        # a cheap bucket starve behind an expensive one). The step
        # donates its operands, so state is rebuilt once more. Skipped
        # above the calibration cap for the same reason D2H is.
        if seeded:
            self.step_block_ms = float(seed["step_block_ms"])
        elif zeros.nbytes <= _D2H_CALIBRATION_CAP_BYTES:
            cal = jax.device_put(zeros, self._sharding)
            t0 = time.perf_counter()
            out2 = self._run_step(cal)
            out2.block_until_ready()
            self.step_block_ms = (time.perf_counter() - t0) * 1e3
            del cal, out2
            self._state = fresh_state()
        else:
            self.step_block_ms = None
        self.last_compile_ms = (time.perf_counter() - t_compile0) * 1e3
        self.state_bytes = _tree_device_bytes(self._state)

    # ------------------------------------------------------------------

    def ensure_compiled(self, batch_shape: Tuple[int, ...],
                        dtype=np.uint8) -> None:
        """Compile for a signature if not already (idempotent) — the
        streamed-ingest assembler calls this before reading
        ``input_sharding`` to lay out its per-shard staging slabs."""
        self.compile(tuple(batch_shape), dtype)

    @property
    def signature(self) -> Optional[Tuple]:
        """The compiled ``((B, H, W, C), dtype)`` signature, or None
        before the first compile — what the serving frontend's
        admission-time geometry check compares a declared stream shape
        against (serve.ServeFrontend.open_stream)."""
        return self._signature

    @property
    def signature_key(self):
        """The CANONICAL ``(op_chain, geometry, dtype)`` serving
        signature (runtime.signature.SignatureKey) — dtype and geometry
        spellings normalized so equal programs can't miss the
        compiled-program pool or the persistent compilation cache by
        spelling. None before the first compile."""
        from dvf_tpu.runtime.signature import engine_signature_key

        return engine_signature_key(self)

    @property
    def input_sharding(self):
        """The batch sharding the compiled step actually expects (set by
        compile(); may differ from the naive batch_sharding when the
        halo router replicated H). None before the first compile."""
        return self._sharding

    @property
    def output_sharding(self):
        """The compiled step's OUTPUT sharding (taken from the warmup
        result) — what the egress fetcher derives its per-shard fetch
        layout from. None before the first compile."""
        return self._out_sharding

    def submit(self, batch: np.ndarray,
               rows: Optional[np.ndarray] = None) -> jax.Array:
        """Dispatch one host batch; returns the (async) on-device result.

        The filter state (if any) is threaded internally across calls —
        device-resident, never copied to host (SURVEY.md §7 hard part 4).
        ``rows`` (session-state filters; ignored otherwise) says which
        session each batch row belongs to — see :func:`device_row_map`;
        None reads the batch as one stream's consecutive frames.
        """
        if self.freed:
            raise RuntimeError(
                "engine was freed (program-pool eviction); re-admission "
                "builds a fresh engine through the pool")
        if self._signature != (tuple(batch.shape), np.dtype(batch.dtype)):
            self.compile(batch.shape, batch.dtype)
        if self.chaos is not None:
            self.chaos.fire("oom")
            self.chaos.fire("compute")
        x = jax.device_put(batch, self._sharding)
        y = self._run_step(x, rows)
        self._count_batch(batch.shape[0])
        return y

    def submit_resident(self, batch: jax.Array,
                        rows: Optional[np.ndarray] = None) -> jax.Array:
        """Serving entry for an already-device-resident batch: the
        streamed ingest path (runtime/ingest.py) shipped the shards while
        they decoded and assembled the mesh array itself, so the internal
        ``device_put`` of :meth:`submit` is skipped — the transfer cost
        it would serialize here was already hidden under decode and the
        previous batch's compute. State threading, donation, and stats
        are identical to :meth:`submit`.
        """
        if self.freed:
            raise RuntimeError(
                "engine was freed (program-pool eviction); re-admission "
                "builds a fresh engine through the pool")
        if self._signature != (tuple(batch.shape), np.dtype(batch.dtype)):
            self.compile(batch.shape, np.dtype(batch.dtype))
        if self.chaos is not None:
            self.chaos.fire("oom")
            self.chaos.fire("compute")
        y = self._run_step(batch, rows)
        self._count_batch(batch.shape[0])
        return y

    def _count_batch(self, frames: int) -> None:
        self.stats.batches += 1
        self.stats.frames += frames
        self.stats.replicated_batches += self._batch_replicated

    def run_device_resident(self, batch: jax.Array) -> jax.Array:
        """Alias of :meth:`submit_resident` kept for the benchmark inner
        loops, which predate the serving-path name."""
        return self.submit_resident(batch)

    def run_probe(self, batch: np.ndarray) -> np.ndarray:
        """Audit-plane probe entry (obs.audit): run the compiled step on
        ``batch`` WITHOUT touching serving state or stats — no state
        threading (the returned state is discarded; stateless filters
        only, where it is None anyway), no batch/frame counters, no
        chaos sites. Safe to call concurrently with the serving
        dispatch: jitted executables are thread-safe and the probe's
        operands are its own fresh device buffers. Blocking
        (materializes the result) — callers are off the hot path by
        contract (swap guards, divergence probes)."""
        # Under the swap lock: commit_swap swings every program field
        # as one atomic update, and a probe racing it must read either
        # the old program wholesale or the new one — never a mix.
        with self._swap_lock:
            if self.freed:
                raise RuntimeError("cannot probe a freed engine")
            if self._step is None or self._signature is None:
                raise RuntimeError("cannot probe an uncompiled engine")
            if self._exec_filter.stateful:
                raise ValueError(
                    f"cannot probe stateful filter {self.filter.name!r}: "
                    f"the probe would consume (donated) live temporal "
                    f"state")
            if (tuple(batch.shape),
                    np.dtype(batch.dtype)) != self._signature:
                raise ValueError(
                    f"probe batch {batch.shape}/{batch.dtype} does not "
                    f"match the compiled signature {self._signature}")
            x = jax.device_put(np.ascontiguousarray(batch),
                               self._sharding)
            step, state = self._step, self._state
        y, _ = step(x, state)
        return np.asarray(y)

    def cost_analysis(self) -> Optional[dict]:
        """XLA's own cost model for the compiled step: total FLOPs and HBM
        bytes accessed per batch. This is what the per-config roofline
        fractions in the bench tables are computed from — the compiler's
        estimate of traffic/arithmetic, not a hand-counted model, so fusion
        (e.g. the cast folded into the filter) is accounted for. Returns
        None when the backend doesn't implement cost analysis.

        Cost note: see :meth:`compiled_step`."""
        if self._step is None or self._signature is None:
            return None
        try:
            ca = self.compiled_step().cost_analysis()
            flops = float(ca.get("flops", 0.0))
            byts = float(ca.get("bytes accessed", 0.0))
        except Exception:  # noqa: BLE001 — cost analysis is best-effort
            return None
        if not (flops or byts):
            return None
        return {"flops_per_batch": flops, "bytes_accessed_per_batch": byts}

    def rebuild(self) -> "Engine":
        """Fresh engine for supervised recovery (resilience.supervisor):
        same filter/mesh/options, recompiled at the old signature — the
        full compile() path, so the replacement is re-warmed and its
        ``h2d_block_ms`` re-calibrated before it takes traffic. A
        stateful filter's temporal state restarts fresh, every session's
        row of it (the wedged engine's device-resident state is
        unrecoverable by definition).
        """
        fresh = Engine(self.filter, mesh=self.mesh, out_uint8=self.out_uint8,
                       chaos=self.chaos, op_chain=self.op_chain,
                       state_rows=self.state_rows)
        if self._signature is not None:
            shape, dtype = self._signature
            fresh.compile(shape, dtype)
        return fresh

    # -- double-buffered hot swap (stall-free reconfiguration) ----------

    def prepare_swap(self, batch_shape: Tuple[int, ...], dtype=np.uint8,
                     force: bool = False) -> dict:
        """Compile the successor program for ``batch_shape``/``dtype``
        ASIDE — a fresh engine traced, compiled, warmed, and calibrated
        on THIS (background) thread while the live program keeps
        serving. Nothing the serving path reads is touched until
        :meth:`commit_swap` adopts the staged successor between ticks.

        ``force=True`` prepares even at the live signature (a fresh
        program + fresh state at the same shape — the supervised-
        recovery rebuild, compiled aside instead of in place).

        Concurrent prepares for the same successor signature dedup onto
        one compile via a per-signature latch (the engine-level mirror
        of ``ProgramPool.acquire``'s per-key latch); a prepare for a
        DIFFERENT signature supersedes the previously staged successor
        (its buffers are freed — last prepare wins).

        Returns ``{"compile_aside_ms", "staged", "cache"}``; ``staged``
        False means the live program already serves this signature and
        nothing was built. Raises on compile failure (and on the chaos
        ``swap`` site) with the live program untouched.
        """
        if self.freed:
            raise RuntimeError("cannot prepare a swap on a freed engine")
        sig = (tuple(batch_shape), np.dtype(dtype))
        if sig == self._signature and not force:
            return {"compile_aside_ms": 0.0, "staged": False,
                    "cache": "live"}
        while True:
            with self._swap_lock:
                st = self._staged
                if st is not None and st._signature == sig and not force:
                    return {"compile_aside_ms": 0.0, "staged": True,
                            "cache": "staged"}
                latch = self._preparing.get(sig)
                if latch is None:
                    self._preparing[sig] = latch = threading.Event()
                    break
            # Another thread is building this successor: wait it out,
            # then re-check (it staged the program, or died and we
            # build).
            latch.wait(timeout=300.0)
        t0 = time.perf_counter()
        try:
            if self.chaos is not None:
                self.chaos.fire("swap")  # injection site: aside-compile
                #   failure — the old program must keep serving
            succ = Engine(self.filter, mesh=self.mesh,
                          out_uint8=self.out_uint8, chaos=self.chaos,
                          op_chain=self.op_chain,
                          state_rows=self.state_rows)
            succ.compile(tuple(batch_shape), dtype)
        except BaseException:
            with self._swap_lock:
                self._preparing.pop(sig, None)
            latch.set()
            raise
        ms = (time.perf_counter() - t0) * 1e3
        with self._swap_lock:
            old, self._staged = self._staged, succ
            self._preparing.pop(sig, None)
        latch.set()
        if old is not None and old is not succ:
            old.free()  # superseded staging
        return {"compile_aside_ms": ms, "staged": True, "cache": "miss"}

    @property
    def swap_staged(self) -> bool:
        """Whether a prepared successor is waiting for commit_swap."""
        with self._swap_lock:
            return self._staged is not None

    def commit_swap(self, migrate_state: bool = True) -> dict:
        """Adopt the staged successor program atomically: ONE lock-
        guarded field swing — call from the thread that owns submits
        (the serving dispatch thread), so a batch never straddles the
        old and new programs. In-flight batches already submitted on
        the old program hold their own result references and drain
        normally; the old program's handles drop here and its buffers
        free once they do.

        Device-resident filter state (a session-state filter's whole
        table, which no batch size shapes) migrates device-to-device when
        the successor's state tree matches shape-for-shape
        (``migrate_state=True``); a geometry-changing swap (or
        ``migrate_state=False`` — supervised recovery, whose old state
        is poisoned by definition) keeps the successor's fresh state.

        Returns ``{"migrate_ms", "stall_ms", "migrated"}`` — stall_ms
        is the measured wall duration of this call, the ONLY serving
        time the swap consumes. Raises (chaos ``swap`` site mid-
        migrate, a failed device copy) with the live program untouched
        and the staged successor freed: a failed swap leaves the old
        program serving.
        """
        with self._swap_lock:
            succ = self._staged
            if succ is None:
                raise RuntimeError(
                    "no staged successor program (prepare_swap first)")
            self._staged = None
            t0 = time.perf_counter()
            migrate_ms = 0.0
            migrated = False
            try:
                if self.chaos is not None:
                    self.chaos.fire("swap")  # injection site: mid-
                    #   migrate failure — abort, old program serving
                if migrate_state and self._exec_filter.stateful \
                        and self._state is not None \
                        and succ._exec_filter.stateful:
                    t_m = time.perf_counter()
                    migrated = self._migrate_state_to(succ)
                    if migrated:
                        migrate_ms = (time.perf_counter() - t_m) * 1e3
            except BaseException:
                succ.free()
                raise
            # The swing: adopt every program field the serving/egress
            # paths read. In place — the engine OBJECT survives, so
            # pool leases, bucket bindings, and probe callers keep one
            # stable identity across any number of swaps.
            for name in ("_step", "_tabled", "_signature", "_state",
                         "_sharding", "_batch_replicated",
                         "_exec_filter", "out_shape", "out_dtype",
                         "step_donates_input", "kernel_plan",
                         "_out_sharding", "h2d_block_ms", "d2h_block_ms",
                         "step_block_ms", "last_compile_ms",
                         "step_conv_ops", "state_bytes"):
                setattr(self, name, getattr(succ, name))
            self.stats.compile_count += succ.stats.compile_count
            # Neuter the successor shell: its device buffers now belong
            # to this engine — its free() must not free them.
            succ._step = None
            succ._state = None
            succ._sharding = None
            succ._out_sharding = None
            succ.state_bytes = 0
            succ.freed = True
            self.swap_count += 1
            stall_ms = (time.perf_counter() - t0) * 1e3
            self.last_swap = {"migrate_ms": round(migrate_ms, 3),
                              "stall_ms": round(stall_ms, 3),
                              "migrated": migrated}
            return dict(self.last_swap)

    def _migrate_state_to(self, succ: "Engine") -> bool:
        """Device-to-device re-placement of the live filter state under
        the successor's shardings — only when the trees match leaf-for-
        leaf (same structure, shapes, dtypes). False = shapes diverged
        (the successor keeps its fresh init state; a geometry change
        resets temporal state by definition)."""
        old_leaves = jax.tree_util.tree_leaves(self._state)
        new_leaves = jax.tree_util.tree_leaves(succ._state)
        if len(old_leaves) != len(new_leaves):
            return False
        for a, b in zip(old_leaves, new_leaves):
            if (tuple(getattr(a, "shape", ())) != tuple(
                    getattr(b, "shape", ()))
                    or getattr(a, "dtype", None) != getattr(b, "dtype",
                                                            None)):
                return False
        succ._state = jax.device_put(self._state,
                                     succ._state_shardings())
        jax.block_until_ready(succ._state)
        return True

    def abort_swap(self) -> bool:
        """Free a staged successor without adopting it (the owner
        decided against the swap, or its commit precondition failed).
        True when something was staged."""
        with self._swap_lock:
            succ, self._staged = self._staged, None
        if succ is not None:
            succ.free()
            return True
        return False

    def free(self) -> None:
        """Release this engine's device residency: the compiled program
        handle, the device-resident state, and the warmup-derived
        sharding refs are dropped so XLA can reclaim the buffers — the
        compiled-program pool's eviction path. Idempotent; a freed
        engine refuses further submits (re-admission goes through a
        FRESH engine so recompilation hits the persistent cache, it
        does not resurrect this object)."""
        if self.freed:
            return
        self.freed = True
        with self._swap_lock:
            staged, self._staged = self._staged, None
        if staged is not None:
            staged.free()  # an un-committed successor must not leak
        self._step = None
        self._state = None
        self._sharding = None
        self._out_sharding = None
        _note_freed_bytes(self.state_bytes)
        _unregister_pool_engine(self)

    def reset_state(self) -> None:
        """Restart the filter's state (every session's row of it)."""
        if self._exec_filter.stateful and self._signature is not None:
            self._state = self._fresh_state(*self._signature)


# ---------------------------------------------------------------------------
# Compiled-program pool (multi-signature serving)
# ---------------------------------------------------------------------------

# Every engine currently holding device buffers under a ProgramPool's
# management. The conftest session-end guard walks this: a pool engine
# still live after every frontend closed means some stop() path stopped
# freeing — a long-lived multi-tenant server would leak one compiled
# program (plus its device state) per churned signature forever.
_POOL_ENGINES: "set" = set()
_POOL_ENGINES_LOCK = threading.Lock()

# Donated/freed device-memory accounting (obs.memory): Engine.free()
# folds the freed engine's measured state residency in here, so the
# scrape-time gauges can report eviction traffic as a monotone counter.
_FREED_DEVICE_BYTES = 0


def _note_freed_bytes(n: int) -> None:
    global _FREED_DEVICE_BYTES
    with _POOL_ENGINES_LOCK:
        _FREED_DEVICE_BYTES += int(n or 0)


def freed_device_bytes_total() -> int:
    """Monotone: device state bytes released by every ``Engine.free()``
    so far (pool evictions, frontend stops, recovery replacements) —
    the ``dvf_mem_engine_freed_bytes_total`` counter's source."""
    with _POOL_ENGINES_LOCK:
        return _FREED_DEVICE_BYTES


def conv_op_names(hlo_text: str) -> List[str]:
    """The instructions of a compiled module's entry computation that hold
    a convolution (their own, or in a computation they call, a fusion
    inside a fusion included), by name as a device trace lists them
    (``fusion.47``): XLA names most such fusions ``fusion.N``, so a
    reader cannot tell them by name."""
    bodies: Dict[str, List[str]] = {}
    body: Optional[List[str]] = None
    entry: List[str] = []
    for line in hlo_text.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            body = entry if head.group(1) else bodies.setdefault(
                head.group(2), [])
        elif body is not None:
            body.append(line)

    def holds(lines) -> bool:
        for ln in lines:
            if " convolution(" in ln:
                return True
            called = re.search(r"calls=%([\w.\-]+)", ln)   # a fusion in a fusion
            if called and holds(bodies.get(called.group(1), [])):
                return True
        return False

    names = []
    for line in entry:
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line)
        if m and holds([line]):
            names.append(m.group(1))
    return names


def _tree_device_bytes(state) -> int:
    """Summed leaf nbytes of a (possibly None) device-resident pytree —
    the engine's measured state residency."""
    if state is None:
        return 0
    try:
        return int(sum(
            int(getattr(leaf, "nbytes", 0) or 0)
            for leaf in jax.tree_util.tree_leaves(state)))
    except Exception:  # noqa: BLE001 — accounting must never raise
        return 0


def _register_pool_engine(engine: "Engine") -> None:
    with _POOL_ENGINES_LOCK:
        _POOL_ENGINES.add(engine)


def _unregister_pool_engine(engine: "Engine") -> None:
    with _POOL_ENGINES_LOCK:
        _POOL_ENGINES.discard(engine)


def live_pool_engines() -> List["Engine"]:
    """Pool-managed engines whose device buffers are still live — the
    conftest leak guard's registry (mirrors fleet.replica.
    live_worker_processes)."""
    with _POOL_ENGINES_LOCK:
        return [e for e in _POOL_ENGINES if not e.freed]


class ProgramPool:
    """Bounded LRU of live compiled Engines, keyed by canonical
    signature (runtime.signature.SignatureKey).

    N serving signatures time-share ONE device without N processes: a
    bucket *leases* its engine (refcounted — a leased program is never
    evicted out from under in-flight batches), releases it when the
    bucket retires, and the program stays WARM in the pool until LRU
    capacity pressure frees its device buffers (``Engine.free``).
    Re-admission of an evicted signature recompiles through ``build`` —
    with the persistent compilation cache armed
    (:func:`enable_compilation_cache`) that recompile is a cache
    deserialize, not a fresh XLA run.

    ``hits``/``misses``/``evictions`` are the ``dvf_compile_cache_*`` /
    pool-eviction registry exports.
    """

    def __init__(self, capacity: int = 8):
        if capacity < 1:
            raise ValueError("pool capacity must be >= 1")
        self.capacity = capacity
        self._lock = threading.Lock()
        # key -> [engine, lease_count]; OrderedDict gives LRU order.
        self._entries: "collections.OrderedDict" = collections.OrderedDict()
        self._building: Dict[Any, threading.Event] = {}
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.closed = False
        self.observer: Optional[Callable] = None  # reconfiguration-
        #   ledger tap (duck-typed: observer(kind, **fields)): the owner
        #   wires it to record pool_acquire / compile / pool_evict
        #   events. Always called OUTSIDE the pool lock; exceptions are
        #   swallowed — accounting must never break a lease.

    def _notify(self, kind: str, **fields) -> None:
        obs = self.observer
        if obs is None:
            return
        try:
            obs(kind, **fields)
        except Exception:  # noqa: BLE001 — see observer comment
            pass

    def acquire(self, key, build: Callable[[], "Engine"],
                cause: Optional[str] = None) -> "Engine":
        """Lease the engine for ``key``: LRU hit (warm — milliseconds)
        or ``build()`` (cold — trace/compile; runs OUTSIDE the pool lock
        so one slow compile can't block every other bucket's lease, with
        a per-key latch so concurrent admits of the same signature
        compile once). ``cause`` labels the ledger event (admission /
        quality / precompile / …)."""
        while True:
            with self._lock:
                if self.closed:
                    raise RuntimeError("program pool is closed")
                ent = self._entries.get(key)
                if ent is not None:
                    self._entries.move_to_end(key)
                    ent[1] += 1
                    self.hits += 1
                    engine = ent[0]
                    break
                latch = self._building.get(key)
                if latch is None:
                    self._building[key] = latch = threading.Event()
                    engine = None
                    break
            latch.wait(timeout=300.0)  # builder finished (or died): re-check
        if engine is not None:
            self._notify("pool_acquire", cause=cause, key=key,
                         cache="hit", engine=engine)
            return engine
        t_build = time.perf_counter()
        try:
            engine = build()
        except BaseException:
            with self._lock:
                self._building.pop(key, None)
            latch.set()
            raise
        build_ms = (time.perf_counter() - t_build) * 1e3
        with self._lock:
            if self.closed:
                # close() raced the build: the pool's free sweep already
                # ran, so inserting now would leak a live program that
                # nothing ever frees. Refuse (below, outside the lock,
                # after freeing what we built).
                self._building.pop(key, None)
                raced_close = True
            else:
                raced_close = False
                self.misses += 1
                self._entries[key] = [engine, 1]
                _register_pool_engine(engine)
                self._building.pop(key, None)
                evicted = self._evict_over_capacity_locked()
        latch.set()
        if raced_close:
            engine.free()
            raise RuntimeError("program pool is closed")
        self._notify("compile", cause=cause, key=key, cache="miss",
                     wall_ms=build_ms, engine=engine)
        self._free_evicted(evicted)
        return engine

    def adopt(self, key, engine: "Engine") -> None:
        """Insert an externally built engine as a leased entry — how the
        frontend's default bucket (whose engine may be caller-built and
        predate its key being known) joins the pool once pinned.
        Raises RuntimeError on a closed pool (adopt racing the owner's
        stop must not insert a program the close sweep already missed)."""
        with self._lock:
            if self.closed:
                raise RuntimeError("program pool is closed")
            if key in self._entries:
                ent = self._entries[key]
                if ent[0] is engine:
                    return
                raise ValueError(f"pool already holds a different engine "
                                 f"for {key}")
            self._entries[key] = [engine, 1]
            self._entries.move_to_end(key)
            _register_pool_engine(engine)
            evicted = self._evict_over_capacity_locked()
        self._free_evicted(evicted)

    def release(self, key) -> None:
        """Drop one lease. The program STAYS warm (that is the point —
        the next admit of this signature is a pool hit) until capacity
        pressure evicts it."""
        evicted = []
        with self._lock:
            ent = self._entries.get(key)
            if ent is None:
                return
            ent[1] = max(0, ent[1] - 1)
            evicted = self._evict_over_capacity_locked()
        self._free_evicted(evicted)

    def replace(self, key, engine: "Engine") -> None:
        """Swap the live engine under an existing lease (supervised
        recovery rebuilt it); the old engine's buffers are freed. On a
        closed pool the rebuilt engine is freed and the call raises —
        a recovery racing the owner's stop() must not insert a program
        the close sweep already missed. A concurrently-retired key
        re-enters WARM (lease 0): nothing holds it, so capacity
        pressure may evict it immediately."""
        old = None
        evicted: List[Tuple[Any, "Engine"]] = []
        with self._lock:
            if self.closed:
                raced_close = True
            else:
                raced_close = False
                ent = self._entries.get(key)
                if ent is None:
                    self._entries[key] = [engine, 0]
                    _register_pool_engine(engine)
                    evicted = self._evict_over_capacity_locked()
                else:
                    old = ent[0]
                    ent[0] = engine
                    _register_pool_engine(engine)
        if raced_close:
            engine.free()
            raise RuntimeError("program pool is closed")
        self._free_evicted(evicted)
        if old is not None and old is not engine:
            old.free()

    def _evict_over_capacity_locked(self) -> List[Tuple[Any, "Engine"]]:
        """Pop LRU un-leased entries while over capacity; leased entries
        are skipped (a live program can't be freed under its bucket), so
        the pool may transiently exceed capacity when every entry is
        leased — bounded by the frontend's max_buckets. Returns
        ``(key, engine)`` pairs for the caller to free (and ledger)
        outside the lock."""
        out: List[Tuple[Any, "Engine"]] = []
        if len(self._entries) <= self.capacity:
            return out
        for key in list(self._entries):
            if len(self._entries) <= self.capacity:
                break
            if self._entries[key][1] == 0:
                out.append((key, self._entries.pop(key)[0]))
                self.evictions += 1
        return out

    def _free_evicted(self, evicted: List[Tuple[Any, "Engine"]]) -> None:
        for key, e in evicted:
            e.free()
            self._notify("pool_evict", cause="capacity", key=key,
                         engine=e)

    def evict(self, key) -> bool:
        """Explicitly drop one un-leased entry (tests; manual cache
        control). False when absent or still leased."""
        with self._lock:
            ent = self._entries.get(key)
            if ent is None or ent[1] > 0:
                return False
            engine = self._entries.pop(key)[0]
            self.evictions += 1
        engine.free()
        self._notify("pool_evict", cause="manual", key=key, engine=engine)
        return True

    def warm_keys(self) -> List:
        """Signatures this pool can serve without a compile — what
        admission-rejection messages enumerate and the fleet's
        warm-replica preference matches against."""
        with self._lock:
            return list(self._entries)

    def peek(self, key) -> Optional["Engine"]:
        """The warm engine under ``key`` WITHOUT taking a lease — the
        audit plane's divergence probe runs through it (a replica is
        'warm on a signature' whether the program is bucket-leased or
        pool-idle). None when absent; the caller must tolerate a
        concurrent eviction (the freed engine's probe raises, which the
        probe paths already contain as 'unprobeable')."""
        with self._lock:
            ent = self._entries.get(key)
            return ent[0] if ent is not None else None

    def close(self) -> None:
        """Free every entry (frontend stop): after this, no pool engine
        holds device buffers — pinned by the conftest leak guard."""
        with self._lock:
            self.closed = True
            engines = [ent[0] for ent in self._entries.values()]
            self._entries.clear()
        for e in engines:
            e.free()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> dict:
        with self._lock:
            return {
                "size": len(self._entries),
                "capacity": self.capacity,
                "leased": sum(1 for ent in self._entries.values()
                              if ent[1] > 0),
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
            }


# ---------------------------------------------------------------------------
# Persistent compilation cache (AOT warm-start)
# ---------------------------------------------------------------------------

# One rule for where compiled programs persist (the path is part of the
# cache key, so a directory that moves never hits): the directory
# JAX_COMPILATION_CACHE_DIR names when it is set, else
# <checkout>/.jax_compile_cache (gitignored), anchored at this package's
# own location — never the working directory, a temp name, a pid or the
# time. XLA keys entries by topology + program fingerprint, so one
# directory serves every (device topology, signature) pair.
_CHECKOUT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")
DEFAULT_COMPILE_CACHE_BYTES = 512 * 1024 * 1024


def resolve_compile_cache_dir() -> str:
    """The compile-cache directory every entry point uses (CLI,
    chip_smoke.py, fleet replicas via the env): the environment's when
    set, the checkout-anchored default otherwise."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or _CHECKOUT_COMPILE_CACHE_DIR)


def prune_compilation_cache(cache_dir: str,
                            max_bytes: int = DEFAULT_COMPILE_CACHE_BYTES,
                            ) -> int:
    """Bound the cache dir: delete oldest-mtime entries until the total
    is under ``max_bytes``. Returns files removed. Best-effort (a
    concurrent process may be writing)."""
    try:
        files = []
        for name in os.listdir(cache_dir):
            path = os.path.join(cache_dir, name)
            try:
                st = os.stat(path)
            except OSError:
                continue
            if os.path.isfile(path):
                files.append((st.st_mtime, st.st_size, path))
    except OSError:
        return 0
    total = sum(size for _, size, _ in files)
    removed = 0
    for _, size, path in sorted(files):
        if total <= max_bytes:
            break
        try:
            os.remove(path)
            removed += 1
            total -= size
        except OSError:
            pass
    return removed


def enable_compilation_cache(
    persist_small: bool = False,
    max_bytes: int = DEFAULT_COMPILE_CACHE_BYTES,
) -> str:
    """Arm jax's persistent compilation cache at
    :func:`resolve_compile_cache_dir` — the one place the cache directory
    is configured.

    A previously-seen signature's recompile (process restart, pool
    re-admission after eviction, a fleet replica respawn) becomes a
    cache deserialize instead of a fresh XLA compile. ``persist_small``
    zeroes the min-compile-time/min-entry-size gates so cheap serving
    programs persist too (jax's defaults only persist compiles over
    ~1 s, which would exclude exactly the small mixed-workload
    signatures the multi-tenant frontend churns through). The directory
    is bounded by :func:`prune_compilation_cache` at arm time, and
    exported to the environment so child processes resolve the same
    one. Returns the directory used.
    """
    cache_dir = resolve_compile_cache_dir()
    os.makedirs(cache_dir, exist_ok=True)
    prune_compilation_cache(cache_dir, max_bytes)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    if persist_small:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir
