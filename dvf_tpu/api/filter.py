"""The Filter protocol — pure batch→batch functions with optional state.

Reference counterpart: the abstract ``Worker.__call__(frame_bytes) -> bytes``
(worker.py:78-80) that plugins like ``InverterWorker`` implement
(inverter.py:29-46). Differences, by design:

- **batched**: a filter maps a whole NHWC batch at once, so the device
  program is one large fused kernel instead of N per-frame Python calls;
- **pure + traceable**: no codec, no I/O, no Python side effects — the
  runtime owns staging/codec, the filter owns math. That is what makes the
  filter jit-able under a mesh;
- **explicit state**: stateful filters (the optical-flow config's 2-frame
  temporal window, BASELINE.json configs[3]) carry device-resident state as a
  pytree threaded through the call, instead of mutable attributes on a worker
  object. State stays on device across batches — no host round trip and no
  re-trace.
- **temporal state is one session's**: the pytree ``init_state`` builds is
  the state of ONE stream. ``fn(batch, state)`` reads a batch as that
  stream's consecutive frames; ``rows(batch, prev, pred)`` is the same body
  over a batch whose rows belong to many streams, each row naming its own
  predecessor. The Engine holds one state per session in a device table
  and runs ``rows``, so which sessions share a batch is data, never shape.
  A temporal filter that is also a net keeps its weights in the same
  state dict under keys it names in ``shared_state``: stored once, handed
  to ``rows`` as they are.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence, Tuple

import jax.numpy as jnp

# A filter body maps (batch, state) -> (batch, state). ``state`` is an
# arbitrary pytree (None for stateless filters).
FilterFn = Callable[[jnp.ndarray, Any], Tuple[jnp.ndarray, Any]]


@dataclasses.dataclass(frozen=True)
class Filter:
    """A named, pure, batched frame filter.

    Attributes:
      name: registry name (plus config, e.g. ``gaussian_blur(k=9)``).
      fn: pure ``(batch, state) -> (batch, state)`` function over float
        NHWC batches in [0, 1].
      init_state: optional ``(batch_shape, dtype) -> pytree`` building the
        initial device state (e.g. the previous-frame window for flow).
      compute_dtype: dtype the runtime should cast uint8 frames to before
        calling ``fn``. bfloat16 keeps HBM traffic halved and feeds the MXU
        natively; pointwise filters may prefer uint8 passthrough.
      uint8_ok: if True, ``fn`` can consume uint8 NHWC batches directly
        (e.g. invert = 255 - x) and the runtime skips the float round trip.
      halo: stencil radius in pixels — how many neighbor rows/cols one
        output pixel depends on (0 = pointwise, k//2 for a k-tap conv,
        None = unknown/unbounded). Spatial sharding (parallel.halo) uses
        this to size the ring halo exchange.
      pad_safe: whether repeat-last-frame batch padding preserves this
        filter's semantics. The single-stream executors (runtime.pipeline,
        the ZMQ worker) pad short batches by repeating the last valid frame
        (static shapes → one compilation) and hand the Engine no row map.
        For stateless filters padded outputs are simply dropped (always
        safe). For stateful filters the padded rows then flow through the
        state update, so ``pad_safe`` asserts: *the post-batch state
        depends only on the most recent valid frame* — true for the
        temporal-window flow family (state = last frame; the padded
        duplicate IS the last valid frame), false for e.g. a running
        average, which would double-count. Those executors refuse short
        batches for ``pad_safe=False`` filters. The serve path does not
        depend on it: its row map marks pad rows, and a pad row neither
        reads nor writes any session's state.
      rows: temporal filters only — the body over rows of many sessions,
        ``rows(batch, prev, pred) -> (out, row_states)``. ``prev`` is a
        pytree of T session states (every leaf of ``init_state``'s tree
        with a leading T); ``pred`` is int32 ``[B]``: row i's predecessor
        is ``prev`` entry ``pred[i]`` when ``pred[i] < T``, else batch row
        ``pred[i] - T`` (always an earlier row of the same session).
        ``pred=None`` is the one-session case ``fn`` is made of: T == 1,
        row 0 follows ``prev`` entry 0 and row i follows row i - 1.
        ``row_states`` has every leaf with a leading B: the session state
        after each row (the caller keeps the one after a session's last
        row). Build such filters with :func:`temporal_filter`. In a
        chain only the temporal members' leaves are per-session
        (:func:`session_leaves`): a member whose state is read-only
        weights gets and returns its single state, stored once.
    """

    name: str
    fn: FilterFn
    init_state: Optional[Callable[[Sequence[int], Any], Any]] = None
    # True when the state is read-only parameters (a neural filter's
    # weights): ``fn`` returns it unchanged, so nothing one batch computes
    # reaches the next and rows of different tenants may share a batch.
    # False for temporal state (flow's previous frame) — see ``temporal``.
    constant_state: bool = False
    compute_dtype: Any = jnp.float32
    uint8_ok: bool = False
    halo: Optional[int] = None
    pad_safe: bool = True
    # Set by FilterChain: the composed stages, in order. Lets spatial
    # sharding (parallel.halo) exchange halos per stage — exact at global
    # frame borders even when intermediates aren't reflection-symmetric —
    # instead of one summed-radius exchange around the fused chain.
    members: Optional[Tuple["Filter", ...]] = None
    # Optional mesh-parallelism hooks (used by the Engine):
    #
    # state_pspecs() -> PartitionSpec pytree matching init_state's tree.
    # The engine places state with these specs instead of replicating it —
    # how a neural filter's weight pytree gets tensor-parallel placement
    # (specs naming a size-1 mesh axis degrade to replication, so one spec
    # tree serves every mesh).
    state_pspecs: Optional[Callable[[], Any]] = None
    # specialize(mesh, batch_shape) -> Filter | None. Called once per
    # compile signature; returning a Filter swaps in a mesh-aware body
    # (e.g. style transfer returns a shard_map'd Megatron-TP forward when
    # the mesh has a model axis). None = keep the generic body.
    specialize: Optional[Callable[[Any, Tuple[int, ...]], Optional["Filter"]]] = None
    rows: Optional[Callable[[jnp.ndarray, Any, Any], Tuple[jnp.ndarray, Any]]] = None
    # kernel_plan(batch_shape) -> dict, for a filter whose body is a kernel
    # of the repo's own: the kernel's name as a trace lists it and the
    # tiling it resolves to for that NHWC shape (ops/pallas_kernels.py
    # ``sobel_bilateral_plan``). The Engine resolves it once per compile
    # (``Engine.kernel_plan``) and the serve path's bucket row passes it on
    # as its ``kernel`` block. None: no such kernel (XLA's own ops only).
    kernel_plan: Optional[Callable[[Tuple[int, ...]], dict]] = None
    # Temporal filters whose state also holds what is no session's: the
    # top-level keys of the state dict that are read-only and stored once
    # (a temporal net's weights beside its sessions' planes). ``rows``
    # gets and returns them as they are (:func:`session_leaves`).
    shared_state: Tuple[str, ...] = ()
    # window: what a temporal filter's state holds of its session, as
    # data, for a filter whose window is deeper than the two frames of
    # the flow family: ``depth`` (predecessors a full window reads; a row
    # served with fewer is warm-up), ``lag_frames`` (the result for a
    # session's frame n answers its frame n - lag_frames: frames of
    # lookahead) and ``leaves`` ({kind: planes a session}). None: depth
    # 1, no lag. The serve path's bucket row passes it on in its
    # ``state`` block and counts ``warm_rows_total`` by ``depth``.
    window: Optional[dict] = None
    # model: a learned filter's network as data (``name``, ``params``,
    # and what its served form costs a frame); the bucket row's
    # ``model`` block. None: the row has none.
    model: Optional[dict] = None

    @property
    def stateful(self) -> bool:
        return self.init_state is not None

    @property
    def temporal(self) -> bool:
        """State that one batch writes and the next reads: one session's
        (see ``rows``), never threaded across sessions."""
        return self.stateful and not self.constant_state

    @property
    def window_depth(self) -> int:
        return int((self.window or {}).get("depth", 1))

    @property
    def lag_frames(self) -> int:
        return int((self.window or {}).get("lag_frames", 0))

    @property
    def session_state(self) -> bool:
        """Temporal, with the many-session body: the Engine keeps a table
        of session states and any number of tenants may share a batch."""
        return self.temporal and self.rows is not None

    def __call__(self, batch: jnp.ndarray, state: Any = None) -> Tuple[jnp.ndarray, Any]:
        return self.fn(batch, state)


def stateless(name: str, fn: Callable[[jnp.ndarray], jnp.ndarray], **kw) -> Filter:
    """Wrap a plain ``batch -> batch`` function as a stateless Filter."""

    def wrapped(batch: jnp.ndarray, state: Any) -> Tuple[jnp.ndarray, Any]:
        return fn(batch), state

    return Filter(name=name, fn=wrapped, **kw)


def temporal_filter(name: str, rows: Callable, init_state: Callable,
                    **kw) -> Filter:
    """A temporal Filter from its many-session body (``Filter.rows``):
    ``fn`` is the one-session case of it — one previous state, rows that
    follow one another — so both spell the same mathematics once."""
    import jax

    def fn(batch: jnp.ndarray, state: Any) -> Tuple[jnp.ndarray, Any]:
        mine = session_leaves(filt, state)
        out, row_states = rows(
            batch, jax.tree.map(lambda a, m: a[None] if m else a, state, mine),
            None)
        return out, jax.tree.map(lambda a, m: a[-1] if m else a,
                                 row_states, mine)

    filt = Filter(name=name, fn=fn, rows=rows, init_state=init_state, **kw)
    return filt


def take_pred(seq: jnp.ndarray, pred: Any) -> jnp.ndarray:
    """Each row's predecessor out of ``seq = [prev entries | batch rows]``
    (see ``Filter.rows``): a slice in the one-session case, a row gather
    otherwise."""
    return seq[:-1] if pred is None else jnp.take(seq, pred, axis=0)


def session_leaves(filt: Filter, state: Any) -> Any:
    """Which leaves of ``filt``'s state tree are per-session (a pytree of
    bools shaped like ``state``): all of a temporal filter's, none of a
    constant-state one's, member by member in a chain; none under a
    temporal filter's ``shared_state`` keys. The Engine gives the marked
    leaves a row per session; the others are stored once."""
    import jax

    if filt.members is not None:
        return tuple(session_leaves(f, s)
                     for f, s in zip(filt.members, state))
    if filt.shared_state:
        return {k: jax.tree.map(
            lambda _: filt.temporal and k not in filt.shared_state, v)
            for k, v in state.items()}
    return jax.tree.map(lambda _: filt.temporal, state)


def FilterChain(*filters: Filter, name: Optional[str] = None) -> Filter:
    """Compose filters left-to-right into one Filter.

    The composed body stays a single traced function, so XLA fuses the whole
    chain into one device program — the TPU analog of the reference's
    "chain of workers" being one process pipeline. State is a tuple of the
    member states.
    """
    chain_name = name or "|".join(f.name for f in filters)
    stateful_members = [f.stateful for f in filters]
    # Stencil radii compose additively along a chain; unknown taints all.
    halos = [f.halo for f in filters]
    chain_halo = sum(halos) if all(h is not None for h in halos) else None

    def fn(batch: jnp.ndarray, state: Any) -> Tuple[jnp.ndarray, Any]:
        state = state if state is not None else tuple(None for _ in filters)
        new_states = []
        for f, s in zip(filters, state):
            batch, s2 = f.fn(batch, s)
            new_states.append(s2)
        return batch, tuple(new_states)

    init_state = None
    if any(stateful_members):
        def init_state(batch_shape, dtype):  # noqa: F811
            return tuple(
                f.init_state(batch_shape, dtype) if f.stateful else None
                for f in filters
            )

    rows = None
    if all(f.rows is not None for f in filters if f.temporal) \
            and any(f.temporal for f in filters):
        def rows(batch, prev, pred):  # noqa: F811
            # Temporal members run their own many-session body; a member
            # whose state is read-only weights keeps its single state
            # (session_leaves marks it as no session's).
            out_states = []
            for f, p in zip(filters, prev):
                if f.rows is not None:
                    batch, p = f.rows(batch, p, pred)
                else:
                    batch, p = f.fn(batch, p)
                out_states.append(p)
            return batch, tuple(out_states)

    return Filter(
        name=chain_name,
        fn=fn,
        init_state=init_state,
        compute_dtype=filters[0].compute_dtype if filters else jnp.float32,
        uint8_ok=all(f.uint8_ok for f in filters) if filters else False,
        halo=chain_halo,
        pad_safe=all(f.pad_safe for f in filters) if filters else True,
        constant_state=all(f.constant_state for f in filters if f.stateful),
        members=tuple(filters),
        rows=rows,
    )
