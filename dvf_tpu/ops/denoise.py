"""Video denoising filter op — FastDVDnet served as a stream.

Wraps :mod:`dvf_tpu.models.fastdvdnet` as a registered temporal filter
whose window is five frames, two of them lookahead. Published, the net
takes the window f(n-4) … f(n) at once: three stage-1 blocks on its
triplets, one stage-2 block on their results, four blocks a frame. A
stream's consecutive windows share two of the three stage-1 results, so
the served form keeps them: on a session's frame n the step computes

    d(n-1) = DenBlock_1(f(n-2), f(n-1), f(n))
    out    = DenBlock_2(d(n-3), d(n-2), d(n-1))       the denoised f(n-2)

two blocks a delivered frame, never four, and exactly the published
result (same weights, same inputs: reuse, no approximation). A session's
state is four frame-sized planes — ``raw`` f(n-1), f(n-2) and ``stage1``
d(n-2), d(n-3) — and a warm-up count; the weights sit beside them in the
state dict, stored once (``Filter.shared_state``).

Reference counterpart: none — the reference's only op is invert
(inverter.py:41).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from dvf_tpu.api.filter import Filter, take_pred, temporal_filter
from dvf_tpu.models.fastdvdnet import (
    DENBLOCK_WEIGHTS,
    FastDvdConfig,
    apply_denblock,
    init_fastdvdnet,
)
from dvf_tpu.ops.registry import register_filter

DEPTH = 4     # predecessors a full window reads: f(n-1), f(n-2) and, through d(n-3), f(n-3), f(n-4)
LAG = 2       # frames of lookahead: the delivery for frame n is the denoised frame n - 2
KEPT = jnp.dtype("float32")   # the four kept planes: raw frames and unclipped stage-1 sums, as computed


@register_filter("video_denoise")
def video_denoise(
    params: Optional[Any] = None,
    sigma: float = 25.0 / 255.0,
    seed: int = 0,
    dtype: Optional[str] = None,
) -> Filter:
    """FastDVDnet (Tassano et al., CVPR 2020) over each session's stream.

    **The delivery for a session's frame n is the denoised frame n − 2**
    (``Filter.window["lag_frames"]``): one frame out for one in, in
    order, two frames late. **Warm-up**: a stream starts as if its first
    frame had been sent four times before — while a session has fewer
    than four predecessors every missing lag holds its first frame (and
    that frame's stage-1 result), so delivery n answers frame
    max(n − 2, 0); the authors' code mirrors at a sequence's start
    instead. **The end of a stream**: the last two frames come out
    denoised only if the tenant sends two more (the authors' mirror:
    frames n − 1 and n − 2 again) and discards those two deliveries'
    own answers; ``close_stream`` flushes nothing.

    ``params=None`` → seeded random weights (benchmark weights); pass a
    trained tree with :func:`dvf_tpu.models.fastdvdnet.init_fastdvdnet`'s
    keys for real denoising. ``sigma`` is the noise map's constant in
    [0, 1] units (the paper trains σ from 5/255 to 55/255). ``dtype``
    pins the convolutions' operand dtype (bfloat16 on the MXU, float32
    accumulation). The four kept planes are float32 (``KEPT``) whatever
    it is: the cached form equals the uncached one to the last bit of
    the float32 residual sums, 24 MB a session at 540p.
    """
    if dtype is None:
        dtype = "bfloat16"
    if dtype not in ("bfloat16", "float32"):
        raise ValueError(f"dtype must be 'bfloat16' or 'float32', got {dtype!r}")
    config = FastDvdConfig(sigma=float(sigma), compute_dtype=jnp.dtype(dtype))

    def plane_shape(h: int, w: int, c: int) -> Tuple[int, ...]:
        """A kept plane is a frame's numbers as rows of 128 lanes where
        they divide (every common video geometry): the table's gathers,
        the lag gathers and the scatter then move whole dense rows. As
        (H, W, 3) the chip stores a plane lane-padded, and each of those
        passes costs milliseconds (PERF.md §6, PR 52)."""
        n = h * w * c
        return (n // 128, 128) if n % 128 == 0 else (h, w, c)

    def init_state(batch_shape: Sequence[int], dtype_: Any):
        _, h, w, c = batch_shape
        if h % 4 or w % 4 or c != 3:
            raise ValueError(f"video_denoise needs RGB frames whose height and width divide by 4 "
                             f"(two stride-2 scales), got {h} x {w} x {c}")
        plane = jnp.zeros(plane_shape(h, w, c), KEPT)
        return {"raw": {"lag1": plane, "lag2": plane},
                "stage1": {"lag1": plane, "lag2": plane},
                "count": jnp.zeros((), jnp.int32),
                "weights": params if params is not None
                else init_fastdvdnet(jax.random.PRNGKey(seed))}

    def rows(batch: jnp.ndarray, prev, pred) -> Tuple[jnp.ndarray, Any]:
        bsz = batch.shape[0]
        w = prev["weights"]
        planes = (bsz,) + plane_shape(*batch.shape[1:])

        def lag(carried, rows_):
            """Each row's predecessor's ``rows_`` out of [table entries |
            batch rows], as kept planes; a session's first row takes its
            own."""
            rows_ = rows_.reshape(planes)
            seq = jnp.concatenate([carried, rows_], axis=0)
            return jnp.where(first.reshape((bsz,) + (1,) * (len(planes) - 1)),
                             rows_, take_pred(seq, pred))

        def frames(x):
            return x.reshape(batch.shape)

        with jax.named_scope("denoise_window"):
            # Frames seen before each row, saturating at DEPTH: DEPTH
            # rounds of "my predecessor's count + 1" reach every row that
            # is within DEPTH rows of the table, and the rest are full.
            seen = jnp.zeros((bsz,), jnp.int32)
            for _ in range(DEPTH):
                seen = take_pred(jnp.concatenate(
                    [prev["count"], jnp.minimum(seen + 1, DEPTH)]), pred)
            first = seen == 0
            f1 = lag(prev["raw"]["lag1"], batch)
            f2 = lag(prev["raw"]["lag2"], f1)
        with jax.named_scope("denoise_stage1"):
            d0 = apply_denblock(w["stage1"], frames(f2), frames(f1), batch, config)
        with jax.named_scope("denoise_window"):
            d1 = lag(prev["stage1"]["lag1"], d0)
            d2 = lag(prev["stage1"]["lag2"], d1)
        with jax.named_scope("denoise_stage2"):
            out = apply_denblock(w["stage2"], frames(d2), frames(d1), d0, config)
        states = {"raw": {"lag1": batch.reshape(planes), "lag2": f1},
                  "stage1": {"lag1": d0.reshape(planes), "lag2": d1},
                  "count": jnp.minimum(seen + 1, DEPTH),
                  "weights": w}
        return out.astype(batch.dtype), states

    return temporal_filter(
        f"video_denoise(fastdvdnet,sigma={sigma:.4g})", rows, init_state,
        shared_state=("weights",),
        compute_dtype=jnp.float32,
        pad_safe=False,   # a repeated last frame would move the window on
        window={"depth": DEPTH, "lag_frames": LAG,
                "leaves": {"raw": 2, "stage1": 2},
                "dtypes": {"raw": KEPT.name, "stage1": KEPT.name}},
        model={"name": "fastdvdnet", "form": "cached", "denblocks_per_frame": 2,
               "params": 2 * DENBLOCK_WEIGHTS, "compute_dtype": dtype})
