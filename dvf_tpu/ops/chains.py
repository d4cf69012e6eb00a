"""Prebuilt filter chains for the benchmark configs.

``sobel_bilateral`` is BASELINE.json configs[2] ("Sobel-edge + bilateral
filter chain, 1080p, batch=16"). Because a FilterChain is one traced
function, XLA fuses the whole chain into a single device program — there is
no inter-op host hop, unlike the reference where chaining ops would mean
chaining worker processes over ZMQ.
"""

from __future__ import annotations

from typing import Optional

from dvf_tpu.api.filter import Filter, FilterChain
from dvf_tpu.ops.registry import get_filter, measured_default_for, register_filter


@register_filter("sobel_bilateral")
def sobel_bilateral(
    d: int = 5, sigma_color: float = 0.1, sigma_space: float = 2.0,
    magnitude_scale: float = 1.0, impl: Optional[str] = None,
) -> Filter:
    """BASELINE configs[2]: Sobel edges then bilateral, one device program.

    ``impl=None`` picks the measured per-backend winner — the fused
    Pallas program on BOTH measured backends. TPU (chip runs on a v5e,
    as_of 2026-10-02, the Engine's step at 1080p, d = 9): batch 64,
    "pallas" 34.3 ms a step (PR 46, ``step_ms.bulk`` of the served cell:
    the kernel 27.5 with its taps in register-sized strips,
    ``scripts/stencil_kernel_probe.py``; 79.05 with the kernel at 70.7
    before, PR 44: the luma and its padding 5.6, rounding and the
    broadcast to three channels 1.8) where "chain" does not compile (XLA
    wants 138 GB of HBM for the 81 shifted views); batch 4, the largest
    at which the chain fits, 6.94 vs 53.25 ms (PR 43, the kernel's
    three-plane whole-tile form: 7.7x, on 0.20 vs 8.64 GiB of scratch);
    at d = 5 the fused step of 64 was 32.3 ms (kernel 24.1) in PR 44 and
    is not measured since.
    CPU: in interpret mode it lowers to ordinary fused XLA ops, a
    legitimate production path, and keeps the whole-tile form.
    "chain" (the two-op jnp chain) remains the default on backends whose
    A/B hasn't been captured yet. Both filters declare the same halo, so
    spatial sharding is unaffected by the choice.
    """
    if impl is None:
        impl = measured_default_for("sobel_bilateral")
    if impl == "pallas":
        return get_filter("sobel_bilateral_pallas", d=d,
                          sigma_color=sigma_color, sigma_space=sigma_space,
                          magnitude_scale=magnitude_scale)
    if impl != "chain":
        raise ValueError(f"impl must be 'chain' or 'pallas', got {impl!r}")
    return FilterChain(
        get_filter("sobel", magnitude_scale=magnitude_scale),
        # impl pinned: "chain" is the A/B's jnp baseline — without the pin
        # the nested bilateral would itself resolve to the TPU Pallas
        # winner and the comparison would be pallas vs half-pallas.
        get_filter("bilateral", d=d, sigma_color=sigma_color,
                   sigma_space=sigma_space, impl="jnp"),
        name=f"sobel_bilateral(d={d})",
    )


@register_filter("chain")
def chain(specs=()) -> Filter:
    """Generic chain from a list of (name, config) pairs or names."""
    members = []
    for spec in specs:
        if isinstance(spec, str):
            members.append(get_filter(spec))
        else:
            name, cfg = spec
            members.append(get_filter(name, **cfg))
    return FilterChain(*members)


@register_filter("cartoon")
def cartoon(d: int = 5, sigma_color: float = 0.15, sigma_space: float = 3.0,
            levels: int = 6, edge_scale: float = 2.0) -> Filter:
    """Cartoon effect: bilateral smoothing + posterized colors, darkened
    along Sobel edges — a three-op fusion XLA compiles to ONE device
    program (the reference would need three chained worker pools)."""
    from dvf_tpu.api.filter import stateless
    from dvf_tpu.ops.bilateral import bilateral_nhwc
    from dvf_tpu.ops.conv import sobel_gradients
    from dvf_tpu.utils.image import rgb_to_gray

    import jax.numpy as jnp

    if levels < 2:
        raise ValueError("levels must be >= 2")  # levels=1 → 0/0 = NaN frames

    def fn(batch: jnp.ndarray) -> jnp.ndarray:
        smooth = bilateral_nhwc(batch, d=d, sigma_color=sigma_color,
                                sigma_space=sigma_space)
        n = float(levels - 1)
        quant = jnp.round(jnp.clip(smooth, 0.0, 1.0) * n) / n
        gx, gy = sobel_gradients(rgb_to_gray(batch))
        edge = jnp.clip(jnp.sqrt(gx * gx + gy * gy) * edge_scale, 0.0, 1.0)
        return (quant * (1.0 - edge)).astype(batch.dtype)

    # Halo: bilateral (d//2) and Sobel (1) both read the ORIGINAL batch,
    # so the requirement is their max, and never 0 (d=1 must not demote
    # this to pointwise under spatial sharding — the Sobel term would read
    # shard-local borders).
    return stateless(f"cartoon(d={d},levels={levels})", fn,
                     halo=max(d // 2, 1))
