"""Filter plugin registry — the framework's operator boundary.

In the reference, the plugin mechanism is *subclassing*: filters subclass
``Worker`` and implement ``__call__(frame_bytes) -> bytes``
(worker.py:78-80, inverter.py:9-46), and each plugin runs as its own OS
process. Here the plugin boundary is a **pure batch→batch jnp function**
registered by name; the runtime traces it once under ``jit`` over a device
mesh and reuses the compiled program for every batch — parallelism comes from
mesh axes, not processes.

A registered factory is ``factory(**config) -> Filter`` (see
:class:`dvf_tpu.api.filter.Filter`). Factories let one op name cover a config
family (e.g. ``gaussian_blur(ksize=9, sigma=2.0)``).
"""

from __future__ import annotations

from typing import Callable, Dict, List

from dvf_tpu.api.filter import Filter

_REGISTRY: Dict[str, Callable[..., Filter]] = {}


def register_filter(name: str) -> Callable[[Callable[..., Filter]], Callable[..., Filter]]:
    """Decorator: register a filter factory under ``name``.

    Re-registration overwrites (last wins) so applications can shadow builtin
    filters, the same way a user of the reference would point the CLI at their
    own ``Worker`` subclass.
    """

    def deco(factory: Callable[..., Filter]) -> Callable[..., Filter]:
        _REGISTRY[name] = factory
        return factory

    return deco


def get_filter(name: str, **config) -> Filter:
    """Instantiate the filter registered under ``name`` with ``config``."""
    try:
        factory = _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"no filter named {name!r}; available: {sorted(_REGISTRY)}"
        ) from None
    return factory(**config)


def list_filters() -> List[str]:
    return sorted(_REGISTRY)


def measured_default(winners: Dict[str, str], fallback: str) -> str:
    """Pick a filter's default implementation from the MEASURED per-backend
    winners.

    ``winners`` maps backend → impl label — a backend with no entry
    falls back to ``fallback`` rather than guessing. Callers pin an
    explicit ``impl=...`` to bypass this entirely.

    Note this touches ``jax.default_backend()`` (initializes the backend):
    it runs at filter-construction time, which in every CLI/worker path is
    after ``_force_platform()``. Plain ``import dvf_tpu`` stays
    backend-free (guarded by tests/test_import_hygiene.py).
    """
    import jax

    return winners.get(jax.default_backend(), fallback)


# The per-backend default of every op that has more than one
# implementation: ``winners`` maps a backend to the impl argument the
# factory picks, ``fallback`` is the impl for every other backend. Two
# TPU winners have a run on this chip behind them: "flow_inner" (PR 27's
# chip runs) and "sobel_bilateral" (PRs 43-46: the pair measured in PR
# 43, whose figures ops/chains.py::sobel_bilateral quotes beside the
# fused step's since, 34.3 ms a batch of 64 in PR 46). The other TPU
# winners were transcribed from A/B rows captured 2026-07-31 through a
# shared chip that no longer exists, and the CPU winners from a CPU
# table; both records are gone: they are unmeasured on this chip and
# stay as they are until ROADMAP D4 replaces them with a ledgered A/B.
MEASURED_DEFAULTS = {
    "bilateral": {
        "winners": {"tpu": "pallas", "cpu": "jnp"},
        "fallback": "jnp",
    },
    "sobel_bilateral": {
        "winners": {"tpu": "pallas", "cpu": "pallas"},
        "fallback": "chain",
    },
    "flow_warp": {
        "winners": {"tpu": "pallas", "cpu": "gather"},
        "fallback": "gather",
    },
    # flow_warp's inner_warp, where the final warp is the bounded kernel
    # (the same +-max_disp contract; with a gather final warp the inner
    # warps stay gathers whatever this says). TPU: PR 27's chip runs, the
    # served table step at 720p batch 64 / 32 sessions: gather_inner
    # 3780.7-3789.7 ms, pallas_inner 220.4-220.7 ms (17x; nine XLA
    # gathers of the 5-channel polynomial stacks were 3.5 s of the step;
    # PERF.md section 6). NOT numerics-identical: the iteration's flow is
    # clipped to the bound (ops/flow.py::_inner_warp_fn). No CPU A/B (the
    # kernel runs in interpret mode there): the fallback.
    "flow_inner": {
        "winners": {"tpu": "pallas"},
        "fallback": "gather",
    },
    # ksize >= 9 branch of gaussian_blur. TPU winner is SHIFT per the
    # 2026-07-31 A/B (shift 1022.4 vs pallas_fused 186.3 fps at 1080p
    # batch 8, rev 9385433) — the only gauss9 A/B captured after accefc6
    # made the Pallas kernels actually lower through Mosaic. pallas_fused's
    # 0.043 HBM fraction makes that capture suspect; ROADMAP D4 re-runs it.
    "gaussian_blur_k9": {
        "winners": {"tpu": "shift", "cpu": "pallas"},
        "fallback": "shift",
    },
    # ksize < 9 branch: shift on both backends.
    "gaussian_blur_small": {
        "winners": {"tpu": "shift", "cpu": "shift"},
        "fallback": "shift",
    },
    # Exact space-to-depth conv rewrite for ESPCN (models.layers.conv2d_s2d;
    # static case in models.analysis). CPU: "ref" (the phase decomposition
    # buys MXU lane utilization, which AVX has no analog of). TPU stays
    # unpinned and falls back to "ref", which is what the cell
    # sr2x_540p.bulk runs; one on-chip probe at its shape read fast 78.3
    # ms a step against ref 34.6 (PERF.md §7, PR 37). The style net's
    # counterpart is no option any more: its stages take the phase form
    # from their shapes (models.style_transfer.stage_forms; the on-chip
    # A/B is PERF.md §6, PR 28).
    "espcn_fast": {
        "winners": {"cpu": "ref"},
        "fallback": "ref",
    },
    # The histogram family's two forms (ops/histogram.py), bit-identical
    # on both backends. TPU: "pallas", counted histograms and lane-gather
    # lookups: PR 49's chip run, 8 frames of 1080p, wall: clahe 12.0 ms
    # against the sort form's 2863.6 (its four image-sized XLA gathers),
    # equalize 9.1 against 637.7 (PERF.md sections 4 and 5). CPU: "sort":
    # the kernels run in interpret mode there (tests/test_histogram_forms.py
    # runs both: the sort form is the faster at every size it uses).
    "clahe": {
        "winners": {"tpu": "pallas", "cpu": "sort"},
        "fallback": "sort",
    },
    "equalize": {
        "winners": {"tpu": "pallas", "cpu": "sort"},
        "fallback": "sort",
    },
}


def measured_default_for(key: str) -> str:
    """Current backend's measured-winner impl for ``MEASURED_DEFAULTS[key]``.

    Same backend-touching caveat as :func:`measured_default` (runs at
    filter-construction time, after ``_force_platform()``) — except when
    every backend resolves to the same impl, which returns without
    initializing the backend (keeps e.g. gaussian_blur(ksize=3)
    backend-free, as it was when its default was a literal)."""
    entry = MEASURED_DEFAULTS[key]
    answers = set(entry["winners"].values()) | {entry["fallback"]}
    if len(answers) == 1:
        return entry["fallback"]
    return measured_default(entry["winners"], entry["fallback"])
