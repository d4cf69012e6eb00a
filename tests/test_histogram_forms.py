"""The histogram family's two forms (dvf_tpu/ops/histogram.py): the counted
form (``impl="pallas"``: ``tile_hist_pallas`` + ``lut_apply_pallas``, in
interpret mode here) equals the sort + gather form (``impl="sort"``) BIT FOR
BIT, for ``clahe`` and ``equalize``, ``on_gray`` both ways, at four small
geometries: one the grid divides (tiles under a lane wide), one it does
not (reflect pad right and bottom, crop; 36 x 52 is the benchmark's toy
cell), and one whose tiles are wider than a lane (130 columns: two lane
tiles a tile, a band wider than a vreg row for ``equalize``). The frames
hold flat black and white patches, so CLAHE's clip and both passes of its
redistribution run. Which form a backend gets by default, and the plan a
step states, are here too."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dvf_tpu.ops import get_filter
from dvf_tpu.ops import histogram as hg
from dvf_tpu.ops import pallas_kernels as pk
from dvf_tpu.ops.registry import MEASURED_DEFAULTS

GEOMETRIES = {"divisible": (32, 48), "reflect_padded": (36, 52), "tile_over_a_lane": (16, 1040),
              "bands_with_filler_rows": (140, 24)}


def _frames(seed, shape):
    b, h, w, c = shape
    rng = np.random.default_rng(seed)
    x = rng.integers(0, 256, shape, dtype=np.uint8)
    x[:, : h // 3, : w // 4] = 0              # saturated patches: bins 0 and 255 pass any clip
    x[:, h // 2:, w // 2: w // 2 + w // 5] = 255
    x[-1, :, -w // 6:] //= 16                 # a few occupied bins: the residual pass has work
    return x


def _run(filt, x):
    """One jitted call (an eager call dispatches the interpreted kernels
    op by op: three times the seconds)."""
    return np.asarray(jax.jit(lambda b: filt.fn(b, None)[0])(x))


_CLAHE, _EQUALIZE = ("clahe", {"clip_limit": 2.0, "grid": 8}), ("equalize", {})
_CASES = [(geometry, *filt, on_gray) for filt in (_CLAHE, _EQUALIZE)
          for geometry in sorted(GEOMETRIES) for on_gray in (False, True)]
# another clip limit and grid, where the clip never binds and where it does
_CASES += [("reflect_padded", "clahe", {"clip_limit": 40.0, "grid": 4}, False),
           ("divisible", "clahe", {"clip_limit": 0.5, "grid": 2}, True)]


@pytest.mark.parametrize(
    "geometry,name,kwargs,on_gray", _CASES,
    ids=[f"{g}-{n}{''.join(f'_{k[0]}{v:g}' for k, v in kw.items())}-{'on_gray' if gray else 'per_channel'}"
         for g, n, kw, gray in _CASES])
def test_counted_form_equals_the_sort_form_bit_for_bit(geometry, name, kwargs, on_gray):
    h, w = GEOMETRIES[geometry]
    x = jnp.asarray(_frames(h + w, (1, h, w, 3)))
    out = {impl: _run(get_filter(name, on_gray=on_gray, impl=impl, **kwargs), x)
           for impl in ("sort", "pallas")}
    assert out["pallas"].dtype == np.uint8 and out["pallas"].shape == x.shape
    assert np.array_equal(out["pallas"], out["sort"]), \
        int(np.abs(out["pallas"].astype(int) - out["sort"].astype(int)).max())
    assert not np.array_equal(out["sort"], np.asarray(x))          # it did something


@pytest.mark.parametrize("name", ["clahe", "equalize"])
def test_float_batches_take_the_counted_form_too(name):
    """A float batch is rounded to uint8, mapped, and returned as float:
    the same bytes either way in."""
    x8 = _frames(5, (1, 32, 48, 3))
    filt = get_filter(name, impl="pallas")
    from_u8 = _run(filt, jnp.asarray(x8))
    from_f32 = _run(filt, jnp.asarray(x8, jnp.float32) / 255.0)
    assert from_f32.dtype == np.float32
    assert np.array_equal(np.round(from_f32 * 255.0).astype(np.uint8), from_u8)


# (tile_h, tile_w, gy, gx): the walk of ``_tile_hist_kernel`` is groups of at most 32 row tiles by strips of one lane tile
TILE_SHAPES = {
    "one_vreg": (8, 128, 1, 1),                        # one row tile, one strip: a bit a word
    "padded_rows_and_lanes": (5, 130, 3, 2),           # 8 x 256 walked for 5 x 130: filler in both directions
    "cell_136x256": (135, 240, 1, 2),                  # the benchmark's tile: 17 row tiles x 2 strips = 34 vregs
    "32_row_tiles": (256, 128, 1, 1),                  # a full word: row tile 31 is the sign bit
    "over_32_row_tiles": (260, 100, 1, 2),             # 33 row tiles: a group of 32 and a group of 1
    "equalize_band": (16, 1920, 2, 1),                 # 15 strips: the loop over strips, as equalize's bands
    "three_strips_with_filler": (24, 300, 1, 3),       # 384 lanes for 300: the last strip mostly filler; a loop of two trips
}


def _plane(kind, shape):
    """Planes on the edges of the bit arithmetic: every bit of a pixel
    clear, every bit set, bit 7 alone, each value once among a constant,
    and seeded noise."""
    if kind in ("zeros", "all_255", "all_128"):
        return np.full(shape, {"zeros": 0, "all_255": 255, "all_128": 128}[kind], np.uint8)
    rng = np.random.default_rng(sum(shape))
    if kind == "noise":
        return rng.integers(0, 256, shape, dtype=np.uint8)
    x = np.full(shape, 77, np.uint8).reshape(shape[0], -1)            # "each_value"
    for n in range(shape[0]):
        x[n, rng.choice(x.shape[1], 256, replace=False)] = np.arange(256)
    return x.reshape(shape)


@pytest.mark.parametrize("kind", ["zeros", "all_255", "all_128", "each_value", "noise"])
@pytest.mark.parametrize("tiling", sorted(TILE_SHAPES))
def test_tile_histograms_are_counts(tiling, kind):
    """``tile_hist_pallas`` against numpy's bincount, a tile: exact over
    every boundary of the walk (one vreg, the cell's 34, more than 32 row
    tiles a strip, a loop over strips, filler rows and lanes) and every
    edge of the bit planes; the zeros a tile is padded with are taken off
    bin 0 again."""
    th, tw, gy, gx = TILE_SHAPES[tiling]
    x = _plane(kind, (2, gy * th, gx * tw))
    tiles = pk.to_tiles(jnp.asarray(x), gy, gx)
    rows, lanes = pk.tile_pad(th, tw)
    assert tiles.shape == (2, gy, rows, gx * lanes)
    got = np.asarray(jax.jit(lambda t: pk.tile_hist_pallas(t, gx, th * tw, "hist", interpret=True))(tiles))
    assert got.shape == (2, gy, gx, 256) and got.dtype == np.int32
    want = np.stack([np.bincount(x[n, ty * th:(ty + 1) * th, tx * tw:(tx + 1) * tw].ravel(), minlength=256)
                     for n in range(2) for ty in range(gy) for tx in range(gx)]).reshape(got.shape)
    assert np.array_equal(got, want), np.argwhere(got != want)[:4]
    assert np.array_equal(np.asarray(pk.from_tiles(tiles, gy, gx, th, tw)), x)


def test_walk_arithmetic_of_the_counting_kernel():
    """``hist_ops_per_pixel``: what the bit-plane walk states for a tile,
    against the compare form's 768: a fuller word is a cheaper pixel."""
    assert pk.hist_ops_per_pixel(136, 256) == 69.6          # the cell's tile: 17 bits a word
    assert pk.hist_ops_per_pixel(256, 128) < pk.hist_ops_per_pixel(128, 1920) < pk.hist_ops_per_pixel(136, 256)
    assert pk.hist_ops_per_pixel(8, 128) > 768              # one bit a word: a vreg pays for a whole word's bins
    assert pk._word_of(32) == -1 and pk._word_of(1) == 1 and pk._word_of(5) == 0x0101_0103


def test_lookup_is_the_tables_entry():
    """``lut_apply_pallas`` without weights: each cell through its own table."""
    rng = np.random.default_rng(1)
    x = rng.integers(0, 256, (2, 2 * 9, 3 * 20), dtype=np.uint8)
    luts = rng.integers(0, 256, (2, 2, 3, 256), dtype=np.int32)
    got = np.asarray(pk.from_tiles(pk.lut_apply_pallas(
        pk.to_tiles(jnp.asarray(x), 2, 3), jnp.asarray(luts), "lookup", interpret=True), 2, 3, 9, 20))
    for n in range(2):
        for cy in range(2):
            for cx in range(3):
                cell = (slice(cy * 9, (cy + 1) * 9), slice(cx * 20, (cx + 1) * 20))
                assert np.array_equal(got[n][cell], luts[n, cy, cx][x[n][cell]])


def test_pinned_factory_and_measured_defaults():
    assert get_filter("clahe_pallas").kernel_plan is not None
    assert get_filter("clahe_pallas").name == get_filter("clahe", impl="pallas").name
    assert get_filter("clahe", impl="sort").kernel_plan is None
    for key in ("clahe", "equalize"):
        assert MEASURED_DEFAULTS[key] == {"winners": {"tpu": "pallas", "cpu": "sort"}, "fallback": "sort"}
    assert get_filter("clahe").kernel_plan is None              # this backend is the CPU: the sort form
    with pytest.raises(ValueError, match="impl"):
        get_filter("clahe", impl="count")
    with pytest.raises(ValueError, match="impl"):
        get_filter("equalize", impl="gather")


def test_plan_states_the_tiling_at_the_cells_shape():
    plan = hg.clahe_plan((64, 1080, 1920, 3), 2.0, 8, False)
    assert plan == {
        "kernel": "clahe_hist", "kernels": ["clahe_hist", "clahe_apply"], "impl": "pallas",
        "grid": 8, "cells": 9, "bins": 256, "planes": 192, "tile_h": 135, "tile_w": 240,
        "tile_h_pad": 136, "tile_w_pad": 256, "clip_abs": 253, "hist_grid": [192, 8],
        "apply_grid": [192, 9], "hist_form": "bitplane", "hist_ops_per_pixel": 69.6,
        "vmem_scratch_bytes": 256 * 8 * 128 * 4,
        "vmem_window_bytes": 136 * 9 * 256, "vmem_limit_bytes": None, "io_dtype": "uint8",
        "compute_dtype": "int32"}
    json.dumps(plan)
    assert hg.clahe_plan((4, 1080, 1920, 3), on_gray=True)["planes"] == 4
    # an 8K frame's windows pass Mosaic's default scoped VMEM: the plan asks for the raised limit
    assert hg.clahe_plan((1, 2160, 3840, 3))["vmem_limit_bytes"] is None
    assert hg.clahe_plan((1, 4320, 7680, 3))["vmem_limit_bytes"] == pk._VMEM_LIMIT_RAISED
    assert hg.clahe_plan((1, 4320, 7680, 3), interpret=True)["vmem_limit_bytes"] is None


def test_lowered_step_carries_the_scopes_and_the_kernels_names():
    filt = get_filter("clahe_pallas", interpret=True)
    lowered = jax.jit(lambda b: filt.fn(b, None)[0]).lower(
        jax.ShapeDtypeStruct((2, 36, 52, 3), jnp.uint8))
    text = lowered.as_text(debug_info=True)
    for scope in ("clahe_hist", "clahe_lut", "clahe_apply"):
        assert f"{scope}/" in text, scope
    assert "stablehlo.sort" not in lowered.as_text()
    sort_form = get_filter("clahe", impl="sort")
    assert "stablehlo.sort" in jax.jit(lambda b: sort_form.fn(b, None)[0]).lower(
        jax.ShapeDtypeStruct((2, 36, 52, 3), jnp.uint8)).as_text()
