"""Static per-layer roofline decomposition for the neural filter models.

VERDICT r4 item 5 asked for either a measured 3x MFU improvement on
style_720p or a committed analysis of what binds it. This module is the
analytic half: for each layer of the style net / ESPCN at a given
geometry it derives

- FLOPs (dense conv arithmetic, 2*K*K*Cin*Cout per output pixel),
- HBM bytes (activation reads/writes at the compute dtype, plus the
  norm's extra read+write pass when XLA does not fuse it into the conv),
- an MXU ideal time: FLOPs / (peak * lane_eff * sublane_eff), where the
  efficiency factors model the systolic array's 128-wide lane (output
  channels) and 128-deep sublane (contraction) tiling -- a conv with
  Cout=3 can use at most 3/128 of the MXU's lanes no matter how XLA
  lowers it,
- an HBM ideal time: bytes / 819 GB/s,

and a per-layer verdict: which ceiling binds, and what the whole model's
best-case serial time is. Comparing that bound to a step measured on the
chip (PERF.md section 5) separates "the model is fundamentally
transfer/arithmetic-bound at these shapes" from "the lowering is leaving
time on the table" -- the distinction the VERDICT asked the round to
establish.

The numbers are a MODEL (peaks from the public v5e datasheet, the same
constants as dvf_tpu.benchmarks.DEVICE_PEAKS; efficiency factors are
idealized tiling, not a simulator). The on-chip companion is
scripts/style_step_probe.py, which times the served step op by op on
the real chip; where the two disagree, the measured number wins. For the
style net it has: PERF.md section 5 holds the measured table (the plain
composition's 284 ms step against the phase-domain forward's).

Usage: python -m dvf_tpu.models.analysis [--json] [--md-out PATH]
"""

from __future__ import annotations

import dataclasses
import json
import math
from typing import List

# Same public-datasheet constants as dvf_tpu.benchmarks.DEVICE_PEAKS
# (duplicated literals would drift; import lazily to stay jax-free).
PEAK_BF16_TFLOPS = 197.0
PEAK_HBM_GBPS = 819.0
# f32 matmuls run at ~1/4 the bf16 MXU rate (two passes per operand pair).
F32_MXU_FRACTION = 0.25


@dataclasses.dataclass
class LayerCost:
    name: str
    kind: str               # conv | norm | upsample | pointwise
    h: int                  # OUTPUT spatial geometry
    w: int
    cin: int
    cout: int
    ksize: int
    flops: float            # per frame
    hbm_bytes: float        # per frame
    lane_eff: float         # Cout / ceil128(Cout) -- MXU lane utilization
    sublane_eff: float      # K / ceil128(K), K = k*k*cin
    mxu_ms: float           # ideal per-frame ms on the MXU model
    hbm_ms: float           # ideal per-frame ms on the HBM model
    note: str = ""

    @property
    def bound(self) -> str:
        if self.flops == 0 and self.hbm_bytes == 0:
            return "free"
        return "mxu" if self.mxu_ms >= self.hbm_ms else "hbm"

    @property
    def ideal_ms(self) -> float:
        return max(self.mxu_ms, self.hbm_ms)


def _ceil_to(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def conv_cost(name: str, h_out: int, w_out: int, cin: int, cout: int,
              ksize: int, dtype_bytes: int = 2, bf16: bool = True,
              note: str = "") -> LayerCost:
    """Dense conv as implicit GEMM: M=(H*W), K=k²·Cin, N=Cout.

    The MXU tiles K onto 128 sublanes and N onto 128 lanes; partial tiles
    waste the remainder. M is spatial and effectively unbounded, so it
    never limits utilization at video geometries."""
    flops = 2.0 * ksize * ksize * cin * cout * h_out * w_out
    k_dim = ksize * ksize * cin
    lane_eff = cout / _ceil_to(cout, 128)
    sublane_eff = k_dim / _ceil_to(k_dim, 128)
    peak = PEAK_BF16_TFLOPS * (1.0 if bf16 else F32_MXU_FRACTION) * 1e12
    mxu_ms = flops / (peak * lane_eff * sublane_eff) * 1e3
    # Traffic: read input tile once (+ halo, negligible at these shapes),
    # write output once. Weights are tiny (<1 MB) and stay resident.
    in_bytes = h_out * w_out * cin * dtype_bytes * (1 if ksize == 1 else 1)
    out_bytes = h_out * w_out * cout * dtype_bytes
    hbm_bytes = in_bytes + out_bytes
    hbm_ms = hbm_bytes / (PEAK_HBM_GBPS * 1e9) * 1e3
    return LayerCost(name, "conv", h_out, w_out, cin, cout, ksize,
                     flops, hbm_bytes, lane_eff, sublane_eff,
                     mxu_ms, hbm_ms, note)


def norm_cost(name: str, h: int, w: int, c: int,
              dtype_bytes: int = 2, note: str = "") -> LayerCost:
    """Instance norm: one read pass for stats + one read-modify-write pass
    (when not fused into the producing conv -- the pessimistic case; XLA
    usually fuses the second pass)."""
    bytes_ = 3 * h * w * c * dtype_bytes
    hbm_ms = bytes_ / (PEAK_HBM_GBPS * 1e9) * 1e3
    return LayerCost(name, "norm", h, w, c, c, 0, 0.0, bytes_, 1.0, 1.0,
                     0.0, hbm_ms, note)


def upsample_cost(name: str, h_out: int, w_out: int, c: int,
                  dtype_bytes: int = 2) -> LayerCost:
    """Nearest upsample: read source, write 4x target (broadcast)."""
    bytes_ = (h_out // 2) * (w_out // 2) * c * dtype_bytes + \
        h_out * w_out * c * dtype_bytes
    hbm_ms = bytes_ / (PEAK_HBM_GBPS * 1e9) * 1e3
    return LayerCost(name, "upsample", h_out, w_out, c, c, 0, 0.0, bytes_,
                     1.0, 1.0, 0.0, hbm_ms)


def style_layer_costs(height: int, width: int, base_channels: int = 32,
                      n_residual: int = 5, bf16: bool = True) -> List[LayerCost]:
    """Per-layer costs for models.style_transfer at one geometry."""
    c1, c2, c3 = base_channels, base_channels * 2, base_channels * 4
    h2, w2 = height // 2, width // 2
    h4, w4 = height // 4, width // 4
    dt = 2 if bf16 else 4
    layers = [
        conv_cost("stem 9x9 3→%d" % c1, height, width, 3, c1, 9, dt, bf16,
                  note="full-res; K=243 pads to 256, N=%d/128 lanes" % c1),
        norm_cost("stem_norm", height, width, c1, dt,
                  note="full-res stats pass"),
        conv_cost("down1 3x3 s2 %d→%d" % (c1, c2), h2, w2, c1, c2, 3, dt, bf16),
        norm_cost("down1_norm", h2, w2, c2, dt),
        conv_cost("down2 3x3 s2 %d→%d" % (c2, c3), h4, w4, c2, c3, 3, dt, bf16),
        norm_cost("down2_norm", h4, w4, c3, dt),
    ]
    for tag, mult in (("res_a/b x%d" % (2 * n_residual), 2 * n_residual),):
        one = conv_cost("trunk conv 3x3 %d→%d" % (c3, c3), h4, w4, c3, c3,
                        3, dt, bf16, note="K=%d, full lanes" % (9 * c3))
        one_norm = norm_cost("trunk norm", h4, w4, c3, dt)
        layers.append(dataclasses.replace(
            one, name=tag, flops=one.flops * mult,
            hbm_bytes=one.hbm_bytes * mult, mxu_ms=one.mxu_ms * mult,
            hbm_ms=one.hbm_ms * mult))
        layers.append(dataclasses.replace(
            one_norm, name="trunk norms x%d" % (2 * n_residual),
            hbm_bytes=one_norm.hbm_bytes * mult,
            hbm_ms=one_norm.hbm_ms * mult))
    layers += [
        upsample_cost("up1 upsample", h2, w2, c3, dt),
        conv_cost("up1 3x3 %d→%d" % (c3, c2), h2, w2, c3, c2, 3, dt, bf16),
        norm_cost("up1_norm", h2, w2, c2, dt),
        upsample_cost("up2 upsample", height, width, c2, dt),
        conv_cost("up2 3x3 %d→%d" % (c2, c1), height, width, c2, c1, 3,
                  dt, bf16),
        norm_cost("up2_norm", height, width, c1, dt),
        conv_cost("out 9x9 %d→3" % c1, height, width, c1, 3, 9, dt, bf16,
                  note="N=3 → 3/128 MXU lanes: the structural floor"),
    ]
    return layers


def espcn_layer_costs(height: int, width: int, scale: int = 2,
                      c1: int = 64, c2: int = 32,
                      bf16: bool = True) -> List[LayerCost]:
    dt = 2 if bf16 else 4
    r2 = 3 * scale * scale
    return [
        conv_cost("feat 5x5 3→%d" % c1, height, width, 3, c1, 5, dt, bf16,
                  note="K=75 pads to 128"),
        conv_cost("map 3x3 %d→%d" % (c1, c2), height, width, c1, c2, 3,
                  dt, bf16),
        conv_cost("head 3x3 %d→%d" % (c2, r2), height, width, c2, r2, 3,
                  dt, bf16, note="N=%d → %d/128 lanes" % (r2, r2)),
        LayerCost("depth_to_space", "upsample", height * scale,
                  width * scale, r2, 3, 0, 0.0,
                  2.0 * height * width * r2,      # uint8: XLA rounds before it
                  1.0, 1.0, 0.0,
                  2.0 * height * width * r2 / (PEAK_HBM_GBPS * 1e9) * 1e3,
                  note="reshape/transpose of uint8; on a v5e layout copies at "
                       "a tenth of this rate (PERF.md section 5)"),
    ]


# One 128-wide row through one 128 x 128 weight tile is a pass; 197 TFLOP/s
# is 6.0e9 of them a second.
MXU_PASSES_PER_S = PEAK_BF16_TFLOPS * 1e12 / (2 * 128 * 128)


def espcn_form_passes(height: int, width: int, phases=None, scale: int = 2,
                      c1: int = 64, c2: int = 32) -> dict:
    """MXU passes a frame of each ESPCN conv in a given form, structural
    zeros included (what the MXU runs, not what the algorithm needs):
    ``phases`` as ``models.espcn.stage_phases`` gives them, None = the
    plain body. A conv reading phases ``fi`` and emitting ``fo`` has
    M = H·W / (foh·fow) rows, K = taps_h·fih · taps_w·fiw · Cin with
    taps = lo + hi + 1 of ``models.layers.zero_phase_taps``, N = foh·fow ·
    Cout; passes = M · ceil(K/128) · ceil(N/128). At the cell's 16 x 540 x
    960 the plain body is 8.3 + 41.5 + 24.9 M passes a batch (12.4 ms at
    the MXU's limit), (2, 2) throughout 4.1 + 37.3 + 18.7 M (10.0 ms),
    the served (1, 2) / (2, 2) / (2, 4) 4.1 + 24.9 + 12.4 M (6.9 ms);
    the v5e reads map and head within 3% of that limit (PERF.md §5)."""
    from dvf_tpu.models.layers import zero_phase_taps   # lazily: this module stays jax-free to import

    phases = phases or {"feat": (1, 1), "map": (1, 1), "head": (1, 1)}

    def extent(k, fi, fo):          # taps x phases read along one axis
        return zero_phase_taps(k, fi, fo)[0].shape[0] * fi

    out, fi = {}, (1, 1)
    for name, k, cin, cout in (("feat", 5, 3, c1), ("map", 3, c1, c2),
                               ("head", 3, c2, 3 * scale * scale)):
        fo = phases[name]
        rows = height * width // (fo[0] * fo[1])
        k_dim = extent(k, fi[0], fo[0]) * extent(k, fi[1], fo[1]) * cin
        n_dim = fo[0] * fo[1] * cout
        passes = rows * math.ceil(k_dim / 128) * math.ceil(n_dim / 128)
        out[name] = {"rows": rows, "k": k_dim, "n": n_dim, "passes": passes,
                     "mxu_ms": passes / MXU_PASSES_PER_S * 1e3}
        fi = fo
    return out


def summarize(layers: List[LayerCost], label: str = "") -> dict:
    total_flops = sum(l.flops for l in layers)
    total_bytes = sum(l.hbm_bytes for l in layers)
    serial_ideal = sum(l.ideal_ms for l in layers)
    mxu_floor = sum(l.mxu_ms for l in layers)
    hbm_floor = sum(l.hbm_ms for l in layers)
    out = {
        "label": label,
        "total_gflops_per_frame": round(total_flops / 1e9, 2),
        "total_hbm_mb_per_frame": round(total_bytes / 1e6, 2),
        "mxu_floor_ms": round(mxu_floor, 3),
        "hbm_floor_ms": round(hbm_floor, 3),
        "serial_ideal_ms": round(serial_ideal, 3),
        "ideal_fps": round(1e3 / serial_ideal, 1) if serial_ideal else None,
        "mfu_at_ideal": round(
            total_flops / (serial_ideal * 1e-3) / (PEAK_BF16_TFLOPS * 1e12),
            4) if serial_ideal else None,
    }
    return out


def render_md(layers: List[LayerCost], summary: dict) -> str:
    lines = [
        f"### {summary.get('label', 'model')}",
        "",
        "| layer | kind | out HxWxC | GFLOP | HBM MB | lane eff | "
        "MXU ms | HBM ms | bound |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for l in layers:
        lines.append(
            f"| {l.name} | {l.kind} | {l.h}x{l.w}x{l.cout} "
            f"| {l.flops / 1e9:.2f} | {l.hbm_bytes / 1e6:.1f} "
            f"| {l.lane_eff:.2f} | {l.mxu_ms:.3f} | {l.hbm_ms:.3f} "
            f"| {l.bound}{' -- ' + l.note if l.note else ''} |")
    lines += ["", "```json", json.dumps(summary, indent=2), "```", ""]
    return "\n".join(lines)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--md-out", default="")
    args = ap.parse_args(argv)

    style = style_layer_costs(720, 1280)
    style_sum = summarize(style,
                          "style_720p (batch-independent, per frame)")
    sr = espcn_layer_costs(540, 960)
    sr_sum = summarize(sr, "sr2x_540p (batch-independent, per frame)")

    if args.json:
        print(json.dumps({"style_720p": style_sum, "sr2x_540p": sr_sum}))
    md = ("# Neural-config roofline decomposition (static model)\n\n"
          "Generated by `python -m dvf_tpu.models.analysis`. Constants: "
          f"{PEAK_BF16_TFLOPS:.0f} bf16 TFLOP/s, {PEAK_HBM_GBPS:.0f} GB/s "
          "HBM (public v5e datasheet). Per-layer MXU times model the "
          "128x128 systolic tiling (lane = output channels, sublane = "
          "k**2*Cin contraction); HBM times are activation traffic at "
          "the compute dtype. The style net's "
          "measured table on the chip, op by op with each op's stage "
          "(the plain composition beside the phase-domain forward that "
          "is served), is PERF.md section 5; scripts/style_step_probe.py "
          "re-measures it.\n\n"
          + render_md(style, style_sum) + "\n" + render_md(sr, sr_sum))
    if args.md_out:
        with open(args.md_out, "w") as f:
            f.write(md)
    else:
        print(md)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
