"""From the profiler's trace (.xplane.pb) to numbers.

Read with jax.profiler.ProfileData and nothing else. A device plane is one
whose name starts with ``/device:`` and is not the host's; on it the line
``XLA Ops`` holds one event per operation run and ``XLA Modules`` one per
program run. The benchmark's own host spans (``chipbench.*``) are logged by
the generator on the host clock and moved onto the trace's clock by the
caller (the trace's zero is where start_trace was called).

  busy_s      union of the XLA Ops intervals, averaged over device planes
  step        events of XLA Modules whose name starts with the step's name,
              less each device's first and last (cut short by the trace's ends)
  device_ops  XLA Ops seconds summed by name, on the busiest device
  idle_gaps   the gaps of that device's busy union, summed by which
              chipbench span covered most of each gap
"""

import glob
import os

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_planes(path):
    """{"devices": {plane: {"ops": [(name, start_ns, dur_ns)], "modules": [...]}},
        "spans": [(name, start_ns, dur_ns)]}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices = {}
    for plane in data.planes:
        if not plane.name.startswith("/device:") or "CPU" in plane.name:
            continue
        for line in plane.lines:
            if line.name in (OPS_LINE, MODULES_LINE):
                key = "ops" if line.name == OPS_LINE else "modules"
                dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
                dev[key] += [(e.name, e.start_ns, e.duration_ns) for e in line.events]
    return {"devices": devices, "spans": []}


def union(intervals):
    """Merged, sorted [(start, end)] of (start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def _label_gap(gap, spans):
    """The span name covering most of the gap (innermost wins ties)."""
    gs, ge = gap
    best, best_cover = "unattributed", 0.0
    for name, s, d in spans:
        cover = min(ge, s + d) - max(gs, s)
        if cover > 0 and cover >= best_cover:     # later start = inner span
            best, best_cover = name, cover
    return best


def _extent(events):
    return (min(s for _, s, _ in events), max(s + d for _, s, d in events)) if events else None


def short_name(op):
    """``%fusion.24`` of ``%fusion.24 = bf16[...] fusion(...)``."""
    return op.split(" = ")[0][:80]


def _clip(events, window):
    """Events cut to the window: what lies outside it was not watched."""
    w0, w1 = window
    return [(n, max(s, w0), min(s + d, w1) - max(s, w0)) for n, s, d in events
            if s + d > w0 and s < w1]


def reduce_trace(planes, step_name):
    """The trace's numbers, or None where no operation ran on a device.
    The traced window is the extent of the devices' own events, from the
    first operation's start to the last one's end: an operation in progress
    when the profiler starts or stops is not recorded (the style net's
    longest takes 0.1 s), so the seconds between start_trace and the first
    event, and between the last event and stop_trace, were not watched and
    are no idle time. The benchmark's spans are cut to that window."""
    per_device = {}
    for name, dev in planes["devices"].items():
        events = dev["ops"] or dev["modules"]
        merged = union([(s, s + d) for _, s, d in events if d > 0])
        per_device[name] = (sum(e - s for s, e in merged) / 1e9, merged)
    if not per_device or not any(b for b, _ in per_device.values()):
        return None
    busy_s = sum(b for b, _ in per_device.values()) / len(per_device)
    w0, w1 = _extent([ev for dev in planes["devices"].values()
                      for ev in dev["ops"] + dev["modules"]])
    window_s = (w1 - w0) / 1e9
    spans = sorted(_clip(planes["spans"], (w0, w1)), key=lambda t: (t[1], -t[2]))
    fullest = max(per_device, key=lambda n: per_device[n][0])
    fullest_busy, merged = per_device[fullest]
    steps = []
    for dev in planes["devices"].values():
        # A program running when the profiler starts or stops is recorded cut
        # short: with them in, five traced seconds of a 284 ms step read 271
        # ms (PERF.md section 6, PR 24). A device's first and last step
        # events are left out wherever others remain.
        own = sorted((s, d) for n, s, d in dev["modules"] if n.startswith(step_name))
        steps += [d / 1e6 for _, d in (own[1:-1] if len(own) > 2 else own)]
    by_op = {}
    for n, _, d in planes["devices"][fullest]["ops"]:
        by_op[short_name(n)] = by_op.get(short_name(n), 0.0) + d / 1e9
    by_gap = {}
    edges = [(w0, w0)] + merged + [(w1, w1)]      # the fullest device's own ends are gaps too
    for (_, e0), (s1, _) in zip(edges, edges[1:]):
        if s1 - e0 < 50_000:            # under 50 us: back-to-back, not a gap
            continue
        label = _label_gap((e0, s1), spans)
        by_gap[label] = by_gap.get(label, 0.0) + (s1 - e0) / 1e9
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "busy_s": busy_s, "window_s": window_s,
        "fullest_busy_s": fullest_busy,
        "idle_pct": 100.0 * (1.0 - fullest_busy / window_s),
        "step_ms": (sum(steps) / len(steps)) if steps else None,
        "steps": len(steps),
        "breakdown": {"device_ops": top(by_op), "idle_gaps": top(by_gap)},
    }


def roofline_pct(cost, peak, step_ms):
    """Least time the chip could take over the time it took, and which
    bound binds. Over 105% means the cost or the time is wrong: raise."""
    t_flops = cost["flops"] / peak["bf16_flops_per_s"]
    t_bytes = cost["bytes"] / peak["hbm_bytes_per_s"]
    pct = 100.0 * max(t_flops, t_bytes) / (step_ms / 1e3)
    if pct > 105.0:
        raise ValueError(f"roofline share {pct:.1f}% is over 105%: the operations or "
                         f"bytes are counted too high, or step_ms leaves out work")
    return pct, ("flops" if t_flops >= t_bytes else "bytes")
