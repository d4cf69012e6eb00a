"""Every cell end to end at a toy size on the CPU: paths, arguments and
control flow before a chip call. Run as

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python3 -m chipbench.rehearse [workload ...]

It prints counts only (frames, batches, accounting, `correct`) and never a
metric under a device's name: a time taken here says nothing about the chip.
"""

import sys
import time

from chipbench import run, spec


def main(argv):
    bench = spec.benchmark()
    names = argv or [w["name"] for w in bench["workloads"]]
    bad = []
    for name in names:
        cell = spec.Cell(name, bench, toy=True)
        result = run.run_cell(cell, seed=2_300_000_017, seconds=3.0, trace=True,
                              require_tpu=False, t_start=time.time())
        counts = {"correct": result["correct"], "attempted": result["attempted"],
                  "failed": result["failed"],
                  "per_layer_readers_that_found_something": sorted(result["metrics"])}
        print(f"[rehearse cpu] {name}: {counts}", flush=True)
        if not result["correct"] or not result["attempted"]:   # lateness on a CPU is no count
            bad.append(name)
    print(f"[rehearse cpu] {len(names) - len(bad)} of {len(names)} cells ran clean"
          + (f"; not clean: {bad}" if bad else ""))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
