"""The control of ``correct``: the reference put in the program's place,
computed one precision step below what the configuration states (or, for a
configuration that states exact integers, on a float path that no longer
keeps them). It has to come out as not correct.

    python3 -m chipbench.controls --workload <cell> --seeds 1,2,3 [--toy]

For each seed it makes the run's own frames and weights, computes the
reference and the control over the whole pool, and prints the numbers the
check compares beside the configuration's limits. The benchmark's runs do
not run it; chipbench/tests/test_correct.py keeps it at a size a test holds.
"""

import argparse
import sys

from chipbench import check, frames, spec


def control_numbers(cell, seed):
    pool = frames.make_pool(seed, cell.frame_shape, int(cell.mix["pool_frames"]))
    params = cell.ref.make_params(seed, cell.config)
    wanted = cell.ref.reference(pool, cell.config, params)
    served = cell.ref.control(pool, cell.config, params)
    samples = [(0, i, f) for i, f in enumerate(served)]   # session 0: frame i is pool[i]
    return check.compare_numbers(samples, wanted, len(pool))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--toy", action="store_true")
    args = ap.parse_args(argv)
    cell = spec.Cell(args.workload, toy=args.toy)
    import jax

    where = f"{jax.devices()[0].platform} {'toy' if args.toy else 'cell size'}"
    passed = []
    for seed in (int(s) for s in args.seeds.split(",")):
        numbers = control_numbers(cell, seed)
        print(f"[control {where}] {cell.name} seed {seed}: {numbers}", flush=True)
        ok = check.decide(numbers, cell.config["limits"])
        print(f"[control {where}] seed {seed}: correct = {ok}", flush=True)
        passed.append(ok)
    return 1 if any(passed) else 0      # a control that comes out correct is the failure


if __name__ == "__main__":
    sys.exit(main())
