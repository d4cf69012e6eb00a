"""The leak control of flow_720p: a shared batch whose rows take their
previous frame from the batch row before them, whoever that belongs to
(what a per-program state would do to a cross-session batch), has to come
out as not correct by the cell's own limits. At toy size here; at the
cell's size, by hand on the chip:

    python3 -m chipbench.tests.test_flow_leak 1,2,3
"""

import sys

from chipbench import check, frames, spec

CELL = "flow_720p.bulk"


def leak_numbers(cell, seed):
    pool = frames.make_pool(seed, cell.frame_shape, int(cell.mix["pool_frames"]))
    wanted = cell.ref.reference(pool, cell.config, None)
    serve = cell.config["serve"]
    sessions = ((serve["max_inflight"] + int(cell.mix["batches_beyond_inflight"]))
                * cell.batch_size // int(cell.mix["window"]))
    samples = cell.ref.leaky(pool, cell.config, None, sessions, cell.batch_size)
    return check.compare_numbers(samples, wanted, len(pool))


def test_leak_is_not_correct_at_toy_size():
    cell = spec.Cell(CELL, toy=True)
    for seed in (5, 2_300_000_017):
        numbers = leak_numbers(cell, seed)
        assert not check.decide(numbers, cell.config["limits"], log=lambda _: None), numbers


if __name__ == "__main__":
    cell = spec.Cell(CELL)
    verdicts = []
    for seed in (int(s) for s in sys.argv[1].split(",")):
        numbers = leak_numbers(cell, seed)
        print(f"[leak control, cell size] {CELL} seed {seed}: {numbers}", flush=True)
        verdicts.append(check.decide(numbers, cell.config["limits"]))
        print(f"[leak control, cell size] seed {seed}: correct = {verdicts[-1]}", flush=True)
    sys.exit(1 if any(verdicts) else 0)
