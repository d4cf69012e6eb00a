"""Seeded input frames: a pool of distinct uint8 frames per run.

Each is a coarse random field blown up to the frame (structure a filter
can act on) under fine noise (so no two pixels need agree), shifted per
pool entry: a moving pattern plus noise. Session k's frame i is pool entry
(k + i) mod len(pool), so neighbouring sessions in one batch carry
different frames and a frame delivered to the wrong session or out of
order compares against other content.
"""

import numpy as np


def make_pool(seed, shape, n):
    h, w, c = shape
    rng = np.random.default_rng(int(seed))
    cell = 16
    coarse = rng.integers(0, 256, (h // cell + 2, w // cell + 2 + n, c), dtype=np.uint8)
    field = np.kron(coarse, np.ones((cell, cell, 1), dtype=np.uint8))
    noise = rng.integers(-24, 25, (h, w, c), dtype=np.int16)
    pool = []
    for i in range(n):
        base = field[:h, i * cell:i * cell + w].astype(np.int16)
        frame = np.clip(base + np.roll(noise, 7 * i, axis=1), 0, 255).astype(np.uint8)
        pool.append(np.ascontiguousarray(frame))
    return pool


def pool_index(session_k, frame_index, n):
    return (session_k + frame_index) % n
