#!/usr/bin/env python3
"""What a batch of frames costs to put on the device, by shape of the same bytes. The mirror of ``d2h_probe.py``.

ROADMAP S1(a)'s probe (PR 47). For the invert batch (uint8[32,1080,1920,3], 199 MB), the stencil cell's
(uint8[64,1080,1920,3], 398 MB) and the style batch (uint8[16,720,1280,3], 44 MB; ``--shapes`` picks) it times, for host
buffers that hold the *same bytes*:

  S4     4 x uint8[B/4,H,W,3]     today's slab path: the staging ``np.copyto`` of B frames into four slabs (``stage_ms``),
                                  four ``device_put`` calls, ``jnp.concatenate`` on the device (``join``)
  R      B x uint8[H,W*C]         byte rows: each frame from a buffer of its own, viewed as bytes (no minor dimension
                                  of 3: nothing for the runtime to re-lay out), a ``device_put`` call a frame, nothing
                                  copied on the host; an unpack program on the device (``U`` below) makes the batch
  Rl     B x uint8[H,W*C]         the same rows handed to ONE ``device_put`` call as a list
  Rt2    B x uint8[H,W*C]         the rows put from 2 helper threads, each a share of the list (``calls_ms``: until the
                                  last thread's call has returned); Rt4 from 4
  R3     B x uint8[H,W,3]         THE SHIPPED ROW PATH (``runtime/ingest.py::put_rows``): a put a frame, the frame as
                                  the client holds it (minor dimension 3: the runtime de-interleaves it, a thread a
                                  transfer); ``ingest_join`` on the device makes the batch (``JB`` below)

Each line: ms for the calls to return (``calls_ms``, and a call: ``call_ms``), ms until every byte has landed
(``land_ms``, from before the first call), GB/s of one batch alone (``gbps``) and of a steady stream of batches with
``--depth`` in flight (``stream_gbps``, ``stream_ms_per_batch``, and what the stream costs the calling thread a batch, staging copy included: ``stream_host_ms``), the same stream with B rows of
D2H in flight beside it (``egress_pack``'s rows of a resident result, a ``copy_to_host_async`` each: ``duplex_*``, and
``duplex_d2h_gbps`` for what came down meanwhile), and whether the bytes on the device equal the frames.

Then the device time (wall of blocking calls on resident operands, an upper bound; least of 5) and compile time of the
programs that make the batch on the device: the unpack that byte rows need (a 0/1 permutation on the MXU over
lane-aligned chunks of the byte row, ``egress_pack``'s mirror; tried first, and not shipped: ``R3`` needs none), written
several ways, and the joins:

  U      the rows stacked inside the program, chunks of 128 pixels, one product a chunk
  Us     each chunk stacked from the rows' own slices (no whole-batch array of interleaved bytes inside the program)
  Uc     C products a chunk, one a plane (N = 128)
  U256   U with chunks of 256 pixels; U512 with 512 (``egress_pack``'s chunk)
  J4     the slab path's join: ``jnp.concatenate`` of the four slabs on the device
  JB     the row path's join: ``runtime.ingest.ingest_join`` of B frames ``uint8[H,W,3]``

Run on the chip:

    chiprun -- python scripts/h2d_probe.py            # writes chiprun_out/h2d_probe.json

``--toy`` runs tiny shapes on whatever backend jax has (the CPU here): it checks the script, and its rates mean
nothing.

``--landing-order`` (PR 54) asks instead what the serve path's landing probe may hold (``runtime/ingest.py::
BatchBuilder._finish_rows``): for ``--batches`` (200) batches of the shipped row path's put (``R3`` as one list), alone and with a
batch of D2H rows in flight beside it, once the LAST row's ``block_until_ready`` has returned, how many of the other
rows are not yet ready and how long the wait for them is (0 and 0: transfers of one ``device_put(list)`` land in order
and the last row proves the batch); then, the other way round, how long after the FIRST row the last one lands (the
first-to-last spread); and what ``is_ready()`` + ``block_until_ready()`` cost on a probe that has landed (the stamp's
cost when it has nothing to see). Writes ``chiprun_out/h2d_landing_order.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

BATCHES = {"invert_1080p": (32, 1080, 1920, 3), "sobel_bilateral_1080p": (64, 1080, 1920, 3),
           "style_720p": (16, 720, 1280, 3)}
TOY = {"toy_a": (8, 32, 48, 3), "toy_b": (4, 16, 960, 3)}
UNPACKS = {"U": ("", 128), "Us": ("s", 128), "Uc": ("c", 128), "U256": ("", 256), "U512": ("", 512)}


def unpack_table(channels: int, px: int = 128) -> np.ndarray:
    """The unpack's permutation for one chunk, ``float32[C, P*C, P]``: byte ``k`` of a chunk of the interleaved row is
    channel ``k % C`` of pixel ``k // C``, so row ``k`` of plane ``k % C`` holds its 1 in column ``k // C``."""
    k = np.arange(px * channels)
    table = np.zeros((channels, px * channels, px), np.float32)
    table[k % channels, k, k // channels] = 1.0
    return table


def unpack_variant(form: str):
    """B rows ``uint8[H, W*C]`` -> ``uint8[B,H,W,C]`` on the MXU (bytes are exact in bfloat16, one term a column in the
    float32 accumulator). ``s`` stacks each chunk from the rows' slices, ``c`` makes a product a plane."""
    import jax.numpy as jnp
    from jax import lax

    def ingest_unpack_variant(rows, table):
        channels, _, p = table.shape
        width = rows[0].shape[1] // channels
        whole = None if "s" in form else jnp.stack(rows)
        planes = [[] for _ in range(channels)]
        for w0 in range(0, width, p):
            n = min(p, width - w0)
            lo, hi = w0 * channels, (w0 + n) * channels
            x = whole[:, :, lo:hi] if whole is not None else jnp.stack([r[:, lo:hi] for r in rows])
            x = x.astype(jnp.bfloat16)
            if "c" in form:
                for c in range(channels):
                    planes[c].append(lax.dot_general(
                        x, table[c, :n * channels, :n], (((2,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32).astype(jnp.uint8))
                continue
            t = jnp.concatenate([table[c, :n * channels, :n] for c in range(channels)], axis=1)
            y = lax.dot_general(x, t, (((2,), (0,)), ((), ())), preferred_element_type=jnp.float32).astype(jnp.uint8)
            for c in range(channels):
                planes[c].append(y[..., c * n:(c + 1) * n])
        return jnp.stack([q[0] if len(q) == 1 else jnp.concatenate(q, axis=-1) for q in planes], axis=-1)

    return ingest_unpack_variant


def landing_order(args, jax, dev) -> int:
    """``--landing-order``: see the module docstring."""
    import jax.numpy as jnp

    from dvf_tpu.runtime.egress import egress_pack, pack_table

    report = {"device": f"{dev.platform}:{dev.device_kind}", "jax": jax.__version__, "toy": args.toy,
              "batches": args.batches, "results": {}}
    for name, shape in (TOY if args.toy else BATCHES).items():
        if args.shapes and name not in args.shapes.split(","):
            continue
        b, h, w, c = shape
        rng = np.random.default_rng(54)
        pool = [[rng.integers(0, 256, (h, w, c), dtype=np.uint8) for _ in range(b)] for _ in range(2)]
        down = None
        if (w * c) % 4 == 0:
            ptable = jax.device_put(jnp.asarray(pack_table(w, c), jnp.bfloat16), dev)
            resident = jax.device_put(np.stack(pool[0]), dev)
            pack = jax.jit(egress_pack)
            jax.block_until_ready(pack(resident, ptable))

            def down():
                out = pack(resident, ptable)
                for r in out:
                    r.copy_to_host_async()
                return out

        jax.block_until_ready(jax.device_put(list(pool[0]), dev))  # warm: the first put pays the allocator
        rows_out = {}
        for mode in ("alone", "duplex"):
            if mode == "duplex" and down is None:
                continue
            late_rows, late_ms, flight_ms, spread_ms = [], [], [], []
            for k in range(args.batches):
                coming = down() if mode == "duplex" else None
                t0 = time.perf_counter()
                rows = jax.device_put(list(pool[k % 2]), dev)
                if k % 10 == 9:     # every tenth batch the other way round: first row, then last
                    rows[0].block_until_ready()
                    t1 = time.perf_counter()
                    rows[-1].block_until_ready()
                    spread_ms.append((time.perf_counter() - t1) * 1e3)
                else:
                    rows[-1].block_until_ready()
                    t1 = time.perf_counter()
                    late_rows.append(sum(not r.is_ready() for r in rows[:-1]))
                    jax.block_until_ready(rows[:-1])
                    late_ms.append((time.perf_counter() - t1) * 1e3)
                    flight_ms.append((t1 - t0) * 1e3)
                if coming is not None:
                    for r in coming:
                        np.asarray(r)
                del rows
            rows_out[mode] = {
                "batches_last_first": len(late_rows),
                "batches_with_a_row_behind_the_last": sum(1 for n in late_rows if n),
                "rows_behind_the_last_max": max(late_rows), "rows_behind_the_last_total": sum(late_rows),
                "wait_for_them_ms_max": round(max(late_ms), 4),
                "wait_for_them_ms_median": round(float(np.median(late_ms)), 4),
                "put_to_last_row_ms_median": round(float(np.median(flight_ms)), 3),
                "batches_first_then_last": len(spread_ms),
                "first_to_last_ms_median": round(float(np.median(spread_ms)), 3),
                "first_to_last_ms_max": round(max(spread_ms), 3)}
            print(f"[{name}] landing order, {mode}: {json.dumps(rows_out[mode])}", flush=True)
        # The stamp's cost where there is nothing to see: the two asks of a probe that has landed.
        rows = jax.block_until_ready(jax.device_put(list(pool[0]), dev))
        for what, arrays in (("last_row", rows[-1:]), ("every_row", rows)):
            n = 2000
            t0 = time.perf_counter()
            for _ in range(n):
                for a in arrays:
                    a.is_ready()
                for a in arrays:
                    a.block_until_ready()
            rows_out[f"landed_probe_us_{what}"] = round((time.perf_counter() - t0) / n * 1e6, 3)
        print(f"[{name}] is_ready() + block_until_ready() on a landed probe, us: last row "
              f"{rows_out['landed_probe_us_last_row']}, every row {rows_out['landed_probe_us_every_row']}", flush=True)
        report["results"][name] = {"shape": list(shape), "modes": rows_out}
    out = os.path.join(os.path.dirname(args.out) or ".", "h2d_landing_order.json")
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    with open(out, "w") as f:
        json.dump(report, f, indent=1)
    print("wrote", out)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--toy", action="store_true", help="tiny shapes, any backend: checks the script only")
    ap.add_argument("--stream", type=int, default=8, help="batches in the steady stream")
    ap.add_argument("--depth", type=int, default=2, help="batches in flight in the stream")
    ap.add_argument("--only", default="", help="comma list of variants (S4,R,U,...) to run")
    ap.add_argument("--shapes", default="", help="comma list of batch names (invert_1080p,...) to run")
    ap.add_argument("--out", default="chiprun_out/h2d_probe.json")
    ap.add_argument("--landing-order", action="store_true",
                    help="whether the rows of one device_put(list) land in order (the landing probe's question)")
    ap.add_argument("--batches", type=int, default=200, help="batches a mode of --landing-order")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from dvf_tpu.runtime.egress import egress_pack, pack_table
    from dvf_tpu.runtime.ingest import ingest_join

    dev = jax.devices()[0]
    if dev.platform == "cpu" and not args.toy:
        print("no accelerator: run through chiprun, or pass --toy", file=sys.stderr)
        return 3
    if args.landing_order:
        return landing_order(args, jax, dev)
    only = set(filter(None, args.only.split(",")))
    shapes = set(filter(None, args.shapes.split(",")))
    pool_t = ThreadPoolExecutor(4)
    report = {"device": f"{dev.platform}:{dev.device_kind}", "jax": jax.__version__, "toy": args.toy,
              "stream": args.stream, "depth": args.depth, "results": {}}

    for name, shape in (TOY if args.toy else BATCHES).items():
        if shapes and name not in shapes:
            continue
        b, h, w, c = shape
        nbytes = int(np.prod(shape))
        rng = np.random.default_rng(47)
        # Two batches of frames, each frame a buffer of its own (as a client's submits are), used in turn.
        pool = [[rng.integers(0, 256, (h, w, c), dtype=np.uint8) for _ in range(b)] for _ in range(2)]
        ref = [np.stack(frames) for frames in pool]
        quarters = [(b * i // 4, b * (i + 1) // 4) for i in range(4) if b * (i + 1) // 4 > b * i // 4]
        slabs = [[np.empty((hi - lo, h, w, c), np.uint8) for lo, hi in quarters] for _ in range(3)]
        concat = jax.jit(lambda parts: jnp.concatenate(parts, axis=0))
        unpack = jax.jit(unpack_variant(""))
        join = jax.jit(ingest_join)
        utable = jax.device_put(jnp.asarray(unpack_table(c), jnp.bfloat16), dev)

        def stage(frames, slot):
            for (lo, hi), slab in zip(quarters, slabs[slot]):
                for i in range(lo, hi):
                    np.copyto(slab[i - lo], frames[i])

        def put_s4(frames, slot, timing):
            t0 = time.perf_counter()
            stage(frames, slot)
            t1 = time.perf_counter()
            parts = [jax.device_put(slab, dev) for slab in slabs[slot]]
            t2 = time.perf_counter()
            timing["stage_ms"] = (t1 - t0) * 1e3
            timing["calls_ms"] = (t2 - t1) * 1e3
            timing["calls"] = len(parts)
            return parts, (lambda: concat(parts))

        def put_rows(frames, slot, timing, view=True, as_list=False, threads=0):
            t1 = time.perf_counter()
            views = [f.reshape(h, w * c) if view else f for f in frames]
            if threads:
                shares = [views[len(views) * i // threads:len(views) * (i + 1) // threads] for i in range(threads)]
                rows = [r for part in pool_t.map(lambda share: jax.device_put(share, dev), shares) for r in part]
            else:
                rows = jax.device_put(views, dev) if as_list else [jax.device_put(v, dev) for v in views]
            t2 = time.perf_counter()
            timing["stage_ms"] = 0.0
            timing["calls_ms"] = (t2 - t1) * 1e3
            timing["calls"] = threads or (1 if as_list else len(rows))
            return rows, ((lambda: unpack(tuple(rows), utable)) if view else (lambda: join(*rows)))

        putters = {"S4": put_s4, "R": put_rows,
                   "Rl": lambda f, s, t: put_rows(f, s, t, as_list=True),
                   "Rt2": lambda f, s, t: put_rows(f, s, t, threads=2),
                   "Rt4": lambda f, s, t: put_rows(f, s, t, threads=4),
                   "R3": lambda f, s, t: put_rows(f, s, t, view=False)}

        # B rows of D2H to keep in flight beside the stream: egress_pack's rows of a resident result.
        down = None     # starts B transfers down; the caller lands them
        if (w * c) % 4 == 0:
            ptable = jax.device_put(jnp.asarray(pack_table(w, c), jnp.bfloat16), dev)
            resident = jax.device_put(ref[0], dev)
            pack = jax.jit(egress_pack)
            jax.block_until_ready(pack(resident, ptable))

            def start_down():
                out = pack(resident, ptable)
                for r in out:
                    r.copy_to_host_async()
                return out

            down = start_down

        rows_out = {}
        for v, put in putters.items():
            if only and v not in only:
                continue
            row = rows_out[v] = {}
            try:
                timing = {}
                arrs, make = put(pool[0], 0, timing)  # warm: the first put pays the allocator, make compiles
                t0 = time.perf_counter()
                batch = jax.block_until_ready(make())
                row["first_make_s"] = round(time.perf_counter() - t0, 2)  # the program's compile is in it
                row["bytes_equal"] = bool(np.array_equal(np.asarray(batch), ref[0]))
                del arrs, batch
                # one batch alone: calls, then landed
                lands, calls, stages = [], [], []
                for k in range(3):
                    timing = {}
                    t0 = time.perf_counter()
                    arrs, make = put(pool[k % 2], k % 3, timing)
                    jax.block_until_ready(arrs)
                    lands.append((time.perf_counter() - t0) * 1e3)
                    calls.append(timing["calls_ms"])
                    stages.append(timing["stage_ms"])
                    del arrs
                i = int(np.argmin(lands))
                row.update(stage_ms=round(stages[i], 2), calls_ms=round(calls[i], 2),
                           call_ms=round(calls[i] / timing["calls"], 3), land_ms=round(lands[i], 2),
                           gbps=round(nbytes / lands[i] / 1e6, 3))

                def stream(duplex):
                    q = deque()
                    t_calls = 0.0
                    d2h_bytes = 0
                    t_all = time.perf_counter()
                    for k in range(args.stream):
                        timing = {}
                        coming = down() if duplex else None
                        arrs, make = put(pool[k % 2], k % 3, timing)
                        t_calls += timing["calls_ms"] + timing["stage_ms"]
                        q.append((arrs, coming))
                        if len(q) > args.depth:
                            old, came = q.popleft()
                            jax.block_until_ready(old)
                            if came is not None:
                                d2h_bytes += sum(np.asarray(r).nbytes for r in came)
                    while q:
                        old, came = q.popleft()
                        jax.block_until_ready(old)
                        if came is not None:
                            d2h_bytes += sum(np.asarray(r).nbytes for r in came)
                    dt = time.perf_counter() - t_all
                    return dt, t_calls, d2h_bytes

                dt, t_calls, _ = stream(False)
                row.update(stream_ms_per_batch=round(dt * 1e3 / args.stream, 2),
                           stream_gbps=round(nbytes * args.stream / dt / 1e9, 3),
                           stream_host_ms=round(t_calls / args.stream, 2))
                if down is not None:
                    dt, t_calls, d2h = stream(True)
                    row.update(duplex_ms_per_batch=round(dt * 1e3 / args.stream, 2),
                               duplex_gbps=round(nbytes * args.stream / dt / 1e9, 3),
                               duplex_host_ms=round(t_calls / args.stream, 2),
                               duplex_d2h_gbps=round(d2h / dt / 1e9, 3))
            except Exception as e:  # noqa: BLE001 — a variant the runtime refuses is a finding
                row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            print(f"[{name}] {v:5s} {json.dumps(row)}", flush=True)

        # The programs that make the batch on the device, on resident operands.
        rows_dev = [jax.device_put(f.reshape(h, w * c), dev) for f in pool[0]]
        parts_dev = [jax.device_put(ref[0][lo:hi], dev) for lo, hi in quarters]
        jax.block_until_ready((rows_dev, parts_dev))
        programs = {v: (unpack_variant(form), px) for v, (form, px) in UNPACKS.items()}
        for v, (fn, px) in list(programs.items()) + [("J4", (None, 0)), ("JB", (None, 0))]:
            if only and v not in only:
                continue
            row = rows_out[v] = {}
            try:
                t0 = time.perf_counter()
                if v == "J4":
                    compiled = concat.lower(parts_dev).compile()
                    operands = (parts_dev,)
                elif v == "JB":
                    operands = tuple(jax.device_put(list(pool[0]), dev))
                    compiled = join.lower(*operands).compile()
                else:
                    table = jax.device_put(jnp.asarray(unpack_table(c, px), jnp.bfloat16), dev)
                    operands = (tuple(rows_dev), table)
                    compiled = jax.jit(fn).lower(*operands).compile()
                row["compile_s"] = round(time.perf_counter() - t0, 2)
                out = jax.block_until_ready(compiled(*operands))
                row["bytes_equal"] = bool(np.array_equal(np.asarray(out), ref[0]))
                del out
                ts = []
                for _ in range(5):
                    t0 = time.perf_counter()
                    jax.block_until_ready(compiled(*operands))
                    ts.append((time.perf_counter() - t0) * 1e3)
                row["program_ms"] = round(min(ts), 3)
                ts = []
                for _ in range(5):  # what the call costs the thread that makes it
                    t0 = time.perf_counter()
                    out = compiled(*operands)
                    ts.append((time.perf_counter() - t0) * 1e3)
                    jax.block_until_ready(out)
                row["dispatch_ms"] = round(min(ts), 3)
                ma = compiled.memory_analysis()
                if ma is not None:
                    row["temp_mb"] = round(ma.temp_size_in_bytes / 1e6, 1)
                    row["code_mb"] = round(ma.generated_code_size_in_bytes / 1e6, 1)
            except Exception as e:  # noqa: BLE001 — a program the compiler refuses is a finding
                row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            print(f"[{name}] {v:5s} {json.dumps(row)}", flush=True)
        del rows_dev, parts_dev
        report["results"][name] = {"shape": list(shape), "mbytes": round(nbytes / 1e6, 1), "variants": rows_out}

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print("wrote", args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
