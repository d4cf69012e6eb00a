#!/usr/bin/env python3
"""What a result costs to bring back from the device, by shape of the same bytes.

ROADMAP S1's ceiling probe (PR 26). For the invert result (uint8[32,1080,1920,3], 199 MB), the style result
(uint8[16,720,1280,3], 44 MB) and, since PR 39, the upscaler's (uint8[16,1080,1920,3], 100 MB) and the flow result
(uint8[64,720,1280,3], 177 MB; ``--shapes`` picks) it times one warm blocking fetch and a steady stream of fetches with
``copy_to_host_async`` in flight, for several device arrays that hold the *same bytes*:

  A      uint8[B,H,W,3]           the step program's result, today's transfer
  A4     A as 4 batch slices      fetched on 4 threads (does the host pass parallelise?)
  B      uint8[B, H*W*3]          flat rows of bytes
  C      uint8[N,128]             lane-dense bytes
  Dn     uint32[N,128]            the issue's pack: XLA's own reshape + bitcast (may not compile: 42x padded temp)
  D      uint32[B,H,W*3/4]        runtime.egress.pack_words (the interleave as a permutation on the MXU)
  R      B x uint32[H,W*3/4]      runtime.egress.egress_pack: D as B results of the one program, a transfer a row,
                                  fetched row by row on the calling thread (what the serve path lands since PR 39)
  Rw     B x uint32[H,W*3/4]      the same B results, sliced inside the program from D whole (R joins each row from
                                  the chunks and builds no whole-batch array)
  Rs     B x uint32[H,W*3/4]      D, then B eager slices of it on the host's dispatch path (the other way to rows)
  D128   uint32[N,128]            D reshaped on the device, N % 8 == 0
  E      D128 in 4 row chunks     fetched on 4 threads
  F      D in pinned_host         placed there by the pack program's out_shardings

Each line: GB/s of the blocking fetch and of the stream, the producing program's time on the device (wall of
blocking calls, an upper bound), and whether the bytes equal A's. R and Rs also say what starting the B transfers
costs the thread that starts them (``start_ms``, a batch) and what one kept row holds on the device once the batch is
dropped (``device_bytes_held_by_a_kept_row``: 0, or the router would have to copy after all). Then, for the arrays np.asarray hands back for A and
D (A's is not C-contiguous on a TPU: it keeps the device's channel-planar order) and for a C-contiguous numpy copy of
each, what the host's own passes cost (bringing it to C order, the slab copy, the router's row copies). Run on the chip:

    chiprun -- python scripts/d2h_probe.py            # writes chiprun_out/d2h_probe.json

``--toy`` runs tiny shapes on whatever backend jax has (the CPU here): it checks the script, and its rates mean
nothing.
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

RESULTS = {"invert_1080p": (32, 1080, 1920, 3), "style_720p": (16, 720, 1280, 3),
           "sr2x_540p": (16, 1080, 1920, 3), "flow_720p": (64, 720, 1280, 3)}
TOY = {"toy_a": (8, 32, 48, 3), "toy_b": (3, 16, 512, 3)}
ROW_VARIANTS = ("R", "Rw", "Rs")  # a device array a batch row, fetched one after another on the calling thread


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--toy", action="store_true", help="tiny shapes, any backend: checks the script only")
    ap.add_argument("--stream", type=int, default=8, help="fetches in the steady stream")
    ap.add_argument("--depth", type=int, default=3, help="copy_to_host_async in flight")
    ap.add_argument("--only", default="", help="comma list of variants (A,D,...) to run")
    ap.add_argument("--shapes", default="", help="comma list of result names (invert_1080p,...) to run")
    ap.add_argument("--out", default="chiprun_out/d2h_probe.json")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax

    from dvf_tpu.runtime.egress import egress_pack, pack_table, pack_words

    dev = jax.devices()[0]
    if dev.platform == "cpu" and not args.toy:
        print("no accelerator: run through chiprun, or pass --toy", file=sys.stderr)
        return 3
    only = set(filter(None, args.only.split(",")))
    shapes = set(filter(None, args.shapes.split(",")))
    pool = ThreadPoolExecutor(4)
    report = {"device": f"{dev.platform}:{dev.device_kind}", "jax": jax.__version__, "toy": args.toy,
              "stream": args.stream, "depth": args.depth, "results": {}}

    def start(a):
        for x in (a if isinstance(a, (tuple, list)) else (a,)):
            x.copy_to_host_async()

    def to_host(a, threads=True):
        """The fetch, as the collect thread would make it; parts on 4 threads, or one after another."""
        if isinstance(a, (tuple, list)):
            return list(pool.map(np.asarray, a)) if threads else [np.asarray(x) for x in a]
        return np.asarray(a)

    def as_bytes(host, shape):
        if isinstance(host, list):
            host = np.concatenate([h.reshape(-1).view(np.uint8) for h in host])
        n = int(np.prod(shape))
        return np.ascontiguousarray(host).reshape(-1).view(np.uint8)[:n].reshape(shape)

    for name, shape in (TOY if args.toy else RESULTS).items():
        if shapes and name not in shapes:
            continue
        b, h, w, c = shape
        nbytes = int(np.prod(shape))
        rng = np.random.default_rng(26)
        x = jax.device_put(rng.integers(0, 256, shape, dtype=np.uint8), dev)
        table = jax.device_put(jnp.asarray(pack_table(w, c), jnp.bfloat16), dev)
        step = jax.jit(lambda v: 255 - v)
        ref = 255 - np.asarray(x)
        pad = (-nbytes) % 4096

        def flat_words(y):  # the issue's D: XLA's own reshape of the planar bytes
            f = y.reshape(-1)
            if pad:
                f = jnp.pad(f, (0, pad))
            return lax.bitcast_convert_type(f.reshape(-1, 128, 4), jnp.uint32)

        def d128(y, t):
            wd = pack_words(y, t).reshape(-1)
            k = (-wd.shape[0]) % 1024
            return (jnp.pad(wd, (0, k)) if k else wd).reshape(-1, 128)

        def chunks(y, t):
            wd = d128(y, t)
            q = -(-wd.shape[0] // 4)
            return tuple(wd[i * q:(i + 1) * q] for i in range(4))

        pinned = None
        try:
            pinned = jax.sharding.SingleDeviceSharding(dev, memory_kind="pinned_host")
        except Exception as e:  # noqa: BLE001 — a backend without the memory kind
            print(f"[{name}] pinned_host: {e!r}")
        variants = {
            "A": (lambda y: y, False),
            "A4": (lambda y: tuple(y[i * b // 4:(i + 1) * b // 4] for i in range(4)) if b % 4 == 0 else None, False),
            "B": (lambda y: y.reshape(b, -1), False),
            "C": (lambda y: jnp.pad(y.reshape(-1), (0, (-nbytes) % 128)).reshape(-1, 128), False),
            "Dn": (flat_words, False),
            "D": (pack_words, True),
            "R": (egress_pack, True),
            "Rw": (lambda y, t: tuple(pack_words(y, t)[i] for i in range(b)), True),
            "Rs": (pack_words, True),
            "D128": (d128, True),
            "E": (chunks, True),
            "F": (pack_words, True),
        }
        rows = {}
        for v, (fn, takes_table) in variants.items():
            if only and v not in only:
                continue
            row = rows[v] = {}
            try:
                if v == "A4" and b % 4:
                    raise ValueError("batch not a multiple of 4")
                if v == "F" and pinned is None:
                    raise ValueError("no pinned_host memory kind")
                jitted = jax.jit(fn, **({"out_shardings": pinned} if v == "F" else {}))
                t0 = time.perf_counter()
                y0 = step(x)
                jax.block_until_ready(y0)
                extra = (table,) if takes_table else ()
                make_args = (y0,) + extra
                compiled = jitted.lower(*make_args).compile()
                row["compile_s"] = round(time.perf_counter() - t0, 2)

                threads = v not in ROW_VARIANTS

                def produce(y):
                    out = compiled(y, *extra)
                    return [out[i] for i in range(b)] if v == "Rs" else out

                def make():
                    return produce(step(x))

                jax.block_until_ready(make())  # warm
                # the producing program's device time: the blocking call less the step's own
                ts = []
                for _ in range(5):
                    y = jax.block_until_ready(step(x))
                    t0 = time.perf_counter()
                    jax.block_until_ready(produce(y))
                    ts.append((time.perf_counter() - t0) * 1e3)
                row["program_ms"] = round(min(ts), 3)
                del y
                in_use = (dev.memory_stats() or {}).get("bytes_in_use")  # with no result of this variant alive
                # one warm blocking fetch (no async copy started before it)
                a = jax.block_until_ready(make())
                t0 = time.perf_counter()
                host = to_host(a, threads)
                dt = time.perf_counter() - t0
                row["block_ms"] = round(dt * 1e3, 2)
                row["block_gbps"] = round(nbytes / dt / 1e9, 3)
                row["bytes_equal_A"] = bool(np.array_equal(as_bytes(host, shape), ref))
                if v in ROW_VARIANTS:
                    # what a kept row holds once its batch is gone: of the host one row, of the device nothing
                    row["transfers"] = len(a)
                    row["rows_share_memory"] = bool(any(np.shares_memory(host[0], o) for o in host[1:]))
                    kept = host[0]
                    del host, a
                    row["device_bytes_held_by_a_kept_row"] = (
                        None if in_use is None else int(dev.memory_stats()["bytes_in_use"] - in_use))
                    row["kept_row_intact"] = bool(np.array_equal(kept.view(np.uint8).reshape(shape[1:]), ref[0]))
                    del kept
                else:
                    del host, a
                starts = []
                # a steady stream: depth transfers in flight, fetch the oldest, issue the next
                q = deque()
                issued = 0

                def issue():
                    a = make()
                    t0 = time.perf_counter()
                    start(a)
                    starts.append((time.perf_counter() - t0) * 1e3)
                    q.append(a)

                for _ in range(min(args.depth, args.stream)):
                    issue()
                    issued += 1
                jax.block_until_ready(list(q))
                per = []
                t_all = time.perf_counter()
                for _ in range(args.stream):
                    a = q.popleft()
                    t0 = time.perf_counter()
                    host = to_host(a, threads)
                    per.append((time.perf_counter() - t0) * 1e3)
                    del host, a
                    if issued < args.stream:
                        issue()
                        issued += 1
                dt = time.perf_counter() - t_all
                row["stream_ms_per_fetch"] = round(dt * 1e3 / args.stream, 2)
                row["stream_gbps"] = round(nbytes * args.stream / dt / 1e9, 3)
                row["stream_fetch_ms"] = [round(p, 1) for p in per]
                row["start_ms"] = round(float(np.median(starts)), 3)
            except Exception as e:  # noqa: BLE001 — a variant the compiler or the runtime refuses is a finding
                row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            print(f"[{name}] {v:5s} {json.dumps(row)}", flush=True)
        # What the host's own passes cost over the array np.asarray hands back, and over a C-contiguous numpy copy of
        # it: the slab copy (the second pass of the plain path) and the router's row copies read that array.
        slab = np.empty(shape, np.uint8)
        slab.fill(0)

        def host_passes(src):
            """src: uint8[shape]; ms for each pass the serve path makes or could make over it."""
            out = {"c_contiguous": bool(src.flags.c_contiguous), "writeable": bool(src.flags.writeable),
                   "strides": list(src.strides)}
            t0 = time.perf_counter()
            np.ascontiguousarray(src).reshape(-1)[::4096].sum()   # a C-order view of it (a copy where it is none)
            out["to_c_order_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
            for k in ("slab_copy_ms", "slab_copy_again_ms"):
                t0 = time.perf_counter()
                np.copyto(slab, src)
                out[k] = round((time.perf_counter() - t0) * 1e3, 2)
            t0 = time.perf_counter()
            frames = [src[i].copy() for i in range(b)]
            out["row_copies_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
            del frames
            t0 = time.perf_counter()
            frames = list(pool.map(lambda i: src[i].copy(), range(b)))
            out["row_copies_4thr_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
            del frames
            return out

        for v, fn, extra in (("A", lambda y: y, ()), ("D", pack_words, (table,))):
            if only and v not in only:
                continue
            a = jax.jit(fn)(step(x), *extra)
            a.copy_to_host_async()
            landed = np.asarray(a)
            if v == "D":
                landed = landed.view(np.uint8).reshape(shape)
            rows[f"host_{v}_landed"] = host_passes(landed)
            rows[f"host_{v}_numpy"] = host_passes(np.array(landed, order="C"))
            del landed, a
            for k in (f"host_{v}_landed", f"host_{v}_numpy"):
                print(f"[{name}] {k:14s} {json.dumps(rows[k])}", flush=True)
        del slab
        report["results"][name] = {"shape": list(shape), "mbytes": round(nbytes / 1e6, 1), "variants": rows}

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print("wrote", args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
