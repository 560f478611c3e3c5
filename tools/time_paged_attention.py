"""Time the paged attention kernel on the chip, alone, under both of its
ways of contracting grouped heads, at the serving cells' geometries.

    python tools/time_paged_attention.py            # on a host with a TPU
    python tools/time_paged_attention.py --cells trinity_mini --calls 50

For each cell and width (``decode``: a row a lane, every slot; ``prefill``:
a chunk a lane, a prefill group's lanes), each attention group (the full
layer's table, the window layers' ring), one JSON line: milliseconds a call
with the rows contracted block-diagonally (every query head against the
whole ``H * D`` row of a page, in tiles of at most 512 query rows, the
kernel's geometry before it had a second arm) and a K/V head at a time
(``per_head``, in the tiles ``rows_tile`` gives it), the tiles a lane of
each, which arm ``rows_tile`` picks there, and the widest gap between
the two arms' outputs.  An arm is forced by moving the kernel's
MXU-rows threshold (``_MXU_ROWS``) for the measurement only.  Lanes
start at positions drawn from ``--seed`` (a chunk anywhere in a prompt
of the cell's lengths), the pools hold seeded bfloat16 noise, and each
lane's pages are its own.  ``--calls`` calls are chained in one jitted
loop (each call's output is the next one's query) and the clock is
stopped when the last is ready.  About a minute on one v5e chip.  Not
part of a benchmark run; ``PERF.md`` records what it read.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# (query heads, K/V heads, head size, page rows, slots, prefill chunk,
# prefill group lanes, positions a lane, window, window ring pages): the
# serving cells' engines (benchmarks/workloads)
CELLS = {
    "trinity_mini": (32, 4, 128, 16, 32, 128, 16, 8448, 2048, 137),
    "phi4_mini_flash_reasoning": (40, 10, 128, 16, 32, 256, 8, 9216, 512, 49),
    "nemotron3_nano_30b_a3b": (32, 2, 128, 16, 128, 256, 8, 2176, 0, 0),
}


def calls(pa, q, pools, positions, tables, window, tile_bytes, n):
    """A jitted chain of ``n`` calls, each fed the last one's output."""
    import jax

    entry = pa.paged_prefill_attention if q.shape[1] > 1 else pa.paged_decode_attention

    @jax.jit
    def chain(q, pk, pv, positions, tables):
        def body(_, q):
            return entry(q, pk, pv, positions, tables, layer=0, block_size=16,
                         window=window, tile_bytes=tile_bytes)

        return jax.lax.fori_loop(0, n, body, q)

    return lambda: chain(q, *pools, positions, tables)


@contextlib.contextmanager
def mxu_rows(pa, rows):
    """The kernel's arm threshold at ``rows`` inside the block: its
    jitted wrappers are traced anew on both sides."""
    old = pa._MXU_ROWS
    pa._MXU_ROWS = rows
    for fn in pa._JITTED.values():
        fn.clear_cache()
    try:
        yield
    finally:
        pa._MXU_ROWS = old
        for fn in pa._JITTED.values():
            fn.clear_cache()


def time_calls(make, reps):
    """(ms a call, the output of one call)."""
    import jax

    one, run = make(1), make(reps)
    out = jax.block_until_ready(one())
    jax.block_until_ready(run())  # compile
    t = time.perf_counter()
    jax.block_until_ready(run())
    return 1e3 * (time.perf_counter() - t) / reps, out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.ops.pallas import paged_attention as pa

    if jax.default_backend() != "tpu":
        sys.exit("time_paged_attention: no TPU (a time from elsewhere is no device number)")
    rng = np.random.default_rng(args.seed)
    key = jax.random.PRNGKey(args.seed)
    for cell in args.cells.split(","):
        QH, KV, D, BS, slots, chunk, group, seq, window, ring = CELLS[cell]
        groups = [("full", 0, seq // BS)] + ([("window", window, ring)] if window else [])
        for width, lanes, G in (("decode", slots, 1), ("prefill", group, chunk)):
            for name, win, MB in groups:
                n = lanes * MB + 1
                key, kk, kv, kq = jax.random.split(key, 4)
                pools = [jax.random.normal(k, (1, n * BS, KV * D), jnp.bfloat16)
                         for k in (kk, kv)]
                q = jax.random.normal(kq, (lanes, G, QH, D), jnp.bfloat16)
                tables = jnp.asarray(
                    1 + rng.permutation(lanes * MB).reshape(lanes, MB), jnp.int32)
                last = seq - G if width == "decode" else seq - chunk
                pos = rng.integers(0, last + 1, lanes)
                if width == "prefill":
                    pos -= pos % chunk  # a chunk starts at a multiple of the chunk
                positions = jnp.asarray(pos, jnp.int32)

                line = dict(cell=cell, width=width, group=name, lanes=lanes, G=G,
                            heads=f"{QH}/{KV}x{D}",
                            picked=pa.rows_tile(G, QH, KV, D)[1])
                outs = {}
                # block-diagonal in tiles of at most 512 query rows
                old_tile = (512 // QH) * QH * KV * D * 4
                for arm, rows, tile in (("block_diagonal", 1 << 30, old_tile),
                                        ("per_head", 1, None)):
                    def make(reps, q=q, pools=pools, positions=positions,
                             tables=tables, win=win, tile=tile):
                        return calls(pa, q, pools, positions, tables, win, tile, reps)

                    with mxu_rows(pa, rows):
                        gt, _ = pa.rows_tile(G, QH, KV, D, tile)
                        ms, outs[arm] = time_calls(make, args.calls)
                    line[f"{arm}_ms"] = round(ms, 4)
                    line[f"{arm}_tiles"] = G // gt
                line["max_gap"] = float(jnp.max(jnp.abs(
                    outs["per_head"].astype(jnp.float32) - outs["block_diagonal"].astype(jnp.float32))))
                print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
