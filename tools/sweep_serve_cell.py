"""Sweep a serving cell's ``slots`` x ``prefill_chunk`` on the chip, in one
process: the model and its seeded weights are built once, an engine is
stood up for each pair (its two programs compile), a warm burst and then
``--seconds`` of the cell's own backlog run through it.

    chiprun -- python tools/sweep_serve_cell.py --workload <cell> \
        --slots 32,64,96 --chunks 128,256,512 --seconds 12 [--check-kernel]

One JSON line a pair: tokens/s, windows, what the allocator held after the
build, the engine's compile seconds; a pair that does not compile or fit
says so and the sweep goes on.  ``--adaptive`` tries every ``slots`` at the
middle chunk first and the other chunks only at the best of those.
``--check-kernel`` first compares the paged kernel at the cell's head
geometry (grouped heads, window, ring) with a dense float32 softmax on the
chip; ``--audit`` counts the first pair's compiled programs' whole-pool
copies and weight casts.  Not part of a benchmark run; ``PERF.md`` records what it read.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def check_kernel(m, e):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.ops.pallas import paged_attention as pa

    QH, KVH, D = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    BS, W, P = e["block_size"], int(m.get("sliding_window") or 0), e["prefill_chunk"]
    pos = np.asarray([5, W + 52, 3 * W + 777], np.int32)
    out = {}
    for window in sorted({0, W}):  # a model without window layers: the full walk alone
        for G in (1, P):
            S = int(pos.max()) + G
            R = -(-(W + P) // BS) + 1 if window else -(-S // BS)
            rng = np.random.default_rng(G + window)
            B = len(pos)
            keys = jnp.asarray(rng.standard_normal((B, S, KVH * D)), jnp.bfloat16)
            vals = jnp.asarray(rng.standard_normal((B, S, KVH * D)), jnp.bfloat16)
            tables = (1 + rng.permutation(B * R)).reshape(B, R).astype(np.int32)
            rows = np.zeros((B, S), np.int64)
            for b in range(B):
                p = np.arange(S)
                page = (p // BS) % R if window else p // BS
                rows[b] = tables[b, page] * BS + p % BS
            n = (B * R + 1) * BS
            pk = jnp.zeros((1, n, KVH * D), jnp.bfloat16)
            pv = jnp.zeros((1, n, KVH * D), jnp.bfloat16)
            for b in range(B):
                # oldest first, so that a ring keeps the newest write a row
                last = int(pos[b]) + G
                pk = pk.at[0, rows[b, :last]].set(keys[b, :last])
                pv = pv.at[0, rows[b, :last]].set(vals[b, :last])
            q = jnp.asarray(rng.standard_normal((B, G, QH, D)), jnp.bfloat16)
            fn = pa.paged_prefill_attention if G > 1 else pa.paged_decode_attention
            got = fn(q, pk, pv, jnp.asarray(pos), jnp.asarray(tables), layer=0,
                     block_size=BS, window=window)
            k4 = jnp.repeat(keys.reshape(B, S, KVH, D), QH // KVH, axis=2).astype(jnp.float32)
            v4 = jnp.repeat(vals.reshape(B, S, KVH, D), QH // KVH, axis=2).astype(jnp.float32)
            s = jnp.einsum("bghd,bshd->bghs", q.astype(jnp.float32), k4,
                           precision="highest") / np.sqrt(D)
            row = jnp.asarray(pos)[:, None] + jnp.arange(G)[None, :]
            kp = jnp.arange(S)[None, None, :]
            seen = kp <= row[:, :, None]
            if window:
                seen &= kp > row[:, :, None] - window
            s = jnp.where(seen[:, :, None, :], s, -jnp.inf)
            want = jnp.einsum("bghs,bshd->bghd", jax.nn.softmax(s, -1), v4, precision="highest")
            out[f"window{window}.G{G}"] = float(
                jnp.max(jnp.abs(got.astype(jnp.float32) - want))
            )
    print(json.dumps({"kernel_max_abs_error_vs_dense_float32": out}), flush=True)
    return max(out.values())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--slots", required=True)
    ap.add_argument("--chunks", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--adaptive", action="store_true")
    ap.add_argument("--check-kernel", action="store_true")
    ap.add_argument("--audit", action="store_true",
                    help="on the first pair, also count the compiled programs' pool "
                         "copies and weight casts (four more compiles)")
    args = ap.parse_args(argv)

    from benchmarks import run as R
    from benchmarks import traffic as T
    from benchmarks.jobs import serve as S

    cell, config, _, device, _ = R.prepare(args.workload)
    import importlib

    # the cell's job names the module its weights are drawn by
    WL = importlib.import_module(f"benchmarks.jobs.{cell['job']}").WL

    import jax

    from flexflow_tpu import FFConfig, FFModel, MachineMesh
    from flexflow_tpu.serve import ServeEngine

    e, t, m = cell["engine"], cell["mix"], config["model"]
    if args.check_kernel and check_kernel(m, e) > 5e-2:
        print("sweep: the kernel disagrees with the dense softmax", file=sys.stderr)
        return 1
    slots = [int(x) for x in args.slots.split(",")]
    chunks = [int(x) for x in args.chunks.split(",")]
    ref = importlib.import_module(f"benchmarks.reference.{config['family']}")
    mod, _, fn = config["builder"].partition(":")
    model = FFModel(FFConfig(
        batch_size=max(slots), compute_dtype=config["compute_dtype"],
        param_dtype=config.get("param_dtype", "float32"),
    ))
    getattr(importlib.import_module(mod), fn)(model, max(slots), e["max_seq"], **config["builder_args"])
    t0 = time.perf_counter()
    model.compile(seed=args.seed, mesh=MachineMesh((1, 1), ("data", "model")))
    WL.fill_executor(ref.param_shapes(m), args.seed, model.executor)
    jax.block_until_ready(model.executor.params)
    print(json.dumps({"model_s": time.perf_counter() - t0, "device": device,
                      "held_bytes": R.memory_stats().get("bytes_in_use")}), flush=True)

    audit = args.audit  # the first pair only

    def one(n_slots, chunk):
        nonlocal audit
        row = {"slots": n_slots, "prefill_chunk": chunk}
        try:
            t1 = time.perf_counter()
            engine = ServeEngine(
                model, slots=n_slots, block_size=e["block_size"], prefill_chunk=chunk,
                sync_every=e["sync_every"], attn=e["attn"], kv_dtype=e["kv_dtype"],
            )
            row["engine_s"] = time.perf_counter() - t1
            row["attn_kernel"] = engine.attn_kernel
            row["held_bytes"] = R.memory_stats().get("bytes_in_use")
            warm = T.generate(T.TrafficSpec(
                n_requests=4, seed=args.seed, prompt_len=(40, 70), max_new=(5, 9),
                vocab=m["vocab_size"]))
            engine.run(S.to_requests(warm, e["max_seq"]))
            reqs = S.to_requests(T.generate(T.spec_from_cell(
                t, seed=args.seed, seconds=args.seconds, vocab=m["vocab_size"])), e["max_seq"])
            report, window_s, _, _ = S.drive(engine, reqs, seconds=args.seconds, backlog=True)
            new = sum(len(r.tokens) for r in reqs)
            row.update(
                serve_tokens_per_s=new / window_s, window_s=window_s, windows=report.windows,
                decode_steps=report.decode_steps, prefill_dispatches=report.prefill_dispatches,
                finished=report.requests_finished, occupancy=report.occupancy_mean,
                prefill_positions=sum(int(r.prefill_pos) for r in reqs),
                peak_bytes=R.memory_peak_bytes(),
            )
            if audit:
                audit = False
                row.update(pool_relayouts=engine.pool_relayouts(),
                           weight_casts=engine.weight_casts(),
                           attn_interpret=engine.attn_interpret,
                           pool_bytes=engine.kv.hbm_bytes(),
                           state_pool_bytes=engine.kv.state_bytes())
            del engine, reqs, report
        except Exception as exc:  # noqa: BLE001 -- a pair that does not fit is a reading
            row["error"] = f"{type(exc).__name__}: {str(exc)[:300]}"
        gc.collect()
        jax.clear_caches()
        print(json.dumps(row), flush=True)
        return row.get("serve_tokens_per_s", 0.0)

    if args.adaptive:
        mid = chunks[len(chunks) // 2]
        best = max(slots, key=lambda n: one(n, mid))
        for c in chunks:
            if c != mid:
                one(best, c)
    else:
        for n in slots:
            for c in chunks:
                one(n, c)
    return 0


if __name__ == "__main__":
    sys.exit(main())
