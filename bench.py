"""Benchmark: BERT-Base training throughput (samples/sec) + MFU on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.
Reference throughput reporting:
``src/metrics_functions/metrics_functions.cc:213-216`` (samples/s print);
the reference commits no absolute numbers, so ``vs_baseline`` stays 1.0
until BASELINE.json gains a recorded point.

One process, one device: ``python bench.py`` measures on the TPU and exits
non-zero when JAX finds none.  ``python bench.py --cpu`` is a smoke of the
same code at toy sizes; its record is named ``cpu_smoke_samples_per_s``,
never the device metric.  Any phase that raises ends the run non-zero.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

# bf16 peak FLOP/s per chip by device kind (public spec sheets)
_PEAK_BF16 = {
    "v4": 275e12,
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v5": 459e12,
    "v6e": 918e12,
    "v6 lite": 918e12,
}


def _peak_flops(device_kind: str) -> float:
    dk = device_kind.lower()
    for key, val in _PEAK_BF16.items():
        if key in dk:
            return val
    raise ValueError(
        f"no bf16 peak for device kind {device_kind!r} "
        f"(known: {sorted(_PEAK_BF16)}); add it to _PEAK_BF16 with its source"
    )


def _attention_core_compare():
    """fwd+bwd ms per call for the Pallas flash kernel vs XLA's fused sdpa
    at BERT-shaped s=512 and long-context s=2048 (bf16, d=64).  Returns
    {s: {"flash_ms", "sdpa_ms"}}."""
    import math
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from flexflow_tpu.ops.pallas.flash_attention import flash_attention

    def sdpa(q, k, v):
        d = q.shape[-1]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
        p = jax.nn.softmax(s / math.sqrt(d), axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    def bwd_chain(core, k, v, reps):
        g = jax.grad(
            lambda q, kk, vv: jnp.sum(core(q, kk, vv).astype(jnp.float32)),
            argnums=(0, 1, 2),
        )

        @jax.jit
        def f(q):
            def body(c, _):
                dq, dk, dv = g(c, k, v)
                return (dq + dk + dv).astype(q.dtype), None

            out, _ = lax.scan(body, q, None, length=reps)
            return jnp.sum(out.astype(jnp.float32))

        return f

    out = {}
    for b, h, s, reps in ((16, 12, 512, 10), (4, 12, 2048, 6)):
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(b, h, s, 64)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(b, h, s, 64)), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(b, h, s, 64)), jnp.bfloat16)
        row = {}
        for name, core in (("flash", flash_attention), ("sdpa", sdpa)):
            f = bwd_chain(core, k, v, reps)
            jax.block_until_ready(f(q))  # compile + warmup
            t0 = _time.perf_counter()
            for _ in range(3):
                r = f(q)
            jax.block_until_ready(r)
            row[f"{name}_ms"] = round(
                (_time.perf_counter() - t0) / 3 / reps * 1000.0, 3
            )
        out[f"s{s}"] = row
    return out


def _median_sps(model, xs, y, batch: int, steps: int, windows: int) -> dict:
    """Median samples/s over independent timing windows, each ended by
    ``block_until_ready`` on the last step's loss.  THE timing
    methodology — headline and secondary configs both use it, so the two
    can never drift apart.  True median: an even window count averages
    the two middle elements (taking the upper-middle would report
    best-of-2 for windows=2)."""
    import jax

    ex = model.executor
    xs = [
        ex._place(a, ex._input_pspec(t), t.shape[0])
        for a, t in zip(xs, ex.graph_inputs)
    ]
    y = ex._place(y, ex._label_pspec(), ex.graph_inputs[0].shape[0])
    loss, _ = ex.train_step(xs, y)
    jax.block_until_ready(loss)  # compile + warmup
    sps = []
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(steps):
            loss, _ = ex.train_step(xs, y)
        jax.block_until_ready(loss)
        sps.append(steps * batch / (time.perf_counter() - t0))
    sps.sort()
    n = len(sps)
    mid = sps[n // 2] if n % 2 else 0.5 * (sps[n // 2 - 1] + sps[n // 2])
    return {
        "samples_per_sec": round(mid, 2),
        "step_time_ms": round(1000.0 * batch / mid, 2),
        "sps_min": round(sps[0], 2),
        "sps_max": round(sps[-1], 2),
        "timing_windows": windows,
    }


def _fit_sync_async_ab(model, x, y, batch: int, batches: int) -> dict:
    """Sync-vs-async A/B on the SAME compiled step, driven through the
    real ``FFModel.fit`` loop in the same process: ``metrics_sync_every=1``
    forces the reference behavior (one blocking device round-trip per
    step) vs the async K-step flush (auto K).  Reports per-mode step
    time, the executor's host-sync count, and the measured host-side
    stall (wall time blocked in forced fetches) with its fraction of the
    loop — the direct evidence that the async pipeline removed the
    per-step pipeline flush."""
    import time as _time

    import numpy as np

    ex = model.executor
    X = np.concatenate([x] * batches)
    Y = np.concatenate([y] * batches)
    out = {}
    for mode, k in (("sync", 1), ("async", 0)):
        h0, s0 = ex.host_syncs, ex.host_stall_s
        t0 = _time.perf_counter()
        model.fit(X, Y, batch_size=batch, epochs=1, verbose=False,
                  metrics_sync_every=k)
        total = _time.perf_counter() - t0
        stall = ex.host_stall_s - s0
        out[mode] = {
            "steps": batches,
            "step_time_ms": round(total / batches * 1e3, 3),
            "host_syncs": ex.host_syncs - h0,
            "host_stall_s": round(stall, 6),
            "stall_fraction": round(stall / total, 4) if total > 0 else 0.0,
        }
    out["speedup"] = round(
        out["sync"]["step_time_ms"] / out["async"]["step_time_ms"], 3
    ) if out["async"]["step_time_ms"] else None
    out["metrics_sync_every_async"] = model._resolve_metrics_sync_every(0)
    return out


def _compile_stacked_ab(on_tpu: bool) -> dict:
    """Stacked-vs-unrolled compile A/B (ISSUE 5): the SAME model traced +
    AOT-compiled with ``--stack-blocks auto`` (repeated transformer
    blocks execute as one ``jax.lax.scan`` over depth-stacked params)
    vs ``off`` (today's unrolled path), at BERT-Base depth 12 and a
    depth-24 variant on the CPU-smoke shapes.  Records per arm:
    ``trace_s`` (jit lower), ``jit_compile_s`` (XLA compile of the
    lowered step), and the steady-state ``step_time_ms`` — stacking
    trades some cross-layer fusion for depth-independent compile, so
    both sides of that trade are recorded."""
    import time as _time

    import jax
    import numpy as np

    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, LossType
    from flexflow_tpu.models.transformer import transformer_encoder

    batch, seq, hidden = (8, 128, 256) if on_tpu else (4, 64, 128)

    def arm(stack: str, layers: int) -> dict:
        cfg = FFConfig(batch_size=batch, stack_blocks=stack)
        m = FFModel(cfg)
        transformer_encoder(
            m, batch=batch, seq=seq, hidden=hidden, heads=8,
            ff_dim=2 * hidden, num_layers=layers, vocab=1000,
            num_classes=16, use_flash=False, raw_input=True,
        )
        m.compile(
            optimizer=AdamOptimizer(alpha=1e-4),
            loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY, seed=0,
        )
        rng = np.random.default_rng(0)
        x = rng.normal(size=(batch, seq, hidden)).astype(np.float32)
        y = rng.integers(0, 16, size=(batch, 1)).astype(np.int32)
        ex = m.executor
        ex._step_jit = ex._build_step()
        inputs, labels = ex.place_batch([x, y])
        args = (ex.params, ex.state, ex.opt_state, inputs, labels, 0)
        t0 = _time.perf_counter()
        lowered = ex._step_jit.lower(*args)
        trace_s = _time.perf_counter() - t0
        t0 = _time.perf_counter()
        compiled = lowered.compile()
        compile_s = _time.perf_counter() - t0
        out = jax.block_until_ready(compiled(*args))
        steps = 5
        t0 = _time.perf_counter()
        for i in range(steps):
            out = compiled(out[0], out[1], out[2], inputs, labels, i + 1)
        jax.block_until_ready(out)
        return {
            "trace_s": round(trace_s, 3),
            "jit_compile_s": round(compile_s, 3),
            "step_time_ms": round(
                (_time.perf_counter() - t0) / steps * 1e3, 2
            ),
        }

    out = {"config": f"b={batch} s={seq} h={hidden} (cpu smoke)" if not on_tpu
           else f"b={batch} s={seq} h={hidden}"}
    for layers in (12, 24):
        un = arm("off", layers)
        st = arm("auto", layers)
        tot_un = un["trace_s"] + un["jit_compile_s"]
        tot_st = st["trace_s"] + st["jit_compile_s"]
        out[f"depth{layers}"] = {
            "unrolled": un,
            "stacked": st,
            "trace_compile_speedup": round(tot_un / tot_st, 2)
            if tot_st > 0 else None,
        }
    return out


def _pipeline_1f1b_ab(on_tpu: bool) -> dict:
    """Pipelined-vs-non-pipelined A/B (ISSUE 8, docs/PIPELINE.md): the
    depth-24 smoke transformer stepped through the same harness twice —
    ``--pipeline off`` vs a forced S=2 / M=4 1F1B schedule (virtual
    stages on one device, real stage submeshes when the mesh carries the
    axis).  Records per-arm AOT step time, the schedule's bubble
    fraction ``(S-1)/(M+S-1)``, the executor host-sync ledger (the 1F1B
    step must add ZERO), and the max |loss| divergence over 5 steps at
    equal global batch — the bench-side shadow of the parity test."""
    import time as _time

    import jax
    import numpy as np

    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, LossType
    from flexflow_tpu.models.transformer import transformer_encoder

    batch, seq, hidden, layers = (8, 128, 256, 24) if on_tpu else (4, 64, 128, 24)

    def arm(pipeline: str, microbatches: int) -> dict:
        cfg = FFConfig(
            batch_size=batch, stack_blocks="auto",
            pipeline=pipeline, microbatches=microbatches,
        )
        m = FFModel(cfg)
        transformer_encoder(
            m, batch=batch, seq=seq, hidden=hidden, heads=8,
            ff_dim=2 * hidden, num_layers=layers, vocab=1000,
            num_classes=16, use_flash=False, raw_input=True,
        )
        m.compile(
            optimizer=AdamOptimizer(alpha=1e-4),
            loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY, seed=0,
        )
        rng = np.random.default_rng(0)
        x = rng.normal(size=(batch, seq, hidden)).astype(np.float32)
        y = rng.integers(0, 16, size=(batch, 1)).astype(np.int32)
        ex = m.executor
        syncs0 = ex.host_syncs
        ex._step_jit = ex._build_step()
        inputs, labels = ex.place_batch([x, y])
        args = (ex.params, ex.state, ex.opt_state, inputs, labels, 0)
        t0 = _time.perf_counter()
        compiled = ex._step_jit.lower(*args).compile()
        compile_s = _time.perf_counter() - t0
        out = jax.block_until_ready(compiled(*args))
        losses = [float(out[3])]
        steps = 5
        t0 = _time.perf_counter()
        for i in range(steps):
            out = compiled(out[0], out[1], out[2], inputs, labels, i + 1)
            losses.append(float(out[3]))
        jax.block_until_ready(out)
        step_ms = (_time.perf_counter() - t0) / steps * 1e3
        spec = ex.pipeline
        return {
            "pipeline": spec.identity() if spec is not None else "off",
            "bubble_frac": round(spec.bubble_frac, 4) if spec else 0.0,
            "jit_compile_s": round(compile_s, 3),
            "step_time_ms": round(step_ms, 2),
            "extra_host_syncs": ex.host_syncs - syncs0,
            "losses": [round(v, 6) for v in losses],
        }

    off = arm("off", 0)
    pl = arm("2", 4)
    return {
        "config": f"b={batch} s={seq} h={hidden} depth={layers}"
        + ("" if on_tpu else " (cpu smoke)"),
        "non_pipelined": off,
        "pipelined": pl,
        "loss_parity_max_abs": round(
            max(abs(a - b) for a, b in zip(off["losses"], pl["losses"])), 6
        ),
        "step_time_ratio": round(
            pl["step_time_ms"] / off["step_time_ms"], 3
        ) if off["step_time_ms"] else None,
    }


def _fit_overlap_smoke() -> dict:
    """The in-process half of :func:`_fit_overlap_ab`: the depth-24
    smoke transformer stepped twice — ``--grad-overlap off`` vs a
    forced ``ring`` — on a (n, 1) data×model mesh over every visible
    device.  Runs in a forced-8-device subprocess on a 1-device CPU
    host (the ring needs data extent > 1 to engage)."""
    import time as _time

    import jax
    import numpy as np

    from flexflow_tpu import (
        AdamOptimizer, FFConfig, FFModel, LossType, MachineMesh,
    )
    from flexflow_tpu.models.transformer import transformer_encoder

    on_tpu = jax.devices()[0].platform == "tpu"
    batch, seq, hidden, layers = (
        (8, 128, 256, 24) if on_tpu else (4, 64, 128, 24)
    )
    n = len(jax.devices())
    if batch % n:  # the data axis must divide the global batch
        batch = n * ((batch + n - 1) // n)

    def arm(go: str) -> dict:
        cfg = FFConfig(
            batch_size=batch, stack_blocks="auto", grad_overlap=go,
        )
        m = FFModel(cfg)
        transformer_encoder(
            m, batch=batch, seq=seq, hidden=hidden, heads=8,
            ff_dim=2 * hidden, num_layers=layers, vocab=1000,
            num_classes=16, use_flash=False, raw_input=True,
        )
        m.compile(
            optimizer=AdamOptimizer(alpha=1e-4),
            loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY, seed=0,
            mesh=MachineMesh((n, 1), ("data", "model")),
        )
        rng = np.random.default_rng(0)
        x = rng.normal(size=(batch, seq, hidden)).astype(np.float32)
        y = rng.integers(0, 16, size=(batch, 1)).astype(np.int32)
        ex = m.executor
        syncs0 = ex.host_syncs
        ex._step_jit = ex._build_step()
        inputs, labels = ex.place_batch([x, y])
        args = (ex.params, ex.state, ex.opt_state, inputs, labels, 0)
        t0 = _time.perf_counter()
        compiled = ex._step_jit.lower(*args).compile()
        compile_s = _time.perf_counter() - t0
        out = jax.block_until_ready(compiled(*args))
        losses = [float(out[3])]
        steps = 5
        t0 = _time.perf_counter()
        for i in range(steps):
            out = compiled(out[0], out[1], out[2], inputs, labels, i + 1)
            losses.append(float(out[3]))
        jax.block_until_ready(out)
        return {
            "grad_overlap": go,
            "ring_engaged": bool(ex._grad_ring),
            "jit_compile_s": round(compile_s, 3),
            "step_time_ms": round(
                (_time.perf_counter() - t0) / steps * 1e3, 2
            ),
            "extra_host_syncs": ex.host_syncs - syncs0,
            "losses": [round(v, 6) for v in losses],
        }

    off = arm("off")
    ring = arm("ring")
    return {
        "config": f"b={batch} s={seq} h={hidden} depth={layers} dp={n}"
        + ("" if on_tpu else " (cpu smoke)"),
        "fused": off,
        "ring": ring,
        "loss_parity_max_abs": round(
            max(abs(a - b)
                for a, b in zip(off["losses"], ring["losses"])), 6
        ),
        "step_time_ratio": round(
            ring["step_time_ms"] / off["step_time_ms"], 3
        ) if off["step_time_ms"] else None,
    }


def _fit_overlap_ab(on_tpu: bool) -> dict:
    """Overlapped-gradient-sync A/B (--grad-overlap, docs/PERF.md
    "Overlapped gradient sync"): (1) the depth-24 smoke transformer
    stepped off-vs-ring at equal global batch — losses must agree at
    parity tolerances and the ring must add ZERO host syncs; (2) the
    BERT-Large priced estimate — ``estimate_strategy_cost`` off vs the
    overlap model's adjustment on a dp=8 placement, recording
    ``exposed_comm_frac`` = exposed ring time / fused sync time (the
    share of the fused tail sync the ring could NOT hide; LOWER is
    better, gated by tools/bench_compare.py)."""
    import jax

    if on_tpu or len(jax.devices()) > 1:
        smoke = _fit_overlap_smoke()
    else:
        # 1-device CPU host: the ring declines at data extent 1, so the
        # smoke runs in a subprocess with 8 forced host devices (the
        # same virtual topology the tier-1 tests pin)
        code = (
            "import importlib.util, json, os; "
            "spec = importlib.util.spec_from_file_location"
            f"('bench', {os.path.abspath(__file__)!r}); "
            "b = importlib.util.module_from_spec(spec); "
            "spec.loader.exec_module(b); "
            "print(json.dumps(b._fit_overlap_smoke()))"
        )
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        )
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True,
            timeout=900, env=env, text=True,
        )
        if r.returncode != 0:
            raise RuntimeError(
                f"overlap smoke child failed: {r.stderr[-300:]}"
            )
        smoke = json.loads(r.stdout.strip().splitlines()[-1])

    # BERT-Large priced estimate (pure pricing — no devices): dp=8 over
    # ICI, the overlap model's whole-step adjustment vs the fused sync
    from flexflow_tpu import FFConfig, FFModel, MachineMesh
    from flexflow_tpu.models.transformer import BERT_LARGE, transformer_encoder
    from flexflow_tpu.parallel.machine import PhysicalTopology
    from flexflow_tpu.parallel.strategy import data_parallel_strategy
    from flexflow_tpu.search.cost import (
        TPUMachineModel,
        estimate_strategy_cost,
        grad_overlap_adjustment,
    )

    model = FFModel(FFConfig(batch_size=8))
    transformer_encoder(
        model, batch=8, seq=512, num_classes=16, vocab=32000,
        use_flash=False, **BERT_LARGE,
    )
    mesh = MachineMesh((8, 1), ("data", "model"))
    mach = TPUMachineModel(
        topology=PhysicalTopology((2, 2, 2), wrap=(True, True, True))
    )
    st = data_parallel_strategy(model.layers, mesh)
    fused_step_s = estimate_strategy_cost(model.layers, st, mach)
    delta, price = grad_overlap_adjustment(
        model.layers, st, mach, mode="auto"
    )
    priced = {
        "config": "bert-large dp=8 (priced estimate)",
        "fused_step_s": round(fused_step_s, 6),
        "ring_step_s": round(fused_step_s - delta, 6),
        "saved_s": round(delta, 6),
    }
    frac = None
    if price is not None and price.get("fused_s"):
        frac = price["exposed_s"] / price["fused_s"]
        priced.update(
            fused_sync_s=round(price["fused_s"], 6),
            exposed_s=round(price["exposed_s"], 6),
            overlap_frac=price["overlap_frac"],
            chains=price["chains"],
        )
    return {
        "smoke": smoke,
        "priced": priced,
        "exposed_comm_frac": round(frac, 4) if frac is not None else None,
    }


def _bench_dlrm(on_tpu: bool) -> dict:
    """Embedding-bound DLRM single-chip step (VERDICT r3 #4 / BASELINE.json
    north star; shapes from reference examples/cpp/DLRM/dlrm.cc:114-241 —
    4 tables, 64-dim sparse features, bot 64-64, top 64-64-2).  ``--cpu``
    runs a scaled-down smoke config."""
    import numpy as np

    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models.dlrm import dlrm

    vocab = 1_000_000 if on_tpu else 1_000
    batch = 2048 if on_tpu else 64
    cfg = FFConfig(batch_size=batch)
    model = FFModel(cfg)
    dlrm(model, batch, embedding_sizes=(vocab,) * 4)
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
    )
    rng = np.random.default_rng(0)
    xs = [
        rng.integers(0, vocab, size=(batch, 1)).astype(np.int32)
        for _ in range(4)
    ]
    xs.append(rng.normal(size=(batch, 4)).astype(np.float32))
    y = rng.uniform(size=(batch, 2)).astype(np.float32)
    out = _median_sps(
        model, xs, y, batch,
        steps=10 if on_tpu else 2, windows=3 if on_tpu else 2,
    )
    out["config"] = f"4x{vocab}-vocab tables, sfs 64, b={batch}" + (
        "" if on_tpu else " (cpu smoke)"
    )
    return out


def _bench_bert_large(on_tpu: bool) -> dict:
    """BERT-Large single-chip short-step config (the second BASELINE.json
    north-star metric), bf16 on TPU."""
    import numpy as np

    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, LossType
    from flexflow_tpu.models.transformer import BERT_LARGE, transformer_encoder
    from flexflow_tpu.ops.base import get_op_def

    batch = 8 if on_tpu else 2
    seq = 512 if on_tpu else 64
    shape = BERT_LARGE if on_tpu else dict(
        hidden=128, heads=8, ff_dim=256, num_layers=2
    )
    cfg = FFConfig(
        batch_size=batch, compute_dtype="bfloat16" if on_tpu else "float32"
    )
    model = FFModel(cfg)
    transformer_encoder(
        model, batch=batch, seq=seq, num_classes=64, raw_input=True, **shape
    )
    model.compile(
        optimizer=AdamOptimizer(alpha=1e-4),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
    )
    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, seq, shape["hidden"])).astype(np.float32)
    y = rng.integers(0, 64, size=(batch, 1)).astype(np.int32)
    out = _median_sps(
        model, [x], y, batch,
        steps=10 if on_tpu else 2, windows=3 if on_tpu else 2,
    )
    if on_tpu:
        import jax

        fwd_flops = sum(
            get_op_def(l.op_type).flops(l)
            for l in model.layers
            if not l.op_type.is_parallel_op
        )
        peak = _peak_flops(jax.devices()[0].device_kind)
        if peak:
            out["mfu"] = round(
                3.0 * fwd_flops / (out["step_time_ms"] / 1000.0) / peak, 4
            )
    out["config"] = (
        f"BERT-Large b={batch} s={seq} bf16" if on_tpu
        else "2-layer h128 (cpu smoke)"
    )
    return out


def _bench_gpt_decode(on_tpu: bool) -> dict:
    """KV-cache decode vs the reference-style full-prefix path (round-5
    verdict #9): tokens/s for each, at a prefix long enough that the
    full-prefix forward's O(S^2) re-computation shows.

    Timing (round-8 de-noise): the single-window measurement swung ±30%
    run-to-run at smoke scale (round-7 CPU records), so both paths
    now take warmup + the MEDIAN over 5 independent timed windows — the
    ``_median_sps`` discipline — and the record carries the min/max
    spread so a reader can see whether a delta clears the noise band."""
    import time

    import numpy as np

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.gpt_decode import GPTDecodeSession
    from flexflow_tpu.models.transformer import gpt_decoder

    batch = 8 if on_tpu else 2
    seq = 512 if on_tpu else 64
    shape = (
        dict(hidden=768, heads=12, ff_dim=3072, num_layers=12)
        if on_tpu
        else dict(hidden=64, heads=4, ff_dim=128, num_layers=2)
    )
    vocab = 32000 if on_tpu else 256
    cfg = FFConfig(
        batch_size=batch,
        compute_dtype="bfloat16" if on_tpu else "float32",
    )
    model = FFModel(cfg)
    gpt_decoder(model, batch, seq, vocab=vocab, **shape)
    model.compile(seed=0)
    rng = np.random.default_rng(0)
    prompt_len = seq // 2
    toks = rng.integers(0, vocab, size=(batch, seq)).astype(np.int32)
    windows = 5

    def median_spread(vals):
        vals = sorted(vals)
        n = len(vals)
        mid = (
            vals[n // 2] if n % 2 else 0.5 * (vals[n // 2 - 1] + vals[n // 2])
        )
        return mid, vals[0], vals[-1]

    sess = GPTDecodeSession(model)  # warms up / compiles the step
    n_steps = 32 if on_tpu else 8
    for t in range(3):  # warmup at measured positions
        p = sess.step(toks[:, t], t)
    float(np.asarray(p)[0, 0])
    sess.reset()
    cached = []
    for w in range(windows):
        # each window decodes a fresh run of positions and ends on a
        # host fetch of the last step's output
        base = prompt_len + w * n_steps // windows
        t0 = time.perf_counter()
        for i in range(n_steps):
            p = sess.step(toks[:, (base + i) % seq], (base + i) % seq)
        float(np.asarray(p)[0, 0])
        cached.append(n_steps * batch / (time.perf_counter() - t0))
    cached_mid, cached_min, cached_max = median_spread(cached)

    # full-prefix path: one masked forward per token (what gpt_generate
    # does); same positions
    cur = toks.copy()
    out = model.eval_batch([cur])  # compile
    float(np.asarray(out).ravel()[0])
    reps = max(2, n_steps // 8)
    full = []
    for _w in range(windows):
        t0 = time.perf_counter()
        for _i in range(reps):
            out = model.eval_batch([cur])
        float(np.asarray(out).ravel()[0])
        full.append(reps * batch / (time.perf_counter() - t0))
    full_mid, full_min, full_max = median_spread(full)

    return {
        "config": f"{'GPT2-small' if on_tpu else 'tiny'} b={batch} s={seq} "
                  f"prefix={prompt_len}",
        "cached_tok_per_s": round(cached_mid, 2),
        "cached_tok_per_s_min": round(cached_min, 2),
        "cached_tok_per_s_max": round(cached_max, 2),
        "full_prefix_tok_per_s": round(full_mid, 2),
        "full_prefix_tok_per_s_min": round(full_min, 2),
        "full_prefix_tok_per_s_max": round(full_max, 2),
        "timing_windows": windows,
        "speedup": round(cached_mid / full_mid, 2) if full_mid else None,
    }


def _serve_continuous_ab(on_tpu: bool) -> dict:
    """Continuous batching + paged KV cache vs the sequential
    per-session demo loop (ISSUE 6 acceptance, docs/SERVING.md): the
    SAME compiled model serves a seeded mixed-length workload

      (a) through the ServeEngine — slot recycling, paged cache, one
          host sync per flush window;
      (b) one request at a time through ``gpt_generate_cached`` (the
          pre-serving story: a session decodes its batch in lockstep,
          so a lone request occupies every lane until it finishes).

    Reports aggregate tokens/s for both arms, the speedup, the serve
    p50/p99 latencies, and ``outputs_match`` — every request's token
    stream must be bit-identical to its solo decode (arm b IS the solo
    reference)."""
    import time as _time

    import numpy as np

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.gpt_decode import GPTDecodeSession, gpt_generate_cached
    from flexflow_tpu.models.transformer import gpt_decoder
    from flexflow_tpu.serve import ServeEngine, TrafficSpec, synthetic_requests

    slots = 8 if on_tpu else 4
    seq = 256 if on_tpu else 64
    shape = (
        dict(hidden=512, heads=8, ff_dim=2048, num_layers=6)
        if on_tpu
        else dict(hidden=64, heads=4, ff_dim=128, num_layers=2)
    )
    vocab = 32000 if on_tpu else 256
    cfg = FFConfig(
        batch_size=slots, compute_dtype="bfloat16" if on_tpu else "float32",
    )
    model = FFModel(cfg)
    gpt_decoder(model, slots, seq, vocab=vocab, **shape)
    model.compile(seed=0)

    spec = TrafficSpec(
        n_requests=24 if on_tpu else 12,
        seed=0,
        rate_rps=0.0,  # saturation shape: all requests queued at t=0
        prompt_len=(8, 32) if on_tpu else (3, 8),
        max_new=(8, 96) if on_tpu else (3, 24),
        vocab=vocab,
    )
    reqs = synthetic_requests(spec)

    # arm (a): continuous batching (compiles its own paged programs).
    # A default-policy SLO engine rides along (ISSUE 17): it evaluates
    # the window records the engine already builds — zero extra syncs —
    # and the record carries availability + alerts fired as comparable
    # metadata (an alert on a smoke box is load, not a regression)
    from flexflow_tpu.obs.slo import SLOEngine, SLOPolicy

    slo = SLOEngine(SLOPolicy())
    engine = ServeEngine(
        model, slots=slots, block_size=16 if on_tpu else 8, sync_every=4,
        slo=slo,
    )
    t0 = _time.perf_counter()
    rep = engine.run(reqs)
    cont_wall = _time.perf_counter() - t0
    cont_tok_s = rep.new_tokens / cont_wall if cont_wall > 0 else 0.0

    # arm (b): sequential per-session — ALSO the solo-decode reference
    # for the bit-identity check (one request at a time, lanes
    # replicated; warmup call first so compile stays out of the window)
    sess = GPTDecodeSession(model)
    solo = {}
    _ = gpt_generate_cached(
        model, np.tile(reqs[0].prompt[None], (slots, 1)),
        reqs[0].max_new_tokens, session=sess,
    )
    t0 = _time.perf_counter()
    seq_tokens = 0
    for r in reqs:
        out, _ = gpt_generate_cached(
            model, np.tile(r.prompt[None], (slots, 1)),
            r.max_new_tokens, session=sess,
        )
        solo[r.id] = out[0, r.prompt_len:]
        seq_tokens += r.max_new_tokens
    seq_wall = _time.perf_counter() - t0
    seq_tok_s = seq_tokens / seq_wall if seq_wall > 0 else 0.0

    by_id = {r.id: r for r in engine.sched.finished}
    outputs_match = len(by_id) == len(reqs) and all(
        np.array_equal(
            np.asarray(by_id[r.id].tokens, np.int32), solo[r.id]
        )
        for r in reqs
    )
    return {
        "config": (
            f"{'mid' if on_tpu else 'tiny'} gpt slots={slots} s={seq} "
            f"{spec.n_requests} reqs"
        ),
        "serve_traffic": spec.identity,
        "serve_tok_s": round(cont_tok_s, 2),
        "sequential_tok_s": round(seq_tok_s, 2),
        "speedup": round(cont_tok_s / seq_tok_s, 2) if seq_tok_s else None,
        "outputs_match": bool(outputs_match),
        "serve_p99_ms": (
            round(rep.tpot_p99_ms, 3) if rep.tpot_p99_ms is not None else None
        ),
        "tpot_p50_ms": (
            round(rep.tpot_p50_ms, 3) if rep.tpot_p50_ms is not None else None
        ),
        "ttft_p50_ms": (
            round(rep.ttft_p50_ms, 3) if rep.ttft_p50_ms is not None else None
        ),
        "ttft_p99_ms": (
            round(rep.ttft_p99_ms, 3) if rep.ttft_p99_ms is not None else None
        ),
        "occupancy_mean": round(rep.occupancy_mean, 4),
        "windows": rep.windows,
        "host_syncs": rep.host_syncs,
        "new_tokens": rep.new_tokens,
        "serve_slo_availability": round(slo.availability, 6),
        "serve_alerts_fired": slo.alerts_fired,
    }


def _serve_prefix_ab(on_tpu: bool) -> dict:
    """Prefix-sharing A/B (ISSUE 11 acceptance, docs/SERVING.md): the
    SAME compiled model serves the SAME shared-system-prompt workload
    through two engines — prefix sharing on vs off — on a KV pool sized
    so the shared blocks are the difference between queueing and
    serving.  Requests arrive staggered (first one prefills and
    registers its prompt blocks before the rest are admitted), so the
    second wave re-attaches the registered blocks instead of charging
    private copies.

    Gated facts: ``peak_active`` with sharing must be >= 2x without
    (the pool admits at least twice the concurrency), every request's
    token stream must be bit-identical across arms, and
    ``prefix_hit_rate`` is recorded for the higher-is-better gate."""
    import numpy as np

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.transformer import gpt_decoder
    from flexflow_tpu.serve import Request, ServeEngine

    slots = 8 if on_tpu else 6
    seq = 128 if on_tpu else 64
    shape = (
        dict(hidden=512, heads=8, ff_dim=2048, num_layers=6)
        if on_tpu
        else dict(hidden=64, heads=4, ff_dim=128, num_layers=2)
    )
    vocab = 32000 if on_tpu else 256
    block_size = 8
    shared_len, n_requests, max_new = 16, 5, 7
    # pool sized so an unshared request needs 3 blocks (17 prompt + 7
    # new = 24 positions) but only 7 blocks exist: without sharing 2
    # requests serve concurrently; with sharing the 2 system-prompt
    # blocks are charged once and 4+ requests fit
    num_blocks = 8
    cfg = FFConfig(
        batch_size=slots, compute_dtype="bfloat16" if on_tpu else "float32",
    )
    model = FFModel(cfg)
    gpt_decoder(model, slots, seq, vocab=vocab, use_flash=False, **shape)
    model.compile(seed=0)

    rng = np.random.default_rng(0)
    sys_prompt = rng.integers(0, vocab, size=(shared_len,)).astype(np.int32)

    def workload():
        # fresh Request objects per arm (the engine mutates them);
        # request 0 arrives alone so its prefill registers the shared
        # blocks before the wave at t=0.3 looks them up
        reqs = []
        for i in range(n_requests):
            prompt = np.concatenate(
                [sys_prompt, np.asarray([int(i) + 1], np.int32)]
            )
            reqs.append(Request(
                prompt=prompt, max_new_tokens=max_new, id=i,
                arrival_s=0.0 if i == 0 else 0.3, tenant="tenant0",
            ))
        return reqs

    results = {}
    for label, sharing in (("shared", True), ("private", False)):
        engine = ServeEngine(
            model, slots=slots, block_size=block_size,
            num_blocks=num_blocks, sync_every=4, prefix_sharing=sharing,
        )
        rep = engine.run(workload())
        streams = {
            r.id: np.asarray(r.tokens, np.int32)
            for r in engine.sched.finished
        }
        results[label] = (rep, streams)

    rep_on, out_on = results["shared"]
    rep_off, out_off = results["private"]
    outputs_match = (
        set(out_on) == set(out_off) == set(range(n_requests))
        and all(np.array_equal(out_on[i], out_off[i]) for i in out_on)
    )
    return {
        "config": (
            f"{'mid' if on_tpu else 'tiny'} gpt pool={num_blocks - 1}blk "
            f"bs={block_size} shared={shared_len}tok {n_requests} reqs"
        ),
        "serve_prefix_hit_rate": (
            round(rep_on.prefix_hit_rate, 4)
            if rep_on.prefix_hit_rate is not None else None
        ),
        "peak_active_shared": rep_on.peak_active,
        "peak_active_private": rep_off.peak_active,
        "concurrency_ratio": (
            round(rep_on.peak_active / rep_off.peak_active, 2)
            if rep_off.peak_active else None
        ),
        "outputs_match": bool(outputs_match),
        "preemptions": rep_on.preemptions,
        "serve_tok_s_shared": round(
            rep_on.new_tokens / rep_on.wall_s, 2
        ) if rep_on.wall_s else None,
        "serve_tok_s_private": round(
            rep_off.new_tokens / rep_off.wall_s, 2
        ) if rep_off.wall_s else None,
        "host_syncs": rep_on.host_syncs,
        "windows": rep_on.windows,
    }


def _serve_spec_ab(on_tpu: bool) -> dict:
    """Speculative-decoding A/B (ISSUE 11 acceptance, docs/SERVING.md):
    the SAME model serves the SAME workload plain vs speculative
    (depth-k draft from the shallow parameter slice, one batched verify
    per window).  To pin the high-accept-rate regime deterministically,
    the model's TAIL layers are zeroed into identities (pre-LN residual
    blocks: zeroing the attention output projection and the second FF
    kernel+bias makes ``x + 0 + 0 = x``), so the draft slice computes
    exactly the full model and every draft token is accepted.

    Gated facts: token streams bit-identical across arms, and
    speculative decode tokens/s >= 1.3x plain at accept rate ~1.0.
    The end-to-end engine runs carry the bit-identity + accept-rate
    facts; the gated throughput comes from chained steady-state timing
    of the compiled programs themselves (the
    ``_attention_core_compare`` methodology: back-to-back calls with
    one sync, median of windows), because a CPU-smoke serve run is
    short enough that scheduler/flush wall noise swamps a 1.5x decode
    delta."""
    import time as _time

    import numpy as np

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.transformer import gpt_decoder
    from flexflow_tpu.serve import ServeEngine, TrafficSpec, synthetic_requests

    slots = 8 if on_tpu else 4
    seq = 128 if on_tpu else 48
    # where speculation wins depends on what decode is bound by.  On
    # accelerators decode streams the full weights per token, so k
    # shallow drafts (1/L of the weights) + ONE full verify pass for
    # k+1 positions is the classic bandwidth win — modest k suffices.
    # XLA:CPU matmuls at smoke sizes are compute-bound instead, so the
    # CPU shape leans on the OTHER term speculation amortizes: deep
    # narrow layers make per-call fixed work (KV gathers, dispatch)
    # dominate, and k=7 drafts at 1/10 depth replace 7 full-depth calls
    num_layers, draft_layers, spec_k = (
        (6, 1, 3) if on_tpu else (16, 1, 7)
    )
    shape = (
        dict(hidden=512, heads=8, ff_dim=2048)
        if on_tpu
        else dict(hidden=128, heads=4, ff_dim=256)
    )
    vocab = 32000 if on_tpu else 256
    # stack_blocks off: the serving programs address per-layer params
    # (dec{i}_*), and 4 identical blocks would auto-stack
    cfg = FFConfig(
        batch_size=slots, compute_dtype="bfloat16" if on_tpu else "float32",
        stack_blocks="off",
    )
    model = FFModel(cfg)
    gpt_decoder(
        model, slots, seq, vocab=vocab, num_layers=num_layers,
        use_flash=False, **shape,
    )
    model.compile(seed=0)

    # zero layers draft_layers..num_layers-1 into identities so the
    # draft slice IS the full model (accept rate 1.0, deterministic)
    import jax.numpy as jnp

    params = model.executor.params
    for i in range(draft_layers, num_layers):
        at = params[f"dec{i}_attn"]
        at["wo"] = jnp.zeros_like(at["wo"])
        if "bo" in at:
            at["bo"] = jnp.zeros_like(at["bo"])
        p1 = params[f"dec{i}_ff1"]
        p1["kernel"] = jnp.zeros_like(p1["kernel"])
        p1["bias"] = jnp.zeros_like(p1["bias"])

    spec = TrafficSpec(
        n_requests=16 if on_tpu else 8,
        seed=0, rate_rps=0.0,
        prompt_len=(4, 10) if on_tpu else (4, 8),
        max_new=(48, 96) if on_tpu else (16, 32),
        vocab=vocab,
    )

    results = {}
    for label, k in (("plain", 0), ("spec", spec_k)):
        engine = ServeEngine(
            model, slots=slots, block_size=16 if on_tpu else 8,
            sync_every=8, spec_k=k, spec_draft_layers=draft_layers,
        )
        reqs = synthetic_requests(spec)
        t0 = _time.perf_counter()
        rep = engine.run(reqs)
        wall = _time.perf_counter() - t0
        streams = {
            r.id: np.asarray(r.tokens, np.int32)
            for r in engine.sched.finished
        }
        results[label] = (
            rep, streams, rep.new_tokens / wall if wall else 0, engine,
        )

    rep_p, out_p, tok_s_p, eng_p = results["plain"]
    rep_s, out_s, tok_s_s, eng_s = results["spec"]
    outputs_match = set(out_p) == set(out_s) and all(
        np.array_equal(out_p[i], out_s[i]) for i in out_p
    )

    # steady-state decode throughput: chain the compiled programs
    # back-to-back into the trash block (tables all-zero — the warmup
    # discipline) and take the median window.  W plain decode calls
    # yield W tokens/slot; one spec macro (k drafts + 1 verify) yields
    # the same W at accept rate 1.
    import jax

    ex = eng_s.model.executor
    B, MB = slots, eng_s.kv.max_blocks_per_seq
    z = jnp.zeros((B,), jnp.int32)
    bt = jnp.zeros((B, MB), jnp.int32)
    W = spec_k + 1
    toksW = jnp.zeros((B, W), jnp.int32)

    def _median_chain(macro_fn, macros=8, windows=3):
        # macro_fn dispatches one macro's programs and returns the
        # chained (ck, cv); the sync sits once at window end
        walls = []
        for _ in range(windows):
            t0 = _time.perf_counter()
            for _ in range(macros):
                out0 = macro_fn()
            jax.block_until_ready(out0)
            walls.append(_time.perf_counter() - t0)
        return sorted(walls)[len(walls) // 2] / macros

    def plain_macro():
        out = None
        for _ in range(W):
            out = eng_p._decode(
                ex.params, eng_p.kv.cache_k, eng_p.kv.cache_v, z, z, bt,
            )
            eng_p.kv.cache_k, eng_p.kv.cache_v = out[-2], out[-1]
        return out[0]

    def spec_macro():
        for _ in range(spec_k):
            out = eng_s._draft(
                ex.params, eng_s.kv.cache_k, eng_s.kv.cache_v, z, z, bt,
            )
            eng_s.kv.cache_k, eng_s.kv.cache_v = out[-2], out[-1]
        out = eng_s._verify(
            ex.params, eng_s.kv.cache_k, eng_s.kv.cache_v, toksW, z, bt,
        )
        eng_s.kv.cache_k, eng_s.kv.cache_v = out[-2], out[-1]
        return out[0]

    plain_macro()  # warm
    spec_macro()
    plain_s = _median_chain(plain_macro)
    spec_s = _median_chain(spec_macro)
    steady_plain = B * W / plain_s if plain_s else 0.0
    steady_spec = B * W / spec_s if spec_s else 0.0

    return {
        "config": (
            f"{'mid' if on_tpu else 'tiny'} gpt L{num_layers} "
            f"(draft {draft_layers}, tail zeroed) k={spec_k} "
            f"{spec.n_requests} reqs"
        ),
        "serve_traffic": spec.identity,
        "serve_spec_k": spec_k,
        "spec_draft_layers": draft_layers,
        "spec_accept_rate": (
            round(rep_s.spec_accept_rate, 4)
            if rep_s.spec_accept_rate is not None else None
        ),
        # gated pair: steady-state decode throughput (chained programs)
        "spec_tok_s": round(steady_spec, 2),
        "plain_tok_s": round(steady_plain, 2),
        "speedup": (
            round(steady_spec / steady_plain, 2) if steady_plain else None
        ),
        # end-to-end serve runs (bit-identity source; wall includes
        # prefill + scheduler + flush, so the ratio is diluted)
        "e2e_spec_tok_s": round(tok_s_s, 2),
        "e2e_plain_tok_s": round(tok_s_p, 2),
        "e2e_speedup": round(tok_s_s / tok_s_p, 2) if tok_s_p else None,
        "outputs_match": bool(outputs_match),
        "spec_host_syncs": rep_s.host_syncs,
        "spec_windows": rep_s.windows,
    }


def _serve_disagg_ab(on_tpu: bool) -> dict:
    """Disaggregated prefill/decode A/B (ISSUE 13 acceptance,
    docs/SERVING.md "Disaggregated prefill/decode"): the SAME compiled
    model serves the SAME bursty workload colocated (one engine, so
    prefill chunks and decode steps share every flush window) vs split
    into a prefill pool + a decode pool joined by the priced ffkv/1
    handoff.

    A decode token is observable at its window's flush, so its latency
    is its window's wall — and under bursty arrivals the colocated
    windows carry prefill chunks for the whole incoming wave while the
    decode pool's windows never do.  The gated fact is the
    per-decode-token window latency (``step_wall_s / decode_steps``
    over decode-bearing windows, read off the ffmetrics streams both
    arms write): ``serve_disagg_p99_tpot_ms`` is the disagg decode
    pool's p99 (LOWER-is-better gate), ``interference_ratio`` =
    colocated p99 / disagg p99 pins the >= 1.3x improvement, and every
    request's token stream must stay bit-identical across arms (greedy
    argmax, same weights — batching composition must not change the
    math)."""
    import tempfile

    import numpy as np

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.transformer import gpt_decoder
    from flexflow_tpu.obs.metrics import read_metrics
    from flexflow_tpu.parallel.network import load_machine_model
    from flexflow_tpu.serve import (
        DisaggregatedCluster,
        ServeEngine,
        TrafficSpec,
        synthetic_requests,
    )

    slots = 8 if on_tpu else 4
    seq = 512 if on_tpu else 160
    shape = (
        dict(hidden=512, heads=8, ff_dim=2048, num_layers=6)
        if on_tpu
        else dict(hidden=128, heads=4, ff_dim=256, num_layers=2)
    )
    vocab = 32000 if on_tpu else 256
    cfg = FFConfig(
        batch_size=slots, compute_dtype="bfloat16" if on_tpu else "float32",
    )
    model = FFModel(cfg)
    gpt_decoder(model, slots, seq, vocab=vocab, **shape)
    model.compile(seed=0)

    # bursty contended shape: prompts long enough that a prefill chunk
    # clearly dominates a mixed window, bursts (burst_factor=4) so new
    # waves land while earlier requests are mid-decode
    spec = TrafficSpec(
        n_requests=32 if on_tpu else 16,
        seed=0,
        rate_rps=25.0,
        burst_factor=4.0,
        prompt_len=(128, 256) if on_tpu else (48, 96),
        max_new=(48, 96) if on_tpu else (24, 48),
        vocab=vocab,
    )
    machine = load_machine_model(os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "examples", "machine_configs", "v5p_2slice.json",
    ))

    def _pctl(vals, q):
        vals = sorted(vals)
        idx = (len(vals) - 1) * q / 100.0
        lo = int(idx)
        hi = min(lo + 1, len(vals) - 1)
        return vals[lo] * (1 - (idx - lo)) + vals[hi] * (idx - lo)

    def _decode_window_tpot_ms(path):
        # per-decode-token observable latency of each decode-bearing
        # window; the disagg stream's prefill-pool windows (phase ==
        # "prefill") never decode, but skip them explicitly anyway
        vals = []
        for r in read_metrics(path):
            s = (r.get("metrics") or {}).get("serve")
            if not s or not s.get("decode_steps"):
                continue
            if s.get("phase") == "prefill":
                continue
            vals.append(
                (r.get("step_wall_s") or 0.0) / s["decode_steps"] * 1e3
            )
        return vals

    with tempfile.TemporaryDirectory() as td:
        col_path = os.path.join(td, "colocated.jsonl")
        dis_path = os.path.join(td, "disagg.jsonl")
        spans_path = os.path.join(td, "disagg_spans.jsonl")

        engine = ServeEngine(
            model, slots=slots, block_size=16 if on_tpu else 8,
            sync_every=4, metrics_out=col_path,
        )
        rep_c = engine.run(synthetic_requests(spec))
        col = {
            r.id: np.asarray(r.tokens, np.int32)
            for r in engine.sched.finished
        }

        # the disagg arm runs TRACED (--serve-spans-out equivalent):
        # tracing is pinned zero-added-sync and bit-identical, and the
        # span stream yields the queue-wait + measured-transit facts
        # the record surfaces (ffspan/1, docs/OBSERVABILITY.md)
        cluster = DisaggregatedCluster(
            model, prefill_slots=slots, decode_slots=slots,
            prefill_block_size=16 if on_tpu else 8,
            decode_block_size=32 if on_tpu else 16,
            sync_every=4, machine=machine, metrics_out=dis_path,
            spans_out=spans_path,
        )
        rep_d = cluster.run(synthetic_requests(spec))
        dis = {}
        for eng in (cluster.prefill, cluster.decode):
            for r in eng.sched.finished:
                dis[r.id] = np.asarray(r.tokens, np.int32)

        tpot_c = _decode_window_tpot_ms(col_path)
        tpot_d = _decode_window_tpot_ms(dis_path)

        from flexflow_tpu.obs.spans import read_spans

        span_recs = read_spans(spans_path)
        # prefill-pool admission waits (the TTFT queue leg) + measured
        # send->deliver transit beside the priced estimate
        queue_ms = [
            (s["t1"] - s["t0"]) * 1e3 for s in span_recs
            if s["name"] == "queue" and s.get("pool") == "prefill"
        ]
        observed_ms = [
            s["attrs"]["observed_ms"] for s in span_recs
            if s["name"] == "handoff_transit"
            and s["attrs"].get("observed_ms") is not None
        ]

    outputs_match = set(col) == set(dis) and all(
        np.array_equal(col[i], dis[i]) for i in col
    )
    p99_c = _pctl(tpot_c, 99) if tpot_c else None
    p99_d = _pctl(tpot_d, 99) if tpot_d else None
    return {
        "config": (
            f"{'mid' if on_tpu else 'tiny'} gpt pools {rep_d.split} "
            f"{spec.n_requests} reqs bursty"
        ),
        "serve_traffic": spec.identity,
        "serve_disagg_split": rep_d.split,
        "serve_disagg_p99_tpot_ms": (
            round(p99_d, 4) if p99_d is not None else None
        ),
        "colocated_p99_tpot_ms": (
            round(p99_c, 4) if p99_c is not None else None
        ),
        "interference_ratio": (
            round(p99_c / p99_d, 3) if p99_c and p99_d else None
        ),
        "outputs_match": bool(outputs_match),
        "serve_handoff_ms": (
            round(rep_d.handoff_p99_ms, 4)
            if rep_d.handoff_p99_ms is not None else None
        ),
        "serve_ttft_queue_ms_p99": (
            round(_pctl(queue_ms, 99), 4) if queue_ms else None
        ),
        "serve_handoff_observed_ms": (
            round(_pctl(observed_ms, 99), 4) if observed_ms else None
        ),
        "handoff_p50_ms": (
            round(rep_d.handoff_p50_ms, 4)
            if rep_d.handoff_p50_ms is not None else None
        ),
        "migrated": rep_d.migrated,
        "migrated_kv_bytes": rep_d.migrated_kv_bytes,
        "transport_backpressure": rep_d.transport_backpressure,
        "prefill_windows": rep_d.prefill_windows,
        "decode_windows": rep_d.decode_windows,
        "colocated_windows": rep_c.windows,
        "ttft_p99_colocated_ms": (
            round(rep_c.ttft_p99_ms, 3)
            if rep_c.ttft_p99_ms is not None else None
        ),
        "ttft_p99_disagg_ms": (
            round(rep_d.ttft_p99_ms, 3)
            if rep_d.ttft_p99_ms is not None else None
        ),
    }


def _serve_fleet_ab(on_tpu: bool) -> dict:
    """Fleet routing A/B (ISSUE 18 acceptance, docs/SERVING.md "Fleet
    tier"): the SAME compiled model serves the SAME bursty multi-tenant
    multi-turn workload behind a 3-replica FleetRouter twice — once
    with prefix-cache-aware routing, once round-robin.

    Round-robin scatters a tenant's shared-prefix repeats across
    replicas, so each replica pays the full prefill for blocks another
    replica already holds; prefix routing reads the replicas'
    window-boundary residency digests and lands repeats where their
    blocks live.  The gated pair: ``serve_fleet_prefix_hit_rate`` (the
    POOLED sum-hits/sum-lookups across replicas, higher-is-better) and
    ``serve_fleet_p99_tpot_ms`` (the prefix arm's p99 per-decode-token
    window latency across every replica's ffmetrics stream — the r13
    disagg convention — LOWER-is-better), and prefix must beat
    round-robin on BOTH: skipped shared prefill removes the chunks
    that inflate mixed windows, and landing repeats together fills
    batched decode steps that round-robin leaves fragmented.  Token
    streams stay bit-identical across arms per request id (greedy
    argmax, same weights — placement must not change the math)."""
    import tempfile

    import numpy as np

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.transformer import gpt_decoder
    from flexflow_tpu.serve import TrafficSpec, synthetic_requests
    from flexflow_tpu.serve.fleet import FleetRouter

    slots = 8 if on_tpu else 4
    seq = 512 if on_tpu else 160
    shape = (
        dict(hidden=512, heads=8, ff_dim=2048, num_layers=6)
        if on_tpu
        else dict(hidden=128, heads=4, ff_dim=256, num_layers=2)
    )
    vocab = 32000 if on_tpu else 256
    cfg = FFConfig(
        batch_size=slots, compute_dtype="bfloat16" if on_tpu else "float32",
    )
    model = FFModel(cfg)
    gpt_decoder(model, slots, seq, vocab=vocab, **shape)
    model.compile(seed=0)

    # bursty shared-prefix multi-tenant shape: 4 tenants whose system
    # prompts span many full KV blocks (the routable residency), short
    # fresh tails, 2-turn sessions (affinity + turn-2 prompt extension),
    # bursts so waves of same-tenant arrivals land together
    spec = TrafficSpec(
        n_requests=32 if on_tpu else 16,
        seed=0,
        rate_rps=25.0,
        burst_factor=4.0,
        prompt_len=(8, 16) if not on_tpu else (32, 64),
        max_new=(16, 32) if not on_tpu else (48, 96),
        vocab=vocab,
        tenants=4,
        shared_prefix=128 if on_tpu else 48,
        interactive_frac=0.5,
        session_turns=2,
    )

    from flexflow_tpu.obs.metrics import read_metrics

    def _pctl(vals, q):
        vals = sorted(vals)
        idx = (len(vals) - 1) * q / 100.0
        lo = int(idx)
        hi = min(lo + 1, len(vals) - 1)
        return vals[lo] * (1 - (idx - lo)) + vals[hi] * (idx - lo)

    def _arm(td, routing):
        base = os.path.join(td, f"m_{routing}.jsonl")
        fr = FleetRouter(
            model, replicas=3, routing=routing, slots=slots,
            block_size=16 if on_tpu else 8, sync_every=4,
            metrics_out=base,
            fleet_out=os.path.join(td, f"fleet_{routing}.jsonl"),
        )
        rep = fr.run(synthetic_requests(spec))
        toks = {
            r.id: np.asarray(r.tokens, np.int32)
            for rp in fr.replicas.values()
            for r in rp.engine.sched.finished
        }
        # per-decode-token observable latency of every decode-bearing
        # window, pooled across the replicas' streams (r13 convention)
        tpot = []
        for name in fr.replicas:
            for r in read_metrics(f"{base}.{name}"):
                s = (r.get("metrics") or {}).get("serve")
                if not s or not s.get("decode_steps"):
                    continue
                tpot.append(
                    (r.get("step_wall_s") or 0.0)
                    / s["decode_steps"] * 1e3
                )
        return fr, rep, toks, tpot

    with tempfile.TemporaryDirectory() as td:
        fr_p, rep_p, toks_p, tpot_p = _arm(td, "prefix")
        fr_r, rep_r, toks_r, tpot_r = _arm(td, "round_robin")

    outputs_match = set(toks_p) == set(toks_r) and all(
        np.array_equal(toks_p[i], toks_r[i]) for i in toks_p
    )
    hit_p = rep_p.fleet_prefix_hit_rate
    hit_r = rep_r.fleet_prefix_hit_rate
    p99_p = _pctl(tpot_p, 99) if tpot_p else None
    p99_r = _pctl(tpot_r, 99) if tpot_r else None
    return {
        "config": (
            f"{'mid' if on_tpu else 'tiny'} gpt x3 replicas "
            f"{spec.n_requests} reqs bursty 4-tenant 2-turn"
        ),
        "serve_traffic": spec.identity,
        "fleet_replicas": 3,
        "fleet_routing": "prefix",
        "serve_fleet_prefix_hit_rate": (
            round(hit_p, 4) if hit_p is not None else None
        ),
        "serve_fleet_p99_tpot_ms": (
            round(p99_p, 4) if p99_p is not None else None
        ),
        "rr_prefix_hit_rate": (
            round(hit_r, 4) if hit_r is not None else None
        ),
        "rr_p99_tpot_ms": (
            round(p99_r, 4) if p99_r is not None else None
        ),
        "prefix_wins_hit_rate": (
            (hit_p or 0.0) > (hit_r or 0.0)
        ),
        "prefix_wins_p99_tpot": (
            p99_p is not None and p99_r is not None and p99_p < p99_r
        ),
        "outputs_match": bool(outputs_match),
        "prefix_routed": rep_p.prefix_routed,
        "sessions": rep_p.sessions,
        "spillovers": rep_p.spillovers,
        "migrations": rep_p.migrations,
        "routed_prefix_arm": rep_p.routed,
        "routed_rr_arm": rep_r.routed,
        "host_syncs_prefix_arm": rep_p.host_syncs,
        "fleet_windows_prefix_arm": rep_p.windows,
    }


def _serve_paged_attn_ab(on_tpu: bool) -> dict:
    """Paged-attention A/B (ISSUE 14 acceptance, docs/PERF.md "Paged
    decode attention"): the SAME model serves the SAME workload through
    two engines — the dense-gather decode path vs the fused Pallas
    paged-attention kernel — and the facts gated are (1) every
    request's token stream is bit-identical across arms and (2) the
    decode program's peak live temp bytes (XLA's
    ``memory_analysis()``, the same source the measured-memory search
    tier reads) are strictly LOWER with the kernel
    (``serve_paged_attn_peak_mb``, lower-is-better).

    The pool is deliberately undersized relative to the compiled
    position range (few live blocks, long virtual length): the dense
    path materializes its per-layer gather at the FULL virtual length
    ``SV = MB * BS`` regardless of how many blocks are live — exactly
    the waste the block-table-native kernel removes.  Off-TPU the
    kernel runs in interpreter mode (tok/s is reported but ungated —
    interpret emulation speed is not kernel speed; the real-chip
    numbers: not measured)."""
    import time as _time

    import numpy as np

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.transformer import gpt_decoder
    from flexflow_tpu.ops.pallas import paged_attention as pa
    from flexflow_tpu.serve import Request, ServeEngine

    slots = 6
    seq = 1024 if on_tpu else 512
    shape = (
        dict(hidden=512, heads=8, ff_dim=2048, num_layers=6)
        if on_tpu
        else dict(hidden=64, heads=4, ff_dim=128, num_layers=2)
    )
    vocab = 32000 if on_tpu else 256
    block_size = 16 if on_tpu else 8
    # live blocks ~ the workload's working set; virtual length = seq
    num_blocks = 48 + 1
    n_requests, max_new = 6, 8

    def build():
        cfg = FFConfig(
            batch_size=slots,
            compute_dtype="bfloat16" if on_tpu else "float32",
        )
        model = FFModel(cfg)
        gpt_decoder(
            model, slots, seq, vocab=vocab, use_flash=False, **shape
        )
        model.compile(seed=0)
        return model

    def workload():
        rng = np.random.default_rng(0)
        reqs = []
        for i in range(n_requests):
            plen = int(rng.integers(4, 14))
            reqs.append(Request(
                prompt=rng.integers(0, vocab, size=(plen,)).astype(
                    np.int32
                ),
                max_new_tokens=max_new, id=i,
            ))
        return reqs

    def decode_peak_bytes(engine) -> int:
        import jax.numpy as jnp

        B, MB = engine.slots, engine.kv.max_blocks_per_seq
        z = jnp.zeros((B,), jnp.int32)
        bt0 = jnp.zeros((B, MB), jnp.int32)
        compiled = engine._decode.lower(
            engine.model.executor.params, engine.kv.cache_k,
            engine.kv.cache_v, z, z, bt0,
        ).compile()
        ma = compiled.memory_analysis()
        return int(ma.temp_size_in_bytes)

    old_interpret = pa.INTERPRET
    if not on_tpu:
        pa.INTERPRET = True  # the only way the kernel runs off-TPU
    try:
        results = {}
        for label in ("gather", "paged"):
            engine = ServeEngine(
                build(), slots=slots, block_size=block_size,
                num_blocks=num_blocks, sync_every=4, attn=label,
            )
            t0 = _time.perf_counter()
            rep = engine.run(workload())
            wall = _time.perf_counter() - t0
            streams = {
                r.id: np.asarray(r.tokens, np.int32)
                for r in engine.sched.finished
            }
            results[label] = (rep, streams, decode_peak_bytes(engine),
                              wall)
    finally:
        pa.INTERPRET = old_interpret

    rep_g, out_g, peak_g, wall_g = results["gather"]
    rep_p, out_p, peak_p, wall_p = results["paged"]
    outputs_match = (
        set(out_g) == set(out_p) == set(range(n_requests))
        and all(np.array_equal(out_g[i], out_p[i]) for i in out_g)
    )
    return {
        "config": (
            f"{'mid' if on_tpu else 'tiny'} gpt sv={seq} "
            f"pool={num_blocks - 1}blk bs={block_size} "
            f"{n_requests} reqs {'native' if on_tpu else 'interpret'}"
        ),
        "serve_attn": "paged",
        "serve_paged_attn_peak_mb": round(peak_p / 1e6, 4),
        "gather_peak_mb": round(peak_g / 1e6, 4),
        "peak_ratio": round(peak_p / peak_g, 4) if peak_g else None,
        "outputs_match": bool(outputs_match),
        "serve_tok_s_paged": (
            round(rep_p.new_tokens / wall_p, 2) if wall_p else None
        ),
        "serve_tok_s_gather": (
            round(rep_g.new_tokens / wall_g, 2) if wall_g else None
        ),
        "windows": rep_p.windows,
        "host_syncs": rep_p.host_syncs,
    }


def _serve_prefill_paged_ab(on_tpu: bool) -> dict:
    """Chunked-prefill A/B (ISSUE 20 acceptance, docs/SERVING.md
    "Chunked prefill on the paged pool"): the SAME model serves the
    SAME long-prompt workload (>= 2k prompt tokens per request, smoke
    scale) through the dense-gather prefill path vs the paged prefill
    kernel, per KV pool dtype (fp32 / int8 / fp8).  Facts gated: (1)
    every request's token stream is bit-identical across arms within
    each kv_dtype, and (2) the PREFILL program's peak live temp bytes
    (XLA ``memory_analysis()``) are <= 0.6x the gather arm's
    (``serve_prefill_peak_mb``, the fp32 paged peak, lower-is-better).

    The pool is undersized relative to the compiled position range:
    the gather path materializes its per-layer K/V gather at the FULL
    virtual length ``SV = MB * BS`` on EVERY chunk — the O(S^2)
    long-context tax — while the paged kernel DMAs only the visible
    pages behind each row group.  TTFT p99 is reported per arm but
    ungated off-TPU (interpret emulation speed is not kernel speed;
    chip numbers: not measured)."""
    import time as _time

    import numpy as np

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.transformer import gpt_decoder
    from flexflow_tpu.ops.pallas import paged_attention as pa
    from flexflow_tpu.serve import Request, ServeEngine

    slots = 4
    # virtual range deliberately > working set (undersized-pool story)
    seq = 4096 if on_tpu else 3072
    shape = (
        dict(hidden=512, heads=8, ff_dim=2048, num_layers=6)
        if on_tpu
        else dict(hidden=32, heads=4, ff_dim=64, num_layers=2)
    )
    vocab = 32000 if on_tpu else 256
    block_size = 64  # big pages keep the interpret-mode grid small
    prefill_chunk = 512 if on_tpu else 256
    n_requests, max_new = 4, 4
    prompt_lo, prompt_hi = 2048, 2113  # >= 2k tokens, always
    blocks_per_req = -(-(prompt_hi - 1 + max_new) // block_size)
    num_blocks = slots * blocks_per_req + 3  # << slots * MB

    def build():
        cfg = FFConfig(
            batch_size=slots,
            compute_dtype="bfloat16" if on_tpu else "float32",
        )
        model = FFModel(cfg)
        gpt_decoder(
            model, slots, seq, vocab=vocab, use_flash=False, **shape
        )
        model.compile(seed=0)
        return model

    def workload():
        rng = np.random.default_rng(0)
        reqs = []
        for i in range(n_requests):
            plen = int(rng.integers(prompt_lo, prompt_hi))
            reqs.append(Request(
                prompt=rng.integers(0, vocab, size=(plen,)).astype(
                    np.int32
                ),
                max_new_tokens=max_new, id=i,
            ))
        return reqs

    def prefill_peak_bytes(engine) -> int:
        import jax.numpy as jnp

        kv = engine.kv
        B, P, MB = engine.slots, engine.prefill_chunk, (
            kv.max_blocks_per_seq
        )
        z = jnp.zeros((B,), jnp.int32)
        bt0 = jnp.zeros((B, MB), jnp.int32)
        pool_args = (kv.cache_k, kv.cache_v) + (
            (kv.scale_k, kv.scale_v) if kv.quantized else ()
        )
        params_arg = getattr(
            engine, "_params_arg", engine.model.executor.params
        )
        compiled = engine._prefill.lower(
            params_arg, *pool_args,
            jnp.zeros((B, P), jnp.int32), z,
            jnp.ones((B,), jnp.int32), bt0,
        ).compile()
        return int(compiled.memory_analysis().temp_size_in_bytes)

    old_interpret = pa.INTERPRET
    if not on_tpu:
        pa.INTERPRET = True  # the only way the kernel runs off-TPU
    try:
        results = {}
        for kv_dtype in ("fp32", "int8", "fp8"):
            for label in ("gather", "paged"):
                engine = ServeEngine(
                    build(), slots=slots, block_size=block_size,
                    num_blocks=num_blocks,
                    prefill_chunk=prefill_chunk, sync_every=4,
                    attn=label, kv_dtype=kv_dtype,
                )
                t0 = _time.perf_counter()
                rep = engine.run(workload())
                wall = _time.perf_counter() - t0
                streams = {
                    r.id: np.asarray(r.tokens, np.int32)
                    for r in engine.sched.finished
                }
                results[(kv_dtype, label)] = (
                    rep, streams, prefill_peak_bytes(engine), wall
                )
    finally:
        pa.INTERPRET = old_interpret

    def match(dt: str) -> bool:
        _, g, _, _ = results[(dt, "gather")]
        _, p, _, _ = results[(dt, "paged")]
        return (
            set(g) == set(p) == set(range(n_requests))
            and all(np.array_equal(g[i], p[i]) for i in g)
        )

    rep_g, _, peak_g, wall_g = results[("fp32", "gather")]
    rep_p, _, peak_p, wall_p = results[("fp32", "paged")]
    ratios = {
        dt: (
            round(
                results[(dt, "paged")][2] / results[(dt, "gather")][2],
                4,
            )
            if results[(dt, "gather")][2]
            else None
        )
        for dt in ("fp32", "int8", "fp8")
    }
    return {
        "config": (
            f"{'mid' if on_tpu else 'tiny'} gpt sv={seq} "
            f"prompts {prompt_lo}..{prompt_hi - 1} "
            f"chunk={prefill_chunk} pool={num_blocks - 1}blk "
            f"bs={block_size} {n_requests} reqs "
            f"{'native' if on_tpu else 'interpret'}"
        ),
        "serve_attn": "paged",
        "serve_prefill_peak_mb": round(peak_p / 1e6, 4),
        "gather_prefill_peak_mb": round(peak_g / 1e6, 4),
        "prefill_peak_ratio_fp32": ratios["fp32"],
        "prefill_peak_ratio_int8": ratios["int8"],
        "prefill_peak_ratio_fp8": ratios["fp8"],
        "outputs_match": bool(all(match(d) for d in
                                  ("fp32", "int8", "fp8"))),
        "outputs_match_fp32": bool(match("fp32")),
        "outputs_match_int8": bool(match("int8")),
        "outputs_match_fp8": bool(match("fp8")),
        "ttft_p99_ms_paged": rep_p.ttft_p99_ms,
        "ttft_p99_ms_gather": rep_g.ttft_p99_ms,
        "serve_tok_s_paged": (
            round(rep_p.new_tokens / wall_p, 2) if wall_p else None
        ),
        "serve_tok_s_gather": (
            round(rep_g.new_tokens / wall_g, 2) if wall_g else None
        ),
        "windows": rep_p.windows,
        "host_syncs": rep_p.host_syncs,
        "prefill_chunks": rep_p.prefill_chunks,
        "prefill_dispatches": rep_p.prefill_dispatches,
        "prefill_attn_kernel": rep_p.prefill_attn_kernel,
    }


def _serve_kv_quant_ab(on_tpu: bool) -> dict:
    """Quantized-KV serving A/B (ISSUE 19 acceptance, docs/SERVING.md
    "Quantized KV cache and weight-only decode"): the SAME model serves
    the SAME workload through a full-precision engine and an int8
    engine (int8 paged KV pool + int8 weight-only decode), and the
    facts recorded are (1) concurrent sessions per pool at the
    ADMISSION level — under the SAME HBM byte budget the int8 pool
    admits >= 1.9x the sessions (``kv_sessions_per_pool_ratio``, from
    the pools' own ``bytes_per_token``, scale stream included), (2)
    ffkv/1 handoff frames for the same session are >= 1.9x smaller
    (``kv_frame_bytes_ratio``, measured on real encode_handoff bytes of
    a spilled session long enough that npz framing overhead does not
    flatter the ratio), and (3) the TRUTHFUL greedy-stream divergence
    count between arms (``divergent_streams`` — quantization is lossy;
    the tiny smoke shape happens to diverge nowhere, but the number is
    measured, never asserted zero here).  ``serve_kv_bytes_per_tok``
    (the int8 arm's per-token pool bytes) is gated lower-is-better by
    tools/bench_compare.py; ``kv_dtype``/``weight_dtype`` ride as
    comparable metadata."""
    import time as _time

    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.transformer import gpt_decoder
    from flexflow_tpu.serve import Request, ServeEngine
    from flexflow_tpu.serve.kvcache import PagedKVCache, quantize_kv
    from flexflow_tpu.serve.wire import encode_handoff

    slots = 4
    seq = 512 if on_tpu else 128
    shape = (
        dict(hidden=512, heads=8, ff_dim=2048, num_layers=6)
        if on_tpu
        else dict(hidden=64, heads=4, ff_dim=128, num_layers=2)
    )
    vocab = 32000 if on_tpu else 256
    block_size = 8
    n_requests, max_new = 6, 8
    sess_len = 96  # admission/frame session depth (multiple of BS)

    def build():
        cfg = FFConfig(
            batch_size=slots,
            compute_dtype="bfloat16" if on_tpu else "float32",
        )
        model = FFModel(cfg)
        gpt_decoder(
            model, slots, seq, vocab=vocab, use_flash=False, **shape
        )
        model.compile(seed=0)
        return model

    def workload():
        rng = np.random.default_rng(0)
        return [
            Request(
                prompt=rng.integers(
                    0, vocab, size=(int(rng.integers(4, 14)),)
                ).astype(np.int32),
                max_new_tokens=max_new, id=i,
            )
            for i in range(n_requests)
        ]

    arms = {}
    for label, kvdt, wdt in (
        ("fp32", "fp32", "fp32"), ("int8", "int8", "int8"),
    ):
        engine = ServeEngine(
            build(), slots=slots, block_size=block_size, sync_every=4,
            kv_dtype=kvdt, weight_dtype=wdt,
        )
        t0 = _time.perf_counter()
        rep = engine.run(workload())
        wall = _time.perf_counter() - t0
        arms[label] = {
            "rep": rep, "wall": wall,
            "streams": {
                r.id: np.asarray(r.tokens, np.int32)
                for r in engine.sched.finished
            },
            "bpt": engine.kv.bytes_per_token,
        }
    s_f, s_q = arms["fp32"]["streams"], arms["int8"]["streams"]
    complete = set(s_f) == set(s_q) == set(range(n_requests))
    divergent = sum(
        1 for i in s_f if not np.array_equal(s_f[i], s_q.get(i))
    )

    # admission: size ONE budget — the fp32 pool provisioned for
    # ``slots`` sessions of sess_len — then count how many sessions
    # each arm's per-token bytes fit into it
    budget = slots * sess_len * arms["fp32"]["bpt"]
    sessions = {
        label: int(budget // (sess_len * arms[label]["bpt"]))
        for label in arms
    }

    # ffkv/1 frame bytes: restore a synthetic sess_len session into a
    # pool of each dtype (quantizing host-side for the int8 arm with
    # the pool's own contract), spill it, and frame the spill exactly
    # as the disagg/fleet transport would
    def frame_bytes(kvdt: str) -> int:
        L, H = shape["num_layers"], shape["heads"]
        D = shape["hidden"] // shape["heads"]
        pool = PagedKVCache(
            L, H, D, slots=1, block_size=block_size,
            max_seq_len=sess_len, kv_dtype=kvdt,
        )
        rng = np.random.default_rng(7)
        dense = rng.standard_normal(
            (2, L, H, sess_len, D)
        ).astype(np.float32)
        payload = {"length": sess_len, "layers": {}}
        if pool.quantized:
            payload["kv_dtype"] = kvdt
        for i in range(L):
            d = {}
            for name, x in (("k", dense[0, i]), ("v", dense[1, i])):
                if pool.quantized:
                    # (len, H, D) layout gives the contract's
                    # per-position scales; back to (H, len, D) on disk
                    q, s = quantize_kv(
                        jnp, jnp.asarray(x.transpose(1, 0, 2)), kvdt
                    )
                    d[name] = np.asarray(q).transpose(1, 0, 2)
                    d["s" + name] = np.asarray(s)
                else:
                    d[name] = x
            payload["layers"][f"layer{i}"] = d
        pool.restore(0, payload, sess_len)
        spill = pool.spill(0, sess_len)
        return len(encode_handoff({
            "id": 0, "prompt": np.zeros((4,), np.int32), "tokens": [],
            "max_new_tokens": 1, "eos_id": None, "kv_spill": spill,
        }))

    fb_f, fb_q = frame_bytes("fp32"), frame_bytes("int8")
    rep_q = arms["int8"]["rep"]
    return {
        "config": (
            f"{'mid' if on_tpu else 'tiny'} gpt sv={seq} bs={block_size} "
            f"{n_requests} reqs sess={sess_len} int8 kv+weights vs fp32"
        ),
        "kv_dtype": "int8",
        "weight_dtype": "int8",
        "serve_kv_bytes_per_tok": arms["int8"]["bpt"],
        "kv_bytes_per_tok_fp32": arms["fp32"]["bpt"],
        "kv_sessions_per_pool": sessions,
        "kv_sessions_per_pool_ratio": (
            round(sessions["int8"] / sessions["fp32"], 4)
            if sessions["fp32"] else None
        ),
        "kv_frame_bytes": {"fp32": fb_f, "int8": fb_q},
        "kv_frame_bytes_ratio": round(fb_f / fb_q, 4) if fb_q else None,
        "outputs_complete": bool(complete),
        "divergent_streams": int(divergent),
        "serve_tok_s_int8": (
            round(rep_q.new_tokens / arms["int8"]["wall"], 2)
            if arms["int8"]["wall"] else None
        ),
        "serve_tok_s_fp32": (
            round(
                arms["fp32"]["rep"].new_tokens / arms["fp32"]["wall"], 2
            )
            if arms["fp32"]["wall"] else None
        ),
        "windows": rep_q.windows,
    }


def _recovery_ab(on_tpu: bool) -> dict:
    """Kill-and-resume A/B (ISSUE 12 acceptance): train a tiny model to
    completion (arm A), then re-run it with a deterministic injected
    device loss mid-run and per-step checkpointing (arm B), time the
    checkpoint restore (``recovery_s``), resume a FRESH model from the
    last checkpoint, and check the resumed run's final weights are
    BIT-identical to the uninterrupted arm (``resume_replay_exact`` —
    gated at true by tools/bench_compare.py).  docs/RESILIENCE.md."""
    import tempfile
    import time as _time

    import numpy as np

    from flexflow_tpu import (
        ActiMode, AdamOptimizer, FFConfig, FFModel, LossType, MachineMesh,
    )
    from flexflow_tpu.runtime.faults import FaultPlan, set_fault_plan

    B, D, C = 16, 16, 8
    N = B * 4  # 4 batches/epoch
    epochs = 2
    kill_step = 6  # mid-epoch-2 (steps are 1-based in the executor)
    spec = f"fit:device_loss@{kill_step}"

    def build():
        cfg = FFConfig(batch_size=B, learning_rate=0.05)
        m = FFModel(cfg)
        t = m.create_tensor((B, D))
        t = m.dense(t, 32, ActiMode.RELU)
        t = m.dense(t, C)
        m.softmax(t)
        m.compile(
            optimizer=AdamOptimizer(alpha=1e-2),
            loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
            mesh=MachineMesh((1, 1), ("data", "model")),
            seed=0,
        )
        return m

    rng = np.random.default_rng(0)
    x = rng.normal(size=(N, D)).astype(np.float32)
    y = rng.integers(0, C, size=(N, 1)).astype(np.int32)

    def flat_weights(m):
        return {
            f"{ln}/{wn}": w
            for ln, ws in m.get_weights().items()
            for wn, w in ws.items()
        }

    with tempfile.TemporaryDirectory() as td:
        ck = os.path.join(td, "recovery_ab.npz")
        # arm A: uninterrupted reference run
        ref = build()
        ref.fit(x, y, epochs=epochs, shuffle=True, verbose=False)
        ref_w = flat_weights(ref)

        # arm B: same run killed at kill_step with per-step checkpoints
        set_fault_plan(FaultPlan.parse(spec, seed=0))
        killed = build()
        try:
            killed.fit(
                x, y, epochs=epochs, shuffle=True, verbose=False,
                checkpoint_every=1, checkpoint_path=ck,
            )
            raise RuntimeError("injected device loss did not fire")
        except RuntimeError as e:
            if getattr(e, "kind", None) != "device_loss":
                raise
        finally:
            set_fault_plan(None)

        # recovery_s: restore the last checkpoint into a fresh model
        resumed = build()
        t0 = _time.perf_counter()
        resumed.load_checkpoint(ck)
        recovery_s = _time.perf_counter() - t0
        # exact resume: replay the remainder from the checkpoint
        resumed = build()
        resumed.fit(
            x, y, epochs=epochs, shuffle=True, verbose=False, resume=ck
        )
        res_w = flat_weights(resumed)
        exact = set(res_w) == set(ref_w) and all(
            ref_w[k].dtype == res_w[k].dtype
            and np.array_equal(
                ref_w[k], res_w[k]
            )
            for k in ref_w
        )

    return {
        "fault_plan": spec,
        "kill_step": kill_step,
        "steps_total": epochs * (N // B),
        "recovery_s": round(recovery_s, 6),
        "resume_replay_exact": bool(exact),
    }


def _bench_secondary(on_tpu: bool) -> dict:
    """The BASELINE.json north-star secondary configs.  A config that
    raises ends the run: the headline line is already printed by then,
    and a half-complete artifact with rc 0 is how a broken path hides."""
    return {
        name: fn(on_tpu)
        for name, fn in (
            ("dlrm", _bench_dlrm),
            ("bert_large", _bench_bert_large),
            ("gpt_decode", _bench_gpt_decode),
            ("serve_continuous_ab", _serve_continuous_ab),
            ("serve_prefix_ab", _serve_prefix_ab),
            ("serve_spec_ab", _serve_spec_ab),
            ("serve_disagg_ab", _serve_disagg_ab),
            ("serve_fleet_ab", _serve_fleet_ab),
            ("serve_paged_attn_ab", _serve_paged_attn_ab),
            ("serve_prefill_paged_ab", _serve_prefill_paged_ab),
            ("serve_kv_quant_ab", _serve_kv_quant_ab),
            ("recovery_ab", _recovery_ab),
        )
    }


# ----------------------------------------------------------------- run
def run_bench(on_tpu: bool) -> None:
    import jax
    import numpy as np

    from flexflow_tpu import (
        AdamOptimizer,
        FFConfig,
        FFModel,
        LossType,
        MachineMesh,
    )
    from flexflow_tpu.models.transformer import BERT_BASE, transformer_encoder
    from flexflow_tpu.ops.base import get_op_def

    batch = int(os.environ.get("FFTPU_BENCH_BATCH", 16 if on_tpu else 4))
    seq = 512 if on_tpu else 64
    cfg_model = BERT_BASE if on_tpu else dict(hidden=128, heads=8, ff_dim=256, num_layers=2)
    dtype = "bfloat16" if on_tpu else "float32"

    from flexflow_tpu.obs import Tracer, configure, set_tracer

    # compile/search/init costs come from the shared tracing vocabulary
    # (docs/OBSERVABILITY.md) instead of ad-hoc perf_counter bracketing
    tracer = configure(level="step")
    # warn (not strict): the ffcheck pass runs post-compile on the
    # instrumented step — outside the timed windows — and the violation
    # count lands in the record for tools/bench_compare.py's zero-gate;
    # a dirty program must not sink the measured headline
    cfg = FFConfig(
        batch_size=batch, compute_dtype=dtype, verify_compiled="warn"
    )
    model = FFModel(cfg)
    transformer_encoder(
        model,
        batch=batch,
        seq=seq,
        num_classes=64,
        raw_input=True,
        **cfg_model,
    )
    model.compile(
        optimizer=AdamOptimizer(alpha=1e-4),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        mesh=MachineMesh((1, 1), ("data", "model")),
    )

    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, seq, cfg_model["hidden"])).astype(np.float32)
    y = rng.integers(0, 64, size=(batch, 1)).astype(np.int32)

    # ONE instrumented step isolates the XLA step compile from steady
    # state; the compiled executable is reused by the untraced timed
    # windows below (the per-step sync tracing inserts must NOT run
    # inside the measured windows)
    model.executor.train_step([x], y)
    compile_stats = model.executor.last_step_stats or {}
    obs_summary = tracer.summary()
    set_tracer(Tracer())  # timed windows take the untraced fast path

    # _median_sps pre-places batches on device (committed arrays
    # short-circuit executor._place — measures the step program, not
    # per-step H2D), ends every window on block_until_ready, and takes a
    # median over independent windows (a single window cherry-picks)
    steps = 20 if on_tpu else 3
    repeats = 5 if on_tpu else 3
    head = _median_sps(model, [x], y, batch, steps=steps, windows=repeats)
    samples_per_sec = head["samples_per_sec"]

    # sync-vs-async fit-loop A/B (same process, same compiled step):
    # the ISSUE-4 acceptance number — how much host-side stall the
    # per-step metric fetch was costing, and that the async K-step
    # flush removes it
    fit_ab = _fit_sync_async_ab(
        model, x, y, batch, batches=32 if on_tpu else 8
    )

    # fwd FLOPs from the op inventory; train step ~ 3x fwd (fwd + bwd 2x)
    fwd_flops = sum(
        get_op_def(l.op_type).flops(l)
        for l in model.layers
        if not l.op_type.is_parallel_op
    )
    step_flops = 3.0 * fwd_flops
    device_kind = jax.devices()[0].device_kind
    peak = _peak_flops(device_kind) if on_tpu else None
    mfu = (step_flops / (head["step_time_ms"] / 1000.0) / peak) if peak else None
    # machine-model identity ("preset:v5e" / "file:<sha256/12>" /
    # "default:..."): compile() priced this run's strategy against this
    # model, and tools/bench_compare.py refuses to gate runs priced
    # against different topologies
    from flexflow_tpu.search.cost import TPUMachineModel

    machine = (
        TPUMachineModel.from_file(cfg.machine_model_file)
        if cfg.machine_model_file
        else TPUMachineModel.detect()
    )
    machine_id = machine.source
    # cost-model accuracy vocabulary (docs/OBSERVABILITY.md "Calibration
    # loop"): MAPE of the search's predicted step time vs the measured
    # median — LOWER is better, gated by tools/bench_compare.py so a
    # cost-model accuracy regression fails like a throughput one.
    # FFTPU_BENCH_CALIBRATION points at a CalibrationStore to score the
    # calibrated tier instead of the raw analytic one (cost_model_tier
    # records which was scored — comparable metadata for the gate).
    cost_model_tier = cfg.cost_model
    cost_model_mape = None
    from flexflow_tpu.search.cost import estimate_strategy_cost

    pred_s = estimate_strategy_cost(
        model.layers, model.executor.strategy, machine
    )
    cal_path = os.environ.get("FFTPU_BENCH_CALIBRATION")
    if cal_path:
        from flexflow_tpu.search.calibration import CalibrationStore

        pred_s = CalibrationStore.load(
            cal_path, expect_identity=machine_id,
            expect_backend=jax.default_backend(),
            expect_dtype=dtype,
        ).correct_step("fit", pred_s)
        cost_model_tier = "calibrated"
    obs_s = head["step_time_ms"] / 1e3
    if obs_s > 0 and pred_s and pred_s > 0:
        cost_model_mape = round(abs(obs_s - pred_s) / obs_s, 6)
    record = {
        # the device metric's name belongs to a device run; --cpu is a
        # smoke of the code path at toy sizes and says so in its name
        "metric": (
            "bert_base_train_throughput" if on_tpu
            else "cpu_smoke_samples_per_s"
        ),
        "value": round(samples_per_sec, 2),
        "unit": "samples/s",
        "machine_model": machine_id,
        # the baseline is the TPU number of record
        "vs_baseline": 1.0 if on_tpu else None,
        "backend": jax.default_backend(),
        "device_kind": device_kind,
        "device_count": len(jax.devices()),
        "compute_dtype": dtype,
        "batch": batch,
        "seq": seq,
        "step_time_ms": head["step_time_ms"],
        "mfu": round(mfu, 4) if mfu is not None else None,
        "peak_flops": peak,
        "sps_min": head["sps_min"],
        "sps_max": head["sps_max"],
        "timing_windows": repeats,
        # async-fit vocabulary: the effective K the untimed default fit
        # loop would use, plus the measured sync-vs-async A/B.
        # tools/bench_compare.py treats metrics_sync_every as comparable
        # metadata — records that predate it still gate.
        "metrics_sync_every": fit_ab.get("metrics_sync_every_async"),
        "fit_sync_async_ab": fit_ab,
        # scan-stacked repeated blocks (--stack-blocks, docs/PERF.md):
        # comparable metadata for the gate, like metrics_sync_every
        "stack_blocks": cfg.stack_blocks,
        # cost-model accuracy (calibration loop): predicted-vs-measured
        # MAPE of the headline step, gated LOWER-is-better; the tier that
        # produced the prediction is comparable metadata
        "cost_model_tier": cost_model_tier,
        "cost_model_mape": cost_model_mape,
        "compile_stacked_ab": None,
        # pipeline parallelism (--pipeline, docs/PIPELINE.md): the
        # headline's pipeline config is comparable metadata (like
        # stack_blocks); pipeline_bubble_frac — the 1F1B A/B's measured
        # warmup/drain bubble — gates LOWER-is-better
        "pipeline": cfg.pipeline,
        "pipeline_bubble_frac": None,
        "pipeline_1f1b_ab": None,
        # shared observability vocabulary (docs/OBSERVABILITY.md): the
        # same field names a --metrics-out training stream carries, so
        # tools/bench_compare.py reads bench artifacts and metrics
        # streams with one code path
        "samples_per_s": round(samples_per_sec, 2),
        "tokens_per_s": round(samples_per_sec * seq, 2),
        "step_wall_s": round(head["step_time_ms"] / 1000.0, 6),
        "jit_compile_s": round(compile_stats.get("compile_s", 0.0), 3),
        "init_params_s": round(
            obs_summary["spans"].get("init_params", {}).get("total_s", 0.0), 3
        ),
        "attn_core_fwdbwd": None,
        "secondary": None,
        # serving vocabulary (docs/SERVING.md): aggregate continuous-
        # batching tokens/s (higher-is-better gate), p99 per-token
        # latency (LOWER-is-better gate), and the traffic identity
        # (seed/shape — comparable metadata, like stack_blocks)
        "serve_tok_s": None,
        "serve_p99_ms": None,
        "serve_traffic": None,
        # multi-tenant scale-out (ISSUE 11): prefix-cache hit rate from
        # the shared-prefix A/B (higher-is-better gate) and the
        # speculative draft depth (comparable metadata — records with
        # different k are different workloads)
        "serve_prefix_hit_rate": None,
        "serve_spec_k": None,
        # disaggregated prefill/decode (ISSUE 13, docs/SERVING.md
        # "Disaggregated prefill/decode"): decode pool p99 per-token
        # window latency under bursty traffic (LOWER-is-better gate),
        # with the handoff latency and the pool split as comparable
        # metadata — different splits are different deployments, not
        # regressions
        "serve_disagg_p99_tpot_ms": None,
        "serve_handoff_ms": None,
        "serve_disagg_split": None,
        # fleet tier (ISSUE 18, docs/SERVING.md "Fleet tier"): the
        # 3-replica fleet A/B's pooled prefix hit rate under
        # prefix-aware routing (higher-is-better gate) and its p99
        # per-token latency (LOWER-is-better gate), with the fleet
        # shape as comparable metadata — different replica counts or
        # policies are different deployments, not regressions
        "serve_fleet_prefix_hit_rate": None,
        "serve_fleet_p99_tpot_ms": None,
        "fleet_replicas": None,
        "fleet_routing": None,
        # per-request tracing (ISSUE 16, docs/OBSERVABILITY.md): the
        # disagg arm runs traced, and the ffspan/1 stream yields the
        # prefill-pool admission-wait p99 (the TTFT queue leg) and the
        # MEASURED handoff transit p99 beside the priced estimate
        # above — comparable metadata, not gated (wall-clock waits are
        # load-shaped, not regressions)
        "serve_ttft_queue_ms_p99": None,
        "serve_handoff_observed_ms": None,
        # SLO ops plane (ISSUE 17, docs/OBSERVABILITY.md "SLOs, alerts,
        # and live introspection"): availability and alerts fired under
        # the default policy during the headline serve run — comparable
        # metadata, not gated (a smoke box firing a burn alert reflects
        # load shape, not a code regression)
        "serve_slo_availability": None,
        "serve_alerts_fired": None,
        # paged decode attention (ISSUE 14, docs/PERF.md "Paged decode
        # attention"): the paged decode program's peak live temp bytes
        # (LOWER-is-better gate — the gather materialization coming
        # back shows up here first) and the decode-attention kernel as
        # comparable metadata
        "serve_paged_attn_peak_mb": None,
        "serve_attn": None,
        # chunked prefill on the paged pool (ISSUE 20, docs/SERVING.md
        # "Chunked prefill on the paged pool"): the fp32 paged PREFILL
        # program's peak live temp bytes (LOWER-is-better gate — the
        # full-virtual-length gather coming back to the prefill phase
        # shows up here first); per-dtype ratios and TTFT ride in the
        # secondary record as comparable metadata
        "serve_prefill_peak_mb": None,
        # quantized KV serving (ISSUE 19, docs/SERVING.md "Quantized KV
        # cache and weight-only decode"): the int8 arm's per-token pool
        # bytes (LOWER-is-better gate — a full-precision pool sneaking
        # back shows up here first) and the storage dtypes as
        # comparable metadata
        "serve_kv_bytes_per_tok": None,
        "kv_dtype": None,
        "weight_dtype": None,
        # resilience (ISSUE 12, docs/RESILIENCE.md): checkpoint-restore
        # wall time (LOWER-is-better), the kill-and-resume bit-identity
        # bit (gated AT TRUE), and the injected fault plan (comparable
        # metadata — records with different plans are different runs)
        "recovery_s": None,
        "resume_replay_exact": None,
        "fault_plan": None,
        # --verify-compiled ffcheck pass (docs/ANALYSIS.md): violation
        # count from the post-compile static analysis of the headline
        # step, gated AT ZERO by tools/bench_compare.py; null when the
        # pass didn't run (verify_compiled=off)
        "analysis_violations": getattr(
            model.executor, "analysis_violations", None
        ),
    }
    # the headline goes out BEFORE the extras, so a run that dies in a
    # secondary config (non-zero exit) still shows what it had measured
    print(json.dumps(record), flush=True)

    # optional per-run metrics record in the training-stream schema
    # (--metrics-out): one step_record with the headline throughput, so
    # bench runs land in the same JSONL timeline as training runs
    metrics_out = os.environ.get("FFTPU_BENCH_METRICS_OUT")
    if metrics_out:
        import time as _time

        from flexflow_tpu.obs import MetricsStream, step_record

        stream = MetricsStream(metrics_out)
        stream.append(step_record(
            step=0,
            t=_time.time(),
            loss=None,
            step_wall_s=record["step_wall_s"],
            compile_s=record["jit_compile_s"],
            jit_cache="miss",
            samples=batch,
            tokens=batch * seq,
            analysis_violations=record["analysis_violations"],
            metrics={"metric": record["metric"], "mfu": record["mfu"]},
        ))
        stream.close()

    # attention-core comparison (round-2 verdict item 1 done-condition):
    # flash vs XLA sdpa at s=512 and s=2048, fwd+bwd, chained in one
    # scan per call (tools/bench_attention.py).
    record["attn_core_fwdbwd"] = _attention_core_compare() if on_tpu else None
    # stacked-vs-unrolled compile A/B (ISSUE 5 acceptance)
    record["compile_stacked_ab"] = _compile_stacked_ab(on_tpu)
    # 1F1B pipeline A/B (ISSUE 8 acceptance)
    ab = _pipeline_1f1b_ab(on_tpu)
    record["pipeline_1f1b_ab"] = ab
    record["pipeline_bubble_frac"] = ab["pipelined"]["bubble_frac"]
    # overlapped-gradient-sync A/B (ISSUE 15 acceptance)
    oab = _fit_overlap_ab(on_tpu)
    record["fit_overlap_ab"] = oab
    record["exposed_comm_frac"] = oab["exposed_comm_frac"]
    record["grad_overlap"] = (
        "ring" if oab["smoke"]["ring"]["ring_engaged"] else "off"
    )
    record["secondary"] = _bench_secondary(on_tpu)
    sab = record["secondary"].get("serve_continuous_ab") or {}
    record["serve_tok_s"] = sab.get("serve_tok_s")
    record["serve_p99_ms"] = sab.get("serve_p99_ms")
    record["serve_traffic"] = sab.get("serve_traffic")
    record["serve_slo_availability"] = sab.get("serve_slo_availability")
    record["serve_alerts_fired"] = sab.get("serve_alerts_fired")
    pab = record["secondary"].get("serve_prefix_ab") or {}
    record["serve_prefix_hit_rate"] = pab.get("serve_prefix_hit_rate")
    xab = record["secondary"].get("serve_spec_ab") or {}
    record["serve_spec_k"] = xab.get("serve_spec_k")
    dab = record["secondary"].get("serve_disagg_ab") or {}
    record["serve_disagg_p99_tpot_ms"] = dab.get("serve_disagg_p99_tpot_ms")
    record["serve_handoff_ms"] = dab.get("serve_handoff_ms")
    record["serve_disagg_split"] = dab.get("serve_disagg_split")
    record["serve_ttft_queue_ms_p99"] = dab.get("serve_ttft_queue_ms_p99")
    record["serve_handoff_observed_ms"] = dab.get(
        "serve_handoff_observed_ms"
    )
    fab = record["secondary"].get("serve_fleet_ab") or {}
    record["serve_fleet_prefix_hit_rate"] = fab.get(
        "serve_fleet_prefix_hit_rate"
    )
    record["serve_fleet_p99_tpot_ms"] = fab.get("serve_fleet_p99_tpot_ms")
    record["fleet_replicas"] = fab.get("fleet_replicas")
    record["fleet_routing"] = fab.get("fleet_routing")
    qab = record["secondary"].get("serve_paged_attn_ab") or {}
    record["serve_paged_attn_peak_mb"] = qab.get("serve_paged_attn_peak_mb")
    record["serve_attn"] = qab.get("serve_attn")
    pfab = record["secondary"].get("serve_prefill_paged_ab") or {}
    record["serve_prefill_peak_mb"] = pfab.get("serve_prefill_peak_mb")
    kvab = record["secondary"].get("serve_kv_quant_ab") or {}
    record["serve_kv_bytes_per_tok"] = kvab.get("serve_kv_bytes_per_tok")
    record["kv_dtype"] = kvab.get("kv_dtype")
    record["weight_dtype"] = kvab.get("weight_dtype")
    rab = record["secondary"].get("recovery_ab") or {}
    record["recovery_s"] = rab.get("recovery_s")
    record["resume_replay_exact"] = rab.get("resume_replay_exact")
    record["fault_plan"] = rab.get("fault_plan")
    print(json.dumps(record), flush=True)


def main() -> None:
    import jax

    from flexflow_tpu.config import apply_compile_cache

    apply_compile_cache()
    cpu = "--cpu" in sys.argv
    if cpu:
        jax.config.update("jax_platforms", "cpu")
    elif jax.default_backend() != "tpu":
        sys.exit(
            f"bench.py: no TPU (jax.default_backend() = "
            f"{jax.default_backend()!r}); nothing was measured.  "
            "`python bench.py --cpu` runs the toy CPU smoke, which is not "
            "a device measurement."
        )
    if "--metrics-out" in sys.argv:
        os.environ["FFTPU_BENCH_METRICS_OUT"] = sys.argv[
            sys.argv.index("--metrics-out") + 1
        ]
    run_bench(on_tpu=not cpu)


if __name__ == "__main__":
    main()
