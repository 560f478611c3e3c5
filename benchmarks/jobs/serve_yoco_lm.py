"""The serving job for a decoder-hybrid-decoder -- a self-decoder of
state-space and attention layers whose last layer's K/V one cache holds,
and a cross-decoder of memory units and cross-attention layers over it
(the ``phi4flash`` family) -- whose float32 weights do not fit the chip
beside the reference's activations: ``jobs/serve.py``'s window
(``drive``, the backlog cut by the engine's own drain, the sample of
finished requests) as ``jobs/serve_lm.py`` runs it, with

* weights made leaf by leaf by the family's own draw
  (``weights_phi4flash.py``: the decays, the attention's lambdas and the
  norms as the family initialises them, every other leaf
  ``weights_by_leaf.py``'s), for the program and for the reference alike;
* the reference run layer by layer over the sample
  (``reference/<family>.py::served_gaps``, handed ``ByLayer``);
* work from ``work_<family>.py`` by the source's key names: the self-
  decoder a position, the cross-decoder a row it ran, the paged kernel's
  calls every layer that reads a cache, a decode step and a prefill
  group each;
* the pools' and the cross-decoder's counters as facts
  (``cross_decoder_rows`` beside ``trunk_rows``, the rows the first
  layer computed; ``kv_rows_visible``, ``state_pool_bytes``).

A family's reference provides ``param_shapes(model)`` and
``served_gaps(params, tokens, rows, served, valid, model, precision)``;
its work module ``served_request_work``, ``serve_flops``,
``paged_attention_bytes``, ``paged_attention_flops``, ``kernel_layers``,
``ssm_state_bytes``.

``prove(ctx, what)``: ``program`` (the program's own reading),
``control`` (the reference in fp8 in its place), and the planted faults
``no_diff`` (``lambda`` 0: the second map not subtracted),
``full_window`` (the window layers see every key) and ``gmu_ones`` (the
memory units read ones), each built into the program through the
builder's own arguments.
"""

from __future__ import annotations

import gc
import importlib
import time

import numpy as np

from benchmarks import traffic as T
from benchmarks import weights_phi4flash as WL
from benchmarks.jobs.serve import (
    drive,
    finished_rows,
    pack_sample,
    pct,
    pick_sample,
    prefill_rows,
    program_relayouts,
    queue_depths,
    to_requests,
)
from benchmarks.jobs.serve_lm import _modules, _warm_burst, request_work

FAULTS = {
    "no_diff": {"lambda_scale": 0.0},
    "full_window": {"sliding_window": 0},
    "gmu_ones": {"gmu_ones": True},
}
# slots a fault's engine takes where the cell's do not fit: window layers
# that see every key hold a full cache each (nine at 32 slots: 13.6 GB)
FAULT_SLOTS = {"full_window": 8}


def build_engine(config: dict, cell: dict, seed: int, shapes: dict, fault=None):
    """Builder -> compile on the one-device mesh -> seed weights, leaf by
    leaf -> engine."""
    from flexflow_tpu import FFConfig, FFModel, MachineMesh
    from flexflow_tpu.serve import ServeEngine

    mod, _, fn = config["builder"].partition(":")
    builder = getattr(importlib.import_module(mod), fn)
    e = cell["engine"]
    slots = FAULT_SLOTS.get(fault, e["slots"])
    ff = FFConfig(
        batch_size=slots, compute_dtype=config["compute_dtype"],
        param_dtype=config.get("param_dtype", "float32"),
    )
    model = FFModel(ff)
    builder(model, slots, e["max_seq"], **dict(config["builder_args"], **FAULTS.get(fault, {})))
    model.compile(seed=seed & 0x7FFFFFFF, mesh=MachineMesh((1, 1), ("data", "model")))
    WL.fill_executor(shapes, seed, model.executor)
    engine = ServeEngine(
        model, slots=slots, block_size=e["block_size"],
        prefill_chunk=e["prefill_chunk"], sync_every=e["sync_every"],
        attn=e["attn"], kv_dtype=e["kv_dtype"],
    )
    return model, engine


def checks_from(ctx, sample, finished, precision="highest") -> list:
    """The widest gap catches a fault that moves every position far; the
    mean over the served tokens one that moves many a little."""
    t, config = ctx.cell["mix"], ctx.config
    limits = ctx.cell["correct_limits"]
    ref, _ = _modules(config)
    packed = pack_sample(sample, t["prompt_len"][1], t["max_new"][1])
    params = WL.ByLayer(ref.param_shapes(config["model"]), ctx.seed)
    gaps = np.asarray(ref.served_gaps(params, *packed, config["model"], precision))
    n_served = int(packed[3].sum())  # padded entries read 0
    short = sum(1 for r in finished if r["n_tokens"] != r["asked"])
    none = float("inf")
    return [
        ("served_logit_gap_max", float(gaps.max()) if n_served else none,
         limits["served_logit_gap_max"]),
        ("served_logit_gap_mean", float(gaps.sum()) / n_served if n_served else none,
         limits["served_logit_gap_mean"]),
        ("finished_with_wrong_token_count", float(short),
         limits["finished_with_wrong_token_count"]),
    ]


def run(ctx) -> dict:
    import jax

    from flexflow_tpu.serve.programs import prefill_group_lanes

    config, cell = ctx.config, ctx.cell
    e, t, m = cell["engine"], cell["mix"], config["model"]
    ref, work = _modules(config)
    shapes = ref.param_shapes(m)
    backlog = t.get("rate_rps", 0) <= 0

    # ---- set-up ---------------------------------------------------------
    marks = [("start", time.perf_counter())]

    def mark(name):
        marks.append((name, time.perf_counter()))

    model, engine = build_engine(config, cell, ctx.seed, shapes)
    mark("build_init_compile")
    setup_peak = ctx.memory_peak_bytes()
    engine.run(to_requests(_warm_burst(ctx.seed, m["vocab_size"]), e["max_seq"]))
    mark("warm_burst")
    spec = T.spec_from_cell(t, seed=ctx.seed, seconds=ctx.seconds, vocab=m["vocab_size"])
    reqs = to_requests(T.generate(spec), e["max_seq"])
    mark("traffic")

    # ---- the window -----------------------------------------------------
    trace = None
    if ctx.trace:
        trace = {"dir": ctx.trace_dir, "start_s": 0.4 * ctx.seconds,
                 "seconds": min(3.0, 0.3 * ctx.seconds)}
    report, window_s, slice_, t_start = drive(
        engine, reqs, seconds=ctx.seconds, backlog=backlog, trace=trace,
    )
    peak = ctx.memory_peak_bytes()
    memory_stats = ctx.memory_stats()

    # ---- what the window did -------------------------------------------
    started = [r for r in reqs if r.t_admitted is not None or r.tokens]
    finished = [r for r in reqs if r.finish_reason in ("length", "eos")]
    rejected = [r for r in reqs if (r.finish_reason or "").startswith("rejected")]
    new_tokens = sum(len(r.tokens) for r in reqs)
    tot = request_work(reqs, e["prefill_chunk"], int(m["sliding_window"]), work)
    # a paged-kernel call a cache-reading layer a decode step, and a prefill
    # group (the dispatch's loop runs its layers once a group of lanes)
    groups = report.prefill_lanes_computed // prefill_group_lanes(e["slots"], e["prefill_chunk"])
    calls = max(1, report.decode_steps + report.prefill_dispatches)
    kernel_calls = max(1, (report.decode_steps + groups) * work.kernel_layers(m))
    kv_item = 2 if config["compute_dtype"] == "bfloat16" else 4
    lat = [r.latency_ms() for r in finished]
    finished_ids = {r.id for r in finished}
    started_ids = {r.id for r in started}
    due = started + [r for r in rejected if r.id not in started_ids]
    if backlog:
        metrics = {"serve_tokens_per_s": new_tokens / window_s}
        attempted, failed = len(due), len(rejected)
    else:
        unfinished = [r for r in reqs if r.id not in finished_ids]
        metrics = {
            "ttft_p50_ms": pct([d["ttft_ms"] for d in lat], 50),
            "ttft_p95_ms": pct([d["ttft_ms"] for d in lat], 95),
            "tpot_p50_ms": pct([d["tpot_ms"] for d in lat], 50),
            "tpot_p95_ms": pct([d["tpot_ms"] for d in lat], 95),
        }
        attempted, failed = len(reqs), len(unfinished)
    fin_rows = finished_rows(finished)
    facts = {
        "window_s": window_s,
        "run_wall_s": report.wall_s,
        "new_tokens": new_tokens,
        "requests_offered": len(reqs),
        "requests_started": len(started),
        "requests_finished": len(finished),
        "requests_rejected": len(rejected),
        "windows": report.windows,
        "host_syncs": report.host_syncs,
        "decode_steps": report.decode_steps,
        "prefill_chunks": report.prefill_chunks,
        "prefill_dispatches": report.prefill_dispatches,
        "prefill_lanes_computed": report.prefill_lanes_computed,
        "prefill_groups": groups,
        "occupancy_mean": report.occupancy_mean,
        "peak_active": report.peak_active,
        "attn_kernel": report.prefill_attn_kernel,
        "attn_interpret": bool(report.attn_interpret),
        "window_wall_ms": 1e3 * window_s / max(1, report.windows),
        "serve_flops": work.serve_flops(m, tot),
        "positions": tot["positions"],
        "paged_kernel_calls": kernel_calls,
        "paged_bytes_per_call": work.paged_attention_bytes(m, tot, kv_item) / kernel_calls,
        "paged_flops_per_call": work.paged_attention_flops(m, tot) / kernel_calls,
        "kv_rows_visible": report.kv_rows_visible,
        "kv_rows_context": report.kv_rows_context,
        "kv_pages_held_full": report.kv_pages_held_full,
        "kv_pages_held_window": report.kv_pages_held_window,
        # rows the cross-decoder computed, and the rows the self-decoder
        # computed (every slot a decode step, every lane of a group a
        # prefill row): their ratio is 100 % without the prefill's cut
        "cross_decoder_rows": report.cross_decoder_rows,
        "trunk_rows": report.decode_steps * e["slots"]
        + report.prefill_lanes_computed * e["prefill_chunk"],
        "state_pool_bytes": report.state_pool_bytes,
        "state_slots_held": report.state_slots_held,
        "state_spills": report.state_spills,
        "state_restores": report.state_restores,
        "ssm_rows": report.ssm_rows,
        "ssm_state_bytes_per_call": work.ssm_state_bytes(m, tot, kv_item) / calls,
        "param_dtypes": sorted({str(x.dtype) for x in jax.tree.leaves(model.executor.params)}),
        "pool_bytes": engine.kv.hbm_bytes(),
        "samples": {
            "ttft_ms": [d["ttft_ms"] for d in lat],
            "tpot_ms": [d["tpot_ms"] for d in lat],
        },
        "queue_depth_mid_and_end": queue_depths(reqs, window_s if backlog else ctx.seconds),
        "memory_stats_after_window": memory_stats,
        "memory_peak_bytes_setup": setup_peak,
        "setup_parts_s": {n: tm - marks[i][1] for i, (n, tm) in enumerate(marks[1:])},
        "serve_compile_s": marks[1][1] - marks[0][1],
        **prefill_rows(reqs, report, e),
        **program_relayouts(ctx, engine),
    }
    sample = pick_sample(fin_rows, ctx.seed)

    # ---- free the program, then the reference ---------------------------
    del model, engine, reqs, started, finished, rejected, due, report, lat
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    checks = checks_from(ctx, sample, fin_rows)
    facts["reference_s"] = time.perf_counter() - t_ref
    facts["sample_tokens"] = sum(r["n_tokens"] for r in sample)
    facts["sample_longest"] = max((len(r["prompt"]) + r["n_tokens"] for r in sample), default=0)
    return {
        "t_window_start": t_start,
        "metrics": metrics,
        "attempted": attempted,
        "failed": failed,
        "memory_peak_bytes": peak,
        "checks": checks,
        "trace": slice_,
        "facts": facts,
    }


def prove(ctx, what: str) -> list:
    """Readings that set the limits (``benchmarks/prove.py``): a short
    window of the program at the cell's own load -- as built, or with a
    planted fault -- then over the sampled prompts and served tokens the
    reference's reading of them (``control``: of the tokens the
    reference in fp8 puts first)."""
    import jax

    config, cell = ctx.config, ctx.cell
    e, t, m = cell["engine"], cell["mix"], config["model"]
    ref, _ = _modules(config)
    shapes = ref.param_shapes(m)
    served = {}  # fault or None -> (sample, finished rows)

    def serve(fault):
        if fault not in served:
            model, engine = build_engine(config, cell, ctx.seed, shapes, fault)
            engine.run(to_requests(_warm_burst(ctx.seed, m["vocab_size"]), e["max_seq"]))
            spec = T.spec_from_cell(t, seed=ctx.seed, seconds=ctx.seconds, vocab=m["vocab_size"])
            reqs = to_requests(T.generate(spec), e["max_seq"])
            drive(engine, reqs, seconds=ctx.seconds, backlog=t.get("rate_rps", 0) <= 0)
            fin_rows = finished_rows(reqs)
            del model, engine, reqs
            gc.collect()
            jax.clear_caches()
            served[fault] = (pick_sample(fin_rows, ctx.seed), fin_rows)
        return served[fault]

    out = []
    for w in what.split("+"):
        sample, fin_rows = serve(w if w in FAULTS else None)
        precision = {"program": "highest", "control": "fp8"}.get(w, "highest" if w in FAULTS else w)
        out += [(f"{w}:{n}", v, lim) for n, v, lim in
                checks_from(ctx, sample, fin_rows, precision)]
    return out
