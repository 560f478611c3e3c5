"""The serving job: one ``ServeEngine`` driven by ``ServeEngine.run``.

Set-up builds the decoder, loads the seed's weights, stands up the
engine (its constructor compiles or loads the decode and the prefill
program and warms both), and serves one short closed burst so the host
path has run once.  The window hands the engine the seed's traffic:

* a backlog cell (``rate_rps`` 0) has every request due at t = 0, more
  than the window can finish; at ``--seconds`` the engine's own
  ``request_drain()`` ends the run at the next window boundary and every
  token produced by then counts;
* a rate cell has open-loop arrivals that cover ``--seconds``; the run
  ends when the last due request has finished, and every request due in
  the window is in the tails.

Afterwards the peak memory is read, the engine is freed, and the plain
reference runs one full forward over a seeded sample of the finished
requests (the longest among them), prompt and served tokens together:
``correct`` rests on how far below the reference's best logit a served
token lies.
"""

from __future__ import annotations

import gc
import importlib
import statistics
import threading
import time

import numpy as np

from benchmarks import trace_reduce as TR
from benchmarks import traffic as T
from benchmarks import weights as W

SAMPLE_REQUESTS = 6  # requests the reference re-reads: some hundreds of tokens
PROGRAMS = r"^jit_(decode|prefill)"  # the engine's programs on the trace's XLA Modules line


def build_engine(config: dict, cell: dict, seed: int, shapes: dict):
    """Builder -> compile on the one-device mesh -> seed weights -> engine."""
    from flexflow_tpu import FFConfig, FFModel, MachineMesh
    from flexflow_tpu.serve import ServeEngine

    mod, _, fn = config["builder"].partition(":")
    builder = getattr(importlib.import_module(mod), fn)
    e = cell["engine"]
    ff = FFConfig(batch_size=e["slots"], compute_dtype=config["compute_dtype"])
    model = FFModel(ff)
    builder(model, e["slots"], e["max_seq"], **config["builder_args"])
    model.compile(seed=seed & 0x7FFFFFFF, mesh=MachineMesh((1, 1), ("data", "model")))
    model.executor.params = W.make_for_executor(shapes, seed, model.executor)
    engine = ServeEngine(
        model, slots=e["slots"], block_size=e["block_size"],
        prefill_chunk=e["prefill_chunk"], sync_every=e["sync_every"],
        attn=e["attn"], kv_dtype=e["kv_dtype"],
    )
    return model, engine


def to_requests(arrivals, max_seq: int):
    from flexflow_tpu.serve.scheduler import Request

    return [
        Request(
            prompt=a.prompt, id=a.id, arrival_s=a.arrival_s, tenant=a.tenant,
            tier=a.tier, session=a.session,
            max_new_tokens=max(1, min(a.max_new_tokens, max_seq - len(a.prompt))),
        )
        for a in arrivals
    ]


def drive(engine, reqs, *, seconds: float, backlog: bool, trace=None):
    """Run the window.  Returns (report, window_s, trace slice or None).

    A backlog is cut at ``seconds`` by ``request_drain()``; the window's
    end is the moment the engine starts to drain (its spill of in-flight
    keys and values to the host comes after the last token and is not
    served time)."""
    import jax

    marks = {}
    real_drain = engine.drain

    def timed_drain():
        marks["drain"] = time.perf_counter()
        return real_drain()

    engine.drain = timed_drain
    stop = threading.Event()
    threads = []
    if backlog:
        def cut():
            if not stop.wait(seconds):
                engine.request_drain()

        threads.append(threading.Thread(target=cut, daemon=True))
    slice_ = {}
    if trace is not None:
        def profile():
            if stop.wait(trace["start_s"]):
                return
            c0 = (engine.windows, engine.decode_steps, engine.prefill_dispatches)
            TR.start_trace(trace["dir"])
            t0 = time.perf_counter()
            stop.wait(trace["seconds"])
            slice_["window_s"] = time.perf_counter() - t0
            c1 = (engine.windows, engine.decode_steps, engine.prefill_dispatches)
            jax.profiler.stop_trace()
            slice_["windows"] = c1[0] - c0[0]
            slice_["decode_steps"] = c1[1] - c0[1]
            slice_["prefill_dispatches"] = c1[2] - c0[2]
            slice_["steps"] = slice_["program_calls"] = (
                slice_["decode_steps"] + slice_["prefill_dispatches"]
            )
            slice_["programs"] = PROGRAMS

        threads.append(threading.Thread(target=profile, daemon=True))
    for t in threads:
        t.start()
    t0 = time.perf_counter()
    try:
        report = engine.run(reqs)
    finally:
        t_end = time.perf_counter()
        stop.set()
        for t in threads:
            t.join()
        engine.drain = real_drain
    window_s = marks.get("drain", t_end) - t0
    return report, window_s, (slice_ or None), t0


def request_work(reqs, prefill_chunk: int, work) -> dict:
    tot = {"positions": 0, "kv_token_reads": 0, "attended_pairs": 0, "logit_rows": 0}
    for r in reqs:
        pp = r.prompt_len if r.tokens else int(r.prefill_pos)
        w = work.served_request_work(
            prompt_len=r.prompt_len, prefill_pos=pp, new_tokens=len(r.tokens),
            prefill_chunk=prefill_chunk,
        )
        for k in tot:
            tot[k] += w[k]
    return tot


def prefill_rows(reqs, report, engine_settings: dict) -> dict:
    """Two facts: the prompt positions the window's requests were
    prefilled by, and the rows its prefill dispatches computed -- each
    dispatch ``slots x prefill_chunk``, whoever is mid-prompt."""
    e = engine_settings
    return {
        "prefill_positions": sum(
            r.prompt_len if r.tokens else int(r.prefill_pos) for r in reqs
        ),
        "prefill_rows_computed": report.prefill_dispatches * e["slots"] * e["prefill_chunk"],
    }


def program_relayouts(ctx, engine) -> dict:
    """A fact of a traced run: the copies of a whole pool, the casts of a
    float32 weight and the copies of a whole expert stack in the engine's
    compiled decode and prefill programs (it lowers and compiles both
    again, after the window: seconds that an untraced run is spared)."""
    if not ctx.trace:
        return {}
    t0 = time.perf_counter()
    n = engine.pool_relayouts() + engine.weight_casts() + engine.expert_relayouts()
    return {"program_relayouts": n, "program_relayouts_s": time.perf_counter() - t0}


def finished_rows(reqs) -> list:
    """What the reference needs of each finished request, as plain data
    that outlives the engine."""
    return [
        {"id": r.id, "prompt": np.asarray(r.prompt), "tokens": list(r.tokens),
         "n_tokens": len(r.tokens), "asked": r.max_new_tokens}
        for r in reqs if r.finish_reason in ("length", "eos")
    ]


def pick_sample(finished, seed: int):
    """``SAMPLE_REQUESTS`` finished requests drawn from the seed, the
    longest among them."""
    if not finished:
        return []
    k = SAMPLE_REQUESTS
    rng = np.random.default_rng(seed)
    longest = max(finished, key=lambda r: (len(r["prompt"]) + r["n_tokens"], -r["id"]))
    rest = [r for r in finished if r is not longest]
    idx = rng.permutation(len(rest))[: max(0, k - 1)]
    return [longest] + [rest[i] for i in idx]


def pack_sample(sample, max_prompt: int, max_new: int):
    """Fixed-shape arrays for the reference: tokens (k, max_prompt +
    max_new) = prompt then served tokens, the rows whose logits chose each
    served token, the served tokens, and which entries are real."""
    k, s = len(sample), max_prompt + max_new
    tokens = np.zeros((k, s), np.int32)
    rows = np.zeros((k, max_new), np.int32)
    served = np.zeros((k, max_new), np.int32)
    valid = np.zeros((k, max_new), bool)
    for i, r in enumerate(sample):
        p, n = r["prompt"], r["tokens"]
        tokens[i, : len(p)] = p
        tokens[i, len(p): len(p) + len(n)] = n
        rows[i, : len(n)] = len(p) - 1 + np.arange(len(n))
        served[i, : len(n)] = n
        valid[i, : len(n)] = True
    return tokens, rows, served, valid


def reference_gaps(ctx, packed, precision="highest"):
    import jax
    import jax.numpy as jnp

    config = ctx.config
    ref = importlib.import_module(f"benchmarks.reference.{config['family']}")
    params = W.make(ref.param_shapes(config["model"]), ctx.seed)
    fn = jax.jit(
        lambda p, t, r, s, v: ref.served_gaps(p, t, r, s, v, config["model"], precision)
    )
    return np.asarray(fn(params, *(jnp.asarray(a) for a in packed)))


def checks_from(ctx, sample, finished, precision="highest") -> list:
    t = ctx.cell["mix"]
    limits = ctx.cell["correct_limits"]
    packed = pack_sample(sample, t["prompt_len"][1] + t.get("shared_prefix", 0), t["max_new"][1])
    gaps = reference_gaps(ctx, packed, precision)
    short = sum(1 for r in finished if r["n_tokens"] != r["asked"])
    return [
        ("served_logit_gap_max", float(gaps.max()) if len(sample) else float("inf"),
         limits["served_logit_gap_max"]),
        ("finished_with_wrong_token_count", float(short),
         limits["finished_with_wrong_token_count"]),
    ]


def pct(vals, q: int):
    vals = [v for v in vals if v is not None]
    if len(vals) < 2:
        return float("nan")
    return statistics.quantiles(vals, n=100, method="inclusive")[q - 1]


def run(ctx) -> dict:
    import jax

    config, cell = ctx.config, ctx.cell
    e, t = cell["engine"], cell["mix"]
    ref = importlib.import_module(f"benchmarks.reference.{config['family']}")
    shapes = ref.param_shapes(config["model"])
    backlog = t.get("rate_rps", 0) <= 0

    # ---- set-up ---------------------------------------------------------
    marks = [("start", time.perf_counter())]

    def mark(name):
        marks.append((name, time.perf_counter()))

    model, engine = build_engine(config, cell, ctx.seed, shapes)
    mark("build_init_compile")
    warm = T.generate(T.TrafficSpec(
        n_requests=4, seed=ctx.seed, prompt_len=(40, 70), max_new=(5, 9),
        vocab=config["model"]["vocab_size"],
    ))
    engine.run(to_requests(warm, e["max_seq"]))
    mark("warm_burst")
    spec = T.spec_from_cell(
        t, seed=ctx.seed, seconds=ctx.seconds, vocab=config["model"]["vocab_size"],
    )
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
    tot = request_work(reqs, e["prefill_chunk"], ctx.work)
    m = config["model"]
    heads, hd = m["n_head"], m["n_embd"] // m["n_head"]
    calls = max(1, report.decode_steps + report.prefill_dispatches)
    kv_item = 2 if config["compute_dtype"] == "bfloat16" else 4
    flops = ctx.work.decoder_serve_flops(
        positions=tot["positions"], attended=tot["attended_pairs"],
        logit_rows=tot["logit_rows"], hidden=m["n_embd"], ff_dim=m["n_inner"],
        num_layers=m["n_layer"], vocab=m["vocab_size"],
    )
    lat = [r.latency_ms() for r in finished]
    started_ids = {r.id for r in started}
    finished_ids = {r.id for r in finished}
    due = started + [r for r in rejected if r.id not in started_ids]
    if backlog:
        metrics = {"serve_tokens_per_s": new_tokens / window_s}
        attempted, failed = len(due), len(rejected)
    else:
        # every request due in the window is in the tails; one that never
        # finished is a failure, not a fast request
        unfinished = [r for r in reqs if r.id not in finished_ids]
        metrics = {
            "ttft_p50_ms": pct([d["ttft_ms"] for d in lat], 50),
            "ttft_p95_ms": pct([d["ttft_ms"] for d in lat], 95),
            "tpot_p50_ms": pct([d["tpot_ms"] for d in lat], 50),
            "tpot_p95_ms": pct([d["tpot_ms"] for d in lat], 95),
        }
        attempted, failed = len(reqs), len(unfinished)
    samples = {
        "ttft_ms": [d["ttft_ms"] for d in lat],
        "tpot_ms": [d["tpot_ms"] for d in lat],
        "queue_wait_ms": [
            1e3 * (r.t_admitted - r.arrival_s) for r in finished
            if r.t_admitted is not None
        ],
        "generator_lag_ms": [
            1e3 * (r.t_submit - r.arrival_s) for r in finished
            if r.t_submit is not None
        ],
    }
    fin_rows = finished_rows(finished)
    facts = {
        "window_s": window_s,
        "run_wall_s": report.wall_s,
        "new_tokens": new_tokens,
        "requests_offered": len(reqs),
        "requests_started": len(started),
        "requests_finished": len(finished),
        "requests_rejected": len(rejected),
        "requests_per_s_finished": len(finished) / window_s,
        "windows": report.windows,
        "host_syncs": report.host_syncs,
        "decode_steps": report.decode_steps,
        "prefill_chunks": report.prefill_chunks,
        "prefill_dispatches": report.prefill_dispatches,
        "occupancy_mean": report.occupancy_mean,
        "peak_active": report.peak_active,
        "attn_kernel": report.prefill_attn_kernel,
        "attn_interpret": bool(report.attn_interpret),
        "window_wall_ms": 1e3 * window_s / max(1, report.windows),
        "serve_flops": flops,
        "positions": tot["positions"],
        "paged_bytes_per_call": ctx.work.paged_attention_bytes(
            kv_token_reads=tot["kv_token_reads"], q_rows=tot["positions"],
            heads=heads, head_dim=hd, kv_itemsize=kv_item, q_itemsize=kv_item,
        ) / calls,
        "paged_flops_per_call": ctx.work.attention_flops(
            tot["attended_pairs"], m["n_embd"]
        ) / calls,
        "samples": samples,
        "queue_depth_mid_and_end": queue_depths(reqs, window_s if backlog else ctx.seconds),
        "memory_stats_after_window": memory_stats,
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


def queue_depths(reqs, window_s: float) -> list:
    """Requests due but not yet admitted at the middle and at the end of
    the window: a queue that is deeper at the end than at the middle is
    growing (the offered rate is above what the engine sustains)."""
    out = []
    for frac in (0.5, 1.0):
        t = frac * window_s
        depth = 0
        for r in reqs:
            if r.arrival_s <= t and (r.t_admitted is None or r.t_admitted > t):
                depth += 1
        out.append(depth)
    return out


def prove(ctx, what: str) -> list:
    """Readings that set the limit of ``served_logit_gap_max``
    (``benchmarks/prove.py``): a short window of the program at the
    cell's own load, then over the same sampled prompts and served
    tokens either the program's own reading (``program``) or the
    control's (``control`` = fp8, ``bf16``): at each position the gap of
    the token the lower precision puts first."""
    config, cell = ctx.config, ctx.cell
    e, t = cell["engine"], cell["mix"]
    ref = importlib.import_module(f"benchmarks.reference.{config['family']}")
    model, engine = build_engine(config, cell, ctx.seed, ref.param_shapes(config["model"]))
    spec = T.spec_from_cell(
        t, seed=ctx.seed, seconds=ctx.seconds, vocab=config["model"]["vocab_size"],
    )
    reqs = to_requests(T.generate(spec), e["max_seq"])
    drive(engine, reqs, seconds=ctx.seconds, backlog=t.get("rate_rps", 0) <= 0)
    fin_rows = finished_rows(reqs)
    del model, engine, reqs
    gc.collect()
    sample = pick_sample(fin_rows, ctx.seed)
    out = []
    for w in what.split("+"):
        precision = {"program": "highest", "control": "fp8"}.get(w, w)
        out += [(f"{w}:{n}", v, lim) for n, v, lim in
                checks_from(ctx, sample, fin_rows, precision)]
    return out
