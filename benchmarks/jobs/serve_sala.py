"""The serving job for a decoder of sparse and linear attention layers
(the ``minicpm_sala`` family) whose float32 weights do not fit the chip
beside the reference's activations: ``jobs/serve.py``'s window
(``drive``, the backlog cut by the engine's own drain, the sample of
finished requests) as ``jobs/serve_yoco_lm.py`` runs it -- the third
copy of that job, for a ``benchmark`` PR to fold (PERF.md §7) -- with

* weights made leaf by leaf by the family's own draw
  (``weights_sala.py``), for the program and for the reference alike;
* the engine keeping each served token's own logit (``token_logits``),
  and the reference run layer by layer over the sample
  (``reference/minicpm_sala.py::served_readings``, handed ``ByLayer``):
  how far each served token's reference logit lies below the
  reference's best, and how far the program's logit of it lies from
  the reference's -- a fault that moves the logits without changing a
  token shows there; it also counts the (position, K/V head) pairs
  whose block selection moves when the query and the compressed keys
  are rounded to bfloat16 (a fact: top-k flips on near-ties);
* work from ``work_sala.py``: every layer a position, the sparse
  layers' attended keys and index entries, the sparse kernel's calls a
  layer and a K/V head a decode step and a prefill group;
* the pools' counters as facts (``sparse_keys_attended`` /
  ``sparse_keys_visible``, counted on the device over every call, and
  the same over decode calls; ``state_pool_bytes``,
  ``index_pool_bytes``);
* each window's wall time, prefill chunks, decode steps and decoding
  lanes (``window_log``): where a run's time went, window by window;
* in a traced run, the share of the traced slice's busy device time in
  the operations whose compiled HLO names the ``ff.lightning`` and
  ``ff.sparse.select`` scopes (the trace names an operation, the
  compiled program's metadata its scope).

``prove(ctx, what)``: ``program`` (the program's own reading),
``control`` (the reference in fp8 in its place), and the planted faults
``dense`` (the sparse layers attend every key; the builder's own
argument), ``state_bf16`` (the lightning state rounded to bfloat16 at
the end of every scan and step) and ``no_decay`` (the lightning decay
zero: every position kept for ever), the last two patched into the
program's code while its engine is built and run (:func:`planted`).
"""

from __future__ import annotations

import bisect
import contextlib
import gc
import importlib
import re
import time

import numpy as np

from benchmarks import traffic as T
from benchmarks import weights_sala as WS
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
from benchmarks.jobs.serve_lm import _warm_burst

def _state_in_bf16(fn):
    """``fn`` (SSD's scan or step) with the state it returns rounded to
    bfloat16's precision.  ``reduce_precision``, not a cast there and
    back: the cast left a TPU program's logits unchanged to the last
    digit (its compiler may keep such a value in float32)."""
    import jax

    def rounded(*args, **kw):
        y, state = fn(*args, **kw)
        return y, jax.lax.reduce_precision(state, exponent_bits=8, mantissa_bits=7)

    return rounded


def _no_decay(fn):
    return lambda *args: 0.0 * fn(*args)


# faults that the builder's arguments plant, and faults patched into the
# program's code: (module, name, wrapper of what is there)
BUILDER_FAULTS = {"dense": {"dense_len": 1 << 30}}
PATCHED_FAULTS = {
    "state_bf16": [("flexflow_tpu.ops.ssm", "ssd_chunked", _state_in_bf16),
                   ("flexflow_tpu.ops.ssm", "ssd_step", _state_in_bf16)],
    "no_decay": [("flexflow_tpu.ops.linear_attention", "lightning_decay", _no_decay)],
}
FAULTS = (*BUILDER_FAULTS, *PATCHED_FAULTS)


@contextlib.contextmanager
def planted(fault):
    """The builder's arguments of ``fault`` (``{}`` for none or a
    patched fault), with its patches in place until the block ends.
    Build, warm and run the engine inside the block: its programs trace
    the ops' code at their first call."""
    saved = []
    try:
        for mod, name, wrap in PATCHED_FAULTS.get(fault, ()):
            m = importlib.import_module(mod)
            saved.append((m, name, getattr(m, name)))
            setattr(m, name, wrap(getattr(m, name)))
        yield BUILDER_FAULTS.get(fault, {})
    finally:
        for m, name, fn in reversed(saved):
            setattr(m, name, fn)


def _modules(config):
    """The family's reference, and this job's work module."""
    from benchmarks import work_sala

    return importlib.import_module(f"benchmarks.reference.{config['family']}"), work_sala


SCOPES = {"lightning_state_share": "ff.lightning", "sparse_select_share": "ff.sparse.select"}


def build_engine(config: dict, cell: dict, seed: int, shapes: dict, builder_args=None):
    """Builder -> compile on the one-device mesh -> seed weights, leaf by
    leaf -> engine keeping each served token's logit
    (``serve_yoco_lm.build_engine`` with this family's weights and the
    builder's arguments of a planted fault)."""
    from flexflow_tpu import FFConfig, FFModel, MachineMesh
    from flexflow_tpu.serve import ServeEngine

    mod, _, fn = config["builder"].partition(":")
    builder = getattr(importlib.import_module(mod), fn)
    e = cell["engine"]
    ff = FFConfig(
        batch_size=e["slots"], compute_dtype=config["compute_dtype"],
        param_dtype=config.get("param_dtype", "float32"),
    )
    model = FFModel(ff)
    builder(model, e["slots"], e["max_seq"], **dict(config["builder_args"], **(builder_args or {})))
    model.compile(seed=seed & 0x7FFFFFFF, mesh=MachineMesh((1, 1), ("data", "model")))
    WS.fill_executor(shapes, seed, model.executor)
    engine = ServeEngine(
        model, slots=e["slots"], block_size=e["block_size"],
        prefill_chunk=e["prefill_chunk"], sync_every=e["sync_every"],
        attn=e["attn"], kv_dtype=e["kv_dtype"], token_logits=True,
    )
    return model, engine


def sample_rows(reqs) -> list:
    """``finished_rows`` with each served token's logit as the engine
    kept it."""
    logits = {r.id: list(r.token_logits) for r in reqs}
    return [dict(row, logits=logits[row["id"]]) for row in finished_rows(reqs)]


def window_log(engine) -> list:
    """Record, for each window the engine runs from now on, its end (s
    on the host clock), prefill chunks, decode steps and decoding lanes
    at its start; returns the list it fills."""
    log, real = [], engine._window

    def logged():
        c0 = (engine.prefill_chunks, engine.decode_steps, len(engine.sched.decode_slots()))
        real()
        log.append((time.perf_counter(), engine.prefill_chunks - c0[0],
                    engine.decode_steps - c0[1], c0[2]))

    engine._window = logged
    return log


def checks_from(ctx, sample, finished, precision="highest"):
    """The means over the served tokens decide -- of the gap below the
    reference's best, and of the program's logit's distance from the
    reference's -- and the widest gap is a tripwire.  Returns the checks
    and two facts: the selection flips counted, the widest distance."""
    t, config = ctx.cell["mix"], ctx.config
    limits = ctx.cell["correct_limits"]
    ref, _ = _modules(config)
    tokens, rows, served, valid = pack_sample(sample, t["prompt_len"][1], t["max_new"][1])
    logits = np.zeros(served.shape, np.float32)
    for i, r in enumerate(sample):
        logits[i, : len(r["logits"])] = r["logits"]
    params = WS.ByLayer(ref.param_shapes(config["model"]), ctx.seed)
    gaps, errs, flips = ref.served_readings(params, tokens, rows, served, logits, valid,
                                            config["model"], precision, count_flips=True)
    gaps, errs = np.asarray(gaps), np.asarray(errs)
    n_served = int(valid.sum())  # padded entries read 0
    short = sum(1 for r in finished if r["n_tokens"] != r["asked"])
    none = float("inf")
    # (position, K/V head) pairs of the sparse layers the sample ran through
    pairs = sum(len(r["prompt"]) + r["n_tokens"] - 1 for r in sample) * (
        config["model"]["num_key_value_heads"] * sum(
            k == "minicpm4" for k in config["model"]["mixer_types"]))
    checks = [
        ("served_logit_gap_max", float(gaps.max()) if n_served else none,
         limits["served_logit_gap_max"]),
        ("served_logit_gap_mean", float(gaps.sum()) / n_served if n_served else none,
         limits["served_logit_gap_mean"]),
        ("served_logit_err_mean", float(errs.sum()) / n_served if n_served else none,
         limits["served_logit_err_mean"]),
        ("finished_with_wrong_token_count", float(short),
         limits["finished_with_wrong_token_count"]),
    ]
    facts = {"selection_flip_share_bf16": 100.0 * flips / pairs if pairs else None,
             "served_logit_err_max": float(errs.max()) if n_served else None}
    return checks, facts


def _op_scopes(text: str) -> dict:
    """``{instruction name: op_name}`` of a compiled program's text."""
    rx = re.compile(r'^\s*(?:ROOT )?%([\w.\-]+) = .*?metadata=\{[^}]*op_name="([^"]*)"')
    return {m.group(1): m.group(2) for m in map(rx.match, text.splitlines()) if m}


def scope_shares(ctx, engine) -> dict:
    """The share (%) of the traced slice's busy device time in the
    operations whose scope (the compiled program's metadata) holds each
    of ``SCOPES``, and the share of busy time whose operation was found
    in a program's text at all (``scope_map_coverage``).  Empty where
    nothing was traced or nothing could be matched."""
    if not ctx.trace:
        return {}
    from benchmarks import trace_reduce as TR

    events = TR.load(TR.find_xplane(ctx.trace_dir))
    texts = engine._program_texts(engine._params_arg)
    scopes = {f"jit_{n}": _op_scopes(t) for n, t in zip(("decode", "prefill"), texts)}
    busy = TR.busy_seconds(events)
    planes = TR.device_planes(events)
    if busy <= 0 or not planes:
        return {}
    found, got = 0.0, dict.fromkeys(SCOPES, 0.0)
    for p in planes:
        mods = sorted(events[p].get(TR.MODULES_LINE, []), key=lambda e: e[1])
        starts = [e[1] for e in mods]
        for name, start, dur in TR.leaves(events[p][TR.OPS_LINE]):
            i = bisect.bisect_right(starts, start) - 1
            if i < 0 or start > mods[i][1] + mods[i][2]:
                continue
            scope = scopes.get(mods[i][0].split("(")[0], {}).get(TR.op_name(name))
            if scope is None:
                continue
            found += dur
            for fact, s in SCOPES.items():
                if s in scope.split("/"):
                    got[fact] += dur
    n = len(planes)
    cover = found / n / busy
    out = {"scope_map_coverage": 100.0 * cover}
    if cover >= 0.5:
        out.update({k: 100.0 * v / n / busy for k, v in got.items()})
    return out


def request_totals(reqs, prefill_chunk: int, m: dict, work) -> dict:
    tot = {}
    for r in reqs:
        pp = r.prompt_len if r.tokens else int(r.prefill_pos)
        w = work.served_request_work(m, prompt_len=r.prompt_len, prefill_pos=pp,
                                     new_tokens=len(r.tokens), prefill_chunk=prefill_chunk)
        for k, v in w.items():
            tot[k] = tot.get(k, 0) + v
    return tot


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
    windows = window_log(engine)
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
    tot = request_totals(reqs, e["prefill_chunk"], m, work)
    # a kernel call a sparse layer and a K/V head a decode step, and a
    # prefill group (the dispatch's loop runs its layers once a group)
    groups = report.prefill_lanes_computed // prefill_group_lanes(e["slots"], e["prefill_chunk"])
    calls = max(1, report.decode_steps + report.prefill_dispatches)
    kernel_calls = max(1, (report.decode_steps + groups) * work.kernel_layers(m))
    kv_item = 2 if config["compute_dtype"] == "bfloat16" else 4
    lat = [r.latency_ms() for r in finished]
    started_ids = {r.id for r in started}
    due = started + [r for r in rejected if r.id not in started_ids]
    metrics = {"serve_tokens_per_s": new_tokens / window_s}
    attempted, failed = len(due), len(rejected)
    fin_rows = sample_rows(finished)
    dec = (report.sparse_decode_keys_attended, report.sparse_decode_keys_visible)
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
        "positions": tot.get("positions", 0),
        "sparse_kernel_calls": kernel_calls,
        "sparse_bytes_per_call": work.sparse_attention_bytes(m, tot, kv_item) / kernel_calls
        if tot else 0,
        "sparse_flops_per_call": work.sparse_attention_flops(m, tot) / kernel_calls
        if tot else 0,
        "sparse_keys_attended": report.sparse_keys_attended,
        "sparse_keys_visible": report.sparse_keys_visible,
        "sparse_decode_keys_attended": dec[0],
        "sparse_decode_keys_visible": dec[1],
        "sparse_decode_keys_share": 100.0 * dec[0] / dec[1] if dec[1] else None,
        "kv_pages_held_full": report.kv_pages_held_full,
        "state_pool_bytes": report.state_pool_bytes,
        "index_pool_bytes": report.index_pool_bytes,
        "state_slots_held": report.state_slots_held,
        "state_spills": report.state_spills,
        "state_restores": report.state_restores,
        "lightning_state_bytes_per_call": work.lightning_state_bytes(m, tot) / calls
        if tot else 0,
        "param_dtypes": sorted({str(x.dtype) for x in jax.tree.leaves(model.executor.params)}),
        "pool_bytes": engine.kv.hbm_bytes(),
        "samples": {
            "ttft_ms": [d["ttft_ms"] for d in lat],
            "tpot_ms": [d["tpot_ms"] for d in lat],
        },
        "tpot_p50_ms": pct([d["tpot_ms"] for d in lat], 50),
        # (end s after the window's start, prefill chunks, decode steps,
        # decoding lanes) a window
        "window_log": [[tm - t_start, c, k, n] for tm, c, k, n in windows],
        "queue_depth_mid_and_end": queue_depths(reqs, window_s),
        "memory_stats_after_window": memory_stats,
        "memory_peak_bytes_setup": setup_peak,
        "setup_parts_s": {n: tm - marks[i][1] for i, (n, tm) in enumerate(marks[1:])},
        "serve_compile_s": marks[1][1] - marks[0][1],
        **prefill_rows(reqs, report, e),
        **program_relayouts(ctx, engine),
        **scope_shares(ctx, engine),
    }
    sample = pick_sample(fin_rows, ctx.seed)

    # ---- free the program, then the reference ---------------------------
    del model, engine, reqs, started, finished, rejected, due, report, lat
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    checks, ref_facts = checks_from(ctx, sample, fin_rows)
    facts["reference_s"] = time.perf_counter() - t_ref
    facts.update(ref_facts)
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
            with planted(fault) as builder_args:
                model, engine = build_engine(config, cell, ctx.seed, shapes, builder_args)
                engine.run(to_requests(_warm_burst(ctx.seed, m["vocab_size"]), e["max_seq"]))
                spec = T.spec_from_cell(t, seed=ctx.seed, seconds=ctx.seconds,
                                        vocab=m["vocab_size"])
                reqs = to_requests(T.generate(spec), e["max_seq"])
                drive(engine, reqs, seconds=ctx.seconds, backlog=t.get("rate_rps", 0) <= 0)
            fin_rows = sample_rows(reqs)
            del model, engine, reqs
            gc.collect()
            jax.clear_caches()
            served[fault] = (pick_sample(fin_rows, ctx.seed), fin_rows)
        return served[fault]

    out = []
    for w in what.split("+"):
        sample, fin_rows = serve(w if w in FAULTS else None)
        precision = {"program": "highest", "control": "fp8"}.get(w, "highest" if w in FAULTS else w)
        checks, ref_facts = checks_from(ctx, sample, fin_rows, precision)
        out += [(f"{w}:{n}", v, lim) for n, v, lim in checks]
        out += [(f"{w}:{n}", v, None) for n, v in ref_facts.items()]
    return out
