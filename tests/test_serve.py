"""Serving subsystem tests (ISSUE 6, docs/SERVING.md).

Covers the paged KV-cache allocator invariants, continuous-batching
scheduler semantics (FIFO admission, mid-flight slot recycling,
graceful rejection), the batched-prefill bit-parity pin (fp32 AND
bf16), HBM sharing past the monolithic cache footprint, the
zero-per-step-sync serve loop, the ServeObjective / ``unity_search
--objective serve`` golden on the 2-slice machine model, the traffic
generator's determinism, and the serve_report / bench_compare tooling.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)))
)

from flexflow_tpu import FFConfig, FFModel, MachineMesh  # noqa: E402
from flexflow_tpu.models.gpt_decode import (  # noqa: E402
    GPTDecodeSession,
    gpt_generate_cached,
)
from flexflow_tpu.models.transformer import gpt_decoder  # noqa: E402
from flexflow_tpu.serve import (  # noqa: E402
    ContinuousBatchingScheduler,
    KVCacheOOM,
    PagedKVCache,
    Request,
    RequestState,
    ServeEngine,
    TrafficSpec,
    synthetic_requests,
)

SLOTS, SEQ, VOCAB = 4, 48, 31
SHAPE = dict(hidden=32, heads=4, ff_dim=64, num_layers=2, vocab=VOCAB)


def _build_model(compute_dtype="float32", batch=SLOTS, seq=SEQ):
    cfg = FFConfig(batch_size=batch, compute_dtype=compute_dtype)
    m = FFModel(cfg)
    gpt_decoder(m, batch, seq, use_flash=False, **SHAPE)
    m.compile(seed=0)
    return m


@pytest.fixture(scope="module")
def model():
    return _build_model()


@pytest.fixture(scope="module")
def engine(model):
    """One shared engine for the read-only-ish loop tests; each test
    runs its own workload (the engine is reusable across runs)."""
    return ServeEngine(model, slots=SLOTS, block_size=8, sync_every=4)


def _solo(model, req):
    """Greedy solo decode of one request on the dense session — the
    reference stream for bit-identity checks."""
    prompt = np.tile(req.prompt[None], (SLOTS, 1))
    out, _ = gpt_generate_cached(model, prompt, req.max_new_tokens)
    return out[0, req.prompt_len:]


# --------------------------------------------------------------- allocator
def test_kvcache_freelist_never_double_allocates():
    kv = PagedKVCache(2, 4, 8, slots=4, block_size=8, max_seq_len=64)
    a = kv.reserve(0, 20)  # 3 blocks
    b = kv.reserve(1, 8)  # 1 block
    assert len(a) == 3 and len(b) == 1
    assert 0 not in a + b, "trash block allocated"
    assert len(set(a + b)) == 4, "block handed out twice"
    kv.check_invariants()
    kv.release(0)
    c = kv.reserve(2, 24)
    assert len(set(b + c)) == len(b) + len(c)
    kv.check_invariants()
    # double-release must be caught, not corrupt the free list
    kv.release(2)
    with pytest.raises(AssertionError):
        kv.release(2)


def test_kvcache_oom_is_explicit_not_corrupting():
    kv = PagedKVCache(2, 4, 8, slots=4, block_size=8, num_blocks=4,
                      max_seq_len=64)
    kv.reserve(0, 24)  # 3 of 3 usable blocks
    assert not kv.can_reserve(8)
    with pytest.raises(KVCacheOOM):
        kv.reserve(1, 8)
    kv.check_invariants()  # failed reserve took nothing
    kv.release(0)
    assert kv.can_reserve(24)


def test_scheduler_graceful_rejection_when_pool_too_small():
    kv = PagedKVCache(2, 4, 8, slots=2, block_size=8, num_blocks=4,
                      max_seq_len=64)
    sched = ContinuousBatchingScheduler(2, kv)
    # 40 positions need 5 blocks; the pool owns 3 — rejected at submit,
    # with a reason, and nothing raises
    r = sched.submit(Request(prompt=np.arange(4), max_new_tokens=36))
    assert r.state is RequestState.REJECTED
    assert "pool holds 3" in r.finish_reason
    # a request that fits goes through normally
    r2 = sched.submit(Request(prompt=np.arange(4), max_new_tokens=12))
    assert r2.state is RequestState.QUEUED
    assert sched.admit() == [r2]


def test_scheduler_fifo_admission_under_full_batch():
    kv = PagedKVCache(2, 4, 8, slots=2, block_size=8, max_seq_len=64)
    sched = ContinuousBatchingScheduler(2, kv)
    reqs = [
        sched.submit(Request(prompt=np.arange(3), max_new_tokens=5, id=i))
        for i in range(5)
    ]
    first = sched.admit()
    assert [r.id for r in first] == [0, 1], "admission must be FIFO"
    assert sched.admit() == []  # batch full: nobody jumps the queue
    sched.finish(reqs[1], now=1.0, reason="length")
    nxt = sched.admit()
    assert [r.id for r in nxt] == [2], "freed slot goes to the queue head"
    assert reqs[2].slot == 1, "recycled slot is reused"


# --------------------------------------------------- batched prefill parity
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_bit_identical_to_token_loop(dtype):
    """Satellite pin: the one-call prefill produces the cache contents
    AND next-token probs of the per-token warmup loop — to the bit under
    compute_dtype=bf16, where every matmul's output is rounded to
    bfloat16; under fp32 to a float32 tolerance plus the same greedy
    token (ROADMAP: bit-identity is no longer the universal contract —
    the one-call prefill multiplies (B * P, hidden) rows where the loop
    multiplies (B, hidden), and this XLA:CPU sums a float32 dot in an
    order that follows the shape: the two differ in the last ulp, 1.3e-7
    on a probability)."""
    model = _build_model(dtype) if dtype != "float32" else _build_model()
    sess = GPTDecodeSession(model)
    rng = np.random.default_rng(7)

    def same(a, b):
        a, b = np.asarray(a), np.asarray(b)
        if dtype == "float32":
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)
        else:
            np.testing.assert_array_equal(a, b)

    for plen in (1, 6, 13):
        prompt = rng.integers(0, VOCAB, size=(SLOTS, plen)).astype(np.int32)
        sess.reset()
        for t in range(plen):
            probs_loop = sess.step(prompt[:, t], t)
        ck = np.asarray(sess.cache_k)
        cv = np.asarray(sess.cache_v)
        sess.reset()
        probs_pre = sess.prefill(prompt, 0)
        same(probs_loop, probs_pre)
        np.testing.assert_array_equal(
            np.argmax(np.asarray(probs_loop), -1),
            np.argmax(np.asarray(probs_pre), -1),
        )
        same(ck, sess.cache_k)
        same(cv, sess.cache_v)


def test_generate_cached_same_tokens_either_prefill(model):
    prompt = np.random.default_rng(1).integers(
        0, VOCAB, size=(SLOTS, 5)
    ).astype(np.int32)
    a, sess = gpt_generate_cached(model, prompt, max_new_tokens=8)
    b, _ = gpt_generate_cached(
        model, prompt, max_new_tokens=8, session=sess, batched_prefill=False
    )
    np.testing.assert_array_equal(a, b)
    assert sess._trace_count == 0, "prefill must not retrace the step"


def test_paged_chunked_prefill_matches_dense_cache(model):
    """The serving layer's CHUNKED paged prefill fills the same K/V
    values the dense session's prefill does (compared through the
    block-table gather), and chunk boundaries don't change them."""
    eng = ServeEngine(model, slots=SLOTS, block_size=8, prefill_chunk=4,
                      sync_every=2)
    rng = np.random.default_rng(3)
    plen = 11  # crosses two chunk boundaries and one block boundary
    prompt = rng.integers(0, VOCAB, size=(plen,)).astype(np.int32)
    r = eng.submit(prompt, 2)
    rep = eng.run()
    assert rep.requests_finished == 1
    # dense reference
    sess = GPTDecodeSession(model)
    sess.reset()
    sess.prefill(np.tile(prompt[None], (SLOTS, 1)), 0)
    ck = np.asarray(sess.cache_k, np.float32)  # (L, B, H, S, D)
    # the engine released the slot at finish; re-reserve to read it back
    # is not possible — instead compare through the solo token stream
    solo = _solo(model, r)
    np.testing.assert_array_equal(np.asarray(r.tokens, np.int32), solo)
    # direct cache comparison on a NON-finishing request
    eng2 = ServeEngine(model, slots=SLOTS, block_size=8, prefill_chunk=4,
                       sync_every=1)
    r2 = eng2.submit(prompt, 30)
    # run windows until prefill is done + one token, then stop by hand
    eng2.sched.admit()
    eng2._t0 = eng2._now()
    for _ in range(4):
        eng2._window()
    slot = r2.slot
    assert r2.state in (RequestState.DECODE, RequestState.PREFILL)
    k_paged, v_paged = eng2.kv.gather_dense(slot, plen)
    # paged vs dense cross-formulation agrees to the ulp (the contraction
    # widths differ: paged pages vs monolithic rows); TOKEN streams are
    # the bit-exact pin (asserted above and in the recycling test)
    np.testing.assert_allclose(
        np.asarray(k_paged, np.float32), ck[:, 0, :, :plen],
        rtol=0, atol=3e-6,
    )


# ----------------------------------------------- continuous batching / loop
def test_slot_recycling_preserves_outputs_bit_identical(model, engine):
    """Mixed-length workload: early finishers free slots mid-flight,
    queued requests take them, and EVERY request's token stream equals
    its solo decode exactly."""
    spec = TrafficSpec(n_requests=10, seed=2, rate_rps=0.0,
                       prompt_len=(2, 7), max_new=(2, 14), vocab=VOCAB)
    reqs = synthetic_requests(spec)
    rep = engine.run(reqs)
    assert rep.requests_finished == 10 and rep.requests_rejected == 0
    assert rep.occupancy_mean > 0
    for r in engine.sched.finished:
        np.testing.assert_array_equal(
            np.asarray(r.tokens, np.int32), _solo(model, r)
        )
    engine.kv.check_invariants()
    assert engine.kv.free_blocks == engine.kv.allocatable_blocks


def test_hbm_sharing_past_monolithic_footprint(model):
    """Acceptance pin: the paged allocator admits a workload whose
    summed max-lengths exceed the monolithic (L, B, H, S, D) cache
    footprint, on a pool SMALLER than that footprint."""
    # pool: 8 usable blocks x 8 positions = 64 cache positions
    eng = ServeEngine(model, slots=SLOTS, block_size=8, num_blocks=9,
                      sync_every=4)
    monolithic_positions = SLOTS * SEQ  # 192
    pool_positions = (eng.kv.num_blocks - 1) * eng.kv.block_size
    assert pool_positions < monolithic_positions
    reqs = []
    for i in range(16):  # 16 x 16 = 256 summed positions > monolithic
        reqs.append(Request(
            prompt=np.arange(1 + (i % 4), dtype=np.int32) + i,
            max_new_tokens=16 - (1 + i % 4), id=i,
        ))
    summed = sum(r.max_len for r in reqs)
    assert summed > monolithic_positions > pool_positions
    rep = eng.run(reqs)
    assert rep.requests_finished == 16 and rep.requests_rejected == 0
    eng.kv.check_invariants()
    # and the outputs still match solo decode through the shared pool
    for r in list(eng.sched.finished)[:4]:
        np.testing.assert_array_equal(
            np.asarray(r.tokens, np.int32), _solo(model, r)
        )


def test_zero_per_step_sync_serve_loop(model, engine):
    """The loop syncs once per flush window (the host_syncs ledger is
    the proof, as in async fit) — NOT once per decode step."""
    ex = model.executor
    h0 = ex.host_syncs
    spec = TrafficSpec(n_requests=6, seed=4, rate_rps=0.0,
                       prompt_len=(2, 5), max_new=(8, 12), vocab=VOCAB)
    rep = engine.run(synthetic_requests(spec))
    assert rep.requests_finished == 6
    syncs = ex.host_syncs - h0
    assert syncs == rep.windows, (syncs, rep.windows)
    assert rep.decode_steps > rep.windows, (
        "windows must batch multiple decode steps per sync"
    )


def test_eos_finishes_early_and_discards_overshoot(model):
    eng = ServeEngine(model, slots=2, block_size=8, sync_every=4)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, VOCAB, size=(4,)).astype(np.int32)
    solo_probe, _ = gpt_generate_cached(
        model, np.tile(prompt[None], (SLOTS, 1)), 20
    )
    stream = solo_probe[0, 4:]
    eos = int(stream[2])  # a token the greedy stream hits (maybe earlier)
    first = int(np.argmax(stream == eos))  # first occurrence stops the run
    r = eng.submit(prompt, 20, eos_id=eos)
    rep = eng.run()
    assert rep.requests_finished == 1
    assert r.finish_reason == "eos"
    assert r.tokens == stream[: first + 1].tolist(), (
        "stream must stop AT the first eos token, overshoot discarded"
    )
    assert len(r.tokens) < 20, "eos must beat the length budget"


def test_serve_metrics_stream_and_report(model, tmp_path, capsys):
    out = tmp_path / "serve.jsonl"
    eng = ServeEngine(model, slots=SLOTS, block_size=8, sync_every=2,
                      metrics_out=str(out))
    spec = TrafficSpec(n_requests=5, seed=5, rate_rps=0.0,
                       prompt_len=(2, 6), max_new=(3, 9), vocab=VOCAB)
    rep = eng.run(synthetic_requests(spec))
    assert rep.requests_finished == 5
    from flexflow_tpu.obs import METRICS_SCHEMA, read_metrics

    recs = read_metrics(str(out))
    assert len(recs) == rep.windows
    assert all(r["schema"] == METRICS_SCHEMA for r in recs)
    serve = [r["metrics"]["serve"] for r in recs]
    assert all("queue_depth" in s and "occupancy" in s for s in serve)
    fin = [f for s in serve for f in s["finished"]]
    assert len(fin) == 5
    assert all(f["ttft_ms"] is not None for f in fin)

    # serve_report renders it (trace_report-style CLI)
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools",
    ))
    import serve_report

    assert serve_report.main([str(out)]) == 0
    text = capsys.readouterr().out
    assert "latency percentiles" in text
    assert "ttft_ms" in text and "per-window" in text


def test_open_loop_arrivals_and_traffic_determinism():
    spec = TrafficSpec(n_requests=8, seed=9, rate_rps=100.0,
                       prompt_len=(2, 6), max_new=(2, 8), vocab=VOCAB)
    a = synthetic_requests(spec)
    b = synthetic_requests(spec)
    assert [r.arrival_s for r in a] == [r.arrival_s for r in b]
    assert all(
        np.array_equal(x.prompt, y.prompt) and x.max_new_tokens == y.max_new_tokens
        for x, y in zip(a, b)
    )
    assert all(
        a[i].arrival_s <= a[i + 1].arrival_s for i in range(len(a) - 1)
    ), "open-loop arrivals are cumulative"
    assert spec.identity == "seed9/n8/p2-6/g2-8/r100/v31"


# ----------------------------------------------------- serving objective
def _machine_2slice():
    from flexflow_tpu.search.cost import TPUMachineModel

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "examples", "machine_configs", "v5p_2slice.json",
    )
    return TPUMachineModel.from_file(path)


def test_serve_objective_prices_tp_over_replication(model):
    """Analytic golden: decode is weight-streaming-bound, so a TP
    sharding (weights split over the model axis) must price a FASTER
    step than full replication on the same mesh — the core fact the
    serving search exploits."""
    from flexflow_tpu.parallel.strategy import (
        data_parallel_strategy,
        tensor_parallel_strategy,
    )
    from flexflow_tpu.serve.objective import ServeObjective, ServeSpec

    mesh = MachineMesh((2, 4), ("data", "model"))
    machine = _machine_2slice()
    obj = ServeObjective(
        machine, ServeSpec(slots=8, kv_len=32, slo_p99_ms=50.0),
        train_tokens=SLOTS * SEQ,
    )
    tp = obj.price(model.layers, tensor_parallel_strategy(model.layers, mesh))
    dp = obj.price(model.layers, data_parallel_strategy(model.layers, mesh))
    assert tp["tok_s"] > dp["tok_s"], (tp, dp)
    assert tp["cost"] < dp["cost"]
    for p in (tp, dp):
        assert p["p99_ms"] > 0 and np.isfinite(p["p99_ms"])
        assert set(p["breakdown"]) == {"mem_s", "flops_s", "coll_s"}


def test_unity_search_objective_serve_2slice_golden(model):
    """Acceptance pin: ``unity_search --objective serve`` returns a
    placement priced by the ServeObjective on the 2-slice machine model
    — analytic tier, no TPU."""
    from flexflow_tpu.search import unity_search
    from flexflow_tpu.serve.objective import ServeSpec

    machine = _machine_2slice()
    mesh = MachineMesh((2, 8), ("data", "model"))
    st = unity_search(
        model.layers, mesh, graph_inputs=model.graph_inputs, budget=5,
        machine=machine, objective="serve",
        serve=ServeSpec(slots=8, kv_len=32, slo_p99_ms=50.0),
    )
    assert st is not None and st.ops
    p = st.serve_price
    assert p is not None and p["objective"] == "serve"
    assert p["tok_s"] > 0 and np.isfinite(p["p99_ms"])
    assert p["feasible"] in (True, False)
    # the serving winner shards the model axis (weight streaming is the
    # binding constraint at decode, and TP splits it) — a pure
    # data-parallel winner would mean the objective didn't engage
    assert any(s > 1 for n, s in zip(st.mesh.axis_names, st.mesh.shape)
               if n == "model"), st.mesh.shape
    # train-objective search on the same inputs does NOT carry a price
    st_train = unity_search(
        model.layers, mesh, graph_inputs=model.graph_inputs, budget=5,
        machine=machine,
    )
    assert st_train.serve_price is None


def test_serve_driver_cli(tmp_path, capsys):
    from flexflow_tpu.serve.driver import main as serve_main

    from flexflow_tpu.obs import HealthMonitor, set_monitor

    out = tmp_path / "drv.jsonl"
    try:
        rc = serve_main([
            "--requests", "3", "--serve-slots", "2", "--seq", "32",
            "--prompt-len", "2:4", "--gen-len", "2:4",
            "--metrics-out", str(out),
        ])
    finally:
        # --metrics-out installs the process monitor; left on it would put
        # every later fit of this worker on the instrumented path
        set_monitor(HealthMonitor())
    assert rc == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    doc = json.loads(line)
    assert doc["metric"] == "serve_demo"
    assert doc["requests_finished"] == 3
    assert doc["serve_traffic"].startswith("seed0/n3/")
    assert out.exists()


# ------------------------------------------------------- bench_compare gate
def test_bench_compare_gates_serve_metrics(tmp_path):
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools",
    ))
    import bench_compare

    base = {
        "metric": "bert_base_train_throughput", "value": 100.0,
        "backend": "cpu", "serve_tok_s": 1000.0, "serve_p99_ms": 10.0,
        "serve_traffic": "seed0/n12/p3-8/g3-24/r0/v256",
    }
    cur = dict(base)
    bp = tmp_path / "base.json"
    bp.write_text(json.dumps(base))

    # within threshold -> PASS
    cp = tmp_path / "cur.json"
    cp.write_text(json.dumps(cur))
    assert bench_compare.main([str(cp), "--baseline", str(bp)]) == 0

    # p99 regression (lower-is-better metric RISES) -> FAIL
    cur_bad = dict(base, serve_p99_ms=20.0)
    cp.write_text(json.dumps(cur_bad))
    assert bench_compare.main([str(cp), "--baseline", str(bp)]) == 1

    # tok/s regression -> FAIL
    cur_bad = dict(base, serve_tok_s=500.0)
    cp.write_text(json.dumps(cur_bad))
    assert bench_compare.main([str(cp), "--baseline", str(bp)]) == 1

    # differing traffic identity is a NOTE, never a refusal
    cur_note = dict(base, serve_traffic="seed1/n12/p3-8/g3-24/r0/v256")
    cp.write_text(json.dumps(cur_note))
    assert bench_compare.main([str(cp), "--baseline", str(bp)]) == 0
