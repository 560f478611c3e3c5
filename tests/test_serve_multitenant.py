"""Multi-tenant serving scale-out tests (ISSUE 11, docs/SERVING.md).

Covers the prefix-sharing allocator (refcounts, hash-keyed index,
retained LRU, copy-on-write, trash-block isolation, sharing-aware
admission math), spill/restore bit-exactness, the SLO-tiered scheduler
(truthful rejection reasons, interactive-over-batch preemption), the
engine-level pins (sharing on/off bit-identity, preemption round-trip
with the zero-sync ledger intact, speculative decoding bit-identity at
whatever accept rate the draft slice achieves), the ``serve_cow``
ffcheck invariant, the additive ffmetrics vocabulary + serve_report
back-compat, and the multi-tenant traffic generator's determinism and
identity-string back-compat.
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)))
)

from flexflow_tpu import FFConfig, FFModel  # noqa: E402
from flexflow_tpu.models.gpt_decode import gpt_generate_cached  # noqa: E402
from flexflow_tpu.models.transformer import gpt_decoder  # noqa: E402
from flexflow_tpu.serve import (  # noqa: E402
    ContinuousBatchingScheduler,
    PagedKVCache,
    Request,
    RequestState,
    ServeEngine,
    TrafficSpec,
    synthetic_requests,
)

SLOTS, SEQ, VOCAB = 4, 48, 31
SHAPE = dict(hidden=32, heads=4, ff_dim=64, num_layers=2, vocab=VOCAB)


@pytest.fixture(scope="module")
def model():
    cfg = FFConfig(batch_size=SLOTS, compute_dtype="float32")
    m = FFModel(cfg)
    gpt_decoder(m, SLOTS, SEQ, use_flash=False, **SHAPE)
    m.compile(seed=0)
    return m


def _solo(model, req):
    """Greedy solo decode on the dense session — the reference stream
    every paged/shared/preempted/speculative variant must match."""
    prompt = np.tile(np.asarray(req.prompt)[None], (SLOTS, 1))
    out, _ = gpt_generate_cached(model, prompt, req.max_new_tokens)
    return out[0, req.prompt_len:]


def _shared_traffic(n=6, seed=3):
    """One tenant, a 16-token system prompt on every request — the
    maximal-sharing shape (2 full 8-token blocks shareable)."""
    return synthetic_requests(TrafficSpec(
        n_requests=n, seed=seed, rate_rps=0.0, prompt_len=(2, 6),
        max_new=(2, 8), vocab=VOCAB, tenants=1, shared_prefix=16,
    ))


# ------------------------------------------------------------- allocator
def test_prefix_share_refcounts_and_admission_discount():
    kv = PagedKVCache(2, 2, 4, slots=2, block_size=8, num_blocks=6,
                      max_seq_len=64)
    p = np.arange(17, dtype=np.int32)  # 2 shareable blocks + 1 token
    kv.reserve(0, 24, prompt=p)  # 3 blocks, nothing indexed yet
    assert kv.commit_prefix(0, p, 17) == 2
    assert kv.free_blocks == 2
    # a raw 4-block budget cannot fit, but the same budget WITH the
    # shared prompt charges only its 2 private blocks
    q = np.concatenate([p[:16], np.asarray([7, 9], np.int32)])
    assert kv.blocks_needed(30, q) == (4, 2)
    assert not kv.can_reserve(30)
    assert kv.can_reserve(30, q)
    kv.reserve(1, 30, prompt=q)
    assert kv.owned(0)[:2] == kv.owned(1)[:2], "prefix blocks not shared"
    assert all(kv.refcount(b) == 2 for b in kv.owned(1)[:2])
    assert kv.shared_len(1) == 16
    assert kv.prefix_hits == 2
    assert kv.free_blocks == 0
    assert kv.shared_write_hazards() == []
    kv.check_invariants()
    # releases: shared blocks survive one owner, then retire to the LRU
    kv.release(0)
    assert all(kv.refcount(b) == 1 for b in kv.owned(1)[:2])
    kv.release(1)
    assert kv.cached_blocks == 2, "registered blocks must be retained"
    kv.check_invariants()
    # a second wave re-attaches from the retained cache (warm hits)
    kv.reserve(0, 30, prompt=q)
    assert kv.prefix_hits == 4 and kv.shared_len(0) == 16
    kv.release(0)
    kv.check_invariants()


def test_ensure_private_cow_and_deregistration():
    import jax.numpy as jnp

    kv = PagedKVCache(2, 2, 4, slots=2, block_size=8, num_blocks=8,
                      max_seq_len=64)
    p = np.arange(17, dtype=np.int32)
    kv.reserve(0, 24, prompt=p)
    ids = np.asarray(kv.owned(0), np.int32)
    rng = np.random.default_rng(0)
    # three pages of the position-major pool: (L, 3 * BS, H * D)
    k_vals = rng.standard_normal((2, 3 * 8, 2 * 4)).astype(np.float32)
    kv.cache_k = kv.cache_k.at[:, kv._rows(ids)].set(jnp.asarray(k_vals))
    assert kv.cache_k.shape == (2, 8 * 8, 2 * 4)
    kv.commit_prefix(0, p, 17)
    kv.reserve(1, 24, prompt=p)
    shared_blk = kv.owned(1)[1]
    assert kv.refcount(shared_blk) == 2
    # CoW on a genuinely shared block: fresh id, contents bit-equal
    before_k, before_v = np.asarray(kv.cache_k), np.asarray(kv.cache_v)
    new_blk = kv.ensure_private(1, 1)
    assert new_blk != shared_blk
    assert kv.refcount(shared_blk) == 1 and kv.refcount(new_blk) == 1
    assert kv.cow_copies == 1
    assert kv.tables[1, 1] == new_blk
    pages_k = np.asarray(kv.cache_k).reshape(2, 8, 8, 2 * 4)
    np.testing.assert_array_equal(
        pages_k[:, new_blk], pages_k[:, shared_blk]
    )
    assert np.any(pages_k[:, new_blk] != 0)
    # one page copied and nothing else: every other row is as it was
    other = np.ones(8 * 8, bool)
    other[kv._rows([new_blk])] = False
    np.testing.assert_array_equal(
        np.asarray(kv.cache_k)[:, other], before_k[:, other]
    )
    np.testing.assert_array_equal(np.asarray(kv.cache_v), before_v)
    assert kv.shared_write_hazards() == []
    # sole-owner-but-indexed path: de-register in place, no copy
    before = kv.cow_copies
    same = kv.ensure_private(0, 1)
    assert same == shared_blk and kv.cow_copies == before
    assert shared_blk not in kv._block_key
    kv.check_invariants()


def test_trash_block_never_shared():
    kv = PagedKVCache(2, 2, 4, slots=2, block_size=8, num_blocks=6,
                      max_seq_len=64)
    p = np.arange(17, dtype=np.int32)
    kv.reserve(0, 24, prompt=p)
    kv.commit_prefix(0, p, 17)
    assert 0 not in kv.owned(0)
    assert kv.refcount(0) == 0
    assert 0 not in kv._index.values()
    kv.release(0)
    assert 0 not in kv._cached
    kv.check_invariants()


def test_spill_restore_round_trip_bit_exact():
    import jax.numpy as jnp

    kv = PagedKVCache(2, 2, 4, slots=2, block_size=4, max_seq_len=32)
    p = np.arange(9, dtype=np.int32)  # 2 shareable 4-token blocks
    kv.reserve(0, 12, prompt=p)
    ids = np.asarray(kv.owned(0), np.int32)
    rng = np.random.default_rng(1)
    k_vals = rng.standard_normal((2, 3 * 4, 2 * 4)).astype(np.float32)
    v_vals = rng.standard_normal((2, 3 * 4, 2 * 4)).astype(np.float32)
    rows = kv._rows(ids)
    kv.cache_k = kv.cache_k.at[:, rows].set(jnp.asarray(k_vals))
    kv.cache_v = kv.cache_v.at[:, rows].set(jnp.asarray(v_vals))
    kv.commit_prefix(0, p, 9)
    k0, v0 = kv.gather_dense(0, 11)
    # the dense payload is (L, H, length, D) whatever the pool's geometry
    assert k0.shape == v0.shape == (2, 2, 11, 4)
    np.testing.assert_array_equal(
        k0, k_vals[:, :11].reshape(2, 11, 2, 4).transpose(0, 2, 1, 3)
    )
    payload = kv.spill(0, 11)
    assert payload["layers"]["layer0"]["k"].shape == (2, 11, 4)
    kv.check_invariants()
    # restore to a DIFFERENT slot: shared prefix re-attaches from the
    # index, the private span scatters back — bytes identical
    shared = kv.restore(1, payload, 12, prompt=p)
    assert shared == 8
    k1, v1 = kv.gather_dense(1, 11)
    np.testing.assert_array_equal(k0, k1)
    np.testing.assert_array_equal(v0, v1)
    kv.check_invariants()


# ------------------------------------------------------------- scheduler
def test_rejection_reasons_truthful_under_sharing():
    kv = PagedKVCache(2, 2, 4, slots=2, block_size=8, num_blocks=4,
                      max_seq_len=80)  # 3 usable blocks
    sched = ContinuousBatchingScheduler(2, kv)
    # nothing indexed: the reason must say no shared prefix applied
    r = sched.submit(Request(prompt=np.arange(4), max_new_tokens=36))
    assert r.state is RequestState.REJECTED
    assert "never fits (no shared prefix applies)" in r.finish_reason
    # register a 2-block prefix, then overflow WITH sharing in play:
    # the reason must cite the discount it already granted
    p = np.arange(17, dtype=np.int32)
    r2 = sched.submit(Request(prompt=p, max_new_tokens=7))
    assert sched.admit() == [r2]
    kv.commit_prefix(r2.slot, p, 17)
    q = np.concatenate([p[:16], np.arange(8, dtype=np.int32)])
    r3 = sched.submit(Request(prompt=q, max_new_tokens=32))  # 7 blocks
    assert r3.state is RequestState.REJECTED
    assert "2 shared prefix blocks discounted" in r3.finish_reason
    assert "5 private blocks still exceed the pool" in r3.finish_reason
    # a budget that overflows raw but fits net-of-sharing is QUEUED
    q2 = np.concatenate([p[:16], np.asarray([1, 2], np.int32)])
    r4 = sched.submit(Request(prompt=q2, max_new_tokens=22))  # 5 blocks
    assert r4.state is RequestState.QUEUED


def test_scheduler_preempts_batch_for_interactive():
    kv = PagedKVCache(2, 2, 4, slots=1, block_size=8, max_seq_len=32)
    sched = ContinuousBatchingScheduler(1, kv)
    b = sched.submit(Request(prompt=np.arange(4), max_new_tokens=4,
                             tier="batch"))
    assert sched.admit() == [b] and b.state is RequestState.PREFILL
    i = sched.submit(Request(prompt=np.arange(3), max_new_tokens=4,
                             tier="interactive"))
    out = sched.admit()
    assert out == [i] and i.slot == 0
    # mid-prefill victim: no payload to spill, prefill restarts on resume
    assert b.state is RequestState.PREEMPTED
    assert b.kv_spill is None and b.prefill_pos == 0
    assert b.preemptions == 1 and sched.preemptions == 1
    assert sched.queue == [b], "victim re-queues at the tier front"
    kv.check_invariants()
    sched.finish(i, now=1.0, reason="length")
    assert sched.admit() == [b] and b.state is RequestState.PREFILL


# ------------------------------------------------------------- engine
@pytest.fixture(scope="module")
def engine_on(model):
    # 12 usable blocks < 4 slots x 4 blocks: the pool is contended, so
    # sharing actually changes what admits concurrently
    return ServeEngine(model, slots=SLOTS, block_size=8, num_blocks=13,
                       sync_every=2, prefix_sharing=True)


def test_prefix_sharing_outputs_bit_identical(model, engine_on):
    reqs_on = _shared_traffic()
    rep_on = engine_on.run(reqs_on)
    eng_off = ServeEngine(model, slots=SLOTS, block_size=8, num_blocks=13,
                          sync_every=2, prefix_sharing=False)
    reqs_off = _shared_traffic()
    rep_off = eng_off.run(reqs_off)
    assert rep_on.requests_finished == rep_off.requests_finished == 6
    assert rep_on.requests_rejected == rep_off.requests_rejected == 0
    assert rep_on.prefix_hit_rate is not None and rep_on.prefix_hit_rate > 0
    assert rep_off.prefix_hit_rate is None, "sharing off must not look up"
    by_id_on = {r.id: r.tokens for r in reqs_on}
    by_id_off = {r.id: r.tokens for r in reqs_off}
    assert by_id_on == by_id_off, "sharing must not change any stream"
    for r in reqs_on:
        np.testing.assert_array_equal(
            np.asarray(r.tokens, np.int32), _solo(model, r)
        )
    for eng in (engine_on, eng_off):
        eng.kv.check_invariants()
        assert eng.kv.free_blocks + eng.kv.cached_blocks == \
            eng.kv.allocatable_blocks


def test_preemption_spill_restore_bit_identical(model, tmp_path, capsys):
    """Two batch decodes hold both slots; an interactive request lands
    mid-flight, preempts one, and EVERY stream — including the spilled
    and resumed victim's — equals its solo decode bit for bit.  The
    sync ledger (one host sync per window) survives, and the metrics
    stream carries the tenant/tier/preemption vocabulary."""
    out = tmp_path / "mt.jsonl"
    eng = ServeEngine(model, slots=2, block_size=8, sync_every=2,
                      metrics_out=str(out))
    ex = model.executor
    h0 = ex.host_syncs
    rng = np.random.default_rng(5)
    b0 = eng.submit(rng.integers(0, VOCAB, size=(4,)).astype(np.int32), 30,
                    tenant="acme", tier="batch")
    b1 = eng.submit(rng.integers(0, VOCAB, size=(4,)).astype(np.int32), 30,
                    tenant="acme", tier="batch")
    eng.sched.admit()
    eng._t0 = eng._now()
    warm = 6
    for _ in range(warm):
        eng._window()
    assert b0.state is RequestState.DECODE
    assert b1.state is RequestState.DECODE
    it = eng.submit(rng.integers(0, VOCAB, size=(3,)).astype(np.int32), 6,
                    tenant="vip", tier="interactive")
    rep = eng.run()
    assert rep.requests_finished == 3 and rep.requests_rejected == 0
    assert eng.sched.preemptions == 1 and b1.preemptions == 1, (
        "the most recently admitted batch decode is the victim"
    )
    assert it.preemptions == 0 and b0.preemptions == 0
    for r in (b0, b1, it):
        assert r.state is RequestState.FINISHED
        np.testing.assert_array_equal(
            np.asarray(r.tokens, np.int32), _solo(model, r)
        )
    # the window's one deliberate sync absorbs spill/restore too
    assert ex.host_syncs - h0 == warm + rep.windows
    assert rep.per_tier["batch"]["preemptions"] == 1
    assert rep.per_tier["interactive"]["ttft_p99_ms"] is not None
    assert set(rep.per_tenant) == {"acme", "vip"}
    eng.kv.check_invariants()

    # metrics vocabulary (additive ffmetrics/1 fields)
    from flexflow_tpu.obs import read_metrics

    recs = read_metrics(str(out))
    serve = [r["metrics"]["serve"] for r in recs]
    assert serve[-1]["preemptions_total"] == 1
    assert all("prefix_hit_rate" in s and "cached_blocks" in s for s in serve)
    assert all("tenants" in s for s in serve)
    fin = [f for s in serve for f in s["finished"]]
    assert {f["tenant"] for f in fin} == {"acme", "vip"}
    assert {f["tier"] for f in fin} == {"batch", "interactive"}
    assert sum(f["preempted"] for f in fin) == 1

    # serve_report renders the per-tenant table + preemption line
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools",
    ))
    import serve_report

    assert serve_report.main([str(out)]) == 0
    text = capsys.readouterr().out
    assert "per-tenant" in text and "preemptions: 1" in text
    assert "acme" in text and "vip" in text


def test_speculative_bit_identical_at_every_accept_rate(model):
    """Speculative decode must emit exactly the plain greedy stream at
    WHATEVER accept rate the 1-layer draft slice achieves on random
    weights — verify rows compute the full model's argmax, so only
    tokens the full model agrees with are ever emitted."""
    eng = ServeEngine(model, slots=SLOTS, block_size=8, sync_every=4,
                      spec_k=2)
    assert eng.spec_draft_layers == 1  # half-depth default on L=2
    ex = model.executor
    h0 = ex.host_syncs
    reqs = synthetic_requests(TrafficSpec(
        n_requests=6, seed=8, rate_rps=0.0, prompt_len=(2, 6),
        max_new=(4, 12), vocab=VOCAB,
    ))
    rep = eng.run(reqs)
    assert rep.requests_finished == 6
    assert rep.spec_k == 2 and rep.spec_draft_layers == 1
    assert rep.spec_drafted > 0
    assert 0.0 <= rep.spec_accept_rate <= 1.0
    for r in reqs:
        np.testing.assert_array_equal(
            np.asarray(r.tokens, np.int32), _solo(model, r)
        )
    # macro steps chain device-to-device: still one sync per window
    assert ex.host_syncs - h0 == rep.windows
    eng.kv.check_invariants()


# ------------------------------------------------------------- ffcheck
def test_serve_cow_violation_fires(engine_on):
    from flexflow_tpu.analysis import analyze_serve_engine

    kv = engine_on.kv
    p = np.arange(17, dtype=np.int32)
    kv.reserve(0, 24, prompt=p)
    kv.commit_prefix(0, p, 17)
    assert kv.shared_write_hazards() == []
    clean = analyze_serve_engine(engine_on, checks=["serve_cow"])
    assert not [v for v in clean.violations if v.check == "serve_cow"]
    # force the hazard: pretend the slot's writable region reaches its
    # still-indexed prefix blocks (a CoW-discipline breach)
    kv._protected[0] = 0
    try:
        rep = analyze_serve_engine(engine_on, checks=["serve_cow"])
        hits = [v for v in rep.violations if v.check == "serve_cow"]
        assert hits and not rep.ok
        assert hits[0].severity == "error"
        assert "copy-on-write" in hits[0].message
        assert hits[0].program == "serve.kvcache"
    finally:
        kv._protected[0] = 2
        kv.release(0)
    kv.check_invariants()


# ----------------------------------------------------- report back-compat
def test_serve_report_backcompat_old_stream():
    """A pre-r11 stream (no tenant/prefix/spec fields) must render
    without the new sections and without crashing."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools",
    ))
    import serve_report

    old = [{
        "step": 0, "step_wall_s": 0.1, "tokens_per_s": 40.0,
        "metrics": {"serve": {
            "queue_depth": 0, "occupancy": 0.5, "decode_steps": 4,
            "prefill_chunks": 1, "active": 1, "rejected_total": 0,
            "finished": [{"id": 0, "tokens": 3, "reason": "length",
                          "ttft_ms": 1.0, "tpot_ms": 0.5}],
        }},
    }]
    text = serve_report.render(old)
    assert "latency percentiles" in text and "per-window" in text
    assert "per-tenant" not in text
    assert "prefix cache" not in text
    assert "speculative decode" not in text


# ------------------------------------------------------------- traffic
def test_multi_tenant_traffic_determinism_and_identity():
    spec = TrafficSpec(
        n_requests=6, seed=11, rate_rps=50.0, prompt_len=(2, 4),
        max_new=(2, 4), vocab=VOCAB, tenants=3, shared_prefix=8,
        interactive_frac=0.4,
    )
    a = synthetic_requests(spec)
    b = synthetic_requests(spec)
    assert all(
        np.array_equal(x.prompt, y.prompt)
        and x.arrival_s == y.arrival_s
        and x.tenant == y.tenant and x.tier == y.tier
        for x, y in zip(a, b)
    )
    # ceil(3 * 0.4) = 2 interactive tenants, round-robin assignment
    tiers = {r.tenant: r.tier for r in a}
    assert tiers == {"tenant0": "interactive", "tenant1": "interactive",
                     "tenant2": "batch"}
    # one tenant's requests share their leading 8 tokens; tenants differ
    t0 = [r.prompt[:8] for r in a if r.tenant == "tenant0"]
    t2 = [r.prompt[:8] for r in a if r.tenant == "tenant2"]
    assert all(np.array_equal(t0[0], x) for x in t0)
    assert not np.array_equal(t0[0], t2[0])
    assert spec.identity == "seed11/n6/p2-4/g2-4/r50/v31/t3/sp8/i0.4"
    # back-compat: default (single-tenant) identity strings are unchanged
    legacy = TrafficSpec(n_requests=8, seed=9, rate_rps=100.0,
                         prompt_len=(2, 6), max_new=(2, 8), vocab=VOCAB)
    assert legacy.identity == "seed9/n8/p2-6/g2-8/r100/v31"
    assert "/t" not in legacy.identity
