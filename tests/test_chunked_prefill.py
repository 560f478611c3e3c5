"""Paged-kernel chunked prefill tests (ISSUE 20, docs/SERVING.md
"Chunked prefill on the paged pool").

Covers kernel-level parity of ``paged_prefill_attention`` against the
dense gather reference at prefill-sized row groups (chunk sizes x
block sizes x chunk-boundary starts x poisoned dead pages x int8/fp8
quantized pools with in-register dequant), engine-level paged-vs-
gather stream bit-identity on prompts long enough to cross chunk
boundaries (including prefix sharing that commits mid-prefill and a
spill/restore preemption), the batched-multi-slot == sequential-
submission contract, the one-dispatch-per-window / zero-added-host-
syncs ledger, the additive ffmetrics/1 ``prefill_attn_kernel`` field
+ serve_report rendering with old/new stream interop, the ffcheck
``paged_attn`` prefill-role audit (fires on a gather prefill program
claiming paged), and the chunked-prefill pricing
(:func:`~flexflow_tpu.search.cost.estimate_prefill_chunk_time`:
paged's visible-page traffic beats gather's full-SV materialization,
``serve_price`` carries the prefill arm under both kernels).
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

import jax.numpy as jnp  # noqa: E402

from flexflow_tpu import FFConfig, FFModel, MachineMesh  # noqa: E402
from flexflow_tpu.models.gpt_decode import gpt_generate_cached  # noqa: E402
from flexflow_tpu.models.transformer import gpt_decoder  # noqa: E402
from flexflow_tpu.ops.pallas import paged_attention as pa  # noqa: E402
from flexflow_tpu.serve import (  # noqa: E402
    RequestState,
    ServeEngine,
    TrafficSpec,
    synthetic_requests,
)
from flexflow_tpu.serve.kvcache import quantize_kv  # noqa: E402

SLOTS, SEQ, VOCAB = 4, 48, 31
SHAPE = dict(hidden=32, heads=4, ff_dim=64, num_layers=2, vocab=VOCAB)


@pytest.fixture(scope="module")
def model():
    cfg = FFConfig(batch_size=SLOTS, compute_dtype="float32")
    m = FFModel(cfg)
    gpt_decoder(m, SLOTS, SEQ, use_flash=False, **SHAPE)
    m.compile(seed=0)
    return m


@pytest.fixture()
def interpret():
    old = pa.INTERPRET
    pa.INTERPRET = True
    yield
    pa.INTERPRET = old


def _solo(model, req):
    prompt = np.tile(np.asarray(req.prompt)[None], (SLOTS, 1))
    out, _ = gpt_generate_cached(model, prompt, req.max_new_tokens)
    return out[0, req.prompt_len:]


def _streams(reqs):
    return {r.id: list(map(int, r.tokens)) for r in reqs}


# --------------------------------------------------------------- kernel
def _pool(x):
    """Pages ``(N, BS, H, D)`` as the kernels take them: position-major
    rows ``(N * BS, H * D)``."""
    return jnp.asarray(x).reshape(-1, x.shape[-2] * x.shape[-1])


def _dense_ref(q, pk, pv, pos, bt, scale):
    """The engine's gather + mul/reduce contraction, in numpy — same
    reference as test_paged_attention.py, here driven at G = chunk;
    ``pk`` / ``pv`` are pages ``(N, BS, H, D)``."""
    B, G, H, D = q.shape
    BS = pk.shape[1]
    MB = bt.shape[1]
    SV = MB * BS
    keys = pk[bt].transpose(0, 3, 1, 2, 4).reshape(B, H, SV, D)
    vals = pv[bt].transpose(0, 3, 1, 2, 4).reshape(B, H, SV, D)
    s = np.einsum("bghd,bhsd->bghs", q, keys).astype(np.float32) * scale
    k_pos = np.arange(SV, dtype=np.int64)
    row = pos[:, None].astype(np.int64) + np.arange(G)[None]
    mask = k_pos[None, None, :] <= row[:, :, None]
    s = np.where(mask[:, :, None, :], s, np.finfo(np.float32).min)
    s -= s.max(axis=-1, keepdims=True)
    p = np.exp(s)
    p /= p.sum(axis=-1, keepdims=True)
    return np.einsum("bghs,bhsd->bghd", p, vals)


def _poison_dead(pk, pv, bt, pos, G, BS):
    """Poison the trash block and every page past each lane's last
    VISIBLE one — correct DMA clamping means they never contribute."""
    MB = bt.shape[1]
    pk[0] = pv[0] = 1e4
    for b in range(bt.shape[0]):
        last = (int(pos[b]) + G - 1) // BS
        for i in range(last + 1, MB):
            pk[bt[b, i]] = 1e4
            pv[bt[b, i]] = 1e4


@pytest.mark.parametrize(
    "B,P,H,D,BS,MB",
    [
        (2, 8, 2, 8, 4, 4),    # chunk spans 2+ pages
        (3, 16, 2, 8, 8, 4),   # prefill-sized chunk, default page
        (1, 32, 4, 16, 8, 6),  # full engine-default chunk, one lane
        (2, 12, 2, 8, 16, 2),  # chunk inside one wide page
    ],
)
def test_prefill_kernel_matches_dense_reference(
    interpret, B, P, H, D, BS, MB
):
    """Parity at prefill row groups: scrambled block tables, ragged
    starts, garbage in every dead page.  Same clamp/mask contract the
    decode tests pin at G=1 — prefill IS that kernel at G=P."""
    rng = np.random.default_rng(101 * B + P)
    N = B * MB + 1
    q = rng.standard_normal((B, P, H, D)).astype(np.float32)
    pk = rng.standard_normal((N, BS, H, D)).astype(np.float32)
    pv = rng.standard_normal((N, BS, H, D)).astype(np.float32)
    perm = rng.permutation(N - 1) + 1
    bt = perm[: B * MB].reshape(B, MB).astype(np.int32)
    pos = rng.integers(0, MB * BS - P + 1, size=(B,)).astype(np.int32)
    _poison_dead(pk, pv, bt, pos, P, BS)
    got = np.asarray(pa.paged_prefill_attention(
        jnp.asarray(q), _pool(pk), _pool(pv),
        jnp.asarray(pos), jnp.asarray(bt), block_size=BS,
    ))
    want = _dense_ref(q, pk, pv, pos, bt, 1.0 / np.sqrt(D))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("start_kind", ["zero", "page_edge", "straddle"])
def test_prefill_kernel_chunk_boundary_starts(interpret, start_kind):
    """Chunk-boundary starts: the engine's later chunks begin at exact
    page multiples (start % BS == 0) or one row before the boundary —
    the visible-page clamp ``(pos0 + P - 1) // BS`` must include
    exactly the straddled pages, never the dead tail."""
    B, P, H, D, BS, MB = 3, 8, 2, 8, 8, 5
    rng = np.random.default_rng(7)
    N = B * MB + 1
    q = rng.standard_normal((B, P, H, D)).astype(np.float32)
    pk = rng.standard_normal((N, BS, H, D)).astype(np.float32)
    pv = rng.standard_normal((N, BS, H, D)).astype(np.float32)
    bt = (rng.permutation(N - 1) + 1)[: B * MB].reshape(B, MB)
    bt = bt.astype(np.int32)
    pos = {
        "zero": np.array([0, 0, 0], np.int32),
        "page_edge": np.array([BS, 2 * BS, 3 * BS], np.int32),
        "straddle": np.array(
            [BS - 1, 2 * BS - 1, 3 * BS - 1], np.int32
        ),
    }[start_kind]
    _poison_dead(pk, pv, bt, pos, P, BS)
    got = np.asarray(pa.paged_prefill_attention(
        jnp.asarray(q), _pool(pk), _pool(pv),
        jnp.asarray(pos), jnp.asarray(bt), block_size=BS,
    ))
    want = _dense_ref(q, pk, pv, pos, bt, 1.0 / np.sqrt(D))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_prefill_kernel_quantized_pool_parity(interpret, kv_dtype):
    """Quantized pools at prefill row groups: per-position scale rows
    ride the same block-table scalar prefetch, dequant happens in
    registers inside the online softmax.  Reference = the dense
    contraction over the HOST-dequantized pool (the one shared rule,
    kvcache.dequantize_kv) — parity proves the in-kernel multiply is
    that rule."""
    B, P, H, D, BS, MB = 2, 16, 2, 8, 8, 4
    rng = np.random.default_rng(23)
    N = B * MB + 1
    q = rng.standard_normal((B, P, H, D)).astype(np.float32)
    fk = rng.standard_normal((N, BS, H, D)).astype(np.float32)
    fv = rng.standard_normal((N, BS, H, D)).astype(np.float32)
    # quantize per POSITION: (N, BS, H, D) -> q, scale (N, BS)
    pk, sk = quantize_kv(jnp, jnp.asarray(fk), kv_dtype)
    pv, sv = quantize_kv(jnp, jnp.asarray(fv), kv_dtype)
    bt = (rng.permutation(N - 1) + 1)[: B * MB].reshape(B, MB)
    bt = bt.astype(np.int32)
    pos = np.array([3, BS * 2], np.int32)
    got = np.asarray(pa.paged_prefill_attention(
        jnp.asarray(q), _pool(pk), _pool(pv), jnp.asarray(pos),
        jnp.asarray(bt), scale_k=sk, scale_v=sv, block_size=BS,
    ))
    # host-side dequant, then the exact fp32 dense reference
    dk = np.asarray(pk, np.float32) * np.asarray(sk)[:, :, None, None]
    dv = np.asarray(pv, np.float32) * np.asarray(sv)[:, :, None, None]
    want = _dense_ref(q, dk, dv, pos, bt, 1.0 / np.sqrt(D))
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=1e-4)


# ------------------------------------------------------------ engine A/B
def _traffic(seed=11, n=4, prompt=(26, 38), new=(2, 5)):
    """Prompts LONGER than the prefill chunk below — every request
    crosses 3+ chunk boundaries before its first token (and prompt +
    budget stays inside SEQ=48 so nothing is rejected at admission)."""
    return synthetic_requests(TrafficSpec(
        n_requests=n, seed=seed, rate_rps=0.0, prompt_len=prompt,
        max_new=new, vocab=VOCAB,
    ))


def test_long_prompt_paged_vs_gather_bit_identical_and_ledger(
    model, interpret
):
    """The acceptance A/B at test scale: long prompts, chunk=8, paged
    and gather engines emit bit-identical streams; the paged arm runs
    ONE batched prefill dispatch per window with prefill work
    (dispatches < per-slot chunks proves cross-slot batching) and
    exactly one host sync per window (the flush — no sync was added)."""
    page = ServeEngine(model, slots=SLOTS, block_size=8,
                       prefill_chunk=8, sync_every=4, attn="paged")
    gath = ServeEngine(model, slots=SLOTS, block_size=8,
                       prefill_chunk=8, sync_every=4, attn="gather")
    reqs_p, reqs_g = _traffic(), _traffic()
    rep_p = page.run(reqs_p)
    rep_g = gath.run(reqs_g)
    assert rep_p.requests_finished == rep_g.requests_finished == 4
    assert _streams(reqs_p) == _streams(reqs_g)
    # ledger: every prompt needs ceil(prompt_len / 8) >= 4 chunks, all
    # 4 slots prefill concurrently, ONE dispatch serves them per window
    for rep in (rep_p, rep_g):
        assert rep.prefill_chunks >= 4 * 4
        assert 0 < rep.prefill_dispatches <= rep.windows
        assert rep.prefill_dispatches < rep.prefill_chunks
        assert rep.host_syncs == rep.windows
    assert rep_p.prefill_attn_kernel == "paged"
    assert rep_g.prefill_attn_kernel == "gather"
    page.kv.check_invariants()


def test_batched_prefill_matches_sequential_submission(model, interpret):
    """Batched-multi-slot == per-slot semantics: the same requests fed
    all-at-once (4 lanes prefill inside one dispatch) and one-at-a-time
    (each window prefills a single slot) produce identical streams, and
    both equal the dense solo decode."""
    batched = ServeEngine(model, slots=SLOTS, block_size=8,
                          prefill_chunk=8, sync_every=4, attn="paged")
    reqs_b = _traffic(seed=12)
    rep_b = batched.run(reqs_b)
    assert rep_b.requests_finished == 4

    solo_eng = ServeEngine(model, slots=SLOTS, block_size=8,
                           prefill_chunk=8, sync_every=4, attn="paged")
    reqs_s = _traffic(seed=12)
    for r in reqs_s:  # one at a time: no two slots ever co-prefill
        solo_eng.submit(r.prompt, r.max_new_tokens)
        got = solo_eng.run()
        assert got.requests_finished == 1
    done = {r.id - reqs_s[0].id: list(map(int, r.tokens))
            for r in solo_eng.sched.finished}
    want = {r.id - reqs_b[0].id: list(map(int, r.tokens))
            for r in reqs_b}
    assert done == want
    # one dense solo anchor (engine-vs-engine bit-identity above covers
    # the rest; per-request solos re-run the dense reference 4x)
    np.testing.assert_array_equal(
        np.asarray(reqs_b[0].tokens, np.int32), _solo(model, reqs_b[0])
    )


def test_prefix_sharing_commits_mid_prefill(model, interpret):
    """A shared prefix LONGER than the chunk: commit_prefix runs after
    every chunk, later requests hit blocks committed by an earlier
    request's partial prefill.  Streams stay bit-identical to the
    unshared gather engine."""
    def traffic():
        return synthetic_requests(TrafficSpec(
            n_requests=4, seed=9, rate_rps=0.0, prompt_len=(8, 20),
            max_new=(2, 5), vocab=VOCAB, tenants=1, shared_prefix=16,
        ))

    # num_blocks=13 staggers admission (2-ish concurrent requests), so
    # later requests look up prefix blocks the FIRST one committed
    # chunk by chunk while still mid-prefill
    page = ServeEngine(model, slots=SLOTS, block_size=8, num_blocks=13,
                       prefill_chunk=8, sync_every=2,
                       prefix_sharing=True, attn="paged")
    gath = ServeEngine(model, slots=SLOTS, block_size=8, num_blocks=13,
                       prefill_chunk=8, sync_every=2,
                       prefix_sharing=False, attn="gather")
    reqs_p, reqs_g = traffic(), traffic()
    rep_p = page.run(reqs_p)
    gath.run(reqs_g)
    assert rep_p.prefix_hit_rate is not None and rep_p.prefix_hit_rate > 0
    assert _streams(reqs_p) == _streams(reqs_g)
    assert page.kv.shared_write_hazards() == []
    page.kv.check_invariants()


def test_spill_restore_preemption_with_chunked_prefill(
    model, interpret
):
    """An interactive request with a multi-chunk prompt preempts a
    mid-flight batch decode: the victim spills, the interactive prompt
    prefills through the batched path in several windows, the victim
    restores — every stream equals its solo decode."""
    eng = ServeEngine(model, slots=2, block_size=8, prefill_chunk=8,
                      sync_every=2, attn="paged")
    rng = np.random.default_rng(15)
    b0 = eng.submit(rng.integers(0, VOCAB, size=(4,)).astype(np.int32),
                    10, tenant="acme", tier="batch")
    b1 = eng.submit(rng.integers(0, VOCAB, size=(4,)).astype(np.int32),
                    10, tenant="acme", tier="batch")
    eng.sched.admit()
    eng._t0 = eng._now()
    for _ in range(4):
        eng._window()
    assert b0.state is RequestState.DECODE
    assert b1.state is RequestState.DECODE
    it = eng.submit(
        rng.integers(0, VOCAB, size=(30,)).astype(np.int32), 5,
        tenant="vip", tier="interactive",
    )
    rep = eng.run()
    assert rep.requests_finished == 3
    assert eng.sched.preemptions == 1
    for r in (b0, b1, it):
        assert r.state is RequestState.FINISHED
        np.testing.assert_array_equal(
            np.asarray(r.tokens, np.int32), _solo(model, r)
        )
    eng.kv.check_invariants()


# ------------------------------------------------------ metrics / report
def test_metrics_prefill_field_and_report_interop(
    model, interpret, tmp_path
):
    """ffmetrics/1 additive ``prefill_attn_kernel`` +
    ``prefill_dispatches`` fields; serve_report renders the chunked-
    prefill line for a new stream and still renders a pre-r20 stream
    (fields popped) without it."""
    out = tmp_path / "prefill.jsonl"
    eng = ServeEngine(model, slots=SLOTS, block_size=8, prefill_chunk=8,
                      sync_every=4, attn="paged", metrics_out=str(out))
    eng.run(_traffic(seed=21))
    from flexflow_tpu.obs import read_metrics

    recs = read_metrics(str(out))
    assert recs
    assert all(
        r["metrics"]["serve"]["prefill_attn_kernel"] == "paged"
        for r in recs
    )
    assert any(
        r["metrics"]["serve"]["prefill_dispatches"] == 1 for r in recs
    )
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools",
    ))
    import serve_report

    new = serve_report.render(recs)
    assert "chunked prefill: paged kernel" in new
    old = json.loads(json.dumps(recs))
    for r in old:
        r["metrics"]["serve"].pop("prefill_attn_kernel")
        r["metrics"]["serve"].pop("prefill_dispatches")
    rendered = serve_report.render(old)  # pre-r20 stream still renders
    assert rendered and "chunked prefill" not in rendered


# ------------------------------------------------------------- ffcheck
def test_ffcheck_prefill_audit_fires_on_gather_program(model):
    """The seeded violation: a gather engine claiming ``paged`` must
    trip the paged_attn audit ON ITS PREFILL PROGRAM — the batched
    chunk program's per-layer pool gather is slots lanes of
    virtual-length K/V, the exact O(S^2) artifact the kernel deletes."""
    from flexflow_tpu.analysis import analyze_serve_engine

    old = pa.INTERPRET
    pa.INTERPRET = False
    try:
        eng = ServeEngine(model, slots=SLOTS, block_size=8,
                          prefill_chunk=8, sync_every=4, attn="gather")
        rep = analyze_serve_engine(eng, checks=["paged_attn"])
        assert not [v for v in rep.violations if v.check == "paged_attn"]
        eng.attn_kernel = "paged"  # the lie
        try:
            rep = analyze_serve_engine(eng, checks=["paged_attn"])
        finally:
            eng.attn_kernel = "gather"
        hits = [
            v for v in rep.violations
            if v.check == "paged_attn" and v.program == "serve.prefill"
        ]
        assert hits and not rep.ok
        assert hits[0].severity == "error"
        assert hits[0].details["nbytes"] >= (
            hits[0].details["lane_kv_bytes"]
        )
    finally:
        pa.INTERPRET = old


# ------------------------------------------------------------- pricing
def _price(model, attn, kv_dtype="fp32", chunk=32, kv_len=512):
    from flexflow_tpu.search.cost import estimate_prefill_chunk_time
    from flexflow_tpu.search.optimizer import Strategy

    mesh = MachineMesh((1,), ("data",))
    return estimate_prefill_chunk_time(
        model.layers, Strategy(mesh), None, chunk=chunk, kv_len=kv_len,
        train_tokens=SLOTS * SEQ, slots=SLOTS, attn_kernel=attn,
        kv_dtype=kv_dtype,
    )


@pytest.mark.parametrize("kv_dtype", ["fp32", "int8", "fp8"])
def test_prefill_pricing_paged_beats_gather(model, kv_dtype):
    """The estimator prices the asymmetry the kernel buys: gather pays
    3x the FULL virtual length per chunk, paged reads the visible
    prefix only — at kv_len >> chunk the gap must be wide, and it must
    WIDEN with depth (that is the O(S^2) term)."""
    paged = _price(model, "paged", kv_dtype)
    gath = _price(model, "gather", kv_dtype)
    for p in (paged, gath):
        assert set(p) == {"chunk_s", "mem_s", "flops_s", "coll_s"}
        assert p["chunk_s"] > 0
    assert paged["mem_s"] < gath["mem_s"]
    # identical arithmetic: the win is traffic, not FLOPs
    assert paged["flops_s"] == gath["flops_s"]
    ratio_512 = gath["chunk_s"] / paged["chunk_s"]
    assert ratio_512 > 2.0
    deep_p = _price(model, "paged", kv_dtype, kv_len=4096)
    deep_g = _price(model, "gather", kv_dtype, kv_len=4096)
    assert deep_g["chunk_s"] / deep_p["chunk_s"] > ratio_512


def test_serve_price_carries_prefill_arm(model):
    """ServeObjective.price attaches the additive ``prefill`` key under
    the same attn/kv arms the decode price uses, with the TTFT estimate
    consistent with chunk_s, and the decode-side keys untouched."""
    from flexflow_tpu.search.optimizer import Strategy
    from flexflow_tpu.serve.objective import ServeObjective, ServeSpec

    mesh = MachineMesh((1,), ("data",))
    st = Strategy(mesh)
    prices = {}
    for attn in ("paged", "gather"):
        spec = ServeSpec(slots=SLOTS, kv_len=256, attn=attn,
                         prefill_chunk=16)
        pr = ServeObjective(None, spec, SLOTS * SEQ).price(
            model.layers, st
        )
        pf = pr["prefill"]
        assert pf["chunk"] == 16 and pf["attn_kernel"] == attn
        assert set(pf["breakdown"]) == {"mem_s", "flops_s", "coll_s"}
        assert pf["per_pos_s"] == pytest.approx(
            pf["chunk_s"] / (SLOTS * 16)
        )
        assert pf["ttft_est_ms"] == pytest.approx(
            pf["chunk_s"] * (256 // 16) * 1e3
        )
        # decode-side price shape is byte-identical to pre-r20 records
        assert set(pr["breakdown"]) == {"mem_s", "flops_s", "coll_s"}
        prices[attn] = pf
    assert prices["paged"]["chunk_s"] < prices["gather"]["chunk_s"]
    json.dumps(prices["paged"])  # the driver prints serve_price
