"""Fused Pallas paged-attention decode tests (ISSUE 14, docs/PERF.md).

Covers kernel-level parity against the dense gather reference (block
sizes x G rows x scrambled block tables x garbage in masked pages),
the ``--serve-attn`` knob semantics (auto declines off-TPU, explicit
``paged`` raises truthfully, ``gather`` stays byte-identical to the
pre-paged engine), end-to-end stream bit-identity paged-vs-gather
across block sizes / prefix sharing / a spill-restore preemption
mid-generation / the speculative verify program at k>=1, the ffcheck
``paged_attn`` audit (clean on the real paged programs, fires on a
gather program claiming to be paged), the additive ffmetrics/1
``attn_kernel`` field + old/new stream interop, and the
``FFTPU_PALLAS_INTERPRET`` env override.  ISSUE 27 adds the page-write
kernel: bit for bit against ``pool.at[i, blk * BS + off].set(rows)``
at every row-group width and pool dtype, the pages no lane names
untouched, and the paged engine (kernel writer) against the gather
engine (XLA scatter) over prefill, decode and slot recycling.  ISSUE 29
makes the pool position-major, ``(L, num_blocks * BS, H * D)``: the
kernels' contractions run on the MXU, so kernel against gather is a
float32 tolerance plus identical greedy streams, and the pool's
geometry is pinned (minor dimension ``heads * head_dim``, no layout
API anywhere in ``serve/`` or ``ops/pallas/``).  ISSUE 31 makes the
kernel's grid one step a lane, whose loop walks the lane's live pages a
compute block at a time: parity where the trip count changes (an idle
lane, exactly one block, one page more, a full table), a NaN block past
the last live page never read, the built call's grid, and the engine's
``attn_walk`` / ``attn_blocks_walked``.
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

from flexflow_tpu import FFConfig, FFModel  # noqa: E402
from flexflow_tpu.models.gpt_decode import gpt_generate_cached  # noqa: E402
from flexflow_tpu.models.transformer import gpt_decoder  # noqa: E402
from flexflow_tpu.ops.pallas import env_interpret  # noqa: E402
from flexflow_tpu.ops.pallas import paged_attention as pa  # noqa: E402
from flexflow_tpu.serve import (  # noqa: E402
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


@pytest.fixture()
def interpret():
    """Force interpreter mode for the duration of one test (the flag
    is module-global on purpose: _paged_call is un-jitted so flipping
    it re-traces — see paged_attention.py)."""
    old = pa.INTERPRET
    pa.INTERPRET = True
    yield
    pa.INTERPRET = old


def _solo(model, req):
    """Greedy solo decode on the dense session — the reference stream
    every paged variant must match bit for bit."""
    prompt = np.tile(np.asarray(req.prompt)[None], (SLOTS, 1))
    out, _ = gpt_generate_cached(model, prompt, req.max_new_tokens)
    return out[0, req.prompt_len:]


def _streams(reqs):
    return {r.id: list(map(int, r.tokens)) for r in reqs}


# --------------------------------------------------------------- kernel
def _pool(x):
    """A test's pages ``(N, BS, H, D)`` (or ``(L, N, BS, H, D)``) as the
    kernels take them: position-major rows ``(N * BS, H * D)``."""
    return x.reshape(*x.shape[:-4], -1, x.shape[-2] * x.shape[-1])


def _dense_ref(q, pk, pv, pos, bt, scale):
    """The engine's gather + mul/reduce contraction, in numpy; ``pk`` /
    ``pv`` are pages ``(N, BS, H, D)``."""
    B, G, H, D = q.shape
    BS = pk.shape[1]
    MB = bt.shape[1]
    SV = MB * BS
    keys = pk[bt].transpose(0, 3, 1, 2, 4).reshape(B, H, SV, D)
    vals = pv[bt].transpose(0, 3, 1, 2, 4).reshape(B, H, SV, D)
    s = np.einsum("bghd,bhsd->bghs", q, keys).astype(np.float32) * scale
    k_pos = np.arange(SV, dtype=np.int64)
    row = pos[:, None].astype(np.int64) + np.arange(G)[None]
    mask = k_pos[None, None, :] <= row[:, :, None]  # (B, G, SV)
    s = np.where(mask[:, :, None, :], s, np.finfo(np.float32).min)
    s -= s.max(axis=-1, keepdims=True)
    p = np.exp(s)
    p /= p.sum(axis=-1, keepdims=True)
    return np.einsum("bghs,bhsd->bghd", p, vals)


@pytest.mark.parametrize(
    "B,G,H,D,BS,MB",
    [
        (3, 1, 2, 8, 4, 3),   # plain decode row
        (2, 3, 4, 16, 8, 2),  # speculative verify rows (k=2)
        (1, 2, 1, 4, 2, 5),   # single head, many small pages
        (4, 1, 2, 8, 16, 2),  # wide pages
    ],
)
def test_kernel_matches_dense_reference(interpret, B, G, H, D, BS, MB):
    """Parity vs the gather reference with scrambled block tables,
    ragged per-lane positions, and GARBAGE (huge values) in every page
    past each lane's last live one — a walk past the last live page or
    a mask leak would blow the comparison up by orders of magnitude."""
    rng = np.random.default_rng(17 * B + G)
    N = B * MB + 1  # + trash block 0
    q = rng.standard_normal((B, G, H, D)).astype(np.float32)
    pk = rng.standard_normal((N, BS, H, D)).astype(np.float32)
    pv = rng.standard_normal((N, BS, H, D)).astype(np.float32)
    # each lane gets a scrambled disjoint set of physical blocks (> 0)
    perm = rng.permutation(N - 1) + 1
    bt = perm[: B * MB].reshape(B, MB).astype(np.int32)
    # ragged positions: lane b's row 0 sits anywhere in its window
    pos = rng.integers(0, MB * BS - G + 1, size=(B,)).astype(np.int32)
    # poison all pages past each lane's last live page AND the trash
    # block: the walk ends before them and the mask covers the rest
    pk[0] = pv[0] = 1e4
    for b in range(B):
        last = (int(pos[b]) + G - 1) // BS
        for i in range(last + 1, MB):
            pk[bt[b, i]] = 1e4
            pv[bt[b, i]] = 1e4
    scale = 1.0 / np.sqrt(D)
    got = np.asarray(
        pa.paged_decode_attention(
            jnp.asarray(q), jnp.asarray(_pool(pk)), jnp.asarray(_pool(pv)),
            jnp.asarray(pos), jnp.asarray(bt), block_size=BS,
        )
    )
    want = _dense_ref(q, pk, pv, pos, bt, scale)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)


def test_kernel_bf16_io_f32_accumulate(interpret):
    """bf16 pools and queries go through the f32 online softmax; the
    result must sit within bf16 resolution of the f32 reference."""
    rng = np.random.default_rng(3)
    B, G, H, D, BS, MB = 2, 1, 2, 8, 4, 3
    N = B * MB + 1
    q = rng.standard_normal((B, G, H, D)).astype(np.float32)
    pk = rng.standard_normal((N, BS, H, D)).astype(np.float32)
    pv = rng.standard_normal((N, BS, H, D)).astype(np.float32)
    bt = (rng.permutation(N - 1) + 1)[: B * MB].reshape(B, MB)
    pos = np.array([5, 11], np.int32)
    out = pa.paged_decode_attention(
        jnp.asarray(q, jnp.bfloat16), jnp.asarray(_pool(pk), jnp.bfloat16),
        jnp.asarray(_pool(pv), jnp.bfloat16), jnp.asarray(pos),
        jnp.asarray(bt, np.int32), block_size=BS,
    )
    assert out.dtype == jnp.bfloat16
    want = _dense_ref(
        np.asarray(jnp.asarray(q, jnp.bfloat16), np.float32),
        np.asarray(jnp.asarray(pk, jnp.bfloat16), np.float32),
        np.asarray(jnp.asarray(pv, jnp.bfloat16), np.float32),
        pos, bt.astype(np.int32), 1.0 / np.sqrt(D),
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), want, atol=3e-2, rtol=3e-2
    )


POOL_DTYPES = {
    "fp32": jnp.float32, "bf16": jnp.bfloat16,
    "int8": jnp.int8, "fp8": jnp.float8_e4m3fn,
}


def _pool_of(kv_dtype, fk, fv):
    """(pool_k, pool_v, sk, sv, dense_k, dense_v) of float32 pages
    ``(N, BS, H, D)``: the pages in ``kv_dtype``, a quantized pool's
    scale rows ``(N, BS)`` (else None), and the float32 pages the dense
    reference reads — the host-dequantized ones (the shared
    ``int * scale`` rule), or the rounded ones."""
    from flexflow_tpu.serve.kvcache import quantize_kv

    if kv_dtype in ("int8", "fp8"):
        pk, sk = quantize_kv(jnp, jnp.asarray(fk), kv_dtype)
        pv, sv = quantize_kv(jnp, jnp.asarray(fv), kv_dtype)
        dk = np.asarray(pk, np.float32) * np.asarray(sk)[:, :, None, None]
        dv = np.asarray(pv, np.float32) * np.asarray(sv)[:, :, None, None]
        return pk, pv, sk, sv, dk, dv
    pk = jnp.asarray(fk, POOL_DTYPES[kv_dtype])
    pv = jnp.asarray(fv, POOL_DTYPES[kv_dtype])
    return pk, pv, None, None, np.asarray(pk, np.float32), np.asarray(pv, np.float32)


@pytest.mark.parametrize("kv_dtype", list(POOL_DTYPES))
@pytest.mark.parametrize("G", [1, 3, 8], ids=["decode", "verify", "chunk"])
def test_kernel_at_page_boundaries_every_pool_dtype(interpret, G, kv_dtype):
    """The block-diagonal contraction against the dense float32
    reference where a row group meets a page: it starts one (row 0 on a
    page's first position), ends one (the last row on a page's last
    position) and crosses one (rows on both sides; for G = 1, the row
    just past the boundary, whose history does) — at G = 1, G = k + 1
    and G = P, for every pool dtype.  A quantized pool is compared
    against the reference over the host-dequantized pages (the shared
    ``int * scale`` rule), a bfloat16 one over the rounded pages."""
    B, H, D, BS, MB = 3, 2, 8, 4, 4
    rng = np.random.default_rng(29 + G)
    N = B * MB + 1
    q = rng.standard_normal((B, G, H, D)).astype(np.float32)
    fk = rng.standard_normal((N, BS, H, D)).astype(np.float32)
    fv = rng.standard_normal((N, BS, H, D)).astype(np.float32)
    bt = (rng.permutation(N - 1) + 1)[: B * MB].reshape(B, MB)
    bt = bt.astype(np.int32)
    crosses = 2 * BS - 1 if G > 1 else 2 * BS
    pos = np.array([BS, 3 * BS - G, crosses], np.int32)
    assert pos[0] % BS == 0 and (pos[1] + G) % BS == 0
    assert G == 1 or pos[2] // BS != (pos[2] + G - 1) // BS
    pk, pv, sk, sv, dk, dv = _pool_of(kv_dtype, fk, fv)
    got = np.asarray(pa.paged_decode_attention(
        jnp.asarray(q), _pool(pk), _pool(pv), jnp.asarray(pos),
        jnp.asarray(bt), scale_k=sk, scale_v=sv, block_size=BS,
    ))
    want = _dense_ref(q, dk, dv, pos, bt, 1.0 / np.sqrt(D))
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=1e-4)


# ------------------------------------------------------------- the walk
# pages of 16 -> 8 pages a compute block; 20 pages a lane -> 3 blocks,
# the last one half past the table
H_K, D_K, BS_K, MB_K = 2, 8, 16, 20
PPB_K = 8


def _walk_pool(kv_dtype, rng, n_blocks):
    shape = (n_blocks, BS_K, H_K, D_K)
    return _pool_of(
        kv_dtype, rng.standard_normal(shape).astype(np.float32),
        rng.standard_normal(shape).astype(np.float32),
    )


@pytest.mark.parametrize("kv_dtype", list(POOL_DTYPES))
@pytest.mark.parametrize("G", [1, 3, 32], ids=["decode", "verify", "chunk"])
def test_kernel_walks_live_pages_only_every_depth(interpret, G, kv_dtype):
    """Against the dense float32 reference where the loop's trip count
    changes: an idle lane (position 0, an all-zero table row: one block
    of the trash block), a lane that ends exactly one compute block
    (``PPB`` pages), one whose last row opens the next block (``PPB + 1``
    pages), and a full table (``pos + G`` reaching ``MB * BS``, the last
    block half past the table's end) — at G = 1, k + 1 and P, for every
    pool dtype."""
    assert pa.attention_walk(4, BS_K, MB_K) == {
        "grid": [4], "pages_per_block": PPB_K, "max_blocks": 3,
    }
    rng = np.random.default_rng(31 + G)
    B = 4
    N = B * MB_K + 1
    pk, pv, sk, sv, dk, dv = _walk_pool(kv_dtype, rng, N)
    q = rng.standard_normal((B, G, H_K, D_K)).astype(np.float32)
    bt = (rng.permutation(N - 1) + 1)[: B * MB_K].reshape(B, MB_K)
    bt = bt.astype(np.int32)
    bt[0] = 0
    pos = np.array(
        [0, PPB_K * BS_K - G, PPB_K * BS_K - G + 1, MB_K * BS_K - G], np.int32
    )
    pages = (pos + G - 1) // BS_K + 1
    assert list(pages[1:]) == [PPB_K, PPB_K + 1, MB_K]
    assert list(pa.lane_blocks(
        pos, G, block_size=BS_K, max_blocks_per_seq=MB_K
    )) == [1, 1, 2, 3]
    got = np.asarray(pa.paged_decode_attention(
        jnp.asarray(q), _pool(pk), _pool(pv), jnp.asarray(pos),
        jnp.asarray(bt), scale_k=sk, scale_v=sv, block_size=BS_K,
    ))
    want = _dense_ref(q, dk, dv, pos, bt, 1.0 / np.sqrt(D_K))
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=1e-4)


@pytest.mark.parametrize("kv_dtype", list(POOL_DTYPES))
@pytest.mark.parametrize("G", [1, 3], ids=["decode", "verify"])
def test_kernel_never_reads_past_the_last_live_page(interpret, G, kv_dtype):
    """The walk ends, it does not mask: table entries past a lane's last
    live page name a block that is NaN all over (its pages where the
    dtype has a NaN, its scale rows in a quantized pool), and the result
    is finite and equal to the one with those entries naming the trash
    block.  Lanes end inside the first block, at its end and inside the
    second."""
    rng = np.random.default_rng(37 + G)
    B = 3
    N = B * MB_K + 2
    bad = N - 1
    pk, pv, sk, sv, _, _ = _walk_pool(kv_dtype, rng, N)
    nan_rows = slice(bad * BS_K, (bad + 1) * BS_K)
    pk, pv = _pool(pk), _pool(pv)
    if kv_dtype != "int8":
        pk = pk.at[nan_rows].set(jnp.nan)
        pv = pv.at[nan_rows].set(jnp.nan)
    if sk is not None:
        sk, sv = sk.at[bad].set(jnp.nan), sv.at[bad].set(jnp.nan)
    q = jnp.asarray(rng.standard_normal((B, G, H_K, D_K)), jnp.float32)
    bt = (rng.permutation(N - 2) + 1)[: B * MB_K].reshape(B, MB_K)
    bt = bt.astype(np.int32)
    pos = np.array([5, PPB_K * BS_K - G, (PPB_K + 2) * BS_K + 3], np.int32)
    clean, dirty = bt.copy(), bt.copy()
    for b in range(B):
        past = (int(pos[b]) + G - 1) // BS_K + 1
        clean[b, past:] = 0
        dirty[b, past:] = bad

    def run(tables):
        return np.asarray(pa.paged_decode_attention(
            q, pk, pv, jnp.asarray(pos), jnp.asarray(tables),
            scale_k=sk, scale_v=sv, block_size=BS_K,
        ))

    got = run(dirty)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, run(clean))


@pytest.mark.parametrize(
    "BS,MB,ppb,blocks",
    [(16, 64, 8, 8), (32, 32, 4, 8), (8, 128, 16, 8), (128, 8, 1, 8),
     (256, 4, 1, 4), (16, 20, 8, 3), (4, 3, 3, 1)],
)
def test_the_built_call_has_one_grid_step_a_lane(BS, MB, ppb, blocks):
    """``pages_per_block`` is read off the page size — the pages that
    make 128 key positions, never more than the table holds — and the
    ``pallas_call`` that is built has one grid step a lane: no page axis,
    whatever the table's length."""
    import jax

    B, G, H, D = 5, 1, 2, 8
    walk = pa.attention_walk(B, BS, MB)
    assert walk == {"grid": [B], "pages_per_block": ppb, "max_blocks": blocks}
    sds = jax.ShapeDtypeStruct
    n = 2 * MB + 1
    jaxpr = jax.make_jaxpr(
        lambda *a: pa.paged_decode_attention(*a, block_size=BS)
    )(
        sds((B, G, H, D), jnp.float32), sds((n * BS, H * D), jnp.float32),
        sds((n * BS, H * D), jnp.float32), sds((B,), jnp.int32),
        sds((B, MB), jnp.int32),
    )

    def calls(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    (call,) = calls(jaxpr.jaxpr)
    assert tuple(call.params["grid_mapping"].grid) == (B,)
    kbuf = [
        v.aval.shape for v in call.params["jaxpr"].invars
        if len(v.aval.shape) == 3 and v.aval.shape[0] == 2
    ]
    assert kbuf == [(2, ppb * BS, H * D)] * 2  # K and V, double-buffered


def test_engine_reports_the_walk_and_the_blocks_walked(model, interpret):
    """``ServeEngine.attn_walk`` is the geometry the engine's kernels were
    built with, and the run's report carries the compute blocks its
    lanes walked beside what the whole table would have taken: every
    live lane here stays inside its first block, so each lane of each
    call walked exactly one."""
    eng = ServeEngine(model, slots=SLOTS, block_size=4, prefill_chunk=5,
                      sync_every=3, attn="paged")
    walk = eng.attn_walk()
    MB = eng.kv.max_blocks_per_seq
    one_walk = {"tiles": 1, "heads": "block_diagonal"}  # one head count
    assert walk == dict(
        pa.attention_walk(SLOTS, 4, MB), decode=one_walk, prefill=one_walk
    )
    assert walk["grid"] == [SLOTS] and walk["pages_per_block"] == MB
    rep = eng.run(synthetic_requests(TrafficSpec(
        n_requests=SLOTS + 2, seed=5, rate_rps=0.0, prompt_len=(3, 12),
        max_new=(2, 5), vocab=VOCAB,
    )))
    lanes = (rep.decode_steps + rep.prefill_dispatches) * SLOTS
    assert rep.attn_blocks_walked == lanes
    assert rep.attn_blocks_full_table == lanes * walk["max_blocks"]
    assert rep.to_dict()["attn_blocks_walked"] == lanes


@pytest.fixture()
def blocks_of_eight_keys(monkeypatch):
    """Compute blocks of 8 key positions instead of 128, so that the
    48-position tables of this file's model hold several: the kernels'
    jitted wrappers are traced anew on both sides of the change."""
    def retrace():
        for fn in pa._JITTED.values():
            fn.clear_cache()

    retrace()
    monkeypatch.setattr(pa, "_BLOCK_KEYS", 8)
    yield
    retrace()


def test_blocks_walked_follow_the_requests_lengths(
    model, gather_engine, interpret, blocks_of_eight_keys
):
    """Pages of 4 rows, two a block, six blocks a table.  One request of
    6 prompt positions and 4 new tokens: two chunks of 4 (pages 1 and 2:
    a block each) and decode steps at positions 6, 7, 8 (pages 2, 2, 3:
    blocks 1, 1, 2) walk 6 blocks, the 15 other lanes of the 5 calls one
    each; the stream is the gather engine's."""
    from flexflow_tpu.serve import Request

    eng = ServeEngine(model, slots=SLOTS, block_size=4, prefill_chunk=4,
                      sync_every=2, attn="paged")
    assert eng.attn_walk() == {
        "grid": [SLOTS], "pages_per_block": 2, "max_blocks": 6,
        "decode": {"tiles": 1, "heads": "block_diagonal"},
        "prefill": {"tiles": 1, "heads": "block_diagonal"},
    }

    def one():
        return [Request(prompt=np.arange(1, 7), max_new_tokens=4)]

    mine, theirs = one(), one()
    rep = eng.run(mine)
    assert (rep.decode_steps, rep.prefill_dispatches) == (3, 2)
    assert rep.attn_blocks_walked == 6 + 15
    assert rep.attn_blocks_full_table == 5 * SLOTS * 6
    rg = gather_engine.run(theirs)
    assert rg.attn_blocks_walked is None and gather_engine.attn_walk() is None
    assert _streams(mine) == _streams(theirs)


# ----------------------------------------------------------- page write
L_W, B_W, H_W, D_W, BS_W, MB_W = 3, 4, 2, 8, 4, 5
N_W = B_W * MB_W + 1  # + trash block 0


def _write_case(case, rng):
    """(G, start, n_valid, idle) of one row-group shape the programs
    write: decode / draft, verify, a prefill chunk."""
    if case == "decode":  # G = 1 anywhere in the lane's window
        return 1, rng.integers(0, MB_W * BS_W, size=(B_W,)), None, ()
    if case == "verify":  # G = k + 1, unaligned, crossing a page boundary
        return 3, np.array([BS_W - 1, 2 * BS_W - 2, 5, 0]), None, ()
    if case == "verify_wide":  # G > 2 pages' worth from an unaligned start
        return 2 * BS_W + 1, np.array([BS_W - 1, 1, 2, BS_W + 3]), None, ()
    # G = P: a full lane, a short tail, a one-row tail, a whole padded lane
    return 2 * BS_W, np.array([BS_W, 3, 2 * BS_W + 1, 0]), \
        np.array([2 * BS_W, 3, 1, 0]), (3,)


@pytest.mark.parametrize("kv_dtype", list(POOL_DTYPES))
@pytest.mark.parametrize(
    "case", ["decode", "verify", "verify_wide", "prefill"]
)
def test_kv_page_write_matches_scatter(interpret, case, kv_dtype):
    """The kernel against the XLA scatter it replaces, bit for bit: the
    rows land where ``pool.at[i, blk * BS + off].set(rows)`` puts them,
    the other layers and every page no lane names are byte-identical
    before and after (the alias writes nothing else), and where a chunk
    has padded rows only the trash block 0 may differ."""
    dt = POOL_DTYPES[kv_dtype]
    rng = np.random.default_rng(len(case) * 7 + len(kv_dtype))
    G, start, n_valid, idle = _write_case(case, rng)
    start = np.asarray(start, np.int32)

    def rand(shape):  # small integers: exact in every pool dtype
        return jnp.asarray(
            rng.integers(-8, 9, size=shape), jnp.float32
        ).astype(dt)

    pool_shape = (L_W, N_W * BS_W, H_W * D_W)
    pk, pv = rand(pool_shape), rand(pool_shape)
    k, v = rand((B_W, G, H_W, D_W)), rand((B_W, G, H_W, D_W))
    bt = (rng.permutation(N_W - 1) + 1)[: B_W * MB_W].reshape(B_W, MB_W)
    bt = bt.astype(np.int32)
    for b in idle:
        bt[b] = 0  # an idle lane rides with an all-zero table row
    # the programs' scatter indices (serve/programs.py::write_kv)
    pos = start[:, None] + np.arange(G)[None]
    blk = bt[np.arange(B_W)[:, None], np.clip(pos // BS_W, 0, MB_W - 1)]
    off = pos % BS_W
    if n_valid is not None:
        valid = np.arange(G)[None] < np.asarray(n_valid)[:, None]
        blk, off = np.where(valid, blk, 0), np.where(valid, off, 0)
    layer = 1
    row = blk * BS_W + off
    want_k = pk.at[layer, row].set(k.reshape(B_W, G, -1))
    want_v = pv.at[layer, row].set(v.reshape(B_W, G, -1))
    got_k, got_v = pa.paged_kv_write(
        pk, pv, layer, k, v, jnp.asarray(start), jnp.asarray(bt),
        None if n_valid is None else jnp.asarray(n_valid, jnp.int32),
        block_size=BS_W,
    )
    assert got_k.dtype == dt and got_v.dtype == dt
    assert got_k.shape == got_v.shape == pool_shape

    def raw(x):  # bytes, not values (fp8 NaN payloads included), by block
        x = np.ascontiguousarray(np.asarray(x)).view(np.uint8)
        return x.reshape(L_W, N_W, -1)

    first = 1 if n_valid is not None else 0  # padded rows: block 0 is free
    for got, want, before in ((got_k, want_k, pk), (got_v, want_v, pv)):
        np.testing.assert_array_equal(
            raw(got)[:, first:], raw(want)[:, first:]
        )
        named = np.zeros(N_W, bool)
        named[blk.ravel()] = True
        named[0] = True
        np.testing.assert_array_equal(
            raw(got)[:, ~named], raw(before)[:, ~named]
        )
        other = [i for i in range(L_W) if i != layer]
        np.testing.assert_array_equal(
            raw(got)[other][:, first:], raw(before)[other][:, first:]
        )


@pytest.mark.parametrize("G", [1, 3, BS_W, 2 * BS_W + 1])
def test_kv_page_write_plan_shares_only_the_trash_block(G):
    """Two grid steps name the same physical page only for block 0: the
    kernel's read-ahead may then never see a live page stale.  Disjoint
    tables, ragged starts, short and empty chunks."""
    rng = np.random.default_rng(G)
    bt = (rng.permutation(N_W - 1) + 1)[: B_W * MB_W].reshape(B_W, MB_W)
    bt[2] = 0
    start = rng.integers(0, MB_W * BS_W - G + 1, size=(B_W,))
    start[2] = 0
    n_valid = rng.integers(0, G + 1, size=(B_W,))
    n_valid[0], n_valid[2] = G, 0
    phys, lo, hi, _ = (
        np.asarray(x) for x in pa._write_plan(
            jnp.asarray(start, jnp.int32), jnp.asarray(bt, jnp.int32),
            G, BS_W, jnp.asarray(n_valid, jnp.int32),
        )
    )
    assert phys.shape == (B_W, (G + BS_W - 2) // BS_W + 1)
    live = phys[phys > 0]
    assert len(set(live.tolist())) == live.size
    assert ((hi > lo) == (phys > 0)).all()  # block 0 takes an empty range
    assert (hi - lo).sum(axis=1).tolist() == n_valid.tolist()


# ------------------------------------------------------------- geometry
def test_pool_geometry_is_one_and_needs_no_layout_api():
    """What the whole change rests on, cheap to break later: the pool's
    minor dimension is the whole ``heads * head_dim`` row (so the layout
    the TPU keeps it in at rest is the one the kernels read), for every
    pool dtype, and nothing under ``serve/`` or ``ops/pallas/`` reaches
    for ``jax.experimental.layout`` to say otherwise."""
    import pathlib
    import re

    from flexflow_tpu.serve.kvcache import KV_DTYPES, PagedKVCache

    for kv_dtype in KV_DTYPES:
        kv = PagedKVCache(3, 4, 16, slots=2, block_size=8, num_blocks=7,
                          max_seq_len=40, kv_dtype=kv_dtype)
        assert kv.cache_k.shape == kv.cache_v.shape == (3, 7 * 8, 4 * 16)
        assert kv.cache_k.shape[-1] == kv.heads * kv.head_dim
        if kv.quantized:
            assert kv.scale_k.shape == kv.scale_v.shape == (3, 7, 8)
        assert kv.hbm_bytes() == 2 * kv.cache_k.size * (
            kv.cache_k.dtype.itemsize
        ) + (2 * kv.scale_k.size * 4 if kv.quantized else 0)
    root = pathlib.Path(pa.__file__).resolve().parents[2]
    layout_api = re.compile(
        r"jax\.experimental\.layout|from jax\.experimental import[^\n]*\blayout\b"
        r"|\bFormat\(|\bLayout\(|DeviceLocalLayout"
    )
    for sub in ("serve", "ops/pallas"):
        for path in sorted((root / sub).glob("*.py")):
            assert not layout_api.search(path.read_text()), path


def test_page_rows_tile_and_the_tpu_rule(monkeypatch):
    """A page is whole sublane tiles of the pool's dtype on a TPU: 8
    rows of float32, 16 of bfloat16, 32 of a one-byte pool.  Where
    ``block_size`` is not a multiple, ``auto`` declines to the gather
    arm and an explicit ``paged`` says what it needs; the interpreter
    takes any page."""
    import jax

    assert pa.page_rows_tile(jnp.float32) == 8
    assert pa.page_rows_tile(jnp.bfloat16) == 16
    assert pa.page_rows_tile(jnp.int8) == 32
    assert pa.page_rows_tile(jnp.float8_e4m3fn) == 32
    monkeypatch.setattr(pa, "INTERPRET", True)
    assert pa.resolve_serve_attn("paged", 4, jnp.int8) == "paged"
    monkeypatch.setattr(pa, "INTERPRET", False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pa.resolve_serve_attn("auto", 16, jnp.bfloat16) == "paged"
    assert pa.resolve_serve_attn("auto", 32, jnp.int8) == "paged"
    assert pa.resolve_serve_attn("auto", 16, jnp.int8) == "gather"
    assert pa.resolve_serve_attn("auto", 8, jnp.bfloat16) == "gather"
    assert pa.resolve_serve_attn("paged", 8, jnp.float32) == "paged"
    with pytest.raises(ValueError, match="multiple of 32"):
        pa.resolve_serve_attn("paged", 16, jnp.int8)


def test_pool_relayouts_counts_whole_pool_copies(model, gather_engine):
    """``count_pool_relayouts`` reads a compiled module's text: a
    ``copy`` or ``transpose`` into an array of the pool's byte size
    counts under any shape (fused ones too), smaller arrays, other
    operations and the compiler's own staging (``copy-start``) do not.
    The engine's counter is an int read on demand; on the CPU the
    gather arm's scatter is in place, so it reads 0."""
    from flexflow_tpu.serve.engine import count_pool_relayouts

    text = """
HloModule jit_decode
%fused_computation (p: bf16[12,24592,768]) -> bf16[12,1537,12,16,64] {
  %p = bf16[12,24592,768]{2,1,0:T(8,128)(2,1)} parameter(0)
  ROOT %transpose.3 = bf16[12,1537,12,16,64]{1,4,3,2,0:T(8,128)(2,1)} transpose(%p), dimensions={0,1,3,2,4}
}
ENTRY %main {
  %ck = bf16[12,24592,768]{2,1,0:T(8,128)(2,1)} parameter(0)
  %copy.184 = bf16[12,24592,768]{2,1,0:T(8,128)(2,1)} copy(%ck)
  %copy.9 = bf16[1,24592,768]{2,1,0} copy(%slice.1)
  %copy-start.1 = (bf16[12,24592,768]{2,1,0:S(1)}, bf16[12,24592,768]{2,1,0}, u32[]{:S(2)}) copy-start(%ck)
  %fusion.2 = bf16[12,1537,12,16,64]{1,4,3,2,0} fusion(%ck), kind=kLoop, calls=%fused_computation
  %decode.18 = bf16[24,1,768]{2,1,0} custom-call(%ck), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode)/copy(x)"}
  ROOT %kv_page_write.2 = (bf16[12,24592,768]{2,1,0}, bf16[12,24592,768]{2,1,0}) custom-call(%ck, %ck), custom_call_target="tpu_custom_call"
}
"""
    assert count_pool_relayouts(text, 12 * 24592 * 768 * 2) == 2
    assert count_pool_relayouts(text, 24592 * 768 * 2) == 1
    assert count_pool_relayouts(text, 12 * 24592 * 768) == 0
    assert gather_engine.pool_relayouts() == 0


# ----------------------------------------------------------------- knob
def test_resolve_serve_attn_semantics():
    old = pa.INTERPRET
    try:
        pa.INTERPRET = False
        # plain CPU: auto must decline so default runs are unchanged
        assert pa.resolve_serve_attn("auto") == "gather"
        assert pa.resolve_serve_attn("gather") == "gather"
        with pytest.raises(ValueError, match="FFTPU_PALLAS_INTERPRET"):
            pa.resolve_serve_attn("paged")
        pa.INTERPRET = True
        assert pa.supported()
        assert pa.resolve_serve_attn("auto") == "paged"
        assert pa.resolve_serve_attn("paged") == "paged"
        assert pa.resolve_serve_attn("gather") == "gather"
        with pytest.raises(ValueError, match="expected auto"):
            pa.resolve_serve_attn("dense")
    finally:
        pa.INTERPRET = old


def test_env_interpret_override(monkeypatch):
    monkeypatch.delenv("FFTPU_PALLAS_INTERPRET", raising=False)
    assert env_interpret() is False
    assert env_interpret(default=True) is True
    for v in ("1", "true", "ON", "Yes"):
        monkeypatch.setenv("FFTPU_PALLAS_INTERPRET", v)
        assert env_interpret() is True
    for v in ("0", "false", "off", "NO"):
        monkeypatch.setenv("FFTPU_PALLAS_INTERPRET", v)
        assert env_interpret(default=True) is False
    with pytest.warns(UserWarning, match="FFTPU_PALLAS_INTERPRET"):
        monkeypatch.setenv("FFTPU_PALLAS_INTERPRET", "maybe")
        assert env_interpret() is False


@pytest.fixture(scope="module")
def gather_engine(model):
    """One shared explicit-gather engine (engines are reusable across
    runs, test_serve.py); also the ffcheck negative-test subject."""
    return ServeEngine(model, slots=SLOTS, block_size=8, sync_every=4,
                       attn="gather")


def test_gather_mode_is_the_default_engine(model, gather_engine):
    """attn='gather' and CPU-auto resolve identically and produce the
    exact streams of an engine that never heard of the knob."""
    old = pa.INTERPRET
    pa.INTERPRET = False
    try:
        _check_gather_default(model, gather_engine)
    finally:
        pa.INTERPRET = old


def _check_gather_default(model, gather_engine):
    reqs_a = synthetic_requests(TrafficSpec(
        n_requests=2, seed=2, rate_rps=0.0, prompt_len=(2, 6),
        max_new=(2, 4), vocab=VOCAB,
    ))
    reqs_b = synthetic_requests(TrafficSpec(
        n_requests=2, seed=2, rate_rps=0.0, prompt_len=(2, 6),
        max_new=(2, 4), vocab=VOCAB,
    ))
    auto = ServeEngine(model, slots=SLOTS, block_size=8, sync_every=4)
    assert auto.attn_kernel == "gather"  # declined: no TPU, no interpret
    assert gather_engine.attn_kernel == "gather"
    auto.run(reqs_a)
    gather_engine.run(reqs_b)
    assert _streams(reqs_a) == _streams(reqs_b)


# ------------------------------------------------------------ engine A/B
@pytest.mark.parametrize("block_size", [4, 16])
def test_paged_streams_bit_identical_across_block_sizes(
    model, gather_engine, interpret, block_size
):
    """Non-default page geometries (the default block_size=8 rides the
    prefix/preemption/speculative tests below).  Greedy streams are
    block-size-invariant, so the shared bs=8 gather engine is the
    reference for both; its streams equal the solo decode already
    (test_serve.py pins), closing paged == solo."""
    reqs_g = synthetic_requests(TrafficSpec(
        n_requests=4, seed=4, rate_rps=0.0, prompt_len=(2, 9),
        max_new=(2, 6), vocab=VOCAB,
    ))
    reqs_p = synthetic_requests(TrafficSpec(
        n_requests=4, seed=4, rate_rps=0.0, prompt_len=(2, 9),
        max_new=(2, 6), vocab=VOCAB,
    ))
    page = ServeEngine(model, slots=SLOTS, block_size=block_size,
                       sync_every=4, attn="paged")
    assert page.attn_kernel == "paged"
    rg = gather_engine.run(reqs_g)
    rp = page.run(reqs_p)
    assert rg.requests_finished == rp.requests_finished == 4
    assert _streams(reqs_g) == _streams(reqs_p)
    page.kv.check_invariants()


def test_paged_write_kernel_streams_equal_scatter_engine(
    model, gather_engine, interpret
):
    """The one choice the engine makes: ``paged`` programs write new
    K/V rows through the page-write kernel, ``gather`` programs through
    the XLA scatter.  More requests than slots, prompts longer than a
    prefill chunk and than a page: batched chunked prefill (short
    tails, idle lanes), decode, and every slot recycled — the greedy
    streams are the same, and both engines say which writer they ran."""
    def traffic():
        return synthetic_requests(TrafficSpec(
            n_requests=3 * SLOTS + 1, seed=27, rate_rps=0.0,
            prompt_len=(3, 21), max_new=(2, 7), vocab=VOCAB,
        ))

    page = ServeEngine(model, slots=SLOTS, block_size=8, prefill_chunk=5,
                       sync_every=3, attn="paged")
    assert page.kv_write == "page_kernel"
    assert gather_engine.kv_write == "xla_scatter"
    reqs_p, reqs_g = traffic(), traffic()
    rp = page.run(reqs_p)
    rg = gather_engine.run(reqs_g)
    assert rp.requests_finished == rg.requests_finished == 3 * SLOTS + 1
    assert rp.prefill_dispatches < rp.prefill_chunks  # lanes co-prefilled
    assert _streams(reqs_p) == _streams(reqs_g)
    assert rp.kv_write == "page_kernel" and rg.kv_write == "xla_scatter"
    assert rp.to_dict()["kv_write"] == "page_kernel"
    page.kv.check_invariants()
    # the audits keep passing on the new programs: the write path's
    # gather of a chunk's rows into page shape (here 2 * 4 lanes * 2
    # pages: more than one lane's 6 pages) is not a gather from the pool
    from flexflow_tpu.analysis import analyze_serve_engine

    rep = analyze_serve_engine(page, checks=["paged_attn", "serve_cow"])
    assert rep.ok, rep.format_human()


def test_paged_composes_with_prefix_sharing(model, interpret):
    """CoW prefix sharing under the paged kernel: reads on shared pages
    only, streams bit-identical to the unshared gather engine."""
    def traffic():
        return synthetic_requests(TrafficSpec(
            n_requests=4, seed=3, rate_rps=0.0, prompt_len=(2, 6),
            max_new=(2, 6), vocab=VOCAB, tenants=1, shared_prefix=16,
        ))

    page = ServeEngine(model, slots=SLOTS, block_size=8, num_blocks=13,
                       sync_every=2, prefix_sharing=True, attn="paged")
    gath = ServeEngine(model, slots=SLOTS, block_size=8, num_blocks=13,
                       sync_every=2, prefix_sharing=False, attn="gather")
    reqs_p, reqs_g = traffic(), traffic()
    rep_p = page.run(reqs_p)
    gath.run(reqs_g)
    assert rep_p.prefix_hit_rate is not None and rep_p.prefix_hit_rate > 0
    assert _streams(reqs_p) == _streams(reqs_g)
    assert page.kv.shared_write_hazards() == []
    page.kv.check_invariants()


def test_paged_spill_restore_preemption_bit_identical(
    model, interpret, tmp_path
):
    """An interactive request preempts a mid-flight batch decode on the
    paged engine; the victim spills, restores, and every stream equals
    its solo decode — the restored pages land wherever the free list
    says, so this exercises fresh block tables mid-generation.  The
    same run's metrics stream carries the additive ``attn_kernel``
    field, and serve_report renders it with and without the field
    (old/new stream interop)."""
    out = tmp_path / "paged.jsonl"
    eng = ServeEngine(model, slots=2, block_size=8, sync_every=2,
                      attn="paged", metrics_out=str(out))
    rng = np.random.default_rng(5)
    b0 = eng.submit(rng.integers(0, VOCAB, size=(4,)).astype(np.int32), 16,
                    tenant="acme", tier="batch")
    b1 = eng.submit(rng.integers(0, VOCAB, size=(4,)).astype(np.int32), 16,
                    tenant="acme", tier="batch")
    eng.sched.admit()
    eng._t0 = eng._now()
    for _ in range(6):
        eng._window()
    assert b0.state is RequestState.DECODE
    assert b1.state is RequestState.DECODE
    it = eng.submit(rng.integers(0, VOCAB, size=(3,)).astype(np.int32), 6,
                    tenant="vip", tier="interactive")
    rep = eng.run()
    assert rep.requests_finished == 3
    assert eng.sched.preemptions == 1 and b1.preemptions == 1
    for r in (b0, b1, it):
        assert r.state is RequestState.FINISHED
        np.testing.assert_array_equal(
            np.asarray(r.tokens, np.int32), _solo(model, r)
        )
    eng.kv.check_invariants()

    # metrics vocabulary: additive ffmetrics/1 attn_kernel field
    from flexflow_tpu.obs import read_metrics

    recs = read_metrics(str(out))
    assert recs
    assert all(
        r["metrics"]["serve"]["attn_kernel"] == "paged" for r in recs
    )
    # old/new stream interop: serve_report renders a pre-r14 stream
    # (no attn_kernel) and the new stream through the same code path
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools",
    ))
    import serve_report

    assert serve_report.render(recs)  # new stream renders
    old = json.loads(json.dumps(recs))
    for r in old:
        r["metrics"]["serve"].pop("attn_kernel")
    assert serve_report.render(old)  # old stream still renders


def test_paged_speculative_verify_bit_identical(model, interpret):
    """Draft (G=1) and verify (G=k+1) both run the paged kernel; the
    emitted streams must still be exactly the plain greedy streams.
    (The ffcheck ``paged_attn`` CLEAN audit over paged decode / draft /
    verify programs runs in tier-0 — tools/ffcheck.py gpt_decode +
    disagg configs; the negative case is pinned below.)"""
    page = ServeEngine(model, slots=SLOTS, block_size=8, sync_every=4,
                       spec_k=2, attn="paged")
    reqs = synthetic_requests(TrafficSpec(
        n_requests=3, seed=8, rate_rps=0.0, prompt_len=(2, 6),
        max_new=(3, 6), vocab=VOCAB,
    ))
    rep = page.run(reqs)
    assert rep.requests_finished == 3
    assert rep.spec_k == 2 and rep.spec_drafted > 0
    for r in reqs:
        np.testing.assert_array_equal(
            np.asarray(r.tokens, np.int32), _solo(model, r)
        )
    page.kv.check_invariants()


# ------------------------------------------------------------- ffcheck
def test_ffcheck_paged_attn_fires_on_gather_program(gather_engine):
    """A gather program claiming ``serve_attn: paged`` must trip the
    audit: the decode jaxpr materializes a pool-virtual-length gather
    that the paged kernel exists to delete."""
    from flexflow_tpu.analysis import analyze_serve_engine

    eng = gather_engine
    # honest gather engines are out of scope: the check skips
    rep = analyze_serve_engine(eng, checks=["paged_attn"])
    assert not [v for v in rep.violations if v.check == "paged_attn"]
    eng.attn_kernel = "paged"  # the lie
    try:
        rep = analyze_serve_engine(eng, checks=["paged_attn"])
    finally:
        eng.attn_kernel = "gather"
    hits = [v for v in rep.violations if v.check == "paged_attn"]
    assert hits and not rep.ok
    assert hits[0].severity == "error"
    assert "gather" in hits[0].message
    assert hits[0].details["nbytes"] >= hits[0].details["lane_kv_bytes"]


@pytest.mark.parametrize("check", ["paged_attn", "kv_quant"])
def test_ffcheck_pool_audits_refuse_to_skip_an_unrecognised_pool(check):
    """The two pool audits find the pool by its label and its rank,
    ``(L, num_blocks * BS, H * D)``.  A program that makes the claim
    (``serve_attn: paged`` / ``kv_dtype: int8``) and shows no such input
    — a pool in another geometry, another label — is a violation: the
    audit had nothing to hold the claim against, and says so instead of
    passing in silence."""
    import jax

    from flexflow_tpu.analysis import analyze_program, capture_jit

    details = {
        "serve_attn": "paged", "kv_dtype": "int8", "block_size": 4,
        "max_blocks_per_seq": 3, "slots": 2,
    }

    def program(ck, cv, tok):
        return tok, ck, cv

    def audit(pool_shape, names=("cache_k", "cache_v", "tok")):
        pool = jnp.zeros(pool_shape, jnp.int8)
        art = capture_jit(
            "serve.decode", "decode", jax.jit(program),
            (pool, pool, jnp.zeros((2,), jnp.int32)),
            arg_names=names, details=details, expects_donation=False,
        )
        return [v for v in analyze_program(art, checks=[check])
                if v.check == check]

    assert audit((2, 7 * 4, 2 * 8)) == []  # the pool as it is
    for hits in (
        audit((2, 7, 2, 4, 8)),  # the geometry before ISSUE 29
        audit((2, 7 * 4, 2 * 8), names=("pool_k", "pool_v", "tok")),
    ):
        assert len(hits) == 1 and hits[0].severity == "error"
        assert "K/V pool" in hits[0].message


