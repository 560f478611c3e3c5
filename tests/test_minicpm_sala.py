"""Serving the tiny ``minicpm_sala`` model -- an InfLLM-v2 block-sparse
attention layer and three lightning-attention layers, MiniCPM's muP
scalings -- through ``ServeEngine``'s chunked prefill and decode, against
``benchmarks/reference/minicpm_sala.py``'s one full forward: the
lightning decay and the two forms of its scan at published layer
indices, the sparse op at a top-k that covers every block (dense
attention), the compressed keys, block scores and selection against
brute force, the logits behind served tokens on both attention arms,
the keys the selection prunes, the index and the conv-less state
through spill and restore, and what the planted faults look like.

Sizes: hidden 256, 4 / 2 heads of 64, vocabulary 512; the sparse sizes
cut so that selection prunes at these lengths (kernel 8, stride 4, block
16, top-k 4, window 32, dense below 64)."""

from __future__ import annotations

import math
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks import weights_sala as WS  # noqa: E402
from benchmarks.jobs import serve_sala as SS  # noqa: E402
from benchmarks.reference import minicpm_sala as R  # noqa: E402
from flexflow_tpu import FFConfig, FFModel, MachineMesh  # noqa: E402
from flexflow_tpu.models.gpt_decode import GPTSpec  # noqa: E402
from flexflow_tpu.models.minicpm_sala import minicpm_sala_decoder  # noqa: E402
from flexflow_tpu.ops import sparse_attention as SA  # noqa: E402
from flexflow_tpu.ops.attention import sdpa  # noqa: E402
from flexflow_tpu.ops.linear_attention import lightning_decay  # noqa: E402
from flexflow_tpu.ops.pallas import paged_attention as pa  # noqa: E402
from flexflow_tpu.ops.ssm import ssd_chunked, ssd_step  # noqa: E402
from flexflow_tpu.serve import Request, ServeEngine  # noqa: E402
from flexflow_tpu.serve.engine import UnsupportedServeConfig  # noqa: E402
from flexflow_tpu.serve.kvcache import PagedKVCache  # noqa: E402

import _compact_prefill as CP  # noqa: E402

SPARSE = dict(kernel=8, stride=4, block=16, topk=4, window=32, dense_len=64)
# published layers 3-6 of an 8-layer stack: sparse, then three lightning
ALL_TYPES = ["lightning-attn"] * 3 + ["minicpm4"] + ["lightning-attn"] * 3 + ["minicpm4"]
IDS = [3, 4, 5, 6]
CFG = dict(
    hidden_size=256, num_attention_heads=4, num_key_value_heads=2, head_dim=64,
    intermediate_size=384, vocab_size=512, lightning_nh=4, lightning_head_dim=64,
    published_num_hidden_layers=8, layer_ids=IDS, mixer_types=[ALL_TYPES[l] for l in IDS],
    rope_theta=10000.0, rms_norm_eps=1e-6, scale_emb=12.0, scale_depth=1.4, dim_model_base=64,
    sparse_config=dict(kernel_size=8, kernel_stride=4, block_size=16, topk=4, window_size=32,
                       dense_len=64),
)
ARGS = dict(hidden=256, heads=4, kv_heads=2, head_dim=64, ff_dim=384, vocab=512, num_layers=8,
            mixer_types=ALL_TYPES, layer_ids=IDS, lightning_heads=4, lightning_head_dim=64,
            dim_model_base=64, **SPARSE)
SLOTS, SEQ, SEED = 3, 160, 7
SHAPES = R.param_shapes(CFG)
# prompts past dense_len (sparse prefill), one that crosses it while it
# decodes, chunks of 32 = two blocks of queries; four requests, three slots
LENS = [(100, 20), (40, 30), (70, 12), (130, 25)]


def build(**over):
    m = FFModel(FFConfig(batch_size=SLOTS))
    minicpm_sala_decoder(m, SLOTS, SEQ, **dict(ARGS, **over))
    m.compile(seed=0, mesh=MachineMesh((1, 1), ("data", "model")))
    WS.fill_executor(SHAPES, SEED, m.executor)
    return m


@pytest.fixture(scope="module")
def model():
    return build()


@pytest.fixture(scope="module")
def ref_params():
    return WS.tree(SHAPES, SEED)


def requests(lens=LENS):
    rng = np.random.default_rng(5)
    return [
        Request(prompt=rng.integers(0, 512, size=(p,)).astype(np.int32), id=i, max_new_tokens=n)
        for i, (p, n) in enumerate(lens)
    ]


def engine_of(model, **kw):
    return ServeEngine(model, slots=SLOTS, block_size=16, prefill_chunk=32, sync_every=4, **kw)


# --------------------------------------------------------- lightning
@pytest.mark.parametrize("l", [10, 15])
def test_lightning_decay_at_published_indices(l):
    lam = lightning_decay(32, l, 32)
    h = np.arange(32)
    want = 2.0 ** (-8.0 * (h + 1) / 32) * (1 - l / 31 + 1e-5)
    np.testing.assert_allclose(lam, want, rtol=1e-6)
    cfg = dict(CFG, lightning_nh=32, published_num_hidden_layers=32)
    np.testing.assert_allclose(R.decays(cfg, l), np.exp(-want), rtol=1e-6)


@pytest.mark.parametrize("l", [10, 15])
def test_ssd_chunked_against_step_with_the_lightning_decay(l):
    """Lightning attention as SSD (dt 1, A = -lambda, B = k, C = q, x =
    v, a group a head): the chunked form, in two calls with the state
    carried, against ``ssd_step`` a position at a time, at the decay of
    published layer ``l`` of 32; rows past ``n_valid`` (dt 0) leave the
    state alone."""
    rng = np.random.default_rng(l)
    b, s, h, d = 2, 40, 4, 8
    x, B, C = (jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32) for _ in range(3))
    A = -jnp.asarray(lightning_decay(h, l, 32))
    dt = jnp.ones((b, s, h), jnp.float32)
    y1, S1 = ssd_chunked(x[:, :24], dt[:, :24], A, B[:, :24], C[:, :24], chunk=16)
    y2, S2 = ssd_chunked(x[:, 24:], dt[:, 24:], A, B[:, 24:], C[:, 24:], chunk=16, state=S1)
    S = jnp.zeros((b, h, d, d), jnp.float32)
    for t in range(s):
        yt, S = ssd_step(x[:, t], dt[:, t], A, B[:, t], C[:, t], S)
        want = y1[:, t] if t < 24 else y2[:, t - 24]
        np.testing.assert_allclose(yt, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(S, S2, rtol=2e-5, atol=2e-5)
    dt0 = dt.at[1, 10:].set(0.0)
    _, S3 = ssd_chunked(x, dt0, A, B, C, chunk=16)
    _, S4 = ssd_chunked(x[1:, :10], dt[1:, :10], A, B[1:, :10], C[1:, :10], chunk=16)
    np.testing.assert_allclose(S3[1], S4[0], rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------ sparse
def test_sparse_op_covering_every_block_is_dense_attention():
    """With a top-k past the blocks there are, every query reads every
    earlier key: the rule is causal attention."""
    rng = np.random.default_rng(1)
    b, s, H, g, d = 2, 64, 4, 2, 16
    q = jnp.asarray(rng.standard_normal((b, s, H, d)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((b, s, g, d)), jnp.float32) for _ in range(2))
    nb = s // 8
    a = dict(kernel=8, stride=4, block=8, window=8, dense_len=0, topk=nb + 2)
    got = SA.sparse_attention(q, k, v, a)
    want = sdpa(*(t.transpose(0, 2, 1, 3) for t in (q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2))),
                causal=True).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # and at the tiny model's sizes it is not
    pruned = SA.sparse_attention(q, k, v, dict(a, topk=3, dense_len=24))
    assert float(jnp.abs(pruned - want).max()) > 1e-2


def test_compressed_keys_and_block_scores_against_brute_force():
    rng = np.random.default_rng(2)
    b, s, H, g, d = 1, 48, 4, 2, 8
    kern, st, blk = 8, 4, 16
    k = rng.standard_normal((b, s, g, d)).astype(np.float32)
    q = rng.standard_normal((b, s, H, d)).astype(np.float32)
    J = s // st
    kbar = np.asarray(SA.compress_keys(jnp.asarray(k), kern, st, J))
    for j in range(J):
        if st * j + kern <= s:
            np.testing.assert_allclose(kbar[0, j], k[0, st * j:st * j + kern].mean(0), rtol=1e-5)
    t = np.arange(s)[None]
    nk = np.asarray(SA.kernels_at(jnp.asarray(t), kern, st))
    r = np.asarray(SA.block_scores(jnp.asarray(q), jnp.asarray(kbar),
                                   jnp.asarray(nk), kernel=kern, stride=st, block=blk))
    for tt in range(0, s, 5):
        there = [j for j in range(J) if st * j + kern - 1 <= tt]
        assert nk[0, tt] == len(there)
        for gg in range(g):
            want = np.zeros(s // blk)
            for h in range(gg * 2, gg * 2 + 2):
                if not there:
                    continue
                sc = np.array([q[0, tt, h] @ kbar[0, j, gg] / math.sqrt(d) for j in there])
                p = np.exp(sc - sc.max()) / np.exp(sc - sc.max()).sum()
                for bb in range(s // blk):
                    over = [p[i] for i, j in enumerate(there)
                            if st * j <= blk * bb + blk - 1 and st * j + kern - 1 >= blk * bb]
                    want[bb] += max(over, default=0.0)
            np.testing.assert_allclose(r[0, tt, gg], want, rtol=1e-5, atol=1e-6)


def brute_select(score, t_first, t_last, *, block, topk, window, dense_len):
    c = t_last // block
    if t_last + 1 <= dense_len:
        return set(range(c + 1))
    lo = max(t_first - window + 1, 0) // block
    chosen = {0} | set(range(lo, c + 1))
    cands = sorted(range(1, lo), key=lambda bb: (-score[bb], bb))
    return chosen | set(cands[:topk - len(chosen)])


@pytest.mark.parametrize("sizes", [dict(block=16, topk=4, window=32, dense_len=64),
                                   dict(block=64, topk=64, window=2048, dense_len=8192)],
                         ids=["tiny", "published"])
def test_selection_against_brute_force_top_k(sizes):
    rng = np.random.default_rng(3)
    nb = 288 if sizes["block"] == 64 else 40
    n = 64
    score = rng.random((n, nb)).astype(np.float32)
    blk = sizes["block"]
    t_last = rng.integers(0, nb * blk, n)
    # half the units a single query, half a block of queries from its start
    t_first = np.where(np.arange(n) % 2 == 0, t_last, t_last // blk * blk)
    sel = np.asarray(SA.select_blocks(jnp.asarray(score), jnp.asarray(t_first),
                                      jnp.asarray(t_last), **sizes))
    table, n_sel = (np.asarray(a) for a in SA.selected_table(jnp.asarray(sel), nb))
    for i in range(n):
        want = brute_select(score[i], int(t_first[i]), int(t_last[i]), **sizes)
        assert set(np.flatnonzero(sel[i])) == want, i
        assert list(table[i, :n_sel[i]]) == sorted(want)
        if t_last[i] + 1 > sizes["dense_len"]:
            assert n_sel[i] == sizes["topk"]


# ------------------------------------------------------- whole model
def test_spec_reads_the_sparse_and_lightning_layers(model):
    s = GPTSpec.from_model(model)
    assert [b.kind for b in s.branches] == ["sparse", "gated_ffn"] + ["lightning", "gated_ffn"] * 3
    assert all(b.scale == pytest.approx(1.4 / math.sqrt(8)) for b in s.branches)
    assert s.embed_scale == 12.0 and s.head_scale == 64 / 256 and s.norm == "rms"
    sp = s.branches[0]
    assert (sp.heads, sp.kv_heads, sp.head_dim, sp.pool_layers) == (4, 2, 64, 2)
    assert sp.attrs == SPARSE
    assert [b.attrs["layer_idx"] for b in s.state_layers] == [4, 5, 6]


def test_ffmodel_forward_against_reference(model, ref_params):
    """The op's forward (a selection per query) against the reference
    handed a one-position prompt (every later position its own unit)."""
    toks = np.random.default_rng(0).integers(0, 512, (SLOTS, SEQ)).astype(np.int32)
    probs = np.asarray(model.eval_batch([toks])).reshape(SLOTS, SEQ, -1)
    rows = np.tile(np.arange(SEQ)[None], (SLOTS, 1))
    ref = jax.nn.log_softmax(R.logits_at(ref_params, toks, rows, CFG)[0], -1)
    np.testing.assert_allclose(np.log(probs), np.asarray(ref), atol=2e-5)


def served_logit_gap(model, ref_params, monkeypatch, attn):
    """The widest gap between the log-probabilities the engine served
    with (a temperature that still picks the argmax) and the
    reference's log-softmax, over every served position."""
    monkeypatch.setattr(pa, "INTERPRET", attn == "paged")
    CP.group_rows(monkeypatch, 2, 32)
    eng = engine_of(model, attn=attn, temperature=1e-4)
    assert eng.attn_kernel == attn and eng.prefill_group_lanes == 2
    if attn == "paged":
        # the sparse layers' walks, a query and a block of queries a unit:
        # 2 query heads of 64 lanes over a K/V head, block-diagonal
        one = {"tiles": 1, "heads": "block_diagonal"}
        walk = eng.attn_walk()
        assert walk["sparse_attend"] == {"decode": one, "prefill": one}
        assert "prefill" not in walk  # no attention of another kind
    seen = {}

    def capture(prog, pos_of):
        def run(*args):
            res = prog(*args)
            for slot, r in eng.sched.active.items():
                p = pos_of(args, slot)
                if p is not None:
                    seen[(r.id, p)] = np.asarray(res[1][slot])
            return res
        return run

    eng._decode = capture(eng._decode, lambda a, s: int(a[-2][s]))
    eng._prefill = capture(
        eng._prefill,
        lambda a, s: int(a[-3][s] + a[-2][s] - 1) if int(a[-2][s]) else None,
    )
    rep = eng.run(requests())
    assert rep.requests_finished == 4 and rep.host_syncs == rep.windows
    eng.kv.check_invariants()
    worst = 0.0
    for r in requests():
        done = next(d for d in rep.per_request if d["id"] == r.id)
        toks = np.concatenate([r.prompt, done["tokens"]]).astype(np.int32)[None]
        rows = (len(r.prompt) - 1 + np.arange(len(done["tokens"])))[None]
        ref = np.asarray(jax.nn.log_softmax(R.logits_at(ref_params, toks, rows, CFG)[0], -1))[0]
        for j, p in enumerate(rows[0]):
            worst = max(worst, np.abs(np.log(seen[(r.id, int(p))]) - ref[j]).max())
    return worst


@pytest.mark.parametrize("attn", ["gather", "paged"])
def test_logits_behind_served_tokens(model, ref_params, monkeypatch, attn):
    """Prompts past ``dense_len`` prefilled in chunks of two blocks of
    queries (groups of two lanes), then decoded a query at a time through
    the index, the selected pages and the lightning states: the served
    log-probabilities against the reference's at every served position,
    to a float32 tolerance."""
    assert served_logit_gap(model, ref_params, monkeypatch, attn) < 5e-5


def test_served_tokens_are_the_reference_argmax_and_selection_prunes(model, ref_params):
    """Greedy, seven requests over three slots: every served token is the
    reference's argmax, and the logit the engine kept for it the
    reference's; the sparse layer's queries read fewer keys than precede
    them, as counted on the device, exactly the reckoning from the
    requests' lengths (a query at ``t`` past ``dense_len`` reads its
    ``topk`` blocks up to itself, ``(topk - 1) block + t % block + 1``
    keys, every key before it otherwise; decode calls the positions past
    each prompt); the pool holds a layer a K/V head and the index and
    state groups their shapes."""
    eng = engine_of(model, attn="gather", token_logits=True)
    kv = eng.kv
    assert (kv.num_layers, kv.heads, kv.state_layers, kv.index_layers) == (2, 1, 3, 1)
    assert [a.shape for a in kv.state_ssm] == [(SLOTS, 4, 64, 64)] * 3
    assert kv.state_conv == [None] * 3
    assert [a.shape for a in kv.index_k] == [(SLOTS, SEQ // 4, 2, 64)]
    lens = LENS + [(90, 9), (66, 14), (16, 40)]
    rep = eng.run(requests(lens))
    assert rep.requests_finished == len(lens)
    src = {r.id: r for r in requests(lens)}
    served = {r.id: r for r in eng.sched.finished}
    for d in rep.per_request:
        r = src[d["id"]]
        toks = np.concatenate([r.prompt, d["tokens"]]).astype(np.int32)[None]
        rows = (len(r.prompt) - 1 + np.arange(len(d["tokens"])))[None]
        logits = np.asarray(served[d["id"]].token_logits)[None]
        assert logits.shape == rows.shape
        gaps, errs, _ = R.served_readings(ref_params, toks, rows, np.asarray(d["tokens"])[None],
                                          logits, np.ones_like(rows, bool), CFG)
        assert float(np.max(gaps)) == 0.0
        assert float(np.max(errs)) < 1e-4
    want = np.zeros((2, 2))  # (all calls, decode calls) x (read, visible)
    for p, n in lens:
        for t in range(p + n - 1):
            keys = (t + 1 if t < 64 else min(t + 1, 3 * 16 + t % 16 + 1), t + 1)
            want[0] += keys
            if t >= p:
                want[1] += keys
    want *= 2  # a K/V head each
    assert (rep.sparse_keys_attended, rep.sparse_keys_visible) == tuple(want[0])
    assert (rep.sparse_decode_keys_attended, rep.sparse_decode_keys_visible) == tuple(want[1])
    assert rep.sparse_keys_attended < 0.8 * rep.sparse_keys_visible
    assert rep.index_pool_bytes == kv.index_bytes() == SLOTS * 2 * 40 * 64 * 4
    kv.check_invariants()


def test_index_and_state_survive_spill_and_restore():
    kv = PagedKVCache(2, 1, 8, slots=3, block_size=4, max_seq_len=32,
                      state_shapes=[(None, (2, 8, 8))], index_shapes=[(8, 2, 8)])
    assert not kv.prefix_sharing
    rng = np.random.default_rng(4)
    kv.reserve(1, 20)
    kv.index_k = [jnp.asarray(rng.standard_normal(a.shape), a.dtype) for a in kv.index_k]
    kv.state_ssm = [jnp.asarray(rng.standard_normal(a.shape), a.dtype) for a in kv.state_ssm]
    kv.cache_k = jnp.asarray(rng.standard_normal(kv.cache_k.shape), kv.cache_k.dtype)
    idx_before = np.asarray(kv.index_k[0][1])
    ssm_before = np.asarray(kv.state_ssm[0][1])
    k_before, _ = kv.gather_dense(1, 18)
    payload = kv.spill(1, 18)
    assert set(payload) >= {"state", "index"}
    assert payload["state"]["layers"]["layer0"]["conv"] is None
    kv.restore(2, payload, 20)
    np.testing.assert_array_equal(np.asarray(kv.index_k[0][2]), idx_before)
    np.testing.assert_array_equal(np.asarray(kv.state_ssm[0][2]), ssm_before)
    np.testing.assert_array_equal(kv.gather_dense(2, 18)[0], k_before)
    assert kv.hbm_bytes() == (2 * kv.cache_k.size * 4 + kv.state_bytes() + kv.index_bytes())
    kv.check_invariants()
    without = dict(payload)
    without.pop("index")
    with pytest.raises(ValueError, match="index layers"):
        kv.restore(0, without, 20)


def test_engine_refuses_a_page_that_is_not_a_block(model):
    with pytest.raises(UnsupportedServeConfig, match="block_size"):
        ServeEngine(model, slots=SLOTS, block_size=8, prefill_chunk=32)
    with pytest.raises(UnsupportedServeConfig, match="whole blocks"):
        ServeEngine(model, slots=SLOTS, block_size=16, prefill_chunk=24)
    with pytest.raises(UnsupportedServeConfig, match="token_logits"):
        engine_of(model, temperature=0.5, token_logits=True)


@pytest.mark.parametrize("fault", SS.FAULTS)
def test_planted_faults_move_the_logits(ref_params, monkeypatch, fault):
    """The benchmark's planted faults move the logits far past the
    float32 tolerance the sound model is held to: the sparse layer
    attending every key (the builder's argument; the op's forward), the
    lightning state rounded to bfloat16 at the end of every scan and
    step, and no decay (both patched into the ops while the engine is
    built and run; what the engine served).  The patches are undone
    after."""
    import flexflow_tpu.ops.linear_attention as LA
    import flexflow_tpu.ops.ssm as SSM

    sound = (SSM.ssd_chunked, SSM.ssd_step, LA.lightning_decay)
    with SS.planted(fault) as builder_args:
        if fault in SS.PATCHED_FAULTS:
            gap = served_logit_gap(build(), ref_params, monkeypatch, "gather")
        else:
            toks = np.random.default_rng(0).integers(0, 512, (SLOTS, SEQ)).astype(np.int32)
            m = build(**builder_args)
            probs = np.asarray(m.eval_batch([toks])).reshape(SLOTS, SEQ, -1)
            rows = np.tile(np.arange(SEQ)[None], (SLOTS, 1))
            ref = jax.nn.log_softmax(R.logits_at(ref_params, toks, rows, CFG)[0], -1)
            gap = np.abs(np.log(probs) - np.asarray(ref)).max()
    assert gap > 1e-3
    assert (SSM.ssd_chunked, SSM.ssd_step, LA.lightning_decay) == sound


def test_published_parameter_count():
    """Layers 9-16 as published: two sparse layers (253.76 M each), six
    lightning layers (285.23 M each), the embedding and the untied head."""
    types = ["lightning-attn"] * 32
    for l in (9, 16):
        types[l] = "minicpm4"
    ids = list(range(9, 17))
    big = dict(CFG, hidden_size=4096, num_attention_heads=32, num_key_value_heads=2,
               head_dim=128, intermediate_size=16384, vocab_size=73448, lightning_nh=32,
               lightning_head_dim=128, published_num_hidden_layers=32, layer_ids=ids,
               mixer_types=[types[l] for l in ids])
    shapes = R.param_shapes(big)
    per = {n: sum(math.prod(s) for s in ws.values()) for n, ws in shapes.items()}
    assert per["l9_attn"] + per["l9_mlp"] == 253_755_648
    assert per["l10_lightning"] + per["l10_mlp"] == 285_217_024
    assert per["tok_embed"] + per["lm_head"] == 601_686_016
    assert sum(per.values()) == 2_820_569_088  # 5.64 GB in bfloat16
