"""Serving a decoder that is not ``gpt_decoder`` (ISSUE 32): the tiny
``afmoe`` model -- gated grouped-query attention, window and full
layers over a pool that keeps the two kinds apart, sigmoid-routed
experts beside a shared one -- through ``ServeEngine``'s chunked
prefill and paged decode, against ``benchmarks/reference/afmoe.py``'s
one full forward; the decoder spec, the pool's two groups, what is
refused, the ops against the reference's pieces, and the weights made
leaf by leaf."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks import weights_by_leaf as WL  # noqa: E402
from benchmarks.reference import afmoe as R  # noqa: E402
from benchmarks.reference.precision import matmul  # noqa: E402
from flexflow_tpu import FFConfig, FFModel, MachineMesh  # noqa: E402
from flexflow_tpu.models.afmoe import afmoe_decoder  # noqa: E402
from flexflow_tpu.models.gpt_decode import GPTDecodeSession, GPTSpec  # noqa: E402
from flexflow_tpu.models.transformer import gpt_decoder  # noqa: E402
from flexflow_tpu.ops.pallas import paged_attention as pa  # noqa: E402
from flexflow_tpu.serve import Request, ServeEngine  # noqa: E402
from flexflow_tpu.serve.engine import UnsupportedServeConfig  # noqa: E402
from flexflow_tpu.serve.kvcache import PagedKVCache  # noqa: E402

import _compact_prefill as CP  # noqa: E402
import _traced_calls as TC  # noqa: E402

CFG = dict(
    hidden_size=64, vocab_size=128, num_hidden_layers=5, num_dense_layers=1,
    layer_types=["sliding_attention"] * 4 + ["full_attention"], sliding_window=8,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, intermediate_size=96,
    moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2, num_shared_experts=1,
    rms_norm_eps=1e-5, rope_theta=10000, route_norm=True, route_scale=2.826,
    score_func="sigmoid",
)
ARGS = dict(
    hidden=64, heads=4, ff_dim=32, num_layers=5, vocab=128, kv_heads=2, head_dim=16,
    dense_ff_dim=96, num_dense_layers=1, num_experts=8, top_k=2, shared_ff_dim=32,
    layer_types=CFG["layer_types"], sliding_window=8, use_flash=False,
)
SLOTS, SEQ, SEED = 3, 64, 11
SHAPES = R.param_shapes(CFG)


def build(dtype="float32", param_dtype="float32", batch=SLOTS, seq=SEQ, **over):
    m = FFModel(FFConfig(batch_size=batch, compute_dtype=dtype, param_dtype=param_dtype))
    afmoe_decoder(m, batch, seq, **dict(ARGS, **over))
    m.compile(seed=0, mesh=MachineMesh((1, 1), ("data", "model")))
    WL.fill_executor(SHAPES, SEED, m.executor)
    return m


@pytest.fixture(scope="module")
def model():
    return build()


@pytest.fixture(scope="module")
def ref_params():
    return WL.tree(SHAPES, SEED)


def requests():
    rng = np.random.default_rng(5)
    # below the window, past it, past a chunk, past several; six requests
    # over three slots, so slots are recycled
    lens = [(5, 7), (13, 9), (30, 12), (21, 6), (9, 10), (40, 8)]
    return [
        Request(prompt=rng.integers(0, 128, size=(p,)).astype(np.int32), id=i,
                max_new_tokens=n)
        for i, (p, n) in enumerate(lens)
    ]


def engine_of(model, **kw):
    return ServeEngine(model, slots=SLOTS, block_size=4, prefill_chunk=8, sync_every=4, **kw)


def gaps_of(report, params, precision="highest"):
    reqs = {r["id"]: r for r in report.per_request}
    src = {r.id: r for r in requests()}
    k, s, n = len(reqs), 40 + 12, 12
    tokens = np.zeros((k, s), np.int32)
    rows = np.zeros((k, n), np.int32)
    served = np.zeros((k, n), np.int32)
    valid = np.zeros((k, n), bool)
    for i, (rid, r) in enumerate(sorted(reqs.items())):
        p, t = src[rid].prompt, r["tokens"]
        tokens[i, : len(p)] = p
        tokens[i, len(p): len(p) + len(t)] = t
        rows[i, : len(t)] = len(p) - 1 + np.arange(len(t))
        served[i, : len(t)] = t
        valid[i, : len(t)] = True
    return np.asarray(R.served_gaps(params, tokens, rows, served, valid, CFG, precision)), valid


# ----------------------------------------------------------- the spec
def test_spec_is_read_from_layers_and_attrs(model):
    s = GPTSpec.from_model(model)
    assert (s.num_layers, s.heads, s.kv_heads, s.head_dim, s.hidden, s.vocab) == (5, 4, 2, 16, 64, 128)
    assert s.norm == "rms" and s.pos_embed is None and s.embed_scale == 8.0
    assert [l.window for l in s.layers] == [8, 8, 8, 8, 0] and s.window == 8
    assert [l.rotary_dim for l in s.layers] == [16, 16, 16, 16, 0]
    assert [l.ffn_kind for l in s.layers] == ["gated"] + ["moe"] * 4 and s.has_moe
    assert all(l.norm_post_attn and l.norm_post_ffn and l.attn_kind == "gated" for l in s.layers)
    assert s.layers[1].moe["score"] == "sigmoid" and not s.is_gpt
    with pytest.raises(ValueError, match="gpt_decoder-shaped"):
        GPTDecodeSession(model)


def test_gpt_decoder_reads_as_before():
    m = FFModel(FFConfig(batch_size=2, compute_dtype="float32"))
    gpt_decoder(m, 2, 16, hidden=32, heads=4, ff_dim=64, num_layers=2, vocab=31, use_flash=False)
    m.compile(seed=0)
    s = GPTSpec.from_model(m)
    assert s.is_gpt and (s.num_layers, s.heads, s.kv_heads, s.head_dim) == (2, 4, 4, 8)
    assert (s.embed, s.pos_embed, s.final_norm, s.head) == ("tok_embed", "pos_embed", "final_ln", "lm_head")
    assert [l.ffn for l in s.layers] == [("dec0_ff0", "dec0_ff1"), ("dec1_ff0", "dec1_ff1")]
    assert s.window == 0 and not s.has_moe


def test_a_model_that_is_no_decoder_is_refused_by_name():
    from flexflow_tpu.models.mlp import mlp

    m = FFModel(FFConfig(batch_size=4))
    mlp(m, 4, 16, [8], 4)
    m.compile(seed=0)
    with pytest.raises(ValueError, match="not a decoder the serve programs know"):
        GPTSpec.from_model(m)


# --------------------------------------------------------- whole model
def test_ffmodel_forward_against_reference(model, ref_params):
    toks = np.random.default_rng(0).integers(0, 128, (SLOTS, SEQ)).astype(np.int32)
    probs = np.asarray(model.eval_batch([toks])).reshape(SLOTS, SEQ, -1)
    rows = np.tile(np.arange(SEQ)[None], (SLOTS, 1))
    ref = jax.nn.log_softmax(R.logits_at(ref_params, toks, rows, CFG), -1)
    np.testing.assert_allclose(np.log(probs), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("attn", ["gather", "paged"])
def test_served_tokens_against_one_full_forward(model, ref_params, attn):
    """Chunked prefill, then decode through the paged cache, three slots
    recycled over six requests, contexts past the window (8) and a chunk
    (8): float32, so the greedy streams ARE the reference's (gap 0 at
    every served token)."""
    old = pa.INTERPRET
    pa.INTERPRET = attn == "paged"
    try:
        eng = engine_of(model, attn=attn)
        rep = eng.run(requests())
    finally:
        pa.INTERPRET = old
    assert rep.requests_finished == 6 and rep.host_syncs == rep.windows
    assert eng.attn_kernel == attn
    gaps, valid = gaps_of(rep, ref_params)
    assert valid.sum() == 7 + 9 + 12 + 6 + 10 + 8
    assert gaps.max() == 0.0
    eng.kv.check_invariants()
    # the window is applied in what a layer reads, not only in the mask
    assert 0 < rep.kv_rows_visible < rep.kv_rows_context
    assert rep.kv_pages_held_window == 3 * eng.kv.ring_blocks
    assert rep.moe_layers == 4 and rep.moe_rows > 0
    assert 0 < rep.moe_experts_touched <= 8 * 4 * (rep.decode_steps + rep.prefill_dispatches)
    assert rep.moe_load_max_over_mean >= 1.0


# --------------------------------- prefill a group of mid-prompt lanes at a time
# Widths whose products XLA:CPU gives a row the same bits at any row
# count (8, 16, 64 or 128 columns: one K/V head of 16 under four query
# heads, FFNs of 64); at 32 or 96 columns it sums a row in another order
# at another row count, a few ulp.  A group is then the full-width
# dispatch bit for bit
EXACT = dict(kv_heads=1, ff_dim=64, dense_ff_dim=64, shared_ff_dim=64)


@pytest.fixture(scope="module")
def exact_model():
    m = FFModel(FFConfig(batch_size=SLOTS))
    afmoe_decoder(m, SLOTS, SEQ, **dict(ARGS, **EXACT))
    m.compile(seed=0, mesh=MachineMesh((1, 1), ("data", "model")))
    return m


@pytest.fixture(scope="module")
def wide_engine(exact_model):
    return ServeEngine(exact_model, slots=8, block_size=4, prefill_chunk=8, sync_every=4)


@pytest.mark.parametrize("n_mid", [1, 3, 4, 5, 8])
def test_prefill_in_groups_is_the_full_width_dispatch_bit_for_bit(
        wide_engine, monkeypatch, n_mid):
    """Groups of four mid-prompt lanes against the full-width dispatch,
    through both pool groups (the window layers' rings and the full
    layer's pages): the same tokens, distributions, expert counters and
    pool rows; a lane from position 0 beside lanes past the window."""
    CP.compact_against_full(monkeypatch, wide_engine, 4, n_mid, seed=n_mid)


def test_engine_prefills_a_group_at_a_time(exact_model, monkeypatch):
    want = engine_of(exact_model).run(requests())
    CP.engine_in_groups(
        monkeypatch, lambda: engine_of(exact_model), requests(), 2, 8, want)


def test_expert_rows_taken_in_several_passes(model, ref_params, monkeypatch):
    """A prefill dispatch's sorted expert rows are taken an eighth a
    pass at serving sizes; here 16 rows a pass (48 rows a dispatch, 6 a
    decode step): the passes the device finds cover every row."""
    from flexflow_tpu.serve import programs

    assert programs.serve_pass_rows(768) == 768 and programs.serve_pass_rows(65536) == 8192
    monkeypatch.setattr(programs, "serve_pass_rows", lambda rows: 16)
    rep = engine_of(model, attn="gather").run(requests())
    gaps, _ = gaps_of(rep, ref_params)
    assert rep.requests_finished == 6 and gaps.max() == 0.0


def test_logits_behind_served_tokens_float32(model, ref_params):
    """With sampling on (at a temperature that still picks the argmax)
    the programs return the distribution: its logarithm against the
    reference's log-softmax at every served position, to a float32
    tolerance."""
    eng = engine_of(model, attn="gather", temperature=1e-4)
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
    assert rep.requests_finished == 6
    worst = 0.0
    for r in requests():
        done = next(d for d in rep.per_request if d["id"] == r.id)
        toks = np.concatenate([r.prompt, done["tokens"]]).astype(np.int32)[None]
        rows = (len(r.prompt) - 1 + np.arange(len(done["tokens"])))[None]
        ref = np.asarray(jax.nn.log_softmax(R.logits_at(ref_params, toks, rows, CFG), -1))[0]
        for j, p in enumerate(rows[0]):
            worst = max(worst, np.abs(np.log(seen[(r.id, int(p))]) - ref[j]).max())
    assert worst < 5e-5


def test_served_tokens_bfloat16_weights_at_rest():
    """bfloat16 weights at rest and bfloat16 compute against the float32
    reference: a served token may differ from the reference's argmax
    where two logits lie within bfloat16's rounding of the activations
    (8 bits of mantissa through five layers), so the tolerance is a gap
    of 0.25 in logits of spread ~1; no weight is converted in a call."""
    m = build("bfloat16", "bfloat16")
    leaves = jax.tree.leaves(m.executor.params)
    assert {str(x.dtype) for x in leaves} == {"bfloat16", "float32"}
    assert m.executor.params["l1_moe"]["router"].dtype == jnp.float32
    assert m.executor.params["l1_moe"]["w_gate"].dtype == jnp.bfloat16
    eng = engine_of(m, attn="gather")
    rep = eng.run(requests())
    assert rep.requests_finished == 6
    gaps, _ = gaps_of(rep, WL.tree(SHAPES, SEED))
    assert gaps.max() < 0.25


@pytest.mark.parametrize("over,what", [
    (dict(sliding_window=0), "a sliding layer attending the whole context"),
    (dict(route_scale=1.0), "route_scale left out"),
])
def test_planted_faults_are_caught(ref_params, over, what):
    rep = engine_of(build(**over), attn="gather").run(requests())
    gaps, _ = gaps_of(rep, ref_params)
    assert gaps.max() > 1e-3, what


def test_fp8_control_is_caught(model, ref_params):
    rep = engine_of(model, attn="gather").run(requests())
    gaps, _ = gaps_of(rep, ref_params, "fp8")
    assert gaps.max() > 1e-3


# ------------------------------------------------------------- refusals
@pytest.mark.parametrize("kw,what", [
    (dict(spec_k=2), "speculation"),
    (dict(weight_dtype="int8"), "weight_dtype"),
    (dict(kv_dtype="int8"), "quantized pool"),
    (dict(kv_dtype="fp8"), "quantized pool"),
    (dict(phase="prefill"), "disaggregated or fleet"),
])
def test_what_is_not_served_for_this_spec_is_refused_by_name(model, kw, what):
    with pytest.raises(UnsupportedServeConfig, match=what):
        engine_of(model, **kw)


# ------------------------------------------------------ the pool's groups
def test_window_group_never_holds_more_than_its_bound():
    kv = PagedKVCache(1, 2, 16, slots=3, block_size=4, max_seq_len=4096,
                      window_layers=4, window=8, chunk=8)
    assert kv.ring_blocks == (8 + 8) // 4 + 1 == 5 and not kv.prefix_sharing
    assert kv.win_k.shape == (4, (3 * 5 + 1) * 4, 32) and kv.cache_k.shape[0] == 1
    kv.reserve(0, 4000)
    kv.reserve(2, 12)
    kv.check_invariants()
    assert kv.pages_held() == {"full": 1000 + 3, "window": 10}
    assert (kv.win_tables[1] == 0).all() and set(kv.win_tables[0]) == {1, 2, 3, 4, 5}
    assert kv.bytes_per_token == 2 * 5 * 32 * 4
    assert kv.hbm_bytes() == 2 * 4 * (kv.cache_k.size + kv.win_k.size)
    kv.release(0)
    kv.check_invariants()
    assert kv.pages_held() == {"full": 3, "window": 5}
    kv.win_tables[1, 0] = 7  # a table row that is not the slot's ring
    with pytest.raises(AssertionError, match="window group"):
        kv.check_invariants()


def test_spill_and_restore_carry_both_groups():
    rng = np.random.default_rng(0)
    kv = PagedKVCache(1, 2, 16, slots=2, block_size=4, max_seq_len=64,
                      window_layers=2, window=8, chunk=8)
    kv.reserve(0, 40)
    kv.cache_k = jnp.asarray(rng.standard_normal(kv.cache_k.shape), jnp.float32)
    kv.cache_v = jnp.asarray(rng.standard_normal(kv.cache_v.shape), jnp.float32)
    kv.win_k = jnp.asarray(rng.standard_normal(kv.win_k.shape), jnp.float32)
    kv.win_v = jnp.asarray(rng.standard_normal(kv.win_v.shape), jnp.float32)
    length = 30
    lo = kv.window_first_held(length)
    assert lo == 20  # position 23 is the oldest a row at 30 sees; its page starts at 20
    full_before = kv.gather_dense(0, length)
    win_before = kv.gather_window(0, lo, length)
    payload = kv.spill(0, length)
    kv.check_invariants()
    assert payload["window"]["lo"] == lo and not kv._owned
    kv.restore(1, payload, 40)
    kv.check_invariants()
    for a, b in zip(full_before, kv.gather_dense(1, length)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(win_before, kv.gather_window(1, lo, length)):
        np.testing.assert_array_equal(a, b)
    one_group = PagedKVCache(1, 2, 16, slots=1, block_size=4, max_seq_len=64)
    with pytest.raises(ValueError, match="window layers"):
        one_group.restore(0, payload, 40)
    one_group.check_invariants()


def test_one_group_cache_is_what_it_was():
    kv = PagedKVCache(2, 4, 8, slots=2, block_size=4, max_seq_len=32)
    assert kv.window_layers == 0 and kv.win_tables is None and kv.prefix_sharing
    kv.reserve(0, 10)
    assert kv.pages_held() == {"full": 3, "window": 0}
    assert kv.bytes_per_token == 2 * 2 * 4 * 8 * 4
    assert "window" not in kv.spill(0, 6)
    kv.check_invariants()


def test_preempted_request_resumes_its_stream(model, ref_params):
    """A decode slot spilled mid-generation (both groups) and restored:
    the stream stays the reference's."""
    eng = engine_of(model, attn="gather")
    reqs = requests()
    done = {"n": 0}
    real = eng._window

    def window():
        real()
        done["n"] += 1
        if done["n"] == 9:
            assert eng.sched._preempt_one(0.0)

    eng._window = window
    rep = eng.run(reqs)
    assert rep.requests_finished == 6 and rep.preemptions == 1
    gaps, _ = gaps_of(rep, ref_params)
    assert gaps.max() == 0.0
    eng.kv.check_invariants()


# ------------------------------------------------------------- the ops
def test_routed_experts_against_reference_moe_block(model, ref_params):
    from flexflow_tpu.ops.base import OpContext, get_op_def

    layer = next(l for l in model.layers if l.name == "l2_moe")
    x = jnp.asarray(np.random.default_rng(1).standard_normal((2, 12, 64)), jnp.float32)
    out = get_op_def(layer.op_type).forward(
        layer, model.executor.params["l2_moe"], [x], OpContext(training=False)
    )[0]
    want = R.moe_block(ref_params["l2_moe"], x, CFG, matmul("highest"))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    # the bias chooses and does not weigh; the scale and the norm do
    w, idx = R.route(ref_params["l2_moe"], x.reshape(-1, 64), CFG, matmul("highest"))
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.826, rtol=1e-5)


@pytest.mark.parametrize("name,window,rotary", [("l0_attn", 8, True), ("l4_attn", 0, False)])
def test_gated_attention_against_reference_attention(model, ref_params, name, window, rotary):
    from flexflow_tpu.ops.base import OpContext, get_op_def

    layer = next(l for l in model.layers if l.name == name)
    assert layer.attrs.get("window", 0) == window and bool(layer.attrs["rotary_dim"]) == rotary
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, 24, 64)), jnp.float32)
    out = get_op_def(layer.op_type).forward(
        layer, model.executor.params[name], [x], OpContext(training=False)
    )[0]
    want = R.attention(ref_params[name], x, CFG, window, rotary, matmul("highest"))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


# ---------------------------------------------------- weights by leaf
def test_a_leaf_made_alone_is_the_leaf_of_the_tree(model, ref_params):
    alone = WL.leaf(SEED, "l3_moe", "w_up", SHAPES["l3_moe"]["w_up"])
    np.testing.assert_array_equal(np.asarray(alone), np.asarray(ref_params["l3_moe"]["w_up"]))
    by_layer = WL.ByLayer(SHAPES, SEED)["l0_attn"]
    assert set(by_layer) == set(SHAPES["l0_attn"])
    np.testing.assert_array_equal(np.asarray(by_layer["wq"]), np.asarray(ref_params["l0_attn"]["wq"]))
    assert abs(float(ref_params["l0_ln_in"]["scale"].mean()) - 1.0) < 0.02
    assert abs(float(ref_params["l0_attn"]["q_norm"].mean()) - 1.0) < 0.02
    assert abs(float(ref_params["l1_moe"]["router_bias"].std()) - 0.02) < 0.01
    other = WL.leaf(SEED + 1, "l3_moe", "w_up", SHAPES["l3_moe"]["w_up"])
    assert not np.array_equal(np.asarray(alone), np.asarray(other))
    big = WL.leaf(2 ** 31 + 5, "tok_embed", "kernel", (4, 4))  # seeds pass 32 signed bits
    assert np.isfinite(np.asarray(big)).all()


def test_the_programs_tree_is_the_cast_of_the_references(ref_params):
    m = build("bfloat16", "bfloat16")
    for lname, ws in SHAPES.items():
        for w in ws:
            have = m.executor.params[lname][w]
            want = ref_params[lname][w].astype(have.dtype)
            np.testing.assert_array_equal(np.asarray(have, np.float32), np.asarray(want, np.float32))
    with pytest.raises(KeyError, match="name different weights"):
        WL.fill_executor({k: v for k, v in SHAPES.items() if k != "lm_head"}, SEED, m.executor)


def test_programs_trace_no_cross_decoder(monkeypatch, wide_engine):
    """Every layer writes its pool, so the programs are those from before
    the cross-decoder: every layer on every row of a prefill."""
    TC.assert_no_cross_decoder(monkeypatch, wide_engine)
