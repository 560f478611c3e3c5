"""Serving the tiny ``phi4flash`` model -- a self-decoder of Mamba-1,
differential window and full attention layers, and a cross-decoder of
gated memory units and differential cross-attention over the full
layer's one cache -- through ``ServeEngine``'s chunked prefill (the
cross-decoder on each lane's last row only) and decode, against
``benchmarks/reference/phi4flash.py``'s one full forward: the selective
scan's three forms, the layer rule and the parameter count, the logits
behind served tokens, prefill in groups of lanes, the pool groups, the
cross-decoder's row counter, and what a planted fault looks like."""

from __future__ import annotations

import math
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks import weights_phi4flash as WP  # noqa: E402
from benchmarks.reference import phi4flash as R  # noqa: E402
from flexflow_tpu import FFConfig, FFModel, MachineMesh  # noqa: E402
from flexflow_tpu.models.gpt_decode import GPTSpec  # noqa: E402
from flexflow_tpu.models.phi4flash import layer_kind, phi4flash_decoder  # noqa: E402
from flexflow_tpu.ops.pallas import paged_attention as pa  # noqa: E402
from flexflow_tpu.ops.ssm import selective_scan, selective_step  # noqa: E402
from flexflow_tpu.serve import Request, ServeEngine  # noqa: E402

import _compact_prefill as CP  # noqa: E402
import _traced_calls as TC  # noqa: E402

CFG = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
    num_hidden_layers=8, vocab_size=101, sliding_window=8, mb_per_layer=2,
    layer_norm_eps=1e-5, mamba_expand=2, mamba_d_state=4, mamba_d_conv=4, mamba_dt_rank=8,
)
ARGS = dict(hidden=64, heads=4, kv_heads=2, ff_dim=96, num_layers=8, vocab=101,
            sliding_window=8, d_inner=128, state_size=4, dt_rank=8)
SLOTS, SEQ, SEED = 3, 64, 11
SHAPES = R.param_shapes(CFG)
# prompts of several chunks of 8 and longer than the window of 8, one
# chunk, less than one; six requests over three slots
LENS = [(21, 7), (9, 9), (30, 12), (5, 6), (17, 10), (26, 8)]


def build(**over):
    m = FFModel(FFConfig(batch_size=SLOTS))
    phi4flash_decoder(m, SLOTS, SEQ, **dict(ARGS, **over))
    m.compile(seed=0, mesh=MachineMesh((1, 1), ("data", "model")))
    WP.fill_executor(SHAPES, SEED, m.executor)
    return m


@pytest.fixture(scope="module")
def model():
    return build()


@pytest.fixture(scope="module")
def ref_params():
    return WP.tree(SHAPES, SEED)


def requests(lens=LENS):
    rng = np.random.default_rng(5)
    return [
        Request(prompt=rng.integers(0, 101, size=(p,)).astype(np.int32), id=i,
                max_new_tokens=n)
        for i, (p, n) in enumerate(lens)
    ]


def engine_of(model, **kw):
    return ServeEngine(model, slots=SLOTS, block_size=4, prefill_chunk=8, sync_every=4, **kw)


# ------------------------------------------------------- the scan's forms
def test_selective_scan_whole_in_chunks_and_step_by_step():
    """The scan over a whole sequence, the same sequence in chunks with
    the state carried, and ``selective_step`` a position at a time agree;
    rows past ``n_valid`` leave the state alone."""
    rng = np.random.default_rng(0)
    b, s, d, n = 2, 13, 6, 4
    x = jnp.asarray(rng.standard_normal((b, s, d)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.5, (b, s, d)), jnp.float32)
    A = -jnp.asarray(np.tile(np.arange(1, n + 1), (d, 1)), jnp.float32)
    B, C = (jnp.asarray(rng.standard_normal((b, s, n)), jnp.float32) for _ in range(2))
    D = jnp.asarray(rng.standard_normal(d), jnp.float32)
    y, S = selective_scan(x, dt, A, B, C, D)
    ys, S2 = [], None
    for lo, hi in ((0, 5), (5, 6), (6, 13)):
        yc, S2 = selective_scan(x[:, lo:hi], dt[:, lo:hi], A, B[:, lo:hi], C[:, lo:hi], D,
                                S2, unroll=4)
        ys.append(yc)
    np.testing.assert_allclose(np.concatenate(ys, 1), y, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(S2, S, rtol=1e-6, atol=1e-6)
    S3 = jnp.zeros((b, d, n), jnp.float32)
    for t in range(s):
        yt, S3 = selective_step(x[:, t], dt[:, t], A, B[:, t], C[:, t], D, S3)
        np.testing.assert_allclose(yt, y[:, t], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(S3, S, rtol=1e-6, atol=1e-6)
    _, S4 = selective_scan(x, dt, A, B, C, D, n_valid=jnp.asarray([5, 0]))
    _, S5 = selective_scan(x[:1, :5], dt[:1, :5], A, B[:1, :5], C[:1, :5], D)
    np.testing.assert_allclose(S4[0], S5[0], rtol=1e-6, atol=1e-6)
    assert float(jnp.abs(S4[1]).max()) == 0.0


# ------------------------------------------------------ the layer rule
@pytest.mark.parametrize("L,want", [(8, "MSMSmFGC"), (32, "MSMSMSMSMSMSMSMSmFGCGCGCGCGCGCGC")])
def test_the_layer_rule(L, want):
    got = "".join(layer_kind(l, L) for l in range(L))
    assert got == want == R.layer_kinds(dict(CFG, num_hidden_layers=L))
    if L == 32:
        counts = [got.count("M") + got.count("m"), got.count("S"), got.count("F"),
                  got.count("G"), got.count("C")]
        assert counts == [9, 8, 1, 7, 7]


def test_published_parameter_count():
    big = dict(CFG, hidden_size=2560, num_attention_heads=40, num_key_value_heads=20,
               intermediate_size=10240, num_hidden_layers=32, vocab_size=200064,
               sliding_window=512, mamba_d_state=16, mamba_dt_rank=160)
    shapes = R.param_shapes(big)
    assert sum(math.prod(s) for ws in shapes.values() for s in ws.values()) == 3852457984
    assert math.prod(shapes["tok_embed"]["kernel"]) == 512163840


def test_spec_reads_the_decoder_hybrid_decoder(model):
    s = GPTSpec.from_model(model)
    assert s.num_layers == 8 and s.self_layers == 6 and s.head_tied and s.norm == "layer"
    assert [b.kind for b in s.branches[::2]] == ["mamba1", "diff"] * 2 + [
        "mamba1", "diff", "gmu", "diff"]
    assert all(l.ffn_kind == "gated" for l in s.layers)
    # the pool's geometry: a K/V pair one head of 2 x 16, a query pair two heads
    assert (s.heads, s.kv_heads, s.head_dim) == (4, 1, 32)
    attn = [b for b in s.branches if b.is_attention]
    assert [b.window for b in attn] == [8, 8, 0, 0]
    assert [b.kv_source for b in attn] == [-1, -1, -1, 5]
    assert [round(b.lambda_init, 6) for b in attn] == [
        round(0.8 - 0.6 * math.exp(-0.3 * l), 6) for l in (1, 3, 5, 7)]
    assert s.state_layers[-1].attrs["exports_memory"] and len(s.state_layers) == 3


# ---------------------------------------------------------- whole model
def test_ffmodel_forward_against_reference(model, ref_params):
    toks = np.random.default_rng(0).integers(0, 101, (SLOTS, SEQ)).astype(np.int32)
    probs = np.asarray(model.eval_batch([toks])).reshape(SLOTS, SEQ, -1)
    rows = np.tile(np.arange(SEQ)[None], (SLOTS, 1))
    ref = jax.nn.log_softmax(R.logits_at(ref_params, toks, rows, CFG), -1)
    np.testing.assert_allclose(np.log(probs), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("over", [dict(sliding_window=0), dict(gmu_ones=True)],
                         ids=["full_window", "gmu_ones"])
def test_planted_faults_move_the_logits(ref_params, over):
    """The benchmark's planted faults, built through the builder's own
    arguments, move the logits far past the 2e-5 the sound model is held
    to (the third, ``lambda_scale`` 0, through the benchmark's own
    ``prove`` in tests/benchmark/test_bench_phi4flash.py)."""
    toks = np.random.default_rng(0).integers(0, 101, (SLOTS, SEQ)).astype(np.int32)
    probs = np.asarray(build(**over).eval_batch([toks])).reshape(SLOTS, SEQ, -1)
    rows = np.tile(np.arange(SEQ)[None], (SLOTS, 1))
    ref = jax.nn.log_softmax(R.logits_at(ref_params, toks, rows, CFG), -1)
    assert np.abs(np.log(probs) - np.asarray(ref)).max() > 1e-3


@pytest.mark.parametrize("attn", ["gather", "paged"])
def test_logits_behind_served_tokens(model, ref_params, monkeypatch, attn):
    """Prompts longer than the chunk and the window, then decode through
    the window rings, the full layer's cache the cross layer reads and
    the Mamba-1 states, the prefill in groups of two lanes: with the
    distribution returned (sampling at a temperature that still picks
    the argmax), its logarithm against the reference's log-softmax at
    every served position, to a float32 tolerance."""
    monkeypatch.setattr(pa, "INTERPRET", attn == "paged")
    CP.group_rows(monkeypatch, 2, 8)
    eng = engine_of(model, attn=attn, temperature=1e-4)
    assert eng.attn_kernel == attn and eng.prefill_group_lanes == 2
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
    reqs = requests(LENS[:4])
    rep = eng.run(reqs)
    assert rep.requests_finished == 4 and rep.host_syncs == rep.windows
    worst = 0.0
    for r in requests(LENS[:4]):
        done = next(d for d in rep.per_request if d["id"] == r.id)
        toks = np.concatenate([r.prompt, done["tokens"]]).astype(np.int32)[None]
        rows = (len(r.prompt) - 1 + np.arange(len(done["tokens"])))[None]
        ref = np.asarray(jax.nn.log_softmax(R.logits_at(ref_params, toks, rows, CFG), -1))[0]
        for j, p in enumerate(rows[0]):
            worst = max(worst, np.abs(np.log(seen[(r.id, int(p))]) - ref[j]).max())
    assert worst < 5e-5
    eng.kv.check_invariants()


def test_pool_groups_and_the_cross_decoder(model, ref_params, monkeypatch):
    """The full group holds the one layer whose K/V the cross layer
    reads; the window group the two window layers; the state group the
    three Mamba-1 layers at their own shapes.  A program writes pages
    for the three layers that own K/V, never for the cross layer.  Six
    requests over three slots, greedy: every served token is the
    reference's argmax, and the cross-decoder ran a row a lane a prefill
    dispatch and a row a slot a decode step."""
    writes = []
    real = pa.paged_kv_write

    def counted(ck, cv, i, *a, **kw):
        writes.append((ck.shape[0], int(i)))
        return real(ck, cv, i, *a, **kw)

    monkeypatch.setattr(pa, "paged_kv_write", counted)
    monkeypatch.setattr(pa, "INTERPRET", True)
    eng = engine_of(model, attn="paged")
    kv = eng.kv
    assert (kv.num_layers, kv.window_layers, kv.state_layers) == (1, 2, 3)
    assert kv.cache_k.shape == (1, kv.num_blocks * 4, 32)
    assert [a.shape for a in kv.state_ssm] == [(SLOTS, 128, 4)] * 3
    assert [a.shape for a in kv.state_conv] == [(SLOTS, 3, 128)] * 3
    # traced once a program: the full group's one layer, the window group's two
    assert sorted(set(writes)) == [(1, 0), (2, 0), (2, 1)]
    rep = eng.run(requests())
    assert rep.requests_finished == 6
    done = {r["id"]: r["tokens"] for r in rep.per_request}
    src = {r.id: r for r in requests()}
    for rid, t in done.items():
        toks = np.concatenate([src[rid].prompt, t]).astype(np.int32)[None]
        rows = (len(src[rid].prompt) - 1 + np.arange(len(t)))[None]
        gaps = R.served_gaps(ref_params, toks, rows, np.asarray(t)[None],
                             np.ones_like(rows, bool), CFG)
        assert float(np.max(gaps)) == 0.0
    assert rep.cross_decoder_rows == rep.prefill_lanes_computed + SLOTS * rep.decode_steps
    # the window layers read less than the whole context
    assert rep.kv_rows_visible < rep.kv_rows_context
    kv.check_invariants()


def test_cross_decoder_rows_are_the_lanes_prefilled(model):
    """Requests that ask one token are prefilled and done: the
    cross-decoder ran one row a lane of each dispatch and nothing else."""
    eng = engine_of(model, attn="gather")
    rep = eng.run(requests([(21, 1), (9, 1), (30, 1), (5, 1)]))
    assert rep.requests_finished == 4 and rep.decode_steps == 0
    assert rep.cross_decoder_rows == rep.prefill_lanes_computed > 0


def test_programs_trace_the_cross_decoder_at_decode_width(model, monkeypatch):
    """A prefill runs the self-decoder (layers 0-5) through the prefill
    kernel and the cross-decoder (layers 6-7) on a row a lane, its cross
    layer through the decode kernel over the full layer's pages; a decode
    step runs all eight layers on its row.  Neither writes a page for the
    cross layer."""
    calls = TC.traced_calls(monkeypatch, engine_of(model))
    common = {"paged_kv_write": 3, "mamba1_mixer": 3, "gated_memory": 1, "diff_combine": 4}
    assert calls["prefill"] == dict(common, paged_prefill_attention=3, paged_decode_attention=1)
    assert calls["decode"] == dict(common, paged_decode_attention=4)
