"""Serving the tiny ``nemotron_h`` model (ISSUE 34) -- layers of one
mixer each: Mamba-2 layers whose recurrent state lives in a per-slot
state pool beside the paged K/V pool, grouped-query attention without
positions, sigmoid-routed ``relu2`` experts of which half are held --
through ``ServeEngine``'s chunked prefill and decode over both pools,
against ``benchmarks/reference/nemotron_h.py``'s one full forward (the
plain recurrence): slots recycled, a request spilled and restored, the
decoder spec, what is refused, and what a planted fault looks like."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks import weights_nemotron_h as WN  # noqa: E402
from benchmarks.reference import nemotron_h as R  # noqa: E402
from flexflow_tpu import FFConfig, FFModel, MachineMesh  # noqa: E402
from flexflow_tpu.models.gpt_decode import GPTDecodeSession, GPTSpec  # noqa: E402
from flexflow_tpu.models.nemotron_h import nemotron_h_decoder  # noqa: E402
from flexflow_tpu.ops.pallas import paged_attention as pa  # noqa: E402
from flexflow_tpu.serve import Request, ServeEngine  # noqa: E402
from flexflow_tpu.serve.engine import UnsupportedServeConfig  # noqa: E402
from flexflow_tpu.serve.scheduler import RequestState  # noqa: E402

CFG = dict(
    hidden_size=64, vocab_size=128, num_hidden_layers=9, hybrid_override_pattern="EMEMEMEM*",
    mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=16, conv_kernel=4,
    chunk_size=8, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    n_routed_experts=4, router_num_experts=8, first_expert=0, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=48, num_experts_per_tok=2, norm_topk_prob=True,
    routed_scaling_factor=2.5, layer_norm_epsilon=1e-5, mlp_hidden_act="relu2",
)
ARGS = dict(
    hidden=64, heads=4, ff_dim=32, num_layers=9, vocab=128, pattern="EMEMEMEM*", kv_heads=2,
    head_dim=16, mamba_heads=4, mamba_head_dim=8, n_groups=2, state_size=16, conv_kernel=4,
    chunk=8, router_experts=8, first_expert=0, held_experts=4, top_k=2, shared_ff_dim=48,
    use_flash=False,
)
SLOTS, SEQ, SEED = 3, 64, 11
SHAPES = R.param_shapes(CFG)
# several chunks of 8 with ragged last chunks (21 = 8 + 8 + 5), one chunk,
# less than one; six requests over three slots, so slots are recycled
LENS = [(21, 7), (9, 9), (30, 12), (5, 6), (17, 10), (26, 8)]


def build(dtype="float32", param_dtype="float32", **over):
    m = FFModel(FFConfig(batch_size=SLOTS, compute_dtype=dtype, param_dtype=param_dtype))
    nemotron_h_decoder(m, SLOTS, SEQ, **dict(ARGS, **over))
    m.compile(seed=0, mesh=MachineMesh((1, 1), ("data", "model")))
    WN.fill_executor(SHAPES, SEED, m.executor)
    return m


@pytest.fixture(scope="module")
def model():
    return build()


@pytest.fixture(scope="module")
def ref_params():
    return WN.tree(SHAPES, SEED)


def requests(lens=LENS):
    rng = np.random.default_rng(5)
    return [
        Request(prompt=rng.integers(0, 128, size=(p,)).astype(np.int32), id=i,
                max_new_tokens=n)
        for i, (p, n) in enumerate(lens)
    ]


def engine_of(model, **kw):
    return ServeEngine(model, slots=SLOTS, block_size=4, prefill_chunk=8, sync_every=4, **kw)


def gaps_of(done, params, precision="highest", cfg=CFG):
    """``done``: {id: tokens}.  Gaps of the served tokens against the
    reference's one full forward."""
    src = {r.id: r for r in requests()}
    k, s, n = len(done), 30 + 12, 12
    tokens = np.zeros((k, s), np.int32)
    rows = np.zeros((k, n), np.int32)
    served = np.zeros((k, n), np.int32)
    valid = np.zeros((k, n), bool)
    for i, (rid, t) in enumerate(sorted(done.items())):
        p = src[rid].prompt
        tokens[i, : len(p)] = p
        tokens[i, len(p): len(p) + len(t)] = t
        rows[i, : len(t)] = len(p) - 1 + np.arange(len(t))
        served[i, : len(t)] = t
        valid[i, : len(t)] = True
    return np.asarray(R.served_gaps(params, tokens, rows, served, valid, cfg, precision)), valid


def served(report):
    return {r["id"]: r["tokens"] for r in report.per_request}


# ----------------------------------------------------------- the spec
def test_spec_reads_layers_of_one_branch(model):
    s = GPTSpec.from_model(model)
    assert (s.num_layers, s.heads, s.kv_heads, s.head_dim, s.hidden, s.vocab) == (9, 4, 2, 16, 64, 128)
    assert s.norm == "rms" and s.pos_embed is None and s.embed_scale == 1.0
    assert [len(l.branches) for l in s.layers] == [1] * 9
    assert [b.kind for b in s.branches] == ["moe", "mamba2"] * 4 + ["mha"]
    assert len(s.state_layers) == 4 and s.has_moe and not s.is_gpt and s.window == 0
    assert s.state_layers[0].attrs["state_size"] == 16 and s.state_layers[0].mixer == ("l1_mamba",)
    moe = s.layers[0].moe
    assert (moe["n_experts"], moe["held"], moe["first_expert"], moe["expert_form"]) == (8, 4, 0, "relu2")
    assert s.layers[8].attn == "l8_attn" and s.layers[8].ffn_kind is None
    assert s.layers[1].attention is None and s.layers[1].ffn_kind is None
    with pytest.raises(ValueError, match="gpt_decoder-shaped"):
        GPTDecodeSession(model)


def test_the_full_pattern_reads_as_its_52_layers():
    m = FFModel(FFConfig(batch_size=1, compute_dtype="float32"))
    nemotron_h_decoder(m, 1, 8, **dict(ARGS, num_layers=52, pattern=(
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")))
    m.compile(seed=0)
    s = GPTSpec.from_model(m)
    kinds = [b.kind for b in s.branches]
    assert s.num_layers == 52 and all(len(l.branches) == 1 for l in s.layers)
    assert (kinds.count("mamba2"), kinds.count("moe"), kinds.count("mha")) == (23, 23, 6)


# --------------------------------------------------------- whole model
def test_ffmodel_forward_against_reference(model, ref_params):
    toks = np.random.default_rng(0).integers(0, 128, (SLOTS, SEQ)).astype(np.int32)
    probs = np.asarray(model.eval_batch([toks])).reshape(SLOTS, SEQ, -1)
    rows = np.tile(np.arange(SEQ)[None], (SLOTS, 1))
    ref = jax.nn.log_softmax(R.logits_at(ref_params, toks, rows, CFG), -1)
    np.testing.assert_allclose(np.log(probs), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("attn", ["gather", "paged"])
def test_served_tokens_against_one_full_forward(model, ref_params, attn):
    """Chunked prefill (prompts of several chunks, ragged last chunks),
    then decode through both pools, three slots recycled over six
    requests: float32, so the greedy streams ARE the reference's (gap 0
    at every served token)."""
    old = pa.INTERPRET
    pa.INTERPRET = attn == "paged"
    try:
        eng = engine_of(model, attn=attn)
        rep = eng.run(requests())
    finally:
        pa.INTERPRET = old
    assert rep.requests_finished == 6 and rep.host_syncs == rep.windows
    assert eng.attn_kernel == attn
    gaps, valid = gaps_of(served(rep), ref_params)
    assert valid.sum() == sum(n for _, n in LENS)
    assert gaps.max() == 0.0
    eng.kv.check_invariants()
    positions = sum(p + n - 1 for p, n in LENS)
    assert rep.ssm_rows == 4 * positions and rep.state_slots_held == 3
    assert rep.state_pool_bytes == eng.kv.state_bytes() == 3 * 4 * (3 * 96 * 4 + 4 * 8 * 16 * 4)
    assert rep.moe_layers == 4 and 0 < rep.moe_rows < 4 * 2 * positions  # held rows only
    assert 0 < rep.moe_experts_touched <= 4 * 4 * (rep.decode_steps + rep.prefill_dispatches)
    assert rep.state_spills == rep.state_restores == 0
    assert not eng.kv.prefix_sharing and rep.kv_pages_held_window == 0


def test_a_recycled_slot_starts_from_nothing_whatever_it_held(model, ref_params):
    """Nothing is zeroed when a slot changes hands: a chunk that starts
    at position 0 reads the slot's state as zero.  Every slot's state is
    poisoned before the run."""
    eng = engine_of(model, attn="gather")
    eng.kv.state_conv = [jnp.full_like(a, 7.0) for a in eng.kv.state_conv]
    eng.kv.state_ssm = [jnp.full_like(a, -3e3) for a in eng.kv.state_ssm]
    rep = eng.run(requests())
    gaps, _ = gaps_of(served(rep), ref_params)
    assert rep.requests_finished == 6 and gaps.max() == 0.0


def test_a_spilled_request_resumes_from_its_state_and_its_keys(model, ref_params):
    """Two batch requests decode in slots 0 and 1; an interactive one
    arrives with every slot taken, so the newest batch decode is
    spilled -- K/V and both states of every state layer -- and restored
    later: every stream is the reference's."""
    eng = ServeEngine(model, slots=2, block_size=4, prefill_chunk=8, sync_every=2)
    src = requests()
    b0 = eng.submit(src[0].prompt, 12, req_id=0, tier="batch")
    b1 = eng.submit(src[2].prompt, 12, req_id=2, tier="batch")
    eng.sched.admit()
    eng._t0 = eng._now()
    for _ in range(5):  # 3 and 4 chunks of prefill, then a few steps each
        eng._window()
    assert b0.state is RequestState.DECODE and b1.state is RequestState.DECODE
    assert 1 < b1.done_tokens < 12
    it = eng.submit(src[4].prompt, 10, req_id=4, tier="interactive")
    rep = eng.run()
    assert rep.requests_finished == 3 and eng.sched.preemptions == 1 and b1.preemptions == 1
    assert rep.state_spills == 1 and rep.state_restores == 1
    assert rep.host_syncs == rep.windows
    gaps, valid = gaps_of({r.id: r.tokens for r in (b0, b1, it)}, ref_params)
    assert valid.sum() == 12 + 12 + 10 and gaps.max() == 0.0
    eng.kv.check_invariants()


def test_logits_behind_served_tokens_float32(model, ref_params):
    """With sampling on (at a temperature that still picks the argmax)
    the programs return the distribution: its logarithm against the
    reference's log-softmax at every served position, to a float32
    tolerance (the chunked scan and the recurrence sum in another
    order)."""
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
    (8 bits of mantissa through nine layers; the state itself is
    float32), so the tolerance is a gap of 0.25 in logits of spread ~1;
    the router's and the decay's leaves stay float32 and no weight is
    converted in a call."""
    m = build("bfloat16", "bfloat16")
    p = m.executor.params
    assert {str(x.dtype) for x in jax.tree.leaves(p)} == {"bfloat16", "float32"}
    assert p["l0_moe"]["router"].dtype == jnp.float32 and p["l0_moe"]["w_up"].dtype == jnp.bfloat16
    assert {str(p["l1_mamba"][w].dtype) for w in ("A_log", "dt_bias", "D")} == {"float32"}
    eng = engine_of(m, attn="gather")
    assert eng.kv.state_conv[0].dtype == jnp.bfloat16 and eng.kv.state_ssm[0].dtype == jnp.float32
    rep = eng.run(requests())
    assert rep.requests_finished == 6
    gaps, _ = gaps_of(served(rep), WN.tree(SHAPES, SEED))
    assert gaps.max() < 0.25
    assert eng.weight_casts() == 0


# ------------------------------------------------------ planted faults
@pytest.mark.parametrize("over,what", [
    (dict(route_scale=1.0), "routed_scaling_factor left out"),
    (dict(expert_act="relu"), "relu for relu2"),
])
def test_planted_faults_are_caught(ref_params, over, what):
    rep = engine_of(build(**over), attn="gather").run(requests())
    gaps, _ = gaps_of(served(rep), ref_params)
    assert gaps.max() > 1e-3, what


def test_a_state_dropped_at_chunk_boundaries_is_caught(model, ref_params, monkeypatch):
    from flexflow_tpu.ops import ssm

    real = ssm.mamba2_mixer

    def forgetful(params, u, a, conv_state=None, ssm_state=None, n_valid=None):
        if u.shape[1] > 1:  # a prefill chunk: read both states as zero
            conv_state, ssm_state = jnp.zeros_like(conv_state), jnp.zeros_like(ssm_state)
        return real(params, u, a, conv_state, ssm_state, n_valid)

    monkeypatch.setattr(ssm, "mamba2_mixer", forgetful)
    rep = engine_of(model, attn="gather").run(requests())
    gaps, _ = gaps_of(served(rep), ref_params)
    assert rep.requests_finished == 6 and gaps.max() > 1e-3


def test_fp8_control_is_caught(model, ref_params):
    rep = engine_of(model, attn="gather").run(requests())
    gaps, _ = gaps_of(served(rep), ref_params, "fp8")
    assert gaps.max() > 1e-3


# ------------------------------------------------------------- refusals
@pytest.mark.parametrize("kw,what", [
    (dict(spec_k=2), "speculation"),
    (dict(weight_dtype="int8"), "weight_dtype"),
    (dict(kv_dtype="int8"), "quantized pool"),
    (dict(kv_dtype="fp8"), "quantized pool"),
    (dict(phase="prefill"), "disaggregated or fleet"),
    (dict(phase="decode"), "disaggregated or fleet"),
])
def test_what_is_not_served_for_this_spec_is_refused_by_name(model, kw, what):
    with pytest.raises(UnsupportedServeConfig, match=what):
        engine_of(model, **kw)


def test_the_programs_thread_every_state_array(model):
    """Both pools and, a state layer, its two states are donated to and
    returned by every program (that the TPU's compiler then updates a
    state in place is ``tests/test_tpu_lowering.py``'s to show; the
    CPU's copies its donated arguments)."""
    eng = engine_of(model, attn="gather")
    assert len(eng._kvs()) == 2 + 2 * 4
    assert [x.shape for x in eng._kvs()[2:]] == [(3, 3, 96)] * 4 + [(3, 4, 8, 16)] * 4
    assert eng._state_report() == {
        "layers": 4, "pool_bytes": eng.kv.state_bytes(),
        "bytes_per_slot": eng.kv.state_bytes_per_slot, "slots_held": 0,
        "spills": 0, "restores": 0,
    }
