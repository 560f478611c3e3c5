"""The serve programs take their weights already cast (ISSUE 33).

``build_serve_programs`` hands the four programs the executor's
parameters in the dtype the matmuls multiply them
(``programs.weights_as_consumed``): float32 leaves of a bfloat16-compute
model cast once, at build, not inside every call.  Pinned here: the
compiled programs hold no convert of a weight (and the counter that says
so does see the converts of a float32 tree); the outputs are the same
bits either way; a leaf that needs no cast is the executor's own array;
``set_weights`` on a built engine is served from its next ``run()``;
engines over one model share one cast tree; the int8 arm is as it was.
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from flexflow_tpu import FFConfig, FFModel, MachineMesh  # noqa: E402
from flexflow_tpu.models.afmoe import afmoe_decoder  # noqa: E402
from flexflow_tpu.models.transformer import gpt_decoder  # noqa: E402
from flexflow_tpu.serve import Request, ServeEngine  # noqa: E402
from flexflow_tpu.serve.engine import count_weight_casts  # noqa: E402
from flexflow_tpu.serve.programs import KEEP_F32  # noqa: E402

SLOTS, SEQ, VOCAB, HIDDEN = 3, 64, 29, 32


def build(compute_dtype, num_layers=4):
    # four blocks: the executor scan-stacks them, so the stored leaves
    # are (depth, ...) stacks, as in the gpt2_small cells
    m = FFModel(FFConfig(batch_size=SLOTS, compute_dtype=compute_dtype))
    gpt_decoder(
        m, SLOTS, SEQ, hidden=HIDDEN, heads=4, ff_dim=64,
        num_layers=num_layers, vocab=VOCAB, use_flash=False,
    )
    m.compile(seed=0, mesh=MachineMesh((1, 1), ("data", "model")))
    return m


def engine_of(model, **kw):
    return ServeEngine(
        model, slots=SLOTS, block_size=8, prefill_chunk=5, sync_every=4, **kw
    )


def requests():
    rng = np.random.default_rng(3)
    return [
        Request(prompt=rng.integers(0, VOCAB, size=(p,)).astype(np.int32),
                id=i, max_new_tokens=n)
        for i, (p, n) in enumerate([(5, 6), (12, 4), (7, 9), (3, 5)])
    ]


def streams(report):
    return {r["id"]: list(r["tokens"]) for r in report.per_request}


def leaves_by_path(tree):
    return {
        (b, w): x for b, ws in tree.items() for w, x in ws.items()
    }


@pytest.fixture(scope="module")
def bf16_model():
    return build("bfloat16")


@pytest.fixture(scope="module")
def bf16_engine(bf16_model):
    return engine_of(bf16_model)


# ------------------------------------------------------------ the counter
def test_count_weight_casts_follows_the_operand_not_the_shape():
    """A written module: a float32 weight stack converted whole in the
    entry, a slice of another converted inside a fusion, one cut by a
    fusion and converted by the next; beside them a float32 activation
    of a weight's own shape converted too, which is no weight."""
    text = """\
HloModule jit_decode, is_scheduled=true

%fused_computation (param_0.1: f32[12,768,768]) -> bf16[768,768] {
  %param_0.1 = f32[12,768,768]{2,1,0} parameter(0)
  %slice.1 = f32[1,768,768]{2,1,0} slice(%param_0.1), slice={[3:4], [0:768], [0:768]}
  %convert.7 = bf16[1,768,768]{2,1,0} convert(%slice.1)
  ROOT %bitcast.2 = bf16[768,768]{1,0} bitcast(%convert.7)
}

%fused_computation.1 (param_0.2: f32[768,768], param_1.2: f32[768,768]) -> (bf16[768,768], f32[768]) {
  %param_0.2 = f32[768,768]{1,0:T(8,128)} parameter(0)
  %param_1.2 = f32[768,768]{1,0:T(8,128)} parameter(1)
  %add.1 = f32[768,768]{1,0} add(%param_0.2, %param_1.2)
  %convert.9 = bf16[768,768]{1,0} convert(%add.1)
  %reduce.1 = f32[768]{0} reduce(%add.1, %param_1.2), dimensions={1}, to_apply=%region
  ROOT %tuple.1 = (bf16[768,768]{1,0:T(8,128)(2,1)}, f32[768]{0}) tuple(%convert.9, %reduce.1)
}

%fused_computation.2 (param_0.3: f32[12,768,3072]) -> f32[768,3072] {
  %param_0.3 = f32[12,768,3072]{2,1,0} parameter(0)
  %slice.3 = f32[1,768,3072]{2,1,0} slice(%param_0.3), slice={[0:1], [0:768], [0:3072]}
  ROOT %bitcast.3 = f32[768,3072]{1,0} bitcast(%slice.3)
}

ENTRY %main.9 (params.1: f32[12,768,768], params.2: f32[12,768,3072], ck.3: bf16[12,24592,768], x.4: f32[768,768]) -> bf16[768,768] {
  %params.1 = f32[12,768,768]{2,1,0:T(8,128)} parameter(0)
  %params.2 = f32[12,768,3072]{2,1,0:T(8,128)} parameter(1)
  %ck.3 = bf16[12,24592,768]{2,1,0:T(8,128)(2,1)} parameter(2)
  %x.4 = f32[768,768]{1,0:T(8,128)} parameter(3)
  %convert.135 = bf16[12,768,3072]{2,1,0:T(8,128)(2,1)} convert(%params.2)
  %fusion.1 = bf16[768,768]{1,0} fusion(%params.1), kind=kLoop, calls=%fused_computation
  %fusion.2 = (bf16[768,768]{1,0:T(8,128)(2,1)}, f32[768]{0}) fusion(%x.4, %x.4), kind=kLoop, calls=%fused_computation.1
  %fusion.3 = f32[768,3072]{1,0} fusion(%params.2), kind=kLoop, calls=%fused_computation.2
  %convert.136 = bf16[768,3072]{1,0} convert(%fusion.3)
  %convert.137 = f32[12,24592,768]{2,1,0} convert(%ck.3)
  ROOT %copy.1 = bf16[768,768]{1,0} copy(%fusion.1)
}
"""
    shapes = {(12, 768, 768), (12, 768, 3072), (768,)}
    assert count_weight_casts(text, shapes, jnp.dtype("bfloat16")) == 3
    # only parameters of a weight's shape are weights; another dtype's
    # converts are not these
    assert count_weight_casts(text, {(12, 768, 768)}, "bfloat16") == 1
    assert count_weight_casts(text, shapes, "float16") == 0
    assert count_weight_casts("", shapes, "bfloat16") == 0


def test_compiled_programs_hold_no_weight_cast(bf16_model, bf16_engine):
    """``weight_casts()`` is 0 on the engine, and the same two programs
    lowered with the executor's float32 tree hold a convert a weight and
    more -- which is how the counter is known to see what it counts."""
    eng = bf16_engine
    assert eng._params_arg is not bf16_model.executor.params
    assert eng.weight_casts() == 0
    shapes = {tuple(x.shape) for x in jax.tree.leaves(bf16_model.executor.params)}
    for text in eng._program_texts(bf16_model.executor.params):
        assert count_weight_casts(text, shapes, jnp.bfloat16) >= len(shapes)
    assert eng.pool_relayouts() == 0


def test_float32_compute_passes_the_tree_through_whole():
    m = build("float32")
    eng = engine_of(m)
    assert eng._params_arg is m.executor.params
    assert m.executor.serve_cast == {}
    assert eng.weight_casts() == 0


# ------------------------------------------------------- the same numbers
@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_outputs_are_bit_identical_on_either_tree(bf16_model, program):
    """Next tokens and the returned float32 distribution of a sampling
    engine's programs, handed ``executor.params`` (cast inside, as every
    call did before) and the once-cast tree: the same bits, and the same
    rows in the pools."""
    eng = engine_of(bf16_model, temperature=0.7, seed=1)
    rng = np.random.default_rng(0)
    B, MB = SLOTS, eng.kv.max_blocks_per_seq
    bt = jnp.asarray(1 + np.arange(B * MB).reshape(B, MB) % (eng.kv.num_blocks - 1), jnp.int32)
    if program == "decode":
        prog = eng._decode
        args = (jnp.asarray(rng.integers(0, VOCAB, (B,)), jnp.int32),
                jnp.asarray([0, 3, 9], jnp.int32), bt)
    else:
        prog = eng._prefill
        P = eng.prefill_chunk
        args = (jnp.asarray(rng.integers(0, VOCAB, (B, P)), jnp.int32),
                jnp.asarray([0, 5, 10], jnp.int32),
                jnp.asarray([P, 2, P], jnp.int32), bt)
    outs = []
    for tree in (bf16_model.executor.params, eng._params_arg):
        pools = [jnp.array(x) for x in eng._kvs()]  # donated by the call
        outs.append(prog(tree, *pools, *args))
    nxt_a, probs_a, *pools_a = outs[0]
    nxt_b, probs_b, *pools_b = outs[1]
    assert probs_a.dtype == jnp.float32 and float(probs_a.sum()) > 0
    np.testing.assert_array_equal(np.asarray(nxt_a), np.asarray(nxt_b))
    np.testing.assert_array_equal(np.asarray(probs_a), np.asarray(probs_b))
    for a, b in zip(pools_a, pools_b):
        np.testing.assert_array_equal(
            np.asarray(a.astype(jnp.float32)), np.asarray(b.astype(jnp.float32))
        )


# ------------------------------------------- what is cast and what is not
def test_cast_leaves_keep_their_stacks_and_dtypes(bf16_model, bf16_engine):
    src = leaves_by_path(bf16_model.executor.params)
    got = leaves_by_path(bf16_engine._params_arg)
    assert got.keys() == src.keys()
    for path, x in src.items():
        assert x.dtype == jnp.float32  # at rest, as the configuration says
        assert got[path].dtype == jnp.bfloat16 and got[path].shape == x.shape
        np.testing.assert_array_equal(
            np.asarray(got[path].astype(jnp.float32)),
            np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32)),
        )
    assert any(x.ndim == 3 for x in got.values())  # a stack stays a stack


def test_bfloat16_at_rest_is_passed_by_reference():
    """A decoder whose weights rest in the compute dtype (the
    ``trinity_mini`` cell's way): nothing is cast, so every leaf the
    programs take IS the executor's -- no second copy of a tree that
    holds most of the chip -- and the router's leaves stay float32."""
    m = FFModel(FFConfig(batch_size=SLOTS, compute_dtype="bfloat16",
                         param_dtype="bfloat16"))
    afmoe_decoder(
        m, SLOTS, SEQ, hidden=64, heads=4, ff_dim=32, num_layers=5, vocab=128,
        kv_heads=2, head_dim=16, dense_ff_dim=96, num_dense_layers=1,
        num_experts=8, top_k=2, shared_ff_dim=32,
        layer_types=["sliding_attention"] * 4 + ["full_attention"],
        sliding_window=8, use_flash=False,
    )
    m.compile(seed=0, mesh=MachineMesh((1, 1), ("data", "model")))
    eng = ServeEngine(m, slots=SLOTS, block_size=4, prefill_chunk=8)
    src = leaves_by_path(m.executor.params)
    got = leaves_by_path(eng._params_arg)
    assert got.keys() == src.keys()
    assert all(got[p] is src[p] for p in src)
    assert m.executor.serve_cast == {}
    routers = [x for (_, w), x in got.items() if w in KEEP_F32]
    assert routers and all(x.dtype == jnp.float32 for x in routers)
    assert any(x.dtype == jnp.bfloat16 for x in got.values())
    assert eng.weight_casts() == 0


def test_float32_router_is_kept_beside_cast_experts():
    """The same decoder float32 at rest: the experts are cast once, the
    router's leaves are the executor's own float32 arrays."""
    m = FFModel(FFConfig(batch_size=SLOTS, compute_dtype="bfloat16"))
    afmoe_decoder(
        m, SLOTS, SEQ, hidden=64, heads=4, ff_dim=32, num_layers=2, vocab=128,
        kv_heads=2, head_dim=16, dense_ff_dim=96, num_dense_layers=1,
        num_experts=8, top_k=2, shared_ff_dim=32,
        layer_types=["sliding_attention", "full_attention"],
        sliding_window=8, use_flash=False,
    )
    m.compile(seed=0, mesh=MachineMesh((1, 1), ("data", "model")))
    eng = ServeEngine(m, slots=SLOTS, block_size=4, prefill_chunk=8)
    src = leaves_by_path(m.executor.params)
    got = leaves_by_path(eng._params_arg)
    for (b, w), x in got.items():
        if w in KEEP_F32:
            assert x is src[(b, w)] and x.dtype == jnp.float32
        else:
            assert x.dtype == jnp.bfloat16
    assert any(w in KEEP_F32 for _, w in got)
    assert eng.weight_casts() == 0


# -------------------------------------------------------------- freshness
def test_set_weights_is_served_from_the_next_run():
    """``set_weights`` replaces leaves of ``executor.params``; a built
    engine re-casts exactly those before its next run (and nothing
    before a run that follows no change) and serves what a fresh engine
    over the new weights serves."""
    m = build("bfloat16")
    eng = engine_of(m)
    before = streams(eng.run(requests()))
    held = leaves_by_path(eng._params_arg)
    assert streams(eng.run(requests())) == before
    again = leaves_by_path(eng._params_arg)
    assert all(again[p] is held[p] for p in held)  # nothing cast again

    rng = np.random.default_rng(9)
    w = m.get_weights()
    m.set_weights({
        "lm_head": {"kernel": rng.normal(size=w["lm_head"]["kernel"].shape)
                    .astype(np.float32)},
        "dec2_ff0": {"kernel": rng.normal(size=w["dec2_ff0"]["kernel"].shape)
                     .astype(np.float32)},
    })
    after = streams(eng.run(requests()))
    assert after != before
    now = leaves_by_path(eng._params_arg)
    changed = {p for p in held if now[p] is not held[p]}
    assert changed == {("lm_head", "kernel"), ("dec0_ff0", "kernel")}
    np.testing.assert_array_equal(
        np.asarray(now[("lm_head", "kernel")].astype(jnp.float32)),
        np.asarray(m.executor.params["lm_head"]["kernel"]
                   .astype(jnp.bfloat16).astype(jnp.float32)),
    )
    assert streams(engine_of(m).run(requests())) == after
    assert eng.weight_casts() == 0


def test_engines_over_one_model_share_their_cast_leaves(bf16_model, bf16_engine):
    other = engine_of(bf16_model, attn="gather", prefix_sharing=False)
    a = leaves_by_path(bf16_engine._params_arg)
    b = leaves_by_path(other._params_arg)
    assert all(a[p] is b[p] for p in a)
    # one cast tree on the executor, whoever asked first
    assert len(bf16_model.executor.serve_cast) == len(a)


# ------------------------------------------------------------ the int8 arm
def test_int8_arm_keeps_its_argument_and_its_stream(bf16_model):
    """Weight-only int8 is int8 at rest by design: its argument stays
    the ``(qparams, scales)`` pair made at build, a run does not touch
    it, and it serves the stream it served before."""
    eng = engine_of(bf16_model, weight_dtype="int8")
    pair = eng._params_arg
    qparams, scales = pair
    assert jax.tree.structure(qparams) == jax.tree.structure(scales)
    assert any(x.dtype == jnp.int8 for x in jax.tree.leaves(qparams))
    first = streams(eng.run(requests()))
    assert eng._params_arg is pair
    assert streams(engine_of(bf16_model, weight_dtype="int8").run(requests())) == first
    assert all(len(t) for t in first.values())
