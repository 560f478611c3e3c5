"""The whole Qwen3-Next decoder through the normal path (builder ->
``compile(search_budget=8)`` -> executor -> ``fit``) against the plain
reference, at hidden 64, 4 layers (3 linear + 1 full), vocabulary 128,
float32 on the CPU, seeded weights from ``benchmarks/weights.py``.

Tolerances, and why.  Both sides compute in float32 on the CPU and
differ in the order of sums only (chunked against token-by-token scan,
grouped matmuls against masked experts, fused against separate
projections).  Forward quantities agree to ~1e-6 relative; 1e-4 on the
probabilities and the loss is that with room.  A gradient leaf is a sum
over 64 tokens of such terms: 2e-3 of the leaf's largest entry.  Adam
divides by sqrt(v): where a gradient entry is near zero its first
updates turn on the entry's sign, so after three steps a handful of
entries differ by up to 2 * 3 * alpha; the change is therefore compared
by its norm per leaf (2 % of the leaf's own change), as the benchmark
compares it.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import weights as W  # noqa: E402
from benchmarks.reference import qwen3_next as ref  # noqa: E402
from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, LossType, MetricsType  # noqa: E402
from flexflow_tpu.models import qwen3_next_decoder  # noqa: E402

B, S = 2, 32
CFG = {
    "hidden_size": 64, "vocab_size": 128, "num_hidden_layers": 4,
    "full_attention_interval": 4, "rms_norm_eps": 1e-6,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "partial_rotary_factor": 0.25, "rope_theta": 1e7,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
    "num_experts": 8, "router_num_experts": 16, "first_expert": 4,
    "num_experts_per_tok": 4, "norm_topk_prob": True,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
}
OPT = {"alpha": 1e-3, "beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8}
BUILDER_ARGS = dict(
    hidden=64, heads=4, ff_dim=32, num_layers=4, vocab=128, kv_heads=2, head_dim=16,
    rotary_dim=4, rope_theta=1e7, linear_k_heads=2, linear_v_heads=4, linear_k_dim=16,
    linear_v_dim=16, conv_kernel=4, router_experts=16, first_expert=4, held_experts=8,
    top_k=4, shared_ff_dim=32,
)
SEED = 2 ** 31 + 11


@pytest.fixture(scope="module")
def built():
    model = FFModel(FFConfig(batch_size=B, search_budget=8))
    out = qwen3_next_decoder(model, B, S, **BUILDER_ARGS)
    assert out.shape == (B, S, CFG["vocab_size"])
    model.compile(
        optimizer=AdamOptimizer(**OPT),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[MetricsType.ACCURACY, MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY],
        seed=3,
    )
    shapes = ref.param_shapes(CFG)
    rng = np.random.default_rng(5)
    ids = rng.integers(0, CFG["vocab_size"], size=(3 * B, S)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1)  # (n, seq) next-token labels, no reshape
    return model, shapes, ids, labels


def _load_seed_weights(model, shapes):
    ex = model.executor
    ex.params = W.make_for_executor(shapes, SEED, ex)
    ex.opt_state = ex.optimizer.init_state(ex.params)


def _leaf(tree, ex, lname, wname):
    _, bucket, d = ex.locate_weight(lname, wname)
    a = tree[bucket][wname]
    return np.asarray(a if d is None else a[d])


def test_search_prices_the_new_ops(built):
    """``compile(search_budget=8)`` went through the search with the new
    ops' ``flops()`` / ``partitionable_dims()``; the model has one layer
    of each kind per period and every reference leaf has a home."""
    model, shapes, _, _ = built
    kinds = [l.op_type.value for l in model.layers]
    assert kinds.count("gated_delta_net") == 3 and kinds.count("gated_attention") == 1
    assert kinds.count("routed_experts") == 4
    from flexflow_tpu.ops import get_op_def

    for l in model.layers:
        op = get_op_def(l.op_type)
        assert op.flops(l) > 0 and 0 in op.partitionable_dims(l)
    have = {
        (l.name, w.name): tuple(w.shape)
        for l in model.layers for w in get_op_def(l.op_type).weights(l)
    }
    want = {(l, w): tuple(s) for l, ws in shapes.items() for w, s in ws.items()}
    assert have == want


def test_logits_and_loss_match_the_reference(built):
    model, shapes, ids, labels = built
    _load_seed_weights(model, shapes)
    params = W.make(shapes, SEED)
    probs = np.asarray(model.executor.forward([ids[:B]]))
    want = jax.nn.softmax(ref.logits(params, jnp.asarray(ids[:B]), CFG), axis=-1)
    np.testing.assert_allclose(probs, want, rtol=1e-4, atol=1e-7)
    got = -np.mean(np.log(np.take_along_axis(probs, labels[:B, :, None], axis=-1)))
    want = float(ref.loss(params, jnp.asarray(ids[:B]), jnp.asarray(labels[:B]), CFG))
    assert abs(got - want) / want < 1e-4


def test_gradients_and_three_adam_steps_through_fit(built):
    model, shapes, ids, labels = built
    ex = model.executor
    _load_seed_weights(model, shapes)
    p0 = jax.tree.map(jnp.copy, ex.params)
    params = W.make(shapes, SEED)
    loss1, grads = jax.value_and_grad(ref.loss)(
        params, jnp.asarray(ids[:B]), jnp.asarray(labels[:B]), CFG
    )
    batches = [(jnp.asarray(ids[i * B:(i + 1) * B]), jnp.asarray(labels[i * B:(i + 1) * B]))
               for i in range(3)]
    readings = ref.train_readings(W.make(shapes, SEED), batches, CFG, OPT)

    syncs0 = ex.host_syncs
    losses = []
    for i in range(3):
        pm = model.fit(ids[i * B:(i + 1) * B], labels[i * B:(i + 1) * B],
                       epochs=1, verbose=False)
        assert pm.train_all == B
        losses.append(pm.sparse_cce_loss / pm.train_all)
        if i == 0:
            m1 = jax.tree.map(jnp.copy, ex.opt_state["m"])
            # the routing counters came with the flush fit makes anyway
            assert ex.host_syncs - syncs0 == 1
            assert pm.counters["moe.rows_over_budget"] == 0.0
            # 4 layers x 64 tokens x top-4 x 8 of 16 held, give or take the draw
            assert 300 < pm.counters["moe.held_rows"] < 750
            assert pm.gauge("moe.load_max_over_mean") >= 1.0
    assert abs(losses[0] - float(loss1)) / float(loss1) < 1e-4
    np.testing.assert_allclose(losses, readings["loss"], rtol=2e-4)

    for lname in shapes:
        for wname in shapes[lname]:
            g_ref = np.asarray(grads[lname][wname])
            g = _leaf(m1, ex, lname, wname) / (1.0 - OPT["beta1"])
            np.testing.assert_allclose(
                g, g_ref, rtol=0, atol=2e-3 * np.abs(g_ref).max() + 1e-9,
                err_msg=f"gradient of {lname}/{wname}",
            )
            change = np.linalg.norm(
                _leaf(ex.params, ex, lname, wname) - _leaf(p0, ex, lname, wname)
            )
            want = readings["change_norm"][lname][wname]
            assert abs(change - want) <= 0.02 * want, (lname, wname, change, want)
            assert want > 0  # every leaf moved: none is cut off from the loss
