"""The three new ops of the Qwen3-Next block against the plain
reference (``benchmarks/reference/qwen3_next.py``), small, float32, CPU.

Tolerances: everything here is float32 on the CPU, where a matmul is
exact to rounding; the program and the reference differ in the ORDER of
float32 sums (chunked against token by token, grouped against masked),
which stays under 1e-5 relative at these sizes.  2e-4 leaves room for
the delta rule's 64-step products and fails on any wrong term.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import qwen3_next as ref  # noqa: E402
from benchmarks.reference.precision import matmul  # noqa: E402
from flexflow_tpu.fftype import DataType, OperatorType  # noqa: E402
from flexflow_tpu.ops import get_op_def  # noqa: E402
from flexflow_tpu.ops import linear_attention as la  # noqa: E402
from flexflow_tpu.ops import moe  # noqa: E402
from flexflow_tpu.ops.base import OpContext  # noqa: E402
from flexflow_tpu.tensor import Layer, Tensor  # noqa: E402

CFG = {
    "hidden_size": 32, "vocab_size": 64, "num_hidden_layers": 4,
    "full_attention_interval": 4, "rms_norm_eps": 1e-6,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "partial_rotary_factor": 0.25, "rope_theta": 1e7,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 8, "linear_conv_kernel_dim": 4,
    "num_experts": 16, "router_num_experts": 16, "first_expert": 0,
    "num_experts_per_tok": 4, "norm_topk_prob": True,
    "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
}
MM = matmul("highest")
TOL = dict(rtol=2e-4, atol=2e-6)


def _draw(shapes, seed, scale=0.3):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return {
        n: (1.0 if n == "scale" else 0.0) + scale * jax.random.normal(k, s, jnp.float32)
        for k, (n, s) in zip(keys, sorted(shapes.items()))
    }


def _layer(op_type, attrs, x):
    t = Tensor(tuple(x.shape), DataType.FLOAT, name="x")
    layer = Layer(op_type, "op", [t], attrs)
    return layer, get_op_def(op_type)


def _rule_inputs(seed, b, s, h, dk, dv, strong_head=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = la.l2_normalize(jax.random.normal(ks[0], (b, s, h, dk))) / np.sqrt(dk)
    k = la.l2_normalize(jax.random.normal(ks[1], (b, s, h, dk)))
    v = jax.random.normal(ks[2], (b, s, h, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (b, s, h)))
    if strong_head is not None:  # this head forgets all but e^-30 of its state a token
        g = g.at[:, :, strong_head].set(-30.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    return q, k, v, g, beta


@pytest.mark.parametrize("seq,chunk,strong", [(16, 8, None), (40, 8, None), (40, 8, 1), (128, 64, 0)])
def test_chunked_rule_is_the_token_by_token_rule(seq, chunk, strong):
    """Values, final state and gradients at 2 and 5 chunks (40 = 5 x 8:
    no padding; 16 = 2 x 8), at the layer's own chunk of 64, and with one
    head's decay so strong that a quotient of cumulative decays would
    overflow (e^{30 * 8}) -- differences of log-decays do not."""
    args = _rule_inputs(3, 2, seq, 3, 8, 8, strong)
    o1, s1 = la.gated_delta_rule_recurrent(*args)
    o2, s2 = la.gated_delta_rule_chunked(*args, chunk=chunk)
    np.testing.assert_allclose(o2, o1, **TOL)
    np.testing.assert_allclose(s2, s1, **TOL)
    # and against the reference's own token loop
    np.testing.assert_allclose(ref.delta_rule_token_by_token(*args), o1, **TOL)

    probe = jax.random.normal(jax.random.PRNGKey(9), o1.shape)

    def scalar(fn):
        return lambda *a: jnp.sum(fn(*a)[0] * probe)

    g1 = jax.grad(scalar(la.gated_delta_rule_recurrent), argnums=range(5))(*args)
    g2 = jax.grad(
        scalar(lambda *a: la.gated_delta_rule_chunked(*a, chunk=chunk)), argnums=range(5)
    )(*args)
    for a, c in zip(g1, g2):
        assert np.all(np.isfinite(c))
        np.testing.assert_allclose(c, a, rtol=5e-4, atol=5e-6)


def test_chunked_rule_pads_a_ragged_tail():
    args = _rule_inputs(4, 1, 21, 2, 8, 8)
    o1, s1 = la.gated_delta_rule_recurrent(*args)
    o2, s2 = la.gated_delta_rule_chunked(*args, chunk=8)
    np.testing.assert_allclose(o2, o1, **TOL)
    np.testing.assert_allclose(s2, s1, **TOL)


def test_gated_delta_net_layer_matches_reference():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, CFG["hidden_size"]))
    p = _draw(ref.param_shapes(CFG)["l0_gdn"], 1)
    layer, op = _layer(OperatorType.GATED_DELTA_NET, dict(
        num_k_heads=2, num_v_heads=4, head_k_dim=8, head_v_dim=8, conv_kernel=4, eps=1e-6), x)
    assert {w.name: w.shape for w in op.weights(layer)} == ref.param_shapes(CFG)["l0_gdn"]
    got = op.forward(layer, p, [x], OpContext(training=True))[0]
    np.testing.assert_allclose(got, ref.gated_delta_net(p, x, CFG, MM), **TOL)


def test_gated_attention_layer_matches_reference():
    """Rotary on a quarter of the head (4 of 16 dims), 2 key-value heads
    under 4 query heads, q/k norm with 1 + w, the sigmoid gate."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, CFG["hidden_size"]))
    p = _draw(ref.param_shapes(CFG)["l3_attn"], 2)
    layer, op = _layer(OperatorType.GATED_ATTENTION, dict(
        num_heads=4, num_kv_heads=2, head_dim=16, rotary_dim=4, rope_theta=1e7, eps=1e-6), x)
    assert {w.name: w.shape for w in op.weights(layer)} == ref.param_shapes(CFG)["l3_attn"]
    got = op.forward(layer, p, [x], OpContext(training=True))[0]
    want = ref.full_attention(p, x, CFG, MM)
    np.testing.assert_allclose(got, want, **TOL)
    # the rotary part is really there: position 0 reads the same, later ones differ
    other = op.forward(layer, p, [jnp.roll(x, 1, axis=1)], OpContext(training=True))[0]
    assert not np.allclose(other[:, 1:], got[:, :-1], rtol=1e-3)


def _moe_layer(x, first, held, shared=16, n=16):
    return _layer(OperatorType.ROUTED_EXPERTS, dict(
        n_experts=n, first_expert=first, held=held, top_k=4, hidden=16,
        shared_hidden=shared), x)


def test_routed_experts_layer_matches_reference():
    x = jax.random.normal(jax.random.PRNGKey(0), (48, CFG["hidden_size"]))
    p = _draw(ref.param_shapes(CFG)["l0_moe"], 3)
    layer, op = _moe_layer(x, 0, 16)
    assert {w.name: w.shape for w in op.weights(layer)} == ref.param_shapes(CFG)["l0_moe"]
    got, held_rows, passes, over, load = op.forward(layer, p, [x], OpContext(training=True))
    np.testing.assert_allclose(got, ref.moe_block(p, x, CFG, MM), **TOL)
    assert float(held_rows) == 48 * 4 and float(passes) == 1 and float(over) == 0.0
    assert float(load) >= 1.0
    # gradients through the sort, the grouped matmuls and the scatter
    probe = jax.random.normal(jax.random.PRNGKey(5), got.shape)
    g_op = jax.grad(lambda q, t: jnp.sum(op.forward(layer, q, [t], OpContext(True))[0] * probe),
                    argnums=(0, 1))(p, x)
    g_ref = jax.grad(lambda q, t: jnp.sum(ref.moe_block(q, t, CFG, MM) * probe),
                     argnums=(0, 1))(p, x)
    for a, c in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_op)):
        np.testing.assert_allclose(c, a, rtol=5e-4, atol=5e-6)


def test_the_shares_parts_add_up_to_the_uncut_layer():
    """16 experts over 4 shares of 4: each share routes over all 16 and
    returns its own experts' part; the four routed parts plus the shared
    expert, counted once, are the uncut reference layer."""
    x = jax.random.normal(jax.random.PRNGKey(7), (40, CFG["hidden_size"]))
    p = _draw(ref.param_shapes(CFG)["l0_moe"], 8)
    whole = ref.moe_block(p, x, CFG, MM)
    shared_only = moe.gated_ffn(
        x, p["shared_gate_proj"], p["shared_up_proj"], p["shared_down_proj"]
    ) * jax.nn.sigmoid(x @ p["shared_gate"])
    total, rows = shared_only, 0.0
    for share in range(4):
        layer, op = _moe_layer(x, 4 * share, 4, shared=0)
        mine = {k: (v[4 * share: 4 * share + 4] if k.startswith("w_") else v)
                for k, v in p.items() if not k.startswith("shared")}
        part, held_rows, _, over, _ = op.forward(layer, mine, [x], OpContext(training=True))
        assert float(over) == 0.0
        total, rows = total + part, rows + float(held_rows)
    assert rows == 40 * 4  # every assignment is some share's
    np.testing.assert_allclose(total, whole, **TOL)
    # one share alone, with the reference given the same share
    cfg = dict(CFG, first_expert=8, num_experts=4)
    layer, op = _moe_layer(x, 8, 4)
    mine = {k: (v[8:12] if k.startswith("w_") else v) for k, v in p.items()}
    np.testing.assert_allclose(
        op.forward(layer, mine, [x], OpContext(training=True))[0],
        ref.moe_block(mine, x, cfg, MM), **TOL)


@pytest.mark.parametrize("factor,passes", [(0.5, 2), (0.3, 4), (0.25, 4)])
def test_rows_over_one_pass_take_further_passes(factor, passes, monkeypatch):
    """A pass too small for the rows routed here (a router that prefers
    this share): further passes take the rest, values and gradients stay
    the reference's, nothing is left out."""
    monkeypatch.setattr(moe, "PASS_ROWS_FACTOR", factor)
    x = jax.random.normal(jax.random.PRNGKey(1), (40, CFG["hidden_size"]))
    p = _draw(ref.param_shapes(CFG)["l0_moe"], 3)
    layer, op = _moe_layer(x, 0, 16)  # e.g. 80 rows a pass for 160 assignments
    assert moe.pass_rows(40, 4, 16, 16) == {0.5: 80, 0.3: 48, 0.25: 40}[factor]
    monkeypatch.undo()
    assert moe.pass_rows(8192, 10, 32, 512) == 10240  # the cell's pass
    monkeypatch.setattr(moe, "PASS_ROWS_FACTOR", factor)
    got, held_rows, n, over, _ = op.forward(layer, p, [x], OpContext(training=True))
    assert float(held_rows) == 160 and float(n) == passes and float(over) == 0.0
    np.testing.assert_allclose(got, ref.moe_block(p, x, CFG, MM), **TOL)
    probe = jax.random.normal(jax.random.PRNGKey(5), got.shape)
    g_op = jax.jit(jax.grad(
        lambda q, t: jnp.sum(op.forward(layer, q, [t], OpContext(True))[0] * probe),
        argnums=(0, 1)))(p, x)
    g_ref = jax.grad(lambda q, t: jnp.sum(ref.moe_block(q, t, CFG, MM) * probe),
                     argnums=(0, 1))(p, x)
    for a, c in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_op)):
        np.testing.assert_allclose(c, a, rtol=5e-4, atol=5e-6)


def test_rows_a_capped_loop_leaves_out_are_counted():
    """``moe.rows_over_budget`` is the held rows less those the passes
    really covered: with one pass fewer than the rows need (what a cap
    on the loop would do) the rows of the last pass are counted as left
    out; with the passes the op computes, none is."""
    x = jax.random.normal(jax.random.PRNGKey(1), (40, CFG["hidden_size"]))
    p = _draw(ref.param_shapes(CFG)["l0_moe"], 3)
    w, idx = moe.route_top_k(x, p["router"], 4)
    experts = (p["w_gate"], p["w_up"], p["w_down"])
    part, counts, passes, covered = moe.held_experts_part(x, w, idx, 0, 48, *experts)
    assert int(passes) == 4 and int(covered) == int(jnp.sum(counts)) == 160
    key = idx.reshape(-1)
    order = jnp.argsort(key, stable=True)
    pad = -160 % 48
    capped, reached = moe._held_passes(
        48, 4, x, w.reshape(-1), *experts, jnp.pad(order, (0, pad)),
        jnp.pad(key[order], (0, pad), constant_values=16), jnp.cumsum(counts), 3)
    assert int(reached) == 144  # 16 rows over
    assert not np.allclose(capped, part, atol=1e-6)


def test_zero_centred_rms_norm():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 8))
    w = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (8,))
    layer, op = _layer(OperatorType.RMS_NORM, dict(eps=1e-6, zero_centered=True), x)
    assert [(s.name, s.shape) for s in op.weights(layer)] == [("weight", (8,))]
    got = op.forward(layer, {"weight": w}, [x], OpContext(training=True))[0]
    np.testing.assert_allclose(got, ref.rms_norm(x, w, 1e-6), rtol=1e-6, atol=1e-6)
