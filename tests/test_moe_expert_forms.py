"""``RoutedExperts`` with ungated ``relu2`` experts and a held share
(ISSUE 34): the op against the plain reference's ``moe_block``; the
share test of the model-configs guide -- what the shares ``[0, 4)`` and
``[4, 8)`` give, the shared expert counted once, adds up to the uncut
layer; a gated layer is what it was."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks import weights_by_leaf as WL  # noqa: E402
from benchmarks.reference import nemotron_h as R  # noqa: E402
from benchmarks.reference.precision import matmul  # noqa: E402
from flexflow_tpu.fftype import OperatorType  # noqa: E402
from flexflow_tpu.ops import get_op_def  # noqa: E402
from flexflow_tpu.ops.base import OpContext  # noqa: E402

CFG = dict(
    hidden_size=64, n_routed_experts=8, router_num_experts=8, first_expert=0,
    moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
    num_experts_per_tok=2, norm_topk_prob=True, routed_scaling_factor=2.5,
    mlp_hidden_act="relu2",
)


def whole_params(seed=4):
    shapes = {"moe": {
        "router": (64, 8), "router_bias": (8,), "w_up": (8, 64, 32), "w_down": (8, 32, 64),
        "shared_up_proj": (64, 48), "shared_down_proj": (48, 64),
    }}
    p = WL.layer(shapes, seed, "moe")
    # a bias that changes the choice, and scores worth choosing between
    p["router"] = p["router"] * 20.0
    p["router_bias"] = p["router_bias"] * 10.0
    return p


def op_forward(p, x, first, held, form="relu2", shared=48, **attrs):
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.fftype import DataType

    m = FFModel(FFConfig(batch_size=x.shape[0]))
    t = m.create_tensor(x.shape, DataType.FLOAT, name="x")
    m.routed_experts(t, 8, 2, 32, first_expert=first, held=held, shared_hidden=shared,
                     score="sigmoid", route_norm=True, route_scale=2.5, router_bias=True,
                     shared_gated=False, expert_form=form, name="moe", **attrs)
    layer = m.layers[-1]
    op = get_op_def(OperatorType.ROUTED_EXPERTS)
    names = {w.name for w in op.weights(layer)}
    share = {k: (v[first:first + held] if k in ("w_up", "w_down") else v)
             for k, v in p.items() if k in names}
    assert set(share) == names
    ctx = OpContext(training=False, rng=None, mesh=None)
    return op.forward(layer, share, [x], ctx), op, layer


@pytest.fixture(scope="module")
def x():
    return jnp.asarray(np.random.default_rng(1).standard_normal((6, 7, 64)), jnp.float32)


def test_relu2_experts_against_the_reference(x):
    p = whole_params()
    out, op, layer = op_forward(p, x, 0, 8)
    ref = R.moe_block(p, x, CFG, matmul("highest"))
    np.testing.assert_allclose(out[0], ref, rtol=2e-5, atol=2e-6)
    assert int(out[1]) == 6 * 7 * 2  # every assignment names a held expert
    ws = {w.name for w in op.weights(layer)}
    assert "w_gate" not in ws and "shared_gate_proj" not in ws and "shared_gate" not in ws
    t = 42
    assert op.flops(layer) == 2.0 * t * 64 * 8 + 4.0 * t * 2 * 64 * 32 + 4.0 * t * 64 * 48
    # the bias chose (it is for choosing only): without it other experts win
    w0, c0 = R.route(p, x.reshape(-1, 64), CFG, matmul("highest"))
    w1, c1 = R.route(dict(p, router_bias=0 * p["router_bias"]), x.reshape(-1, 64), CFG,
                     matmul("highest"))
    assert (np.asarray(c0) != np.asarray(c1)).any()
    np.testing.assert_allclose(np.asarray(w0).sum(-1), 2.5, rtol=1e-5)


def test_the_shares_add_up_to_the_uncut_layer(x):
    """Shares [0, 4) and [4, 8), the router 8 wide in both; the shared
    expert is what every chip computes alike: counted once."""
    p = whole_params()
    mm = matmul("highest")
    whole = R.moe_block(p, x, CFG, mm)
    lo, _, _ = op_forward(p, x, 0, 4)
    hi, _, _ = op_forward(p, x, 4, 4, shared=0)
    np.testing.assert_allclose(lo[0] + hi[0], whole, rtol=2e-5, atol=2e-6)
    assert int(lo[1]) + int(hi[1]) == 6 * 7 * 2 and int(lo[1]) > 0 and int(hi[1]) > 0
    # and the reference's own shares, which is what a cell's reference is handed
    ref_lo = R.moe_block({k: (v[:4] if k in ("w_up", "w_down") else v) for k, v in p.items()},
                         x, dict(CFG, n_routed_experts=4), mm)
    ref_hi = R.moe_block({k: (v[4:] if k in ("w_up", "w_down") else v) for k, v in p.items()},
                         x, dict(CFG, n_routed_experts=4, first_expert=4), mm, shared=False)
    np.testing.assert_allclose(lo[0], ref_lo, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(hi[0], ref_hi, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(ref_lo + ref_hi, whole, rtol=2e-5, atol=2e-6)


def test_relu_for_relu2_is_another_layer(x):
    p = whole_params()
    relu, _, _ = op_forward(p, x, 0, 8, form="relu")
    ref = R.moe_block(p, x, dict(CFG, mlp_hidden_act="relu"), matmul("highest"))
    np.testing.assert_allclose(relu[0], ref, rtol=2e-5, atol=2e-6)
    relu2, _, _ = op_forward(p, x, 0, 8)
    assert np.abs(np.asarray(relu[0]) - np.asarray(relu2[0])).max() > 1e-3


def test_relu2_share_has_gradients(x):
    """The dynamic loop of passes has its own backward rule: it runs
    without a gate's weights too."""
    from flexflow_tpu.ops.moe import held_experts_part, route_top_k

    p = whole_params()
    xs = x.reshape(-1, 64)

    def loss(w_up, w_down):
        w, idx = route_top_k(xs, p["router"], 2, score="sigmoid", bias=p["router_bias"],
                             route_scale=2.5)
        out, *_ = held_experts_part(xs, w, idx, 0, 16, None, w_up, w_down, "relu2")
        return jnp.sum(out ** 2)

    g_up, g_down = jax.grad(loss, argnums=(0, 1))(p["w_up"], p["w_down"])
    assert g_up.shape == p["w_up"].shape and float(jnp.abs(g_up).max()) > 0
    assert g_down.shape == p["w_down"].shape and float(jnp.abs(g_down).max()) > 0
