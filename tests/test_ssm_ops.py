"""The Mamba-2 mixer's functional core (``flexflow_tpu/ops/ssm.py``,
ISSUE 34) against the recurrence it stands for: the chunked scan with a
state handed in and handed back, lengths that are no multiple of the
chunk, rows past ``n_valid``; the single step; the convolution from a
carried state; the grouped gated norm; the op; and the family's draw of
the decay weights (``benchmarks/weights_nemotron_h.py``)."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks import weights_by_leaf as WL  # noqa: E402
from benchmarks import weights_nemotron_h as WN  # noqa: E402
from benchmarks.reference import nemotron_h as R  # noqa: E402
from flexflow_tpu.fftype import OperatorType  # noqa: E402
from flexflow_tpu.ops import get_op_def  # noqa: E402
from flexflow_tpu.ops import ssm  # noqa: E402

B, H, P, G, N = 2, 4, 8, 2, 16


def inputs(s, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((B, s, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.7, (B, s, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(1.0, 16.0, (H,)), jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((B, s, G, N)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((B, s, G, N)), jnp.float32)
    S0 = jnp.asarray(rng.standard_normal((B, H, P, N)), jnp.float32)
    return x, dt, A, Bm, C, S0


@pytest.mark.parametrize("s,chunk", [(24, 8), (21, 8), (5, 8), (16, 16), (37, 4)])
@pytest.mark.parametrize("with_state", [False, True])
def test_chunked_scan_is_the_recurrence(s, chunk, with_state):
    x, dt, A, Bm, C, S0 = inputs(s, seed=s)
    S0 = S0 if with_state else None
    y_ref, S_ref = ssm.ssd_recurrent(x, dt, A, Bm, C, S0)
    y, S = ssm.ssd_chunked(x, dt, A, Bm, C, chunk, S0)
    np.testing.assert_allclose(y, y_ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(S, S_ref, rtol=2e-5, atol=2e-5)


def test_the_recurrence_is_the_references():
    """``ssd_recurrent`` (what the chunked form is held to) and the plain
    reference's own ``selective_scan`` are the same recurrence."""
    x, dt, A, Bm, C, _ = inputs(19, seed=3)
    D = jnp.ones((H,), jnp.float32)
    y, _ = ssm.ssd_recurrent(x, dt, A, Bm, C)
    ref = R.selective_scan(x, dt, A, Bm, C, D) - x
    np.testing.assert_allclose(y, ref, rtol=1e-5, atol=1e-5)


def test_state_handed_from_chunk_to_chunk_is_one_scan():
    """Three calls, each from the state the last handed back (a prompt
    ingested in chunks of 8, the last ragged), against one scan."""
    x, dt, A, Bm, C, _ = inputs(21, seed=7)
    y_ref, S_ref = ssm.ssd_recurrent(x, dt, A, Bm, C)
    S, ys = None, []
    for lo in (0, 8, 16):
        sl = slice(lo, min(lo + 8, 21))
        y, S = ssm.ssd_chunked(x[:, sl], dt[:, sl], A, Bm[:, sl], C[:, sl], 8, S)
        ys.append(y)
    np.testing.assert_allclose(jnp.concatenate(ys, 1), y_ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(S, S_ref, rtol=2e-5, atol=2e-5)


def test_steps_are_the_recurrence_and_dt_zero_leaves_the_state():
    x, dt, A, Bm, C, S0 = inputs(6, seed=1)
    y_ref, S_ref = ssm.ssd_recurrent(x, dt, A, Bm, C, S0)
    S = S0
    for t in range(6):
        y, S = ssm.ssd_step(x[:, t], dt[:, t], A, Bm[:, t], C[:, t], S)
        np.testing.assert_allclose(y, y_ref[:, t], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(S, S_ref, rtol=1e-6, atol=1e-6)
    _, S_same = ssm.ssd_step(x[:, 0], jnp.zeros((B, H)), A, Bm[:, 0], C[:, 0], S0)
    np.testing.assert_array_equal(S_same, S0)
    # rows past n_valid (dt 0) in a chunk: the state after the valid rows
    n_valid = jnp.asarray([4, 0])
    live = jnp.arange(6)[None, :, None] < n_valid[:, None, None]
    _, S_cut = ssm.ssd_chunked(x, jnp.where(live, dt, 0.0), A, Bm, C, 4, S0)
    _, S_four = ssm.ssd_recurrent(x[:, :4], dt[:, :4], A, Bm[:, :4], C[:, :4], S0)
    np.testing.assert_allclose(S_cut[0], S_four[0], rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(S_cut[1], S0[1])


def test_conv_with_state_is_the_whole_sequence_conv():
    rng = np.random.default_rng(2)
    c, taps, s = 12, 4, 19
    x = jnp.asarray(rng.standard_normal((B, s, c)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((c, taps)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((c,)), jnp.float32)
    whole = R.causal_conv(x, w, b)
    y0, st0 = ssm.conv_with_state(x, w, b)
    np.testing.assert_allclose(y0, whole, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(st0, x[:, -3:])
    # in chunks of 8 with the state carried; the last chunk ragged (3 of 8 valid)
    state, ys = None, []
    xp = jnp.pad(x, ((0, 0), (0, 5), (0, 0)), constant_values=99.0)
    for lo, n in ((0, 8), (8, 8), (16, 3)):
        y, state = ssm.conv_with_state(xp[:, lo:lo + 8], w, b, state, jnp.full((B,), n))
        ys.append(y[:, :n])
    np.testing.assert_allclose(jnp.concatenate(ys, 1), whole, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(state, x[:, -3:])
    # a lane with n_valid 0 keeps its state
    _, kept = ssm.conv_with_state(xp[:, :8], w, b, state, jnp.zeros((B,), jnp.int32))
    np.testing.assert_array_equal(kept, state)


def test_grouped_gated_norm():
    rng = np.random.default_rng(4)
    y = rng.standard_normal((3, 5, 32)).astype(np.float32)
    z = rng.standard_normal((3, 5, 32)).astype(np.float32)
    w = rng.standard_normal((32,)).astype(np.float32)
    g = y * (z / (1 + np.exp(-z)))
    gg = g.reshape(3, 5, 4, 8)
    want = (gg / np.sqrt((gg ** 2).mean(-1, keepdims=True) + 1e-5)).reshape(3, 5, 32) * w
    np.testing.assert_allclose(ssm.gated_group_norm(y, z, w, 4, 1e-5), want, rtol=1e-5, atol=1e-5)


CFG = dict(
    hidden_size=32, mamba_num_heads=H, mamba_head_dim=P, n_groups=G, ssm_state_size=N,
    conv_kernel=4, layer_norm_epsilon=1e-5,
)
ATTRS = dict(num_heads=H, head_dim=P, n_groups=G, state_size=N, conv_kernel=4, chunk=8, eps=1e-5)


def mixer_params(seed=9):
    d, cw = H * P, H * P + 2 * G * N
    shapes = {"m": {
        "in_proj": (32, d + cw + H), "conv": (cw, 4), "conv_bias": (cw,), "A_log": (H,),
        "dt_bias": (H,), "D": (H,), "scale": (d,), "out_proj": (d, 32),
    }}
    return WN.layer(shapes, seed, "m")


def test_the_mixer_whole_and_from_its_states():
    from benchmarks.reference.precision import matmul

    p = mixer_params()
    u = jnp.asarray(np.random.default_rng(0).standard_normal((B, 21, 32)), jnp.float32)
    ref = R.mamba2(p, u, CFG, matmul("highest"))
    out, conv_s, ssm_s = ssm.mamba2_mixer(p, u, ATTRS)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)
    # two chunks, then single steps, from the states handed on
    o1, c1, s1 = ssm.mamba2_mixer(p, u[:, :8], ATTRS, None, None, jnp.full((B,), 8))
    o2, c2, s2 = ssm.mamba2_mixer(p, u[:, 8:16], ATTRS, c1, s1, jnp.full((B,), 8))
    outs = [o1, o2]
    for t in range(16, 21):
        o, c2, s2 = ssm.mamba2_mixer(p, u[:, t:t + 1], ATTRS, c2, s2, jnp.ones((B,), jnp.int32))
        outs.append(o)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), ref, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(s2, ssm_s, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(c2, conv_s, rtol=1e-6, atol=1e-6)


def test_the_op_declares_its_weights_work_and_split():
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.fftype import DataType

    m = FFModel(FFConfig(batch_size=2))
    x = m.create_tensor((2, 16, 32), DataType.FLOAT, name="x")
    m.mamba2_mixer(x, H, P, G, N, 4, 8, 1e-5, name="mix")
    layer = m.layers[-1]
    op = get_op_def(OperatorType.MAMBA2_MIXER)
    d, cw = H * P, H * P + 2 * G * N
    ws = {w.name: w.shape for w in op.weights(layer)}
    assert ws == {"in_proj": (32, d + cw + H), "conv": (cw, 4), "conv_bias": (cw,),
                  "A_log": (H,), "dt_bias": (H,), "D": (H,), "scale": (d,), "out_proj": (d, 32)}
    assert op.fp32_weights == {"A_log", "dt_bias", "D"}
    assert op.partitionable_dims(layer) == {0: "sample"}
    t = 2 * 16
    proj = 2 * t * 32 * (2 * d + cw + H)
    assert op.flops(layer) == proj + 2 * t * cw * 4 + 2 * t * H * (4 * (N / 2 + P) + 2 * P * N)


def test_decay_weights_are_drawn_as_the_family_draws_them():
    a_log = np.asarray(WN.leaf(3, "l1_mamba", "A_log", (4096,)))
    dt_bias = np.asarray(WN.leaf(3, "l1_mamba", "dt_bias", (4096,)))
    D = np.asarray(WN.leaf(3, "l1_mamba", "D", (4096,)))
    a, dt = np.exp(a_log), np.log1p(np.exp(dt_bias))
    assert 1.0 <= a.min() < 1.1 and 15.9 < a.max() <= 16.0
    assert 0.00099 < dt.min() < 0.0011 and 0.09 < dt.max() < 0.1001
    assert abs(np.log(dt).mean() - (np.log(0.001) + np.log(0.1)) / 2) < 0.1
    assert abs(D.mean() - 1.0) < 0.01 and 0.015 < D.std() < 0.025
    # a per-token decay between e^-1.6 and e^-0.001
    assert np.exp(-a * dt).min() > np.exp(-1.61) and np.exp(-a * dt).max() < 1.0
    # every other leaf is weights_by_leaf's, and a leaf is made alone
    np.testing.assert_array_equal(
        WN.leaf(3, "l1_mamba", "in_proj", (8, 4)), WL.leaf(3, "l1_mamba", "in_proj", (8, 4)))
    np.testing.assert_array_equal(WN.leaf(3, "l1_mamba", "A_log", (4096,)), a_log)
    assert not np.array_equal(np.asarray(WN.leaf(4, "l1_mamba", "A_log", (4096,))), a_log)
    assert WN.leaf(3, "l1_mamba", "D", (4,), jnp.bfloat16).dtype == jnp.bfloat16
