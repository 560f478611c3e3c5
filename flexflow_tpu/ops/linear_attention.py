"""Gated DeltaNet: linear attention with a gated delta-rule state.

No reference analog (FlexFlow 2022 has no recurrent-state layer).  The
layer is Qwen3-Next's linear-attention mixer (Yang et al., "Gated Delta
Networks", arXiv:2412.06464): fused input projections, a causal
depthwise convolution over ``[q, k, v]``, and per value head a state
``S`` (``dk x dv``, float32) that every token first decays and then
corrects by the delta rule::

    S <- exp(g_t) S;   S <- S + k_t (x) (beta_t (v_t - S^T k_t));   o_t = S^T q_t

``gated_delta_rule_recurrent`` is that recurrence token by token (the
exact form, for tests).  ``gated_delta_rule_chunked`` is what the layer
runs: the WY / UT form over chunks of 64 tokens -- inside a chunk the
``beta``-weighted corrections are one unit-lower-triangular solve, and
across chunks a ``lax.scan`` carries ``S``.  Every decay factor is the
exponential of a *difference* of cumulative log-decays (never positive,
masked before the exponential), so a head that forgets half its state a
token neither overflows nor divides by an underflowed number.
"""

from __future__ import annotations

import math
from typing import List

import jax
import jax.numpy as jnp

from flexflow_tpu.fftype import OperatorType
from flexflow_tpu.initializer import (
    OnesInitializer,
    ZeroInitializer,
    default_kernel_initializer,
)
from flexflow_tpu.ops.base import OpContext, OpDef, ShapeDtype, WeightSpec, register_op
from flexflow_tpu.tensor import Layer

CHUNK = 64


def l2_normalize(x, eps: float = 1e-6):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def causal_depthwise_conv(x, w):
    """``y[t, c] = sum_j w[c, j] * x[t - (K-1) + j, c]`` over ``x``
    (batch, seq, channels) with ``w`` (channels, K); positions before
    the sequence read zero (no bias)."""
    taps = w.shape[-1]
    s = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(xp[:, j:j + s, :] * w[:, j] for j in range(taps))


def gated_delta_rule_recurrent(q, k, v, g, beta, state=None):
    """Token by token.  ``q``, ``k`` (b, s, h, dk); ``v`` (b, s, h, dv);
    ``g`` (log-decay, <= 0) and ``beta`` (b, s, h).  Float32 throughout.
    Returns ``(o (b, s, h, dv), final state (b, h, dk, dv))``."""
    f32 = jnp.float32
    q, k, v, g, beta = (t.astype(f32) for t in (q, k, v, g, beta))
    b, _, h, dk = q.shape
    if state is None:
        state = jnp.zeros((b, h, dk, v.shape[-1]), f32)

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = S * jnp.exp(g_t)[..., None, None]
        mem = jnp.einsum("bhkv,bhk->bhv", S, k_t)
        S = S + jnp.einsum("bhk,bhv->bhkv", k_t, (v_t - mem) * b_t[..., None])
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))
    state, o = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(o, 0, 1), state


def gated_delta_rule_chunked(q, k, v, g, beta, chunk: int = CHUNK, state=None):
    """The same function of the same arguments in chunks of ``chunk``
    tokens.  Matmul operands keep the dtype ``q`` arrives in (bfloat16
    under mixed precision) and accumulate in float32; decays, the
    triangular solve and the state are float32."""
    f32 = jnp.float32
    mm = q.dtype
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    pad = -s % chunk
    if pad:  # k = 0, beta = 0, g = 0: a padded token leaves the state alone
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta)
        )
    n = (s + pad) // chunk

    def blocks(t):  # (b, s, h, ...) -> (b, h, n, chunk, ...)
        t = t.reshape((b, n, chunk) + t.shape[2:])
        return jnp.moveaxis(t, 3, 1)

    q, k, v = blocks(q), blocks(k), blocks(v)
    g, beta = blocks(g.astype(f32)), blocks(beta.astype(f32))

    def dot(spec, a, c):
        return jnp.einsum(spec, a.astype(mm), c.astype(mm), preferred_element_type=f32)

    G = jnp.cumsum(g, axis=-1)  # cumulative log-decay inside the chunk
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(lower, G[..., :, None] - G[..., None, :], -jnp.inf))
    kb = k.astype(f32) * beta[..., None]
    vb = v.astype(f32) * beta[..., None]
    # (I + L) [u | w] = [beta v | beta k e^G]: row i is corrected by what
    # rows j < i have already written, each decayed from j to i
    L = jnp.tril(dot("bhnid,bhnjd->bhnij", kb, k) * decay, -1)
    rhs = jnp.concatenate([vb, kb * jnp.exp(G)[..., None]], axis=-1)
    sol = jax.scipy.linalg.solve_triangular(
        L + jnp.eye(chunk, dtype=f32), rhs, lower=True, unit_diagonal=True
    )
    u, w = sol[..., :dv], sol[..., dv:]
    qk = dot("bhnid,bhnjd->bhnij", q, k) * decay
    q_in = q.astype(f32) * jnp.exp(G)[..., None]  # decayed from the chunk's start
    k_out = k.astype(f32) * jnp.exp(G[..., -1:] - G)[..., None]  # to its end
    g_all = jnp.exp(G[..., -1])

    def step(S, x):
        u_i, w_i, qk_i, q_i, k_i, g_i = x
        v_new = u_i - dot("bhik,bhkv->bhiv", w_i, S)
        o = dot("bhik,bhkv->bhiv", q_i, S) + dot("bhij,bhjv->bhiv", qk_i, v_new)
        S = S * g_i[..., None, None] + dot("bhik,bhiv->bhkv", k_i, v_new)
        return S, o

    if state is None:
        state = jnp.zeros((b, h, dk, dv), f32)
    xs = tuple(jnp.moveaxis(t, 2, 0) for t in (u, w, qk, q_in, k_out, g_all))
    state, o = jax.lax.scan(step, state, xs)
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, n * chunk, dv)  # (n, b, h, c, dv) ->
    return jnp.moveaxis(o, 1, 2)[:, :s], state


class GatedDeltaNet(OpDef):
    """Input (B, S, E) -> output (B, S, E).  Attrs: ``num_k_heads``,
    ``num_v_heads``, ``head_k_dim``, ``head_v_dim``, ``conv_kernel``,
    ``eps``.  Weights (column order of the fused projections is
    ``[q | k | v | z]`` and ``[b | a]``, heads contiguous)::

        in_proj_qkvz (E, 2*Hk*dk + 2*Hv*dv)   in_proj_ba (E, 2*Hv)
        conv (2*Hk*dk + Hv*dv, K)             A_log, dt_bias (Hv,)  float32
        scale (dv,)  -- the gated RMSNorm     out_proj (Hv*dv, E)
    """

    op_type = OperatorType.GATED_DELTA_NET
    fp32_weights = frozenset({"A_log", "dt_bias"})

    def infer(self, layer: Layer) -> List[ShapeDtype]:
        t = layer.inputs[0]
        return [(t.shape, t.dtype)]

    def _dims(self, layer: Layer):
        a = layer.attrs
        return a["num_k_heads"], a["num_v_heads"], a["head_k_dim"], a["head_v_dim"]

    def weights(self, layer: Layer) -> List[WeightSpec]:
        t = layer.inputs[0]
        e, dt = t.shape[-1], t.dtype
        hk, hv, dk, dv = self._dims(layer)
        init = layer.attrs.get("kernel_initializer") or default_kernel_initializer()
        return [
            WeightSpec("in_proj_qkvz", (e, 2 * hk * dk + 2 * hv * dv), dt, init),
            WeightSpec("in_proj_ba", (e, 2 * hv), dt, init),
            WeightSpec("conv", (2 * hk * dk + hv * dv, layer.attrs["conv_kernel"]), dt, init),
            WeightSpec("A_log", (hv,), dt, ZeroInitializer()),
            WeightSpec("dt_bias", (hv,), dt, OnesInitializer()),
            WeightSpec("scale", (dv,), dt, OnesInitializer()),
            WeightSpec("out_proj", (hv * dv, e), dt, init),
        ]

    def forward(self, layer, params, inputs, ctx: OpContext):
        x = inputs[0]
        f32 = jnp.float32
        hk, hv, dk, dv = self._dims(layer)
        b, s, _ = x.shape
        with jax.named_scope("ff.gdn"):
            qkvz = x @ params["in_proj_qkvz"]
            ba = jnp.matmul(x, params["in_proj_ba"], preferred_element_type=f32)
            mixed, z = jnp.split(qkvz, [2 * hk * dk + hv * dv], axis=-1)
            mixed = jax.nn.silu(causal_depthwise_conv(mixed, params["conv"]))
            q, k, v = jnp.split(mixed, [hk * dk, 2 * hk * dk], axis=-1)
            beta = jax.nn.sigmoid(ba[..., :hv])
            g = -jnp.exp(params["A_log"].astype(f32)) * jax.nn.softplus(
                ba[..., hv:] + params["dt_bias"].astype(f32)
            )
            q = l2_normalize(q.reshape(b, s, hk, dk)) * (1.0 / math.sqrt(dk))
            k = l2_normalize(k.reshape(b, s, hk, dk))
            q, k = (jnp.repeat(t.astype(x.dtype), hv // hk, axis=2) for t in (q, k))
            o, _ = gated_delta_rule_chunked(q, k, v.reshape(b, s, hv, dv), g, beta)
            ms = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
            o = o * jax.lax.rsqrt(ms + layer.attrs.get("eps", 1e-6))
            o = o * params["scale"].astype(f32) * jax.nn.silu(
                z.reshape(b, s, hv, dv).astype(f32)
            )
            return [o.reshape(b, s, hv * dv).astype(x.dtype) @ params["out_proj"]]

    def flops(self, layer: Layer) -> float:
        t = layer.inputs[0]
        b, s, e = t.shape
        hk, hv, dk, dv = self._dims(layer)
        proj = 2.0 * b * s * e * (2 * hk * dk + 2 * hv * dv + 2 * hv + hv * dv)
        conv = 2.0 * b * s * (2 * hk * dk + hv * dv) * layer.attrs["conv_kernel"]
        # per token and value head: the chunk's k k^T, q k^T and their
        # products with the values (4 x chunk/2 x (dk + dv) on average)
        # and three products with the state (w S, q S, k^T v)
        rule = 2.0 * b * s * hv * (CHUNK * (dk + dv) + 3 * dk * dv)
        return proj + conv + rule

    def partitionable_dims(self, layer):
        return {0: "sample"}  # the recurrence runs along dim 1


register_op(GatedDeltaNet())


# ------------------------------------------------------------ lightning
def lightning_decay(num_heads: int, layer_idx: int, num_layers: int):
    """MiniMax-01's lightning-attention decay a head: ``lambda_h = s_h (1
    - l / (L - 1) + 1e-5)`` with ``s_h = 2^(-8 (h + 1) / num_heads)``,
    ``l`` the layer's index in a stack of ``L`` (float32, (heads,))."""
    import numpy as np

    h = np.arange(num_heads, dtype=np.float64)
    slope = 2.0 ** (-8.0 * (h + 1) / num_heads)
    return (slope * (1.0 - layer_idx / (num_layers - 1) + 1e-5)).astype(np.float32)


def lightning_mixer(params, u, a: dict, state=None, n_valid=None, pos=None):
    """Lightning attention over ``u`` (batch, seq, hidden): per head a
    (head_dim x head_dim) float32 state that every position decays by the
    head's fixed ``exp(-lambda)`` and adds ``v (x) k`` to, read out by
    the query::

        S_t = exp(-lambda) S_{t-1} + v_t (x) k_t;   o_t = S_t q_t / sqrt(d)
        out = W_o (RMSNorm(o over all heads) * sigmoid(W_g u))

    q and k per-head RMS-normed, then rotary at ``pos`` (batch, seq)
    (from 0 when None) when ``rope_theta`` is set.  The recurrence is SSD
    (``ops/ssm.py``) with ``dt = 1``, ``A = -lambda``, ``B = k``, ``C =
    q``, ``x = v`` and a group a head: :func:`~flexflow_tpu.ops.ssm.ssd_step`
    for one row a lane with a state, :func:`~flexflow_tpu.ops.ssm.ssd_chunked`
    otherwise.  ``n_valid`` (batch,): rows at or past it leave the state
    alone (``dt`` 0).  Returns ``(out, state')``."""
    from flexflow_tpu.ops.attention import rotate_half_rope_at
    from flexflow_tpu.ops.norm import rms_norm_f32
    from flexflow_tpu.ops.ssm import ssd_chunked, ssd_step

    f32 = jnp.float32
    H, d = a["num_heads"], a["head_dim"]
    b, s, _ = u.shape
    q = (u @ params["wq"]).reshape(b, s, H, d)
    k = (u @ params["wk"]).reshape(b, s, H, d)
    v = (u @ params["wv"]).reshape(b, s, H, d)
    q = rms_norm_f32(q, params["q_norm"], a["eps"])
    k = rms_norm_f32(k, params["k_norm"], a["eps"])
    if a.get("rope_theta"):
        if pos is None:
            pos = jnp.arange(s)[None, :]
        q = rotate_half_rope_at(q, pos, d, a["rope_theta"])
        k = rotate_half_rope_at(k, pos, d, a["rope_theta"])
    q, k = q.astype(u.dtype), k.astype(u.dtype)
    dt = jnp.ones((b, s, H), f32)
    if n_valid is not None:
        dt = jnp.where(jnp.arange(s)[None, :, None] < n_valid[:, None, None], dt, 0.0)
    A = -jnp.asarray(lightning_decay(H, a["layer_idx"], a["num_layers"]))
    with jax.named_scope("ff.lightning"):
        if s == 1 and state is not None:
            y, state = ssd_step(v[:, 0], dt[:, 0], A, k[:, 0], q[:, 0], state)
            y = y[:, None]
        else:
            y, state = ssd_chunked(v, dt, A, k, q, a.get("chunk", 128), state)
    y = y.reshape(b, s, H * d) * (1.0 / math.sqrt(d))
    y = rms_norm_f32(y, params["o_norm"], a["eps"])
    y = y * jax.nn.sigmoid((u @ params["wg"]).astype(f32))
    return y.astype(u.dtype) @ params["wo"], state


class LightningAttention(OpDef):
    """Input (B, S, E) -> output (B, S, E): :func:`lightning_mixer`.
    Attrs: ``num_heads``, ``head_dim``, ``layer_idx`` and ``num_layers``
    (the decay's, as published), ``rope_theta`` (0: no rotary), ``eps``,
    ``chunk``.  Weights ``wq``, ``wk``, ``wv``, ``wg``
    (E, H*d), ``wo`` (H*d, E), ``q_norm``, ``k_norm`` (d,), ``o_norm``
    (H*d,)."""

    op_type = OperatorType.LIGHTNING_ATTENTION

    def infer(self, layer: Layer) -> List[ShapeDtype]:
        t = layer.inputs[0]
        return [(t.shape, t.dtype)]

    def weights(self, layer: Layer) -> List[WeightSpec]:
        t = layer.inputs[0]
        e, dt = t.shape[-1], t.dtype
        w = layer.attrs["num_heads"] * layer.attrs["head_dim"]
        init = layer.attrs.get("kernel_initializer") or default_kernel_initializer()
        return [
            WeightSpec("wq", (e, w), dt, init),
            WeightSpec("wk", (e, w), dt, init),
            WeightSpec("wv", (e, w), dt, init),
            WeightSpec("wg", (e, w), dt, init),
            WeightSpec("wo", (w, e), dt, init),
            WeightSpec("q_norm", (layer.attrs["head_dim"],), dt, OnesInitializer()),
            WeightSpec("k_norm", (layer.attrs["head_dim"],), dt, OnesInitializer()),
            WeightSpec("o_norm", (w,), dt, OnesInitializer()),
        ]

    def forward(self, layer, params, inputs, ctx: OpContext):
        return [lightning_mixer(params, inputs[0], layer.attrs)[0]]

    def flops(self, layer: Layer) -> float:
        b, s, e = layer.inputs[0].shape
        h, d = layer.attrs["num_heads"], layer.attrs["head_dim"]
        # five projections; a position and head: the state's update and
        # read-out (2 d d each) and the chunk's pairs (chunk / 2 on average)
        chunk = layer.attrs.get("chunk", 128)
        return 2.0 * b * s * (5 * e * h * d + h * (2 * d * d + chunk * d))

    def partitionable_dims(self, layer):
        return {0: "sample"}  # the recurrence runs along dim 1


register_op(LightningAttention())
