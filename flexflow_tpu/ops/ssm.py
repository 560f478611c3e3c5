"""Mamba-2: a state-space mixer with a scalar decay a head.

No reference analog (FlexFlow 2022 has no recurrent-state layer).  The
layer is the ``nemotron_h`` family's ``M`` mixer (Dao & Gu, "Transformers
are SSMs", arXiv:2405.21060): one input projection ``[z | xBC | dt]``, a
causal depthwise convolution with bias over ``xBC``, and per head a state
``S`` (``head_dim x state``, float32) that every token decays by a scalar
and adds an outer product to::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;    y_t = S_t C_t + D x_t

with ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head and
``B``, ``C`` shared by the heads of a group; then a gated RMSNorm over
each group's channels (gate first) and the output projection.

The functional core is ONE set of functions, called by the op's forward
(whole sequences, no state handed in) and by the serve programs
(``serve/programs.py``: a chunk from a slot's state, or one step):

* :func:`conv_with_state` -- the convolution from the last ``K - 1``
  inputs a lane carries, handing back those of the rows it was given;
* :func:`ssd_chunked` -- the recurrence in chunks (inside a chunk the
  decays are the exponential of a *difference* of cumulative log-decays,
  never positive, masked before the exponential; across chunks a
  ``lax.scan`` carries ``S``), from a state handed in, handing the last
  back;
* :func:`ssd_step` -- one token of the recurrence;
* :func:`ssd_recurrent` -- :func:`ssd_step` token by token (the exact
  form, for tests);
* :func:`gated_group_norm`;
* :func:`mamba2_mixer` -- the layer, from its weights and attrs.

A row with ``dt = 0`` leaves ``S`` as it was (decay 1, nothing added):
that is how rows past ``n_valid`` and idle lanes pass through.

Mamba-1 (Gu & Dao, arXiv:2312.00752; the ``phi4flash`` family's
state-space layers) decays every (channel, state) pair by its own
factor, so the SSD matrix form does not apply: ``[x | z] = u W_in``,
``x = silu(conv(x))``, ``[delta | B | C] = x W_x``, ``dt =
softplus(delta W_dt + b_dt)`` a channel, ``A = -exp(A_log)`` of shape
(channels, state), and a state ``S`` (channels x state, float32)::

    S_t = exp(dt_t (.) A) (.) S_{t-1} + (dt_t x_t) (x) B_t;    y_t = S_t C_t + D x_t

then ``m = y * silu(z)`` and ``out = m W_out``.  Its functions:
:func:`selective_step` (one position), :func:`selective_scan` (a
``lax.scan`` over positions from a state handed in) and
:func:`mamba1_mixer`; :func:`conv_with_state` is shared.  The layer may
export ``m``, the memory the ``phi4flash`` family's gated memory units
(:func:`gated_memory`) read.
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

CHUNK = 128


def conv_with_state(x, w, b, state=None, n_valid=None):
    """Causal depthwise convolution ``y[t, c] = b[c] + sum_j w[c, j] *
    u[t + j, c]`` over ``u`` = the lane's last ``K - 1`` inputs
    (``state`` (batch, K - 1, channels), oldest first; zeros when None)
    followed by ``x`` (batch, seq, channels); ``w`` (channels, K).
    Returns ``(y, state')``: ``state'`` is the last ``K - 1`` inputs once
    the lane's first ``n_valid`` (batch,) rows are in (all of them when
    None; a lane with ``n_valid`` 0 keeps its state)."""
    taps = w.shape[-1]
    bsz, s, c = x.shape
    if state is None:
        state = jnp.zeros((bsz, taps - 1, c), x.dtype)
    u = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    y = sum(u[:, j:j + s, :] * w[:, j] for j in range(taps)) + b
    if n_valid is None:
        return y, u[:, s:]
    at = n_valid[:, None] + jnp.arange(taps - 1)[None, :]  # (batch, K - 1)
    return y, jnp.take_along_axis(u, at[:, :, None], axis=1)


def ssd_step(x, dt, A, B, C, state):
    """One token: ``x`` (b, h, p), ``dt`` (b, h) >= 0, ``A`` (h,) < 0,
    ``B``, ``C`` (b, g, n) (head ``i`` reads group ``i // (h / g)``),
    ``state`` (b, h, p, n) float32.  Returns ``(y (b, h, p) float32,
    state')``; the ``D`` skip is the caller's."""
    f32 = jnp.float32
    b, h, p = x.shape
    g, n = B.shape[1:]
    dt = dt.astype(f32)
    decay = jnp.exp(dt * A.astype(f32))
    dx = (x.astype(f32) * dt[..., None]).reshape(b, g, h // g, p)
    S = state.reshape(b, g, h // g, p, n)  # a group's heads share B and C
    S = S * decay.reshape(b, g, h // g, 1, 1) + dx[..., None] * B.astype(f32)[:, :, None, None, :]
    # multiply and reduce, in float32: a (p, n) state a head against one
    # vector is memory traffic, not a matmul worth rounding operands for
    y = jnp.sum(S * C.astype(f32)[:, :, None, None, :], axis=-1)
    return y.reshape(b, h, p), S.reshape(state.shape)


def ssd_recurrent(x, dt, A, B, C, state=None):
    """:func:`ssd_step` over ``x`` (b, s, h, p), ``dt`` (b, s, h), ``B``,
    ``C`` (b, s, g, n), token by token.  Float32 throughout."""
    b, _, h, p = x.shape
    if state is None:
        state = jnp.zeros((b, h, p, B.shape[-1]), jnp.float32)

    def step(S, t):
        y, S = ssd_step(t[0], t[1], A, t[2], t[3], S)
        return S, y

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, B, C))
    state, y = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(y, 0, 1), state


def ssd_chunked(x, dt, A, B, C, chunk: int = CHUNK, state=None):
    """The same function of the same arguments in chunks of ``chunk``
    tokens.  Matmul operands keep the dtype ``x`` arrives in (bfloat16
    under mixed precision) and accumulate in float32; decays and the
    state are float32.  Returns ``(y (b, s, h, p) float32, state')``."""
    f32 = jnp.float32
    mm = x.dtype
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    pad = -s % chunk
    if pad:  # dt = 0: a padded token leaves the state alone
        x, dt, B, C = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, B, C)
        )
    nc = (s + pad) // chunk
    rep = h // g

    def blocks(t):  # (b, s, ...) -> (nc, b, chunk, ...)
        return jnp.moveaxis(t.reshape((b, nc, chunk) + t.shape[2:]), 1, 0)

    def dot(spec, a, c):
        return jnp.einsum(spec, a.astype(mm), c.astype(mm), preferred_element_type=f32)

    dt = dt.astype(f32)
    a = dt * A.astype(f32)  # log-decay a token, <= 0
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    if state is None:
        state = jnp.zeros((b, h, p, n), f32)

    def one(S, t):
        x_c, dt_c, a_c, B_c, C_c = t  # (b, c, g, r, p), (b, c, g, r), (b, c, g, n)
        cum = jnp.cumsum(a_c, axis=1)  # log-decay from the chunk's start
        cum_h = jnp.moveaxis(cum, 1, -1)  # (b, g, r, c)
        decay = jnp.exp(jnp.where(
            lower, cum_h[..., :, None] - cum_h[..., None, :], -jnp.inf
        ))  # (b, g, r, i, j): from token j to token i >= j
        cb = dot("bign,bjgn->bgij", C_c, B_c)
        w = cb[:, :, None] * decay * jnp.moveaxis(dt_c, 1, -1)[..., None, :]
        y = dot("bgrij,bjgrp->bigrp", w, x_c)
        # what the state the chunk started from adds, decayed to token i
        y = y + dot("bign,bgrpn->bigrp", C_c, S) * jnp.exp(cum)[..., None]
        # the state at the chunk's end: each token's outer product decayed
        # from its position to the end
        to_end = jnp.exp(cum[:, -1:] - cum) * dt_c
        S = S * jnp.exp(cum[:, -1])[..., None, None] + dot(
            "bjgrp,bjgn->bgrpn", x_c.astype(f32) * to_end[..., None], B_c
        )
        return S, y

    # a group's heads share B and C: heads as (group, head of the group)
    x = x.reshape(x.shape[:2] + (g, rep, p))
    dt, a = (t.reshape(t.shape[:2] + (g, rep)) for t in (dt, a))
    state = state.reshape(b, g, rep, p, n)
    state, y = jax.lax.scan(one, state, tuple(blocks(t) for t in (x, dt, a, B, C)))
    y = jnp.moveaxis(y, 0, 1).reshape(b, nc * chunk, h, p)
    return y[:, :s], state.reshape(b, h, p, n)


def gated_group_norm(y, z, w, groups: int, eps: float):
    """``N_w(y * silu(z))`` with the mean square taken over each of
    ``groups`` equal runs of the last dim (gate first, then the norm).
    Float32."""
    f32 = jnp.float32
    y = y.astype(f32) * jax.nn.silu(z.astype(f32))
    yg = y.reshape(y.shape[:-1] + (groups, -1))
    yg = yg * jax.lax.rsqrt(jnp.mean(jnp.square(yg), axis=-1, keepdims=True) + eps)
    return yg.reshape(y.shape) * w.astype(f32)


def mamba2_dims(a: dict):
    """``(heads, head_dim, groups, state, inner width, conv channels)``
    of a ``Mamba2Mixer`` layer's attrs."""
    h, p, g, n = a["num_heads"], a["head_dim"], a["n_groups"], a["state_size"]
    return h, p, g, n, h * p, h * p + 2 * g * n


def mamba2_mixer(params, u, a: dict, conv_state=None, ssm_state=None, n_valid=None):
    """The layer over ``u`` (batch, seq, hidden) from weights ``params``
    and attrs ``a``; matmul operands in ``u``'s dtype.  With no state
    handed in a lane starts from nothing.  ``n_valid`` (batch,): rows at
    or past it change neither state (``dt`` 0, the conv's tail taken at
    ``n_valid``).  One row a lane takes the single-step update.  Returns
    ``(out (batch, seq, hidden), conv state', ssm state')``."""
    f32 = jnp.float32
    h, p, g, n, d, cw = mamba2_dims(a)
    b, s, _ = u.shape
    with jax.named_scope("ff.ssm"):
        zxd = u @ params["in_proj"]
        z, xbc = zxd[..., :d], zxd[..., d:d + cw]
        dt = jax.nn.softplus(zxd[..., d + cw:].astype(f32) + params["dt_bias"].astype(f32))
        lo, hi = a.get("time_step_limit") or (0.0, math.inf)
        if (lo, hi) != (0.0, math.inf):
            dt = jnp.clip(dt, lo, hi)
        if n_valid is not None:
            dt = jnp.where(jnp.arange(s)[None, :, None] < n_valid[:, None, None], dt, 0.0)
        with jax.named_scope("ff.ssm.conv"):
            xbc, conv_state = conv_with_state(
                xbc, params["conv"], params["conv_bias"], conv_state, n_valid
            )
            xbc = jax.nn.silu(xbc)
        x = xbc[..., :d].reshape(b, s, h, p)
        B = xbc[..., d:d + g * n].reshape(b, s, g, n)
        C = xbc[..., d + g * n:].reshape(b, s, g, n)
        A = -jnp.exp(params["A_log"].astype(f32))
        if s == 1 and ssm_state is not None:
            with jax.named_scope("ff.ssm.step"):
                y, ssm_state = ssd_step(x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], ssm_state)
                y = y[:, None]
        else:
            with jax.named_scope("ff.ssm.scan"):
                y, ssm_state = ssd_chunked(x, dt, A, B, C, a["chunk"], ssm_state)
        y = y + x.astype(f32) * params["D"].astype(f32)[:, None]
        y = gated_group_norm(y.reshape(b, s, d), z, params["scale"], g, a["eps"])
        return y.astype(u.dtype) @ params["out_proj"], conv_state, ssm_state


class Mamba2Mixer(OpDef):
    """Input (B, S, E) -> output (B, S, E).  Attrs: ``num_heads``,
    ``head_dim``, ``n_groups``, ``state_size``, ``conv_kernel``,
    ``chunk``, ``eps``, ``time_step_limit`` (None: no clamp).  Weights
    (column order of the fused projection is ``[z | x | B | C | dt]``,
    heads and groups contiguous)::

        in_proj (E, 2*H*P + 2*G*N + H)     conv (H*P + 2*G*N, K), conv_bias
        A_log, dt_bias, D (H,)  float32    scale (H*P,)  -- the gated RMSNorm
        out_proj (H*P, E)
    """

    op_type = OperatorType.MAMBA2_MIXER
    fp32_weights = frozenset({"A_log", "dt_bias", "D"})

    def infer(self, layer: Layer) -> List[ShapeDtype]:
        t = layer.inputs[0]
        return [(t.shape, t.dtype)]

    def weights(self, layer: Layer) -> List[WeightSpec]:
        t = layer.inputs[0]
        e, dt = t.shape[-1], t.dtype
        h, _, _, _, d, cw = mamba2_dims(layer.attrs)
        init = layer.attrs.get("kernel_initializer") or default_kernel_initializer()
        return [
            WeightSpec("in_proj", (e, d + cw + h), dt, init),
            WeightSpec("conv", (cw, layer.attrs["conv_kernel"]), dt, init),
            WeightSpec("conv_bias", (cw,), dt, ZeroInitializer()),
            WeightSpec("A_log", (h,), dt, ZeroInitializer()),
            WeightSpec("dt_bias", (h,), dt, ZeroInitializer()),
            WeightSpec("D", (h,), dt, OnesInitializer()),
            WeightSpec("scale", (d,), dt, OnesInitializer()),
            WeightSpec("out_proj", (d, e), dt, init),
        ]

    def forward(self, layer, params, inputs, ctx: OpContext):
        return [mamba2_mixer(params, inputs[0], layer.attrs)[0]]

    def flops(self, layer: Layer) -> float:
        b, s, e = layer.inputs[0].shape
        h, p, g, n, d, cw = mamba2_dims(layer.attrs)
        proj = 2.0 * b * s * e * (2 * d + cw + h)
        conv = 2.0 * b * s * cw * layer.attrs["conv_kernel"]
        # a token and head: the chunk's C B^T and its product with the
        # values (chunk / 2 visible on average), the state's read-out and
        # its update (2 p n each)
        scan = 2.0 * b * s * h * (layer.attrs["chunk"] / 2 * (n / (h // g) + p) + 2 * p * n)
        return proj + conv + scan

    def partitionable_dims(self, layer):
        return {0: "sample"}  # the recurrence runs along dim 1


register_op(Mamba2Mixer())


# ----------------------------------------------------------------- Mamba-1
def selective_step(x, dt, A, B, C, D, state):
    """One position: ``x``, ``dt`` (b, d), ``A`` (d, n) < 0, ``B``, ``C``
    (b, n) (shared by the channels), ``D`` (d,), ``state`` (b, d, n)
    float32.  Returns ``(y (b, d) float32, state')``."""
    f32 = jnp.float32
    x, dt = x.astype(f32), dt.astype(f32)
    S = (jnp.exp(dt[..., None] * A.astype(f32)) * state
         + (dt * x)[..., None] * B.astype(f32)[:, None, :])
    y = jnp.sum(S * C.astype(f32)[:, None, :], axis=-1) + D.astype(f32) * x
    return y, S


def selective_scan(x, dt, A, B, C, D, state=None, n_valid=None, unroll: int = 1):
    """:func:`selective_step` over ``x``, ``dt`` (b, s, d) and ``B``,
    ``C`` (b, s, n), a ``lax.scan`` over positions that carries the
    float32 (b, d, n) state (zeros when None).  ``n_valid`` (b,): rows at
    or past it leave the state alone (``dt`` 0: decay 1, nothing added).
    Returns ``(y (b, s, d) float32, state')``."""
    b, s, d = x.shape
    if state is None:
        state = jnp.zeros((b, d, A.shape[-1]), jnp.float32)
    if n_valid is not None:
        dt = jnp.where(jnp.arange(s)[None, :, None] < n_valid[:, None, None], dt, 0)

    def step(S, t):
        y, S = selective_step(t[0], t[1], A, t[2], t[3], D, S)
        return S, y

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, B, C))
    state, y = jax.lax.scan(step, state, xs, unroll=max(1, min(unroll, s)))
    return jnp.moveaxis(y, 0, 1), state


# positions a prefill's scan takes a loop iteration: the loop's overhead a
# position is then an eighth of one step's
SCAN_UNROLL = 8


def mamba1_mixer(params, u, a: dict, conv_state=None, ssm_state=None, n_valid=None):
    """The Mamba-1 layer over ``u`` (batch, seq, hidden) from weights
    ``params`` and attrs ``a`` (``d_inner``, ``state_size``,
    ``conv_kernel``, ``dt_rank``); matmul operands in ``u``'s dtype.  As
    :func:`mamba2_mixer`: no state handed in is a lane that starts from
    nothing, ``n_valid`` (batch,) keeps rows at or past it out of both
    states, one row a lane with a state takes the single step.  Returns
    ``(out, conv state', ssm state', m)``: ``m = y * silu(z)`` (batch,
    seq, d_inner) in ``u``'s dtype, the tensor ``out_proj`` reads."""
    f32 = jnp.float32
    d, n, r = a["d_inner"], a["state_size"], a["dt_rank"]
    s = u.shape[1]
    with jax.named_scope("ff.ssm"):
        xz = u @ params["in_proj"]
        x, z = xz[..., :d], xz[..., d:]
        with jax.named_scope("ff.ssm.conv"):
            x, conv_state = conv_with_state(
                x, params["conv"], params["conv_bias"], conv_state, n_valid
            )
            x = jax.nn.silu(x)
        dbc = x @ params["x_proj"]
        dt = jax.nn.softplus(
            (dbc[..., :r] @ params["dt_proj"]).astype(f32) + params["dt_bias"].astype(f32)
        )
        B, C = dbc[..., r:r + n], dbc[..., r + n:r + 2 * n]
        A = -jnp.exp(params["A_log"].astype(f32))
        if s == 1 and ssm_state is not None:
            with jax.named_scope("ff.ssm.step"):
                if n_valid is not None:
                    dt = jnp.where(n_valid[:, None, None] > 0, dt, 0.0)
                y, ssm_state = selective_step(
                    x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], params["D"], ssm_state
                )
                y = y[:, None]
        else:
            with jax.named_scope("ff.ssm.scan"):
                y, ssm_state = selective_scan(
                    x, dt, A, B, C, params["D"], ssm_state, n_valid, SCAN_UNROLL
                )
        m = (y * jax.nn.silu(z.astype(f32))).astype(u.dtype)
        return m @ params["out_proj"], conv_state, ssm_state, m


def state_shapes(kind: str, a: dict):
    """``(conv state, ssm state)`` shapes a slot of a state layer of
    ``kind`` (``mamba2`` | ``mamba1`` | ``lightning``, which has no conv:
    None) with attrs ``a`` holds."""
    if kind == "lightning":
        d = a["head_dim"]
        return None, (a["num_heads"], d, d)
    if kind == "mamba2":
        h, p, _, n, _, cw = mamba2_dims(a)
        return (a["conv_kernel"] - 1, cw), (h, p, n)
    return (a["conv_kernel"] - 1, a["d_inner"]), (a["d_inner"], a["state_size"])


class Mamba1Mixer(OpDef):
    """Input (B, S, E) -> output (B, S, E), and with ``exports_memory``
    also ``m`` (B, S, d_inner).  Attrs: ``d_inner``, ``state_size``,
    ``conv_kernel``, ``dt_rank``, ``exports_memory``.  Weights (``in_proj``'s
    columns ``[x | z]``, ``x_proj``'s ``[delta | B | C]``)::

        in_proj (E, 2 d)    conv (d, K), conv_bias (d,)    x_proj (d, R + 2 N)
        dt_proj (R, d)      dt_bias, D (d,), A_log (d, N)  float32
        out_proj (d, E)
    """

    op_type = OperatorType.MAMBA1_MIXER
    fp32_weights = frozenset({"A_log", "dt_bias", "D"})

    def infer(self, layer: Layer) -> List[ShapeDtype]:
        t = layer.inputs[0]
        outs = [(t.shape, t.dtype)]
        if layer.attrs.get("exports_memory"):
            outs.append((t.shape[:-1] + (layer.attrs["d_inner"],), t.dtype))
        return outs

    def weights(self, layer: Layer) -> List[WeightSpec]:
        t = layer.inputs[0]
        e, dt = t.shape[-1], t.dtype
        a = layer.attrs
        d, n, r = a["d_inner"], a["state_size"], a["dt_rank"]
        init = a.get("kernel_initializer") or default_kernel_initializer()
        return [
            WeightSpec("in_proj", (e, 2 * d), dt, init),
            WeightSpec("conv", (d, a["conv_kernel"]), dt, init),
            WeightSpec("conv_bias", (d,), dt, ZeroInitializer()),
            WeightSpec("x_proj", (d, r + 2 * n), dt, init),
            WeightSpec("dt_proj", (r, d), dt, init),
            WeightSpec("dt_bias", (d,), dt, ZeroInitializer()),
            WeightSpec("A_log", (d, n), dt, ZeroInitializer()),
            WeightSpec("D", (d,), dt, OnesInitializer()),
            WeightSpec("out_proj", (d, e), dt, init),
        ]

    def forward(self, layer, params, inputs, ctx: OpContext):
        out, _, _, m = mamba1_mixer(params, inputs[0], layer.attrs)
        return [out, m] if layer.attrs.get("exports_memory") else [out]

    def flops(self, layer: Layer) -> float:
        b, s, e = layer.inputs[0].shape
        a = layer.attrs
        d, n, r = a["d_inner"], a["state_size"], a["dt_rank"]
        proj = 2.0 * b * s * (e * 2 * d + d * (r + 2 * n) + r * d + d * e)
        # a position: the conv's taps, the state's decay, update and read-out
        return proj + 2.0 * b * s * d * (a["conv_kernel"] + 3 * n)

    def partitionable_dims(self, layer):
        return {0: "sample"}  # the recurrence runs along dim 1


register_op(Mamba1Mixer())


def gated_memory(h, m, w_in, w_out):
    """A gated memory unit: ``(silu(h W_in) * m) W_out``, the gate and
    the product in float32, matmul operands in ``h``'s dtype."""
    g = jax.nn.silu((h @ w_in).astype(jnp.float32)) * m.astype(jnp.float32)
    return g.astype(h.dtype) @ w_out


class GatedMemoryUnit(OpDef):
    """Inputs ``h`` (B, S, E) and a memory ``m`` (B, S, d) another layer
    exported (a :class:`Mamba1Mixer`'s) -> (B, S, E).  Attrs: ``d_mem``,
    ``memory_ones`` (ones read in the memory's place).  Weights ``w_in``
    (E, d), ``w_out`` (d, E)."""

    op_type = OperatorType.GATED_MEMORY

    def infer(self, layer: Layer) -> List[ShapeDtype]:
        t = layer.inputs[0]
        return [(t.shape, t.dtype)]

    def weights(self, layer: Layer) -> List[WeightSpec]:
        t = layer.inputs[0]
        e, d = t.shape[-1], layer.attrs["d_mem"]
        init = layer.attrs.get("kernel_initializer") or default_kernel_initializer()
        return [WeightSpec("w_in", (e, d), t.dtype, init),
                WeightSpec("w_out", (d, e), t.dtype, init)]

    def forward(self, layer, params, inputs, ctx: OpContext):
        m = inputs[1]
        if layer.attrs.get("memory_ones"):
            m = jnp.ones_like(m)
        with jax.named_scope("ff.gmu"):
            return [gated_memory(inputs[0], m, params["w_in"], params["w_out"])]

    def flops(self, layer: Layer) -> float:
        b, s, e = layer.inputs[0].shape
        return 4.0 * b * s * e * layer.attrs["d_mem"]

    def partitionable_dims(self, layer):
        return {0: "sample", 1: "seq"}


register_op(GatedMemoryUnit())
