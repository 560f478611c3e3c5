"""Plain reference: the ``phi4flash`` decoder (Microsoft
Phi-4-mini-flash-reasoning, SambaY), float32.

Every layer is pre-norm with two branches, LayerNorm with weight and
bias (``LN(x) = (x - mean) / sqrt(var + eps) * w + b``)::

    x0 = E[id]                                   (no positions anywhere)
    x  = x + Mixer_l(LN1_l(x));   x = x + MLP_l(LN2_l(x))
    logits = LN_f(x_L) E^T                       (tied head, no bias)

``MLP(x) = W_2 (silu(x W_g) * x W_u)`` (``fc1``'s two halves, ``[g |
u]``).  With ``half = num_hidden_layers / 2`` the mixer of layer ``l``:

* ``l <= half`` and ``l % mb_per_layer == 0``: Mamba-1 (``d =
  mamba_expand * hidden``, ``N = mamba_d_state``, ``R =
  mamba_dt_rank``): ``[x | z] = u W_in``; ``x = silu(conv(x) + b)``,
  causal depthwise, ``mamba_d_conv`` taps; ``[delta | B | C] = x W_x``;
  ``dt = softplus(delta W_dt + b_dt)``; ``A = -exp(A_log)`` (d, N); THE
  RECURRENCE ITSELF, position by position (``lax.scan``)::

      S_t = exp(dt_t (.) A) (.) S_{t-1} + (dt_t x_t) (x) B_t,   y_t = S_t C_t + D x_t

  ``m = y * silu(z)``, ``out = m W_out``; layer ``half``'s ``m`` is the
  memory;
* other ``l < half``: differential attention over the last
  ``sliding_window`` keys (a row's own among them);
* ``l = half + 1``: differential attention over every earlier key;
  its K/V are what the cross layers read;
* ``l > half + 1``, even: ``out = (silu(x W_in) * m) W_out`` (the gated
  memory unit over layer ``half``'s ``m`` at the same position);
* ``l > half + 1``, odd: differential cross-attention: ``q = x W_q``,
  keys and values layer ``half + 1``'s.

Differential attention: heads of ``hd = hidden / num_attention_heads``;
query heads ``2i, 2i + 1`` are pair ``i`` (``q1``, ``q2``), K/V heads
likewise (``k1``, ``k2``; values ``[v1 | v2]``, 2 hd wide); query pair
``i`` reads K/V pair ``i // (H / KV)``; causal::

    A_j = softmax(q_j k_j^T / sqrt(hd) + mask) [v1 | v2]
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,
    lambda_init = 0.8 - 0.6 exp(-0.3 l)
    out = (RMSNorm_{2 hd}(A_1 - lambda A_2) * w_sub (1 - lambda_init)) W_o

(``RMSNorm`` with ``layer_norm_eps``).  Self layers project ``[q | k |
v]`` with one matrix.  No biases but the LayerNorms' and the conv's
and ``dt_proj``'s.

One full forward over whole sequences, no cache, no batching of the
scan, no kernel.  At the published widths the float32 weights (15.4 GB)
do not fit the chip with the activations, so the forward goes LAYER BY
LAYER: ``params`` is any mapping ``layer name -> {weight: array}`` and
is asked for a layer's weights when the layer runs
(``benchmarks/weights_phi4flash.py::ByLayer``); attention runs a
request and a block of query rows at a time; the head only at the rows
asked for.  The blocking changes where the arithmetic is done, not what
it is.  Imports nothing of the program.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from benchmarks.reference.parts import layer_norm
from benchmarks.reference.precision import matmul

Q_BLOCK = 512  # query rows of a request whose float32 scores are held at once


def layer_kinds(cfg: dict) -> str:
    """One letter a layer: ``M`` Mamba-1 (``m``: the one whose output is
    the memory), ``S`` window, ``F`` full, ``G`` memory unit, ``C``
    cross-attention."""
    L, mb = cfg["num_hidden_layers"], cfg["mb_per_layer"]
    half = L // 2
    out = []
    for l in range(L):
        if l <= half:
            out.append(("m" if l == half else "M") if l % mb == 0 else "S")
        elif l == half + 1:
            out.append("F")
        else:
            out.append("G" if l % 2 == 0 else "C")
    return "".join(out)


def mixer_name(l: int, kind: str) -> str:
    return f"l{l}_" + {"M": "mamba", "m": "mamba", "S": "attn", "F": "attn",
                       "G": "gmu", "C": "xattn"}[kind]


def dims(cfg: dict) -> dict:
    h, H, KV = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return dict(h=h, H=H, KV=KV, hd=h // H, d=cfg["mamba_expand"] * h,
                N=cfg["mamba_d_state"], R=cfg["mamba_dt_rank"], K=cfg["mamba_d_conv"],
                f=cfg["intermediate_size"], v=cfg["vocab_size"])


def param_shapes(cfg: dict) -> dict:
    D = dims(cfg)
    h, hd, d, N, R = D["h"], D["hd"], D["d"], D["N"], D["R"]
    ln = {"scale": (h,), "bias": (h,)}
    lam = {n: (hd,) for n in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")}
    out = {"tok_embed": {"kernel": (D["v"], h)}, "final_ln": dict(ln)}
    for l, kind in enumerate(layer_kinds(cfg)):
        out[f"l{l}_ln1"] = dict(ln)
        out[f"l{l}_ln2"] = dict(ln)
        out[f"l{l}_mlp"] = {"w_gate": (h, D["f"]), "w_up": (h, D["f"]), "w_down": (D["f"], h)}
        if kind in "Mm":
            shapes = {
                "in_proj": (h, 2 * d), "conv": (d, D["K"]), "conv_bias": (d,),
                "x_proj": (d, R + 2 * N), "dt_proj": (R, d), "dt_bias": (d,),
                "A_log": (d, N), "D": (d,), "out_proj": (d, h),
            }
        elif kind == "G":
            shapes = {"w_in": (h, d), "w_out": (d, h)}
        elif kind == "C":
            shapes = {"wq": (h, D["H"] * hd), "wo": (D["H"] * hd, h), **lam,
                      "subln": (2 * hd,)}
        else:
            shapes = {"wqkv": (h, (D["H"] + 2 * D["KV"]) * hd), "wo": (D["H"] * hd, h),
                      **lam, "subln": (2 * hd,)}
        out[mixer_name(l, kind)] = shapes
    return out


# ------------------------------------------------------------------ M
def causal_conv(x, w, b):
    """``y[t, c] = b[c] + sum_j w[c, j] x[t - (K - 1) + j, c]`` over
    ``x`` (b, s, c); positions before the sequence read zero."""
    taps, s = w.shape[-1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(xp[:, j:j + s, :] * w[:, j] for j in range(taps)) + b


def selective_scan(x, dt, A, B, C, D):
    """The recurrence, position by position: ``x``, ``dt`` (b, s, d),
    ``A`` (d, N), ``B``, ``C`` (b, s, N), ``D`` (d,)."""
    def step(S, t):
        x_t, dt_t, B_t, C_t = t
        S = jnp.exp(dt_t[..., None] * A) * S + (dt_t * x_t)[..., None] * B_t[:, None, :]
        return S, jnp.sum(S * C_t[:, None, :], axis=-1) + D * x_t

    S0 = jnp.zeros((x.shape[0], x.shape[2], A.shape[1]), jnp.float32)
    _, y = jax.lax.scan(step, S0, tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1)


def mamba1(p, u, cfg, mm):
    """``u`` (b, s, hidden) -> (out, m)."""
    D = dims(cfg)
    d, N, R = D["d"], D["N"], D["R"]
    xz = mm(u, p["in_proj"])
    x, z = xz[..., :d], xz[..., d:]
    x = jax.nn.silu(causal_conv(x, p["conv"], p["conv_bias"]))
    dbc = mm(x, p["x_proj"])
    dt = jax.nn.softplus(mm(dbc[..., :R], p["dt_proj"]) + p["dt_bias"])
    B, C = dbc[..., R:R + N], dbc[..., R + N:]
    y = selective_scan(x, dt, -jnp.exp(p["A_log"]), B, C, p["D"])
    m = y * jax.nn.silu(z)
    return mm(m, p["out_proj"]), m


# ------------------------------------------------------------ attention
def _q_block(s: int) -> int:
    return max(b for b in range(1, min(s, Q_BLOCK) + 1) if s % b == 0)


def diff_attention(p, q, k, v, l, cfg, mm, window):
    """``q`` (b, s, H hd), ``k``, ``v`` (b, s, KV hd) -> (b, s, hidden),
    a request and ``_q_block(s)`` query rows at a time."""
    D = dims(cfg)
    H, KV, hd = D["H"], D["KV"], D["hd"]
    b, s, _ = q.shape
    rep = H // KV
    q = q.reshape(b, s, H // 2, 2, hd).transpose(0, 2, 3, 1, 4)  # (b, pairs, 2, s, hd)
    k = jnp.repeat(k.reshape(b, s, KV // 2, 2, hd).transpose(0, 2, 3, 1, 4), rep, axis=1)
    v = jnp.repeat(v.reshape(b, s, KV // 2, 2 * hd).transpose(0, 2, 1, 3), rep, axis=1)
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * l)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam_init)
    pos = jnp.arange(s)
    qb = _q_block(s)

    def one_request(args):
        q1, k1, v1 = args  # (pairs, 2, s, hd), (pairs, 2, s, hd), (pairs, s, 2 hd)

        def rows(lo):
            qs = jax.lax.dynamic_slice_in_dim(q1, lo, qb, axis=2)
            scores = mm(qs, jnp.swapaxes(k1, -1, -2)) / math.sqrt(hd)  # (pairs, 2, qb, s)
            row = (lo + jnp.arange(qb))[:, None]
            seen = pos[None, :] <= row
            if window:
                seen &= pos[None, :] > row - window
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            a = mm(probs, v1[:, None])  # (pairs, 2, qb, 2 hd)
            o = a[:, 0] - lam * a[:, 1]
            o = o / jnp.sqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                             + cfg["layer_norm_eps"])
            return o * p["subln"] * (1.0 - lam_init)  # (pairs, qb, 2 hd)

        o = jax.lax.map(rows, jnp.arange(0, s, qb))  # (s / qb, pairs, qb, 2 hd)
        return o.transpose(0, 2, 1, 3).reshape(s, H * hd)

    return mm(jax.lax.map(one_request, (q, k, v)), p["wo"])


# ------------------------------------------------------------ forward
@functools.lru_cache(maxsize=None)
def _steps(cfg_key: str, precision: str):
    """The jitted pieces a forward is made of, one set a configuration
    and precision: each takes only the weights it needs."""
    cfg = json.loads(cfg_key)
    mm = matmul(precision)
    eps, W = cfg["layer_norm_eps"], int(cfg["sliding_window"] or 0)
    D = dims(cfg)
    qw, kw = D["H"] * D["hd"], D["KV"] * D["hd"]

    @jax.jit
    def embed(p, tokens):
        return p["kernel"][tokens]

    def mlp(p, x):
        return mm(jax.nn.silu(mm(x, p["w_gate"])) * mm(x, p["w_up"]), p["w_down"])

    @functools.partial(jax.jit, static_argnames=("l", "kind"))
    def layer(p_ln1, p, p_ln2, p_mlp, x, mem, kv, *, l, kind):
        """One layer: ``(x, memory, shared K/V)`` -> the same three."""
        h = layer_norm(p_ln1, x, eps)
        if kind in "Mm":
            out, m = mamba1(p, h, cfg, mm)
            mem = m if kind == "m" else mem
        elif kind == "G":
            out = mm(jax.nn.silu(mm(h, p["w_in"])) * mem, p["w_out"])
        elif kind == "C":
            out = diff_attention(p, mm(h, p["wq"]), kv[..., :kw], kv[..., kw:], l, cfg, mm, 0)
        else:
            qkv = mm(h, p["wqkv"])
            q, k, v = qkv[..., :qw], qkv[..., qw:qw + kw], qkv[..., qw + kw:]
            out = diff_attention(p, q, k, v, l, cfg, mm, W if kind == "S" else 0)
            if kind == "F":
                kv = jnp.concatenate([k, v], axis=-1)
        x = x + out
        return x + mlp(p_mlp, layer_norm(p_ln2, x, eps)), mem, kv

    @jax.jit
    def head(p_ln, p_embed, x, rows):
        sel = jnp.take_along_axis(x, rows[:, :, None], axis=1)
        return mm(layer_norm(p_ln, sel, eps), p_embed["kernel"].T)

    return embed, layer, head


def _key(cfg: dict) -> str:
    return json.dumps({k: v for k, v in cfg.items() if not isinstance(v, dict)},
                      sort_keys=True)


def hidden_states(params, tokens, cfg, precision="highest"):
    """``tokens`` (b, s) int -> the last layer's output (b, s, hidden),
    before the final norm.  Asks ``params`` for a layer's weights when
    the layer runs."""
    embed, layer, _ = _steps(_key(cfg), precision)
    x = embed(params["tok_embed"], tokens)
    mem = kv = jnp.zeros((), jnp.float32)
    for l, kind in enumerate(layer_kinds(cfg)):
        x, mem, kv = layer(params[f"l{l}_ln1"], params[mixer_name(l, kind)],
                           params[f"l{l}_ln2"], params[f"l{l}_mlp"], x, mem, kv,
                           l=l, kind=kind)
    return x


def logits_at(params, tokens, rows, cfg, precision="highest"):
    """Next-token logits (b, r, vocab) at positions ``rows`` (b, r)."""
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, tokens, cfg, precision)
        head = _steps(_key(cfg), precision)[2]
        return head(params["final_ln"], params["tok_embed"], x, jnp.asarray(rows))


def served_gaps(params, tokens, rows, served, valid, cfg, precision="highest"):
    """For each served token, how far its reference logit lies below the
    reference's best at that position (0 where the reference would have
    picked it too).  ``precision`` other than ``highest`` is the
    control: the token judged is then the one that precision puts
    first, not the served one.  Padded entries (``valid`` false) read 0.
    Not to be wrapped in one ``jax.jit``: the forward frees a layer's
    weights before it makes the next."""
    tokens, rows = jnp.asarray(tokens), jnp.asarray(rows)
    served = jnp.asarray(served).astype(jnp.int32)
    if precision != "highest":
        served = jnp.argmax(logits_at(params, tokens, rows, cfg, precision), axis=-1)
    ref = logits_at(params, tokens, rows, cfg, "highest")
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, served[:, :, None], axis=-1)[..., 0]
    return jnp.where(jnp.asarray(valid), best - got, 0.0)
