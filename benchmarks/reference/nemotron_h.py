"""Plain reference: the ``nemotron_h`` decoder (NVIDIA Nemotron-H /
Nemotron 3 Nano), float32.

Each layer is ONE mixer behind one norm, by the letter of
``hybrid_override_pattern`` (``N_w(x) = x / sqrt(mean x^2 + eps) * w``,
plain)::

    x0 = E[id]                                          (no scale)
    x  = x + Mixer_i(N_i(x))          M | * | E
    logits = N_f(x_L) W_head                            (untied)

``M`` (Mamba-2, ``d = mamba_num_heads * mamba_head_dim``, ``G =
n_groups``, ``N = ssm_state_size``): ``[z | xBC | dt] = u W_in``
(d | d + 2 G N | heads); ``xBC = silu(conv(xBC) + b)``, causal
depthwise, ``conv_kernel`` taps; ``[x | B | C] = xBC`` (heads of
``mamba_head_dim`` | G x N | G x N; head h reads group ``h // (heads /
G)``); ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head;
THE RECURRENCE ITSELF, position by position (``lax.scan``; no chunks,
no state handed over)::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,    y_t = S_t C_t + D x_t

then ``y = N^grouped_w(y * silu(z))`` (the mean square over each of the
G groups of d / G channels, gate first) and ``out = y W_out``.  No
biases but the conv's.  ``*``: q (H heads of ``head_dim``), k, v (KV
heads), no biases, no gate, no q/k norm, NO positional encoding; causal
softmax(q k^T / sqrt(head_dim)) v, each KV head serving H / KV query
heads; ``out = attn W_o``.  ``E``: ``s = sigmoid(x W_r)`` over
``router_num_experts`` in float32, chosen = top-k of ``s + b`` (the
selection bias, for choosing only), ``w = s[chosen] / (sum + 1e-20)``
(``norm_topk_prob``) ``* routed_scaling_factor``, ``out = E_shared(x)
+ sum_k w_k E_chosen_k(x)``, ``E(x) = W_down act(W_up x)`` with ``act``
= ``relu(.)^2`` (``mlp_hidden_act`` ``relu2``); only the experts
``first_expert .. first_expert + n_routed_experts`` are HELD here, and
what the others would add is left out (the chip's share of the
deployment, as the program's); no token is dropped.

One full forward over whole sequences: dense masks, no cache, no
kernel, experts by a loop over the held ones with a 0/1-weighted sum.
At the published widths the forward goes LAYER BY LAYER: ``params`` is
any mapping ``layer name -> {weight: array}`` and is asked for a
layer's weights when that layer runs (``benchmarks/weights_nemotron_h.py
::ByLayer`` makes them from the seed then); attention runs a request
and a block of query rows at a time; the head only at the rows asked
for.  Imports nothing of the program.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from benchmarks.reference.precision import matmul

Q_BLOCK = 512  # query rows of a request whose float32 scores are held at once


def layer_kinds(cfg: dict) -> str:
    pattern = cfg["hybrid_override_pattern"]
    assert len(pattern) == cfg["num_hidden_layers"] and set(pattern) <= set("M*E"), pattern
    return pattern


def mixer_name(i: int, kind: str) -> str:
    return f"l{i}_" + {"M": "mamba", "*": "attn", "E": "moe"}[kind]


def mamba_dims(cfg: dict):
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    return H, P, G, N, H * P, H * P + 2 * G * N


def param_shapes(cfg: dict) -> dict:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    Hq, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    H, _, _, _, d, cw = mamba_dims(cfg)
    f, fs = cfg["moe_intermediate_size"], cfg["moe_shared_expert_intermediate_size"]
    held, E = cfg["n_routed_experts"], cfg.get("router_num_experts", cfg["n_routed_experts"])
    out = {
        "tok_embed": {"kernel": (v, h)},
        "norm_f": {"scale": (h,)},
        "lm_head": {"kernel": (h, v)},
    }
    for i, kind in enumerate(layer_kinds(cfg)):
        out[f"l{i}_norm"] = {"scale": (h,)}
        if kind == "M":
            shapes = {
                "in_proj": (h, d + cw + H), "conv": (cw, cfg["conv_kernel"]),
                "conv_bias": (cw,), "A_log": (H,), "dt_bias": (H,), "D": (H,),
                "scale": (d,), "out_proj": (d, h),
            }
        elif kind == "*":
            shapes = {"wq": (h, Hq * hd), "wk": (h, KV * hd), "wv": (h, KV * hd),
                      "wo": (Hq * hd, h)}
        else:
            shapes = {
                "router": (h, E), "router_bias": (E,),
                "w_up": (held, h, f), "w_down": (held, f, h),
                "shared_up_proj": (h, fs), "shared_down_proj": (fs, h),
            }
        out[mixer_name(i, kind)] = shapes
    return out


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def act(cfg: dict):
    name = cfg.get("mlp_hidden_act", "relu2")
    if name == "relu2":
        return lambda x: jnp.square(jax.nn.relu(x))
    if name == "relu":
        return jax.nn.relu
    raise ValueError(f"mlp_hidden_act {name!r}: relu2 | relu")


# ------------------------------------------------------------------ M
def causal_conv(x, w, b):
    """``y[t, c] = b[c] + sum_j w[c, j] x[t - (K - 1) + j, c]`` over
    ``x`` (b, s, c); positions before the sequence read zero."""
    taps, s = w.shape[-1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(xp[:, j:j + s, :] * w[:, j] for j in range(taps)) + b


def selective_scan(x, dt, A, B, C, D):
    """The recurrence, position by position: ``x`` (b, s, H, P), ``dt``
    (b, s, H), ``A``, ``D`` (H,), ``B``, ``C`` (b, s, G, N)."""
    b, _, H, P = x.shape
    rep = H // B.shape[2]

    def step(S, t):
        x_t, dt_t, B_t, C_t = t
        B_t, C_t = jnp.repeat(B_t, rep, axis=1), jnp.repeat(C_t, rep, axis=1)  # (b, H, N)
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :])
        return S, jnp.sum(S * C_t[:, :, None, :], axis=-1) + D[:, None] * x_t

    S0 = jnp.zeros((b, H, P, B.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(step, S0, tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1)


def mamba2(p, u, cfg, mm):
    """``u`` (b, s, hidden) -> (b, s, hidden)."""
    H, P, G, N, d, cw = mamba_dims(cfg)
    b, s, _ = u.shape
    zxd = mm(u, p["in_proj"])
    z, xbc, dt = zxd[..., :d], zxd[..., d:d + cw], zxd[..., d + cw:]
    xbc = jax.nn.silu(causal_conv(xbc, p["conv"], p["conv_bias"]))
    x = xbc[..., :d].reshape(b, s, H, P)
    B = xbc[..., d:d + G * N].reshape(b, s, G, N)
    C = xbc[..., d + G * N:].reshape(b, s, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = selective_scan(x, dt, -jnp.exp(p["A_log"]), B, C, p["D"]).reshape(b, s, d)
    y = (y * jax.nn.silu(z)).reshape(b, s, G, d // G)
    y = y / jnp.sqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + cfg["layer_norm_epsilon"])
    return mm(y.reshape(b, s, d) * p["scale"], p["out_proj"])


# ------------------------------------------------------------------ *
def _q_block(s: int) -> int:
    return max(b for b in range(1, min(s, Q_BLOCK) + 1) if s % b == 0)


def attention(p, x, cfg, mm):
    """``x`` (b, s, h) -> (b, s, h), a request and ``_q_block(s)`` query
    rows at a time."""
    b, s, _ = x.shape
    H, KV, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    rep = H // KV
    q = mm(x, p["wq"]).reshape(b, s, H, d).transpose(0, 2, 1, 3)
    k = mm(x, p["wk"]).reshape(b, s, KV, d).transpose(0, 2, 1, 3)
    v = mm(x, p["wv"]).reshape(b, s, KV, d).transpose(0, 2, 1, 3)
    pos = jnp.arange(s)
    qb = _q_block(s)

    def one_request(args):
        q1, k1, v1 = args  # (H, s, d), (KV, s, d)
        k1, v1 = jnp.repeat(k1, rep, axis=0), jnp.repeat(v1, rep, axis=0)

        def rows(lo):
            qs = jax.lax.dynamic_slice_in_dim(q1, lo, qb, axis=1)
            scores = mm(qs, k1.transpose(0, 2, 1)) / math.sqrt(d)  # (H, qb, s)
            seen = pos[None, :] <= (lo + jnp.arange(qb))[:, None]
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            return mm(probs, v1)  # (H, qb, d)

        o = jax.lax.map(rows, jnp.arange(0, s, qb))  # (s / qb, H, qb, d)
        return o.transpose(0, 2, 1, 3).reshape(s, H * d)

    return mm(jax.lax.map(one_request, (q, k, v)), p["wo"])


# ------------------------------------------------------------------ E
def route(p, x, cfg, mm):
    """``x`` (t, h) -> (weights (t, k), chosen experts (t, k)), over all
    of the router's outputs."""
    s = jax.nn.sigmoid(mm(x, p["router"]))
    _, chosen = jax.lax.top_k(s + p["router_bias"], cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * cfg["routed_scaling_factor"], chosen


def moe_block(p, x, cfg, mm, shared: bool = True):
    """``x`` (..., h): every HELD expert over every token, weighted by
    what the router gave it there (0 where it was not chosen); the
    shared expert unless ``shared`` is false (the share test counts it
    once)."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    f = act(cfg)
    E = cfg.get("router_num_experts", cfg["n_routed_experts"])
    first = cfg.get("first_expert", 0)
    w, chosen = route(p, x, cfg, mm)
    dense_w = jnp.sum(jax.nn.one_hot(chosen, E, dtype=jnp.float32) * w[..., None], axis=1)

    def expert(e, acc):
        y = mm(f(mm(x, p["w_up"][e])), p["w_down"][e])
        return acc + jax.lax.dynamic_slice_in_dim(dense_w, first + e, 1, axis=1) * y

    out = jax.lax.fori_loop(0, p["w_up"].shape[0], expert, jnp.zeros(x.shape, jnp.float32))
    if shared:
        out = out + mm(f(mm(x, p["shared_up_proj"])), p["shared_down_proj"])
    return out.reshape(shape)


# ------------------------------------------------------------ forward
@functools.lru_cache(maxsize=None)
def _steps(cfg_key: str, precision: str):
    """The jitted pieces a forward is made of, one set a configuration
    and precision: each takes only the weights it needs."""
    cfg = json.loads(cfg_key)
    mm = matmul(precision)
    eps = cfg["layer_norm_epsilon"]
    mixers = {"M": mamba2, "*": attention, "E": moe_block}

    @jax.jit
    def embed(p, tokens):
        return p["kernel"][tokens]

    @functools.partial(jax.jit, static_argnames=("kind",))
    def layer(p_norm, p, x, *, kind):
        return x + mixers[kind](p, rms_norm(x, p_norm["scale"], eps), cfg, mm)

    @jax.jit
    def head(p_norm, p, x, rows):
        sel = jnp.take_along_axis(x, rows[:, :, None], axis=1)
        return mm(rms_norm(sel, p_norm["scale"], eps), p["kernel"])

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
    for i, kind in enumerate(layer_kinds(cfg)):
        x = layer(params[f"l{i}_norm"], params[mixer_name(i, kind)], x, kind=kind)
    return x


def logits_at(params, tokens, rows, cfg, precision="highest"):
    """Next-token logits (b, r, vocab) at positions ``rows`` (b, r)."""
    x = hidden_states(params, tokens, cfg, precision)
    head = _steps(_key(cfg), precision)[2]
    return head(params["norm_f"], params["lm_head"], x, jnp.asarray(rows))


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
