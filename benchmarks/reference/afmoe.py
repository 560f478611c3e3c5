"""Plain reference: the ``afmoe`` decoder (Arcee Trinity), float32.

Sandwich-norm causal decoder with gated grouped-query attention, window
and full layers mixed, and a sigmoid-routed mixture of experts beside a
shared expert (``N_w(x) = x / sqrt(mean x^2 + eps) * w``, plain)::

    x0 = E[id] * sqrt(hidden)                          (mup_enabled)
    h  = x + N_post_attn(Attn_i(N_in(x)))
    y  = h + N_post_mlp(FFN_i(N_pre_mlp(h)))
    logits = N(x_L) W_head                             (untied)

``Attn``: q (H heads of d), k, v (KV heads), gate (H * d) projections,
no biases; per-head RMSNorm of q and k over d; rotate-half rotary over
all d dims (absolute position) on ``sliding_attention`` layers ONLY;
causal softmax(q k^T / sqrt(d)) v with each KV head serving H / KV
query heads; on a sliding layer query t sees keys t - window + 1 .. t;
``out = (attn * sigmoid(gate)) W_o``.  ``FFN_i`` is the dense gated FFN
``W_d(silu(W_g x) * W_u x)`` for ``i < num_dense_layers`` and after that
the MoE block: ``s = sigmoid(x W_r)`` in float32, chosen = top-k of
``s + b`` (the selection bias, for choosing only), ``w = s[chosen]``,
``w /= sum(w) + 1e-20`` (``route_norm``), ``w *= route_scale``,
``out = E_shared(x) + sum_k w_k E_chosen_k(x)``; no token is dropped.

What the configuration's ``assumed`` lists and this file follows:
``wq`` holds, per query head, the query's d columns and then the
gate's; the selection bias is a weight drawn like any other.

One full forward over whole sequences: dense masks, no cache, no chunks,
no kernel, experts by a loop over all of them with a 0/1-weighted sum.
At the published widths the float32 weights (17 GB) do not fit the chip,
so the forward goes LAYER BY LAYER: ``params`` is any mapping
``layer name -> {weight: array}`` and is asked for a layer's weights
when that layer runs (``benchmarks/weights_by_leaf.py::ByLayer`` makes
them from the seed then, and they are freed after); attention runs a
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

from benchmarks.reference.precision import matmul

Q_BLOCK = 512  # query rows of a request whose float32 scores are held at once


def layer_kinds(cfg: dict) -> list:
    """``[(window or 0, rotary?, "dense" | "moe")]`` a layer."""
    out = []
    for i in range(cfg["num_hidden_layers"]):
        sliding = cfg["layer_types"][i] == "sliding_attention"
        out.append((
            int(cfg["sliding_window"]) if sliding else 0, sliding,
            "dense" if i < cfg["num_dense_layers"] else "moe",
        ))
    return out


def param_shapes(cfg: dict) -> dict:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    H, KV, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    F, f, E = cfg["intermediate_size"], cfg["moe_intermediate_size"], cfg["num_experts"]
    fs = f * cfg["num_shared_experts"]
    out = {
        "tok_embed": {"kernel": (v, h)},
        "final_norm": {"scale": (h,)},
        "lm_head": {"kernel": (h, v)},
    }
    for i, (_, _, ffn) in enumerate(layer_kinds(cfg)):
        for n in ("ln_in", "ln_post_attn", "ln_pre_mlp", "ln_post_mlp"):
            out[f"l{i}_{n}"] = {"scale": (h,)}
        out[f"l{i}_attn"] = {
            "wq": (h, H * 2 * d), "wk": (h, KV * d), "wv": (h, KV * d),
            "wo": (H * d, h), "q_norm": (d,), "k_norm": (d,),
        }
        if ffn == "dense":
            out[f"l{i}_ffn"] = {"w_gate": (h, F), "w_up": (h, F), "w_down": (F, h)}
        else:
            out[f"l{i}_moe"] = {
                "router": (h, E), "router_bias": (E,),
                "w_gate": (E, h, f), "w_up": (E, h, f), "w_down": (E, f, h),
                "shared_gate_proj": (h, fs), "shared_up_proj": (h, fs),
                "shared_down_proj": (fs, h),
            }
    return out


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def rotate_half(x, pos, theta):
    """Rotary positions over all of the last dim of ``x`` (..., s, d),
    pairing dim j with j + d / 2; ``pos`` (s,)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _q_block(s: int) -> int:
    return max(b for b in range(1, min(s, Q_BLOCK) + 1) if s % b == 0)


def attention(p, x, cfg, window: int, rotary: bool, mm):
    """``x`` (b, s, h) -> (b, s, h), a request and ``_q_block(s)`` query
    rows at a time."""
    b, s, _ = x.shape
    H, KV, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, rep = cfg["rms_norm_eps"], H // KV
    qg = mm(x, p["wq"]).reshape(b, s, H, 2 * d)
    q, gate = qg[..., :d], qg[..., d:].reshape(b, s, H * d)
    k = mm(x, p["wk"]).reshape(b, s, KV, d)
    v = mm(x, p["wv"]).reshape(b, s, KV, d)
    q = rms_norm(q, p["q_norm"], eps).transpose(0, 2, 1, 3)  # (b, H, s, d)
    k = rms_norm(k, p["k_norm"], eps).transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    pos = jnp.arange(s)
    if rotary:
        q, k = rotate_half(q, pos, cfg["rope_theta"]), rotate_half(k, pos, cfg["rope_theta"])
    qb = _q_block(s)

    def one_request(args):
        q1, k1, v1 = args  # (H, s, d), (KV, s, d)
        k1, v1 = jnp.repeat(k1, rep, axis=0), jnp.repeat(v1, rep, axis=0)

        def rows(lo):
            qs = jax.lax.dynamic_slice_in_dim(q1, lo, qb, axis=1)
            scores = mm(qs, k1.transpose(0, 2, 1)) / math.sqrt(d)  # (H, qb, s)
            t = (lo + jnp.arange(qb))[:, None]
            seen = pos[None, :] <= t
            if window:
                seen &= pos[None, :] > t - window
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            return mm(probs, v1)  # (H, qb, d)

        o = jax.lax.map(rows, jnp.arange(0, s, qb))  # (s / qb, H, qb, d)
        return o.transpose(0, 2, 1, 3).reshape(s, H * d)

    o = jax.lax.map(one_request, (q, k, v))  # (b, s, H * d)
    return mm(o * jax.nn.sigmoid(gate), p["wo"])


def gated_ffn(x, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def route(p, x, cfg, mm):
    """``x`` (t, h) -> (weights (t, k), chosen experts (t, k))."""
    s = jax.nn.sigmoid(mm(x, p["router"]))
    _, chosen = jax.lax.top_k(s + p["router_bias"], cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg["route_norm"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * cfg["route_scale"], chosen


def moe_block(p, x, cfg, mm):
    """``x`` (..., h): every expert over every token, weighted by what
    the router gave it there (0 where it was not chosen)."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    E = cfg["num_experts"]
    w, chosen = route(p, x, cfg, mm)
    dense_w = jnp.sum(jax.nn.one_hot(chosen, E, dtype=jnp.float32) * w[..., None], axis=1)

    def expert(e, acc):
        y = gated_ffn(x, p["w_gate"][e], p["w_up"][e], p["w_down"][e], mm)
        return acc + dense_w[:, e][:, None] * y

    out = jax.lax.fori_loop(0, E, expert, jnp.zeros(x.shape, jnp.float32))
    out = out + gated_ffn(
        x, p["shared_gate_proj"], p["shared_up_proj"], p["shared_down_proj"], mm
    )
    return out.reshape(shape)


@functools.lru_cache(maxsize=None)
def _steps(cfg_key: str, precision: str):
    """The jitted pieces a forward is made of, one set a configuration
    and precision: each takes only the weights it needs."""
    cfg = json.loads(cfg_key)
    mm = matmul(precision)
    eps = cfg["rms_norm_eps"]

    @jax.jit
    def embed(p, tokens):
        return p["kernel"][tokens] * math.sqrt(cfg["hidden_size"])

    @functools.partial(jax.jit, static_argnames=("window", "rotary"))
    def attn(p_in, p, p_post, x, *, window, rotary):
        y = attention(p, rms_norm(x, p_in["scale"], eps), cfg, window, rotary, mm)
        return x + rms_norm(y, p_post["scale"], eps)

    @functools.partial(jax.jit, static_argnames=("kind",))
    def ffn(p_pre, p, p_post, x, *, kind):
        y = rms_norm(x, p_pre["scale"], eps)
        if kind == "dense":
            y = gated_ffn(y, p["w_gate"], p["w_up"], p["w_down"], mm)
        else:
            y = moe_block(p, y, cfg, mm)
        return x + rms_norm(y, p_post["scale"], eps)

    @jax.jit
    def head(p_norm, p, x, rows):
        sel = jnp.take_along_axis(x, rows[:, :, None], axis=1)
        return mm(rms_norm(sel, p_norm["scale"], eps), p["kernel"])

    return embed, attn, ffn, head


def _key(cfg: dict) -> str:
    return json.dumps({k: v for k, v in cfg.items() if not isinstance(v, dict)},
                      sort_keys=True)


def hidden_states(params, tokens, cfg, precision="highest"):
    """``tokens`` (b, s) int -> the last layer's output (b, s, hidden),
    before the final norm.  Asks ``params`` for a layer's weights when
    the layer runs."""
    embed, attn, ffn, _ = _steps(_key(cfg), precision)
    x = embed(params["tok_embed"], tokens)
    for i, (window, rotary, kind) in enumerate(layer_kinds(cfg)):
        x = attn(params[f"l{i}_ln_in"], params[f"l{i}_attn"], params[f"l{i}_ln_post_attn"],
                 x, window=window, rotary=rotary)
        x = ffn(params[f"l{i}_ln_pre_mlp"],
                params[f"l{i}_ffn" if kind == "dense" else f"l{i}_moe"],
                params[f"l{i}_ln_post_mlp"], x, kind=kind)
    return x


def logits_at(params, tokens, rows, cfg, precision="highest"):
    """Next-token logits (b, r, vocab) at positions ``rows`` (b, r)."""
    x = hidden_states(params, tokens, cfg, precision)
    head = _steps(_key(cfg), precision)[3]
    return head(params["final_norm"], params["lm_head"], x, jnp.asarray(rows))


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
