"""Plain reference: the Qwen3-Next hybrid decoder, one chip's share, float32.

Source: ``Qwen/Qwen3-Next-80B-A3B-Instruct`` ``config.json``
(``model_type`` ``qwen3_next``) and the Gated DeltaNet paper
(arXiv:2412.06464).  Layer ``i`` (all norms over the last axis)::

    h = x + Mixer_i(N(x));  y = h + MoE(N(h));  N(x) = x / sqrt(mean x^2 + eps) * (1 + w)

``Mixer_i`` is gated softmax attention where ``(i + 1) %
full_attention_interval == 0`` and Gated DeltaNet otherwise; a final
``N``, then the head over the held rows of the vocabulary.

* Full attention: ``[q | gate] = x W_q`` per head, ``k``, ``v`` from
  ``num_key_value_heads``; ``q``, ``k`` RMS-normed per head (``1 + w``);
  rotary (rotate-half) on the first ``partial_rotary_factor`` of the
  head; causal softmax of ``q k^T / sqrt(d)``, every key-value head
  serving ``heads / kv_heads`` neighbouring query heads; ``out =
  (attn * sigmoid(gate)) W_o``.
* Gated DeltaNet: ``[q, k, v, z] = x W_qkvz``, ``[b, a] = x W_ba``;
  causal depthwise convolution over ``[q, k, v]`` then SiLU; ``beta =
  sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; ``q``, ``k``
  l2-normalised per head, ``q`` scaled by ``1/sqrt(dk)``, both repeated
  to the value heads.  Per head the state ``S`` (dk x dv) follows, TOKEN
  BY TOKEN, ``S <- e^g S;  S <- S + k (x) (beta (v - S^T k));  o = S^T q``.
  ``out = (o / rms(o) * w * silu(z)) W_out``.
* MoE: ``p = softmax(x W_r)`` over ALL ``router_num_experts``, top-k,
  weights renormalised; the experts ``first_expert .. first_expert +
  num_experts`` are held here, ``E(x) = W_d (silu(W_g x) * W_u x)``; the
  block returns ``sum_{k: e_k held} w_k E_{e_k}(x) + sigmoid(x . w_s)
  E_shared(x)``.  Nothing stands in for the absent experts.  Every
  token goes through every held expert under a mask (dropless by
  construction).

Departures, listed in the configuration under ``assumed``: no
multi-token-prediction module, no auxiliary balance loss, one document
a sequence, the fused projections' column order (``[q | k | v | z]``,
``[b | a]``, per head ``[q | gate]``), weights from
``benchmarks/weights.py``.  Adam as in ``bert_encoder.py``.

Straightforward jax.numpy; imports nothing of the program.  To fit the
published widths beside float32 weights, gradients and Adam's moments
(10 GB) it is computed in blocks, none of which changes the
mathematics: every layer is rematerialised in the backward pass
(``jax.checkpoint``), the token-by-token scan is checkpointed every
``SCAN_BLOCK`` tokens, attention runs over blocks of query rows (each
row's softmax is over all its keys at once), the experts run one after the other (``lax.scan``) and the
loss's rows under ``lax.map``; the initial weights wait on the host for
the change to be taken.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.reference.bert_encoder import adam_alpha, adam_step, leaf_norms
from benchmarks.reference.precision import matmul

SCAN_BLOCK = 64
QUERY_BLOCK = 512
LOSS_BLOCK = 1024


def is_full_attention(i: int, cfg: dict) -> bool:
    return (i + 1) % cfg["full_attention_interval"] == 0


def param_shapes(cfg: dict) -> dict:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    H, KV, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    held, f, fs = cfg["num_experts"], cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    out = {"tok_embed": {"kernel": (v, h)}}
    for i in range(cfg["num_hidden_layers"]):
        out[f"l{i}_ln0"] = {"weight": (h,)}
        if is_full_attention(i, cfg):
            out[f"l{i}_attn"] = {
                "wq": (h, H * 2 * d), "wk": (h, KV * d), "wv": (h, KV * d),
                "wo": (H * d, h), "q_norm": (d,), "k_norm": (d,),
            }
        else:
            out[f"l{i}_gdn"] = {
                "in_proj_qkvz": (h, 2 * hk * dk + 2 * hv * dv),
                "in_proj_ba": (h, 2 * hv),
                "conv": (2 * hk * dk + hv * dv, cfg["linear_conv_kernel_dim"]),
                "A_log": (hv,), "dt_bias": (hv,), "scale": (dv,),
                "out_proj": (hv * dv, h),
            }
        out[f"l{i}_ln1"] = {"weight": (h,)}
        out[f"l{i}_moe"] = {
            "router": (h, cfg["router_num_experts"]),
            "w_gate": (held, h, f), "w_up": (held, h, f), "w_down": (held, f, h),
            "shared_gate_proj": (h, fs), "shared_up_proj": (h, fs),
            "shared_down_proj": (fs, h), "shared_gate": (h, 1),
        }
    out["final_norm"] = {"weight": (h,)}
    out["lm_head"] = {"kernel": (h, v)}
    return out


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + w)


def silu(x):
    return x * jax.nn.sigmoid(x)


# ------------------------------------------------------------ full attention
def rotary(x, rotary_dim, theta):
    """x (b, s, heads, d): rotate-half on the first ``rotary_dim`` dims."""
    half = rotary_dim // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([ang, ang], axis=-1)  # (s, rotary_dim)
    cos, sin = jnp.cos(emb)[None, :, None, :], jnp.sin(emb)[None, :, None, :]
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    half_turned = jnp.concatenate([-rot[..., half:], rot[..., :half]], axis=-1)
    return jnp.concatenate([rot * cos + half_turned * sin, rest], axis=-1)


def full_attention(p, x, cfg, mm):
    b, s, _ = x.shape
    H, KV, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    rotary_dim = int(d * cfg["partial_rotary_factor"])
    qg = mm(x, p["wq"]).reshape(b, s, H, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = mm(x, p["wk"]).reshape(b, s, KV, d)
    v = mm(x, p["wv"]).reshape(b, s, KV, d)
    q = rotary(rms_norm(q, p["q_norm"], eps), rotary_dim, cfg["rope_theta"])
    k = rotary(rms_norm(k, p["k_norm"], eps), rotary_dim, cfg["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=2).transpose(0, 2, 3, 1)  # (b, H, d, s)
    v = jnp.repeat(v, H // KV, axis=2).transpose(0, 2, 1, 3)  # (b, H, s, d)
    q = q.transpose(0, 2, 1, 3)
    blk = min(QUERY_BLOCK, s)
    assert s % blk == 0
    key_pos = jnp.arange(s)

    def rows(args):
        q_blk, start = args  # (b, H, blk, d): these rows against all keys
        scores = mm(q_blk, k) / math.sqrt(d)
        seen = key_pos[None, :] <= (start + jnp.arange(blk))[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return mm(probs, v)

    q_blocks = q.reshape(b, H, s // blk, blk, d).transpose(2, 0, 1, 3, 4)
    o = jax.lax.map(jax.checkpoint(rows), (q_blocks, jnp.arange(0, s, blk)))
    o = o.transpose(1, 0, 3, 2, 4)  # (blocks, b, H, blk, d) -> (b, blocks, blk, H, d)
    o = o.reshape(b, s, H * d) * jax.nn.sigmoid(gate.reshape(b, s, H * d))
    return mm(o, p["wo"])


# ----------------------------------------------------------- gated delta net
def delta_rule_token_by_token(q, k, v, g, beta):
    """q, k (b, s, h, dk); v (b, s, h, dv); g, beta (b, s, h).  One
    token at a time; the backward pass keeps the state every
    ``SCAN_BLOCK`` tokens and recomputes in between."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]

    def token(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = S * jnp.exp(g_t)[..., None, None]
        remembered = jnp.sum(S * k_t[..., :, None], axis=-2)  # S^T k
        S = S + k_t[..., :, None] * ((v_t - remembered) * b_t[..., None])[..., None, :]
        return S, jnp.sum(S * q_t[..., :, None], axis=-2)  # S^T q

    def block(S, xs):
        return jax.lax.scan(token, S, xs)

    blk = SCAN_BLOCK if s % SCAN_BLOCK == 0 else s
    xs = tuple(
        jnp.moveaxis(t, 1, 0).reshape((s // blk, blk) + t.shape[:1] + t.shape[2:])
        for t in (q, k, v, g, beta)
    )
    _, o = jax.lax.scan(jax.checkpoint(block), jnp.zeros((b, h, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(o.reshape((s,) + o.shape[2:]), 0, 1)  # (b, s, h, dv)


def gated_delta_net(p, x, cfg, mm):
    b, s, _ = x.shape
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    taps = cfg["linear_conv_kernel_dim"]
    qkvz = mm(x, p["in_proj_qkvz"])
    ba = mm(x, p["in_proj_ba"])
    qkv, z = qkvz[..., : 2 * hk * dk + hv * dv], qkvz[..., 2 * hk * dk + hv * dv:]
    # causal depthwise convolution, no bias: tap j reads taps-1-j tokens back
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = jnp.zeros_like(qkv)
    for j in range(taps):
        conv = conv + padded[:, j:j + s, :] * p["conv"][:, j]
    qkv = silu(conv)
    q = qkv[..., : hk * dk].reshape(b, s, hk, dk)
    k = qkv[..., hk * dk: 2 * hk * dk].reshape(b, s, hk, dk)
    v = qkv[..., 2 * hk * dk:].reshape(b, s, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])
    q = q / jnp.sqrt(jnp.sum(jnp.square(q), axis=-1, keepdims=True) + 1e-6) / math.sqrt(dk)
    k = k / jnp.sqrt(jnp.sum(jnp.square(k), axis=-1, keepdims=True) + 1e-6)
    q, k = jnp.repeat(q, hv // hk, axis=2), jnp.repeat(k, hv // hk, axis=2)
    o = delta_rule_token_by_token(q, k, v, g, beta)
    o = o / jnp.sqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    o = o * p["scale"] * silu(z.reshape(b, s, hv, dv))
    return mm(o.reshape(b, s, hv * dv), p["out_proj"])


# ----------------------------------------------------------------------- MoE
def route(p, x, cfg):
    """(weights (t, k), expert ids (t, k)) over the router's full width.
    Always float32 ``highest``: the routing is not what the control lowers."""
    logits = jnp.matmul(x, p["router"], precision=jax.lax.Precision.HIGHEST)
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w, idx


def gated_ffn(x, w_gate, w_up, w_down, mm):
    return mm(silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def moe_block(p, x, cfg, mm):
    """x (t, hidden).  The experts in ``p`` are ``first_expert`` and
    those that follow it."""
    first = cfg["first_expert"]
    w, idx = route(p, x, cfg)

    @jax.checkpoint
    def one_expert(e, w_gate, w_up, w_down):
        weight = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)  # 0 where not routed
        return weight[:, None] * gated_ffn(x, w_gate, w_up, w_down, mm)

    def add_expert(acc, args):
        return acc + one_expert(*args), None

    held = p["w_gate"].shape[0]
    out, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(x), (jnp.arange(held), p["w_gate"], p["w_up"], p["w_down"])
    )
    gate = jax.nn.sigmoid(mm(x, p["shared_gate"]))
    return out + gate * gated_ffn(
        x, p["shared_gate_proj"], p["shared_up_proj"], p["shared_down_proj"], mm
    )


# --------------------------------------------------------------------- model
def _layer(params, i, x, cfg, mm, chosen=None):
    """One layer; ``chosen`` (a list) also collects the layer's routed
    expert ids, sorted per token."""
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, params[f"l{i}_ln0"]["weight"], eps)
    if is_full_attention(i, cfg):
        x = x + full_attention(params[f"l{i}_attn"], h, cfg, mm)
    else:
        x = x + gated_delta_net(params[f"l{i}_gdn"], h, cfg, mm)
    h = rms_norm(x, params[f"l{i}_ln1"]["weight"], eps)
    b, s, d = h.shape
    h = h.reshape(b * s, d)
    if chosen is not None:
        chosen.append(jnp.sort(route(params[f"l{i}_moe"], h, cfg)[1], axis=-1))
    return x + moe_block(params[f"l{i}_moe"], h, cfg, mm).reshape(b, s, d)


def hidden_states(params, ids, cfg, precision="highest"):
    mm = matmul(precision)
    x = params["tok_embed"]["kernel"][ids]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(lambda p, t, i=i: _layer(p, i, t, cfg, mm))(params, x)
    return rms_norm(x, params["final_norm"]["weight"], cfg["rms_norm_eps"])


def logits(params, ids, cfg, precision="highest"):
    """(b, s, held vocabulary) next-token logits."""
    return matmul(precision)(hidden_states(params, ids, cfg, precision), params["lm_head"]["kernel"])


def loss(params, ids, labels, cfg, precision="highest", rows=None):
    """Mean next-token cross-entropy over the held vocabulary; ``ids``,
    ``labels`` (b, s) int.  ``rows`` (a slice of the b*s label rows)
    plants the half-batch fault: the mean is over those rows only."""
    mm = matmul(precision)
    x = hidden_states(params, ids, cfg, precision)
    x = x.reshape(-1, x.shape[-1])
    y = labels.reshape(-1).astype(jnp.int32)
    if rows is not None:
        x, y = x[rows], y[rows]
    blk = LOSS_BLOCK if x.shape[0] % LOSS_BLOCK == 0 else x.shape[0]

    def picked(args):
        x_blk, y_blk = args
        logp = jax.nn.log_softmax(mm(x_blk, params["lm_head"]["kernel"]), axis=-1)
        return jnp.take_along_axis(logp, y_blk[:, None], axis=-1)[:, 0]

    lp = jax.lax.map(jax.checkpoint(picked), (x.reshape(-1, blk, x.shape[-1]), y.reshape(-1, blk)))
    return -jnp.mean(lp)


def train_readings(params, batches, cfg, opt, precision="highest", rows=None):
    """Follow the first ``len(batches)`` Adam steps from ``params``;
    ``batches`` are ``(ids, labels)`` pairs.  Returns each step's loss,
    the norm of the first step's gradient per leaf, and the norm of
    every leaf's change after the last step (``bert_encoder.py``'s
    contract)."""
    p0 = jax.device_get(params)  # on the host: the update below donates its arguments
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    step = jax.jit(
        lambda p, x, y: jax.value_and_grad(loss)(p, x, y, cfg, precision, rows)
    )
    update = jax.jit(
        lambda p, g, m, v, a: adam_step(p, g, m, v, a, opt), donate_argnums=(0, 2, 3)
    )
    losses, g1 = [], None
    for t, (x, y) in enumerate(batches, start=1):
        l, g = step(params, x, y)
        losses.append(l)
        if t == 1:
            g1 = jax.jit(leaf_norms)(g)
        params, m, v = update(params, g, m, v, adam_alpha(t, opt))
    del m, v, g
    change = jax.jit(
        lambda a, b: leaf_norms(jax.tree.map(lambda p, q: p - q, a, b))
    )(params, p0)
    return {
        "loss": [float(l) for l in losses],
        "grad_norm": jax.tree.map(float, g1),
        "change_norm": jax.tree.map(float, change),
    }


def routed_sets(params, ids, cfg, precision="highest"):
    """Every layer's chosen expert ids (layers, b*s, k), sorted per
    token -- for the share of tokens whose set differs between two
    precisions."""
    mm = matmul(precision)
    x = params["tok_embed"]["kernel"][ids]
    chosen = []
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(params, i, x, cfg, mm, chosen)
    return jnp.stack(chosen)
