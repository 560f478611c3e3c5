"""Plain reference: GPT-2 style pre-LN causal decoder, float32.

Radford et al. 2019 (GPT-2 117M/124M: n_embd 768, n_head 12, n_layer 12,
n_ctx 1024, vocab 50257): token embedding plus learned positions,
blocks of ``x += attn(ln(x)); x += mlp(ln(x))`` with tanh-GELU, a final
LayerNorm and an output head.  Departures the configuration lists under
``assumed`` and this file follows: the output head is its own matrix
(not tied to the token embedding) and attention has no biases.

One full forward over a whole sequence, no cache, no kernels, no
batching tricks: prefill-then-decode through the program's paged pool
has to agree with it.  Imports nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.reference.parts import gelu_tanh as _gelu_tanh
from benchmarks.reference.parts import layer_norm as _ln
from benchmarks.reference.precision import matmul


def param_shapes(cfg: dict) -> dict:
    h, ff, v, ctx = cfg["n_embd"], cfg["n_inner"], cfg["vocab_size"], cfg["n_ctx"]
    out = {
        "tok_embed": {"kernel": (v, h)},
        "pos_embed": {"value": (ctx, h)},
        "final_ln": {"scale": (h,), "bias": (h,)},
        "lm_head": {"kernel": (h, v)},
    }
    for i in range(cfg["n_layer"]):
        out[f"dec{i}_ln0"] = {"scale": (h,), "bias": (h,)}
        out[f"dec{i}_attn"] = {"wq": (h, h), "wk": (h, h), "wv": (h, h), "wo": (h, h)}
        out[f"dec{i}_ln1"] = {"scale": (h,), "bias": (h,)}
        out[f"dec{i}_ff0"] = {"kernel": (h, ff), "bias": (ff,)}
        out[f"dec{i}_ff1"] = {"kernel": (ff, h), "bias": (h,)}
    return out


def hidden_states(params, tokens, cfg, precision="highest"):
    """``tokens`` (b, s) int -> final-LayerNorm'd states (b, s, n_embd)."""
    mm = matmul(precision)
    b, s = tokens.shape
    H = cfg["n_head"]
    h = cfg["n_embd"]
    d = h // H
    eps = cfg["layer_norm_epsilon"]
    x = params["tok_embed"]["kernel"][tokens] + params["pos_embed"]["value"][:s][None]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def heads(t):
        return t.reshape(b, s, H, d).transpose(0, 2, 1, 3)

    for i in range(cfg["n_layer"]):
        at = params[f"dec{i}_attn"]
        y = _ln(params[f"dec{i}_ln0"], x, eps)
        q, k, v = heads(mm(y, at["wq"])), heads(mm(y, at["wk"])), heads(mm(y, at["wv"]))
        scores = mm(q, k.transpose(0, 1, 3, 2)) / math.sqrt(d)
        scores = jnp.where(causal, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        o = mm(probs, v).transpose(0, 2, 1, 3).reshape(b, s, h)
        x = x + mm(o, at["wo"])
        y = _ln(params[f"dec{i}_ln1"], x, eps)
        p0, p1 = params[f"dec{i}_ff0"], params[f"dec{i}_ff1"]
        f = _gelu_tanh(mm(y, p0["kernel"]) + p0["bias"])
        x = x + mm(f, p1["kernel"]) + p1["bias"]
    return _ln(params["final_ln"], x, eps)


def logits_at(params, tokens, rows, cfg, precision="highest"):
    """Next-token logits (b, r, vocab) at positions ``rows`` (b, r)."""
    x = hidden_states(params, tokens, cfg, precision)
    sel = jnp.take_along_axis(x, rows[:, :, None], axis=1)
    return matmul(precision)(sel, params["lm_head"]["kernel"])


def served_gaps(params, tokens, rows, served, valid, cfg, precision="highest"):
    """For each served token, how far its reference logit lies below the
    reference's best at that position (0 where the reference would have
    picked it too).  ``precision`` other than ``highest`` is the
    control: the token judged is then the one that precision puts
    first, not the served one.  Padded entries (``valid`` false) read 0.
    """
    ref = logits_at(params, tokens, rows, cfg, "highest")
    if precision != "highest":
        served = jnp.argmax(logits_at(params, tokens, rows, cfg, precision), axis=-1)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, served[:, :, None].astype(jnp.int32), axis=-1)[..., 0]
    return jnp.where(valid, best - got, 0.0)
