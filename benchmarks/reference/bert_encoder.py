"""Plain reference: BERT-style post-LN encoder classifier, float32.

Devlin et al. 2018 (BERT-Base: L=12, H=768, A=12, FFN 3072), as the
configuration file states it is run here: float embeddings fed in (no
token table), mean-pool over the sequence, one dense head, softmax,
sparse categorical cross-entropy; no dropout.  Departures from the
paper that the configuration lists under ``assumed`` and this file
follows: tanh-approximated GELU, LayerNorm eps 1e-5, no attention
biases.  Adam is Kingma & Ba 2015 in its section-2 efficient form
(``alpha_t = alpha*sqrt(1-b2^t)/(1-b1^t)``, epsilon beside sqrt(v)).

Straightforward jax.numpy; imports nothing of the program and takes
nothing the program made.  Each block is rematerialised in the backward
pass so three float32 steps at b16 s512 fit beside nothing else on a
16 GB chip.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.reference.parts import gelu_tanh as _gelu_tanh
from benchmarks.reference.parts import layer_norm as _ln
from benchmarks.reference.precision import matmul


def param_shapes(cfg: dict) -> dict:
    h, ff, n = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_labels"]
    out = {}
    for i in range(cfg["num_hidden_layers"]):
        out[f"enc{i}_attn"] = {"wq": (h, h), "wk": (h, h), "wv": (h, h), "wo": (h, h)}
        out[f"enc{i}_ln0"] = {"scale": (h,), "bias": (h,)}
        out[f"enc{i}_ff0"] = {"kernel": (h, ff), "bias": (ff,)}
        out[f"enc{i}_ff1"] = {"kernel": (ff, h), "bias": (h,)}
        out[f"enc{i}_ln1"] = {"scale": (h,), "bias": (h,)}
    out["cls_head"] = {"kernel": (h, n), "bias": (n,)}
    return out


def _block(params, i, x, cfg, mm):
    b, s, h = x.shape
    H = cfg["num_attention_heads"]
    d = h // H
    eps = cfg["layer_norm_eps"]
    at = params[f"enc{i}_attn"]

    def heads(t):
        return t.reshape(b, s, H, d).transpose(0, 2, 1, 3)

    q, k, v = heads(mm(x, at["wq"])), heads(mm(x, at["wk"])), heads(mm(x, at["wv"]))
    scores = mm(q, k.transpose(0, 1, 3, 2)) / math.sqrt(d)
    probs = jax.nn.softmax(scores, axis=-1)
    o = mm(probs, v).transpose(0, 2, 1, 3).reshape(b, s, h)
    x = _ln(params[f"enc{i}_ln0"], mm(o, at["wo"]) + x, eps)
    p0, p1 = params[f"enc{i}_ff0"], params[f"enc{i}_ff1"]
    f = _gelu_tanh(mm(x, p0["kernel"]) + p0["bias"])
    f = mm(f, p1["kernel"]) + p1["bias"]
    return _ln(params[f"enc{i}_ln1"], f + x, eps)


def loss(params, x, y, cfg, precision="highest"):
    """Mean sparse categorical cross-entropy of the classifier on a
    batch ``x`` (b, s, hidden) float, ``y`` (b,) int."""
    mm = matmul(precision)
    x = x.astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(lambda p, t, i=i: _block(p, i, t, cfg, mm))(params, x)
    pooled = jnp.mean(x, axis=1)
    hd = params["cls_head"]
    logits = mm(pooled, hd["kernel"]) + hd["bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, y.reshape(-1, 1).astype(jnp.int32), axis=-1)
    return -jnp.mean(picked)


def adam_step(params, grads, m, v, alpha_t, opt):
    """One Adam update; ``alpha_t`` is the bias-corrected step size."""
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["epsilon"]
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    params = jax.tree.map(
        lambda w, a, c: w - alpha_t * a / (jnp.sqrt(c) + eps), params, m, v
    )
    return params, m, v


def adam_alpha(t: int, opt: dict) -> float:
    return opt["alpha"] * math.sqrt(1.0 - opt["beta2"] ** t) / (1.0 - opt["beta1"] ** t)


def leaf_norms(tree):
    return jax.tree.map(lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), tree)


def train_readings(params, batches, cfg, opt, precision="highest", rows=None):
    """Follow the first ``len(batches)`` Adam steps from ``params``.

    Returns each step's loss, the norm of the first step's gradient per
    leaf, and the norm of every leaf's change after the last step.
    ``rows`` (a slice) plants the half-batch fault: the loss and its
    mean are taken over those rows only.
    """
    p0 = params
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    step = jax.jit(
        lambda p, x, y: jax.value_and_grad(loss)(p, x, y, cfg, precision)
    )
    update = jax.jit(lambda p, g, m, v, a: adam_step(p, g, m, v, a, opt))
    losses, g1 = [], None
    for t, (x, y) in enumerate(batches, start=1):
        if rows is not None:
            x, y = x[rows], y[rows]
        l, g = step(params, x, y)
        losses.append(l)
        if t == 1:
            g1 = jax.jit(leaf_norms)(g)
        params, m, v = update(params, g, m, v, adam_alpha(t, opt))
    change = jax.jit(
        lambda a, b: leaf_norms(jax.tree.map(lambda p, q: p - q, a, b))
    )(params, p0)
    return {
        "loss": [float(l) for l in losses],
        "grad_norm": jax.tree.map(float, g1),
        "change_norm": jax.tree.map(float, change),
    }
