"""Plain reference: the ``minicpm_sala`` decoder (OpenBMB MiniCPM-SALA),
float32.

Every layer is pre-norm (RMSNorm with a weight, ``rms_norm_eps``) with
two branches, each scaled by ``scale_depth / sqrt(L)`` (``L`` the
published depth, ``published_num_hidden_layers``) before it joins the
stream::

    x0 = scale_emb * E[id]
    x  = x + c Mixer_l(N1_l(x));   x = x + c MLP_l(N2_l(x))
    logits = W_head(N_f(x) / (hidden_size / dim_model_base))

``MLP(x) = W_down(silu(x W_gate) * x W_up)``.  Layer ``layer_ids[i]``
(its published index ``l``) has the mixer ``mixer_types[i]``:

* ``lightning-attn``: ``q, k, v = x W_q, x W_k, x W_v`` (``lightning_nh``
  heads of ``lightning_head_dim``), q and k RMS-normed a head (weights
  ``q_norm``, ``k_norm``), then rotary (rotate-half, ``rope_theta``, the
  whole head) at the absolute position; THE RECURRENCE ITSELF, position
  by position (``lax.scan``), a head ``h`` with ``lambda = 2^(-8 (h + 1)
  / nh) (1 - l / (L - 1) + 1e-5)``::

      S_t = exp(-lambda) S_{t-1} + k_t^T v_t,    o_t = q_t S_t / sqrt(d)

  ``out = W_o(RMSNorm_{nh d}(o) * sigmoid(x W_g))`` (one norm over all
  heads, weight ``o_norm``);
* ``minicpm4``: ``q`` (``num_attention_heads``) and its output gate from
  one matrix, per head ``[q | gate]``; ``k``, ``v``
  (``num_key_value_heads``), q and k RMS-normed a head, no positions;
  InfLLM-v2 (``sparse_config``: kernel ``K``, stride ``s``, block ``B``,
  ``topk``, window ``W``, ``dense_len``).  Compressed key ``j`` of a K/V
  head is the mean of its keys ``s j .. s j + K - 1``, there once
  position ``s j + K - 1`` is.  A query's block scores: for each of the
  head's query heads the softmax over the compressed keys there of ``q .
  K_j / sqrt(d)``, the largest over the kernels that overlap the block,
  summed over the query heads.  Its selected set: block 0, the blocks
  that hold ``t_first - W + 1 .. t_last``, and the best-scoring blocks
  before them (ties to the lower block), ``topk`` in all; every block up
  to ``t_last``'s where ``t_last + 1 <= dense_len``.  The query attends
  the keys of its set up to itself.  A DECODED position (at or past the
  prompt's length) is its own unit, ``t_first = t_last = t``; the
  PROMPT's positions share a selection a block: the block's prompt
  positions' scores are summed, ``t_first`` is the block's first
  position and ``t_last`` its last prompt position (the serving rule:
  ``assumed`` in the configuration).  ``out = W_o(o * sigmoid(gate))``.

One full forward over whole sequences, no cache, no kernel.  The float32
weights do not fit the chip beside the activations, so the forward goes
LAYER BY LAYER (``params[name]`` is asked for a layer's weights when it
runs: ``weights_sala.ByLayer``), a layer a request at a time; sparse
attention a block of ``Q_BLOCK`` query rows at a time; the head only at
the rows asked for.  The blocking changes where the arithmetic is done, not what
it is.  Imports nothing of the program.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.precision import matmul

Q_BLOCK = 512  # query rows of a request whose float32 scores are held at once


def dims(cfg: dict) -> dict:
    sp = cfg["sparse_config"]
    return dict(
        h=cfg["hidden_size"], H=cfg["num_attention_heads"], KV=cfg["num_key_value_heads"],
        d=cfg["head_dim"], f=cfg["intermediate_size"], v=cfg["vocab_size"],
        LH=cfg["lightning_nh"], Ld=cfg["lightning_head_dim"],
        L=cfg["published_num_hidden_layers"], K=sp["kernel_size"], s=sp["kernel_stride"],
        B=sp["block_size"], topk=sp["topk"], W=sp["window_size"], dense=sp["dense_len"],
    )


def layers(cfg: dict):
    """``(published index, kind)`` of the layers run, in order."""
    return list(zip(cfg["layer_ids"], cfg["mixer_types"]))


def mixer_name(l: int, kind: str) -> str:
    return f"l{l}_" + ("attn" if kind == "minicpm4" else "lightning")


def param_shapes(cfg: dict) -> dict:
    D = dims(cfg)
    h, H, KV, d, LH, Ld = D["h"], D["H"], D["KV"], D["d"], D["LH"], D["Ld"]
    out = {"tok_embed": {"kernel": (D["v"], h)}, "norm_f": {"scale": (h,)},
           "lm_head": {"kernel": (h, D["v"])}}
    for l, kind in layers(cfg):
        out[f"l{l}_norm1"] = {"scale": (h,)}
        out[f"l{l}_norm2"] = {"scale": (h,)}
        out[f"l{l}_mlp"] = {"w_gate": (h, D["f"]), "w_up": (h, D["f"]), "w_down": (D["f"], h)}
        if kind == "minicpm4":
            shapes = {"wq": (h, H * 2 * d), "wk": (h, KV * d), "wv": (h, KV * d),
                      "wo": (H * d, h), "q_norm": (d,), "k_norm": (d,)}
        else:
            w = LH * Ld
            shapes = {"wq": (h, w), "wk": (h, w), "wv": (h, w), "wg": (h, w), "wo": (w, h),
                      "q_norm": (Ld,), "k_norm": (Ld,), "o_norm": (w,)}
        out[mixer_name(l, kind)] = shapes
    return out


def rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """Rotate-half rotary over the whole head: ``x`` (b, s, heads, d)."""
    d, s = x.shape[-1], x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv  # (s, d / 2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ------------------------------------------------------------ lightning
def decays(cfg: dict, l: int):
    D = dims(cfg)
    h = np.arange(D["LH"], dtype=np.float64)
    lam = 2.0 ** (-8.0 * (h + 1) / D["LH"]) * (1.0 - l / (D["L"] - 1) + 1e-5)
    return jnp.asarray(np.exp(-lam), jnp.float32)


def lightning(p, x, cfg, l, mm):
    """``x`` (b, s, hidden) normed -> (b, s, hidden)."""
    D = dims(cfg)
    b, s, _ = x.shape
    nh, d, eps = D["LH"], D["Ld"], cfg["rms_norm_eps"]
    q = rope(rms(mm(x, p["wq"]).reshape(b, s, nh, d), p["q_norm"], eps), cfg["rope_theta"])
    k = rope(rms(mm(x, p["wk"]).reshape(b, s, nh, d), p["k_norm"], eps), cfg["rope_theta"])
    v = mm(x, p["wv"]).reshape(b, s, nh, d)
    gamma = decays(cfg, l)[None, :, None, None]

    def step(S, t):  # S (b, nh, d_k, d_v)
        q_t, k_t, v_t = t
        S = gamma * S + k_t[..., :, None] * v_t[..., None, :]
        return S, jnp.einsum("bhk,bhkv->bhv", q_t, S, precision=jax.lax.Precision.HIGHEST)

    S0 = jnp.zeros((b, nh, d, d), jnp.float32)
    _, o = jax.lax.scan(step, S0, tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, s, nh * d) / math.sqrt(d)
    o = rms(o, p["o_norm"], eps) * jax.nn.sigmoid(mm(x, p["wg"]))
    return mm(o, p["wo"])


# ------------------------------------------------------------ sparse
def compressed_keys(k, cfg):
    """``k`` (s, KV, d) -> (J, KV, d), J = s // stride: entry ``j`` the mean
    over positions ``stride j .. stride j + kernel - 1`` (past the end:
    zeros, and such an entry is never there for a query)."""
    D = dims(cfg)
    s = k.shape[0]
    J = s // D["s"]
    kp = jnp.pad(k, ((0, D["K"]), (0, 0), (0, 0)))
    pos = D["s"] * jnp.arange(J)[:, None] + jnp.arange(D["K"])[None, :]  # (J, K)
    return jnp.mean(kp[pos], axis=1)


def overlaps(cfg: dict, s: int):
    """``(NB, m)`` kernels overlapping each block and whether each is real."""
    D = dims(cfg)
    nb, J = s // D["B"], s // D["s"]
    first = (D["B"] * np.arange(nb) - D["K"] + 1 + D["s"] - 1) // D["s"]
    last = (D["B"] * np.arange(nb) + D["B"] - 1) // D["s"]
    m = int((last - first).max()) + 1
    j = first[:, None] + np.arange(m)[None, :]
    ok = (j >= 0) & (j <= last[:, None]) & (j < J)
    return jnp.asarray(np.clip(j, 0, J - 1)), jnp.asarray(ok)


def selection(score, t_first, t_last, cfg):
    """``score`` (r, KV, NB), ``t_first``, ``t_last`` (r,) -> mask (r, KV, NB)."""
    D = dims(cfg)
    nb = score.shape[-1]
    b = jnp.arange(nb)
    c = (t_last // D["B"])[:, None, None]
    lo = (jnp.maximum(t_first - D["W"] + 1, 0) // D["B"])[:, None, None]
    forced = (b == 0) | ((b >= lo) & (b <= c))
    cand = (b >= 1) & (b < lo)
    n_top = D["topk"] - jnp.sum(forced, axis=-1, keepdims=True)
    order = jnp.argsort(-jnp.where(cand, score, -jnp.inf), axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)
    sel = forced | (cand & (rank < n_top))
    return jnp.where((t_last + 1 <= D["dense"])[:, None, None], b <= c, sel)


def sparse(p, x, plen, npos, cfg, mm, count_flips=False):
    """``x`` (b, s, hidden) normed, ``plen`` (b,) prompt lengths -> (b, s,
    hidden), and the (position, K/V head) pairs before ``npos`` (b,)
    whose selection under bfloat16 query and compressed keys differs
    from float32's."""
    D = dims(cfg)
    b, s, _ = x.shape
    H, KV, d, eps = D["H"], D["KV"], D["d"], cfg["rms_norm_eps"]
    rep, Bk = H // KV, D["B"]
    qg = mm(x, p["wq"]).reshape(b, s, H, 2 * d)
    q, gate = rms(qg[..., :d], p["q_norm"], eps), qg[..., d:]
    k = rms(mm(x, p["wk"]).reshape(b, s, KV, d), p["k_norm"], eps)
    v = mm(x, p["wv"]).reshape(b, s, KV, d)
    ov, ov_ok = overlaps(cfg, s)
    qb = max(r for r in range(Bk, min(s, Q_BLOCK) + 1, Bk) if s % r == 0)
    pos = jnp.arange(s)
    J = s // D["s"]

    def one_request(args):
        q1, k1, v1, n_p, n_pos = args  # (s, H, d), (s, KV, d), (s, KV, d), (), ()
        kbar = compressed_keys(k1, cfg)  # (J, KV, d)

        def scores_of(qs, kb, t):
            sc = jnp.einsum("rgkd,jgd->rgkj", qs.reshape(-1, KV, rep, d), kb,
                            precision=jax.lax.Precision.HIGHEST) / math.sqrt(d)
            there = (D["s"] * jnp.arange(J) + D["K"] - 1)[None, :] <= t[:, None]  # (r, J)
            pr = jax.nn.softmax(jnp.where(there[:, None, None], sc, -jnp.inf), axis=-1)
            pr = jnp.where(there[:, None, None], pr, 0.0)
            blockwise = jnp.where(ov_ok, pr[..., ov], 0.0).max(-1)  # (r, KV, rep, NB)
            return blockwise.sum(2)  # (r, KV, NB)

        def select(qs, kb, t):
            r = scores_of(qs, kb, t)
            prompt = t < n_p
            # the prompt's rows: their block's prompt rows' scores summed
            tiles = r.reshape(-1, Bk, KV, r.shape[-1])
            tile_sum = jnp.where(prompt.reshape(-1, Bk)[..., None, None], tiles, 0.0).sum(1)
            r = jnp.where(prompt[:, None, None], jnp.repeat(tile_sum, Bk, axis=0), r)
            first = jnp.where(prompt, t // Bk * Bk, t)
            last = jnp.where(prompt, jnp.minimum(t // Bk * Bk + Bk, n_p) - 1, t)
            return selection(r, first, last, cfg)

        def rows(lo):
            t = lo + jnp.arange(qb)
            qs = jax.lax.dynamic_slice_in_dim(q1, lo, qb, axis=0)  # (qb, H, d)
            sel = select(qs, kbar, t)  # (qb, KV, NB)
            flips = jnp.zeros((), jnp.int32)
            if count_flips:
                bf = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
                other = select(bf(qs), bf(kbar), t)
                flips = jnp.sum(jnp.any(other != sel, axis=-1) & (t < n_pos)[:, None])
            seen = sel[:, :, pos // Bk] & (pos[None, None, :] <= t[:, None, None])  # (qb, KV, s)
            sc = jnp.einsum("rgkd,sgd->rgks", qs.reshape(qb, KV, rep, d), k1,
                            precision=jax.lax.Precision.HIGHEST) / math.sqrt(d)
            pr = jax.nn.softmax(jnp.where(seen[:, :, None], sc, -jnp.inf), axis=-1)
            o = jnp.einsum("rgks,sgd->rgkd", pr, v1, precision=jax.lax.Precision.HIGHEST)
            return o.reshape(qb, H * d), flips

        o, flips = jax.lax.map(rows, jnp.arange(0, s, qb))
        return o.reshape(s, H * d), flips

    o, flips = jax.lax.map(one_request, (q, k, v, plen, npos))
    o = o * jax.nn.sigmoid(gate.reshape(b, s, H * d))
    return mm(o, p["wo"]), flips


# ------------------------------------------------------------ forward
@functools.lru_cache(maxsize=None)
def _steps(cfg_key: str, precision: str):
    """The jitted pieces a forward is made of, one set a configuration
    and precision: each takes only the weights it needs."""
    cfg = json.loads(cfg_key)
    mm = matmul(precision)
    eps = cfg["rms_norm_eps"]
    D = dims(cfg)
    c = cfg["scale_depth"] / math.sqrt(D["L"])

    @jax.jit
    def embed(p, tokens):
        return cfg["scale_emb"] * p["kernel"][tokens]

    def mlp(p, x):
        return mm(jax.nn.silu(mm(x, p["w_gate"])) * mm(x, p["w_up"]), p["w_down"])

    @functools.partial(jax.jit, static_argnames=("l", "kind", "count_flips"))
    def layer(p_n1, p, p_n2, p_mlp, x, plen, npos, *, l, kind, count_flips):
        def one(args):  # a request at a time: its float32 rows fit the chip
            x1, n_p, n_pos = args
            x1 = x1[None]
            h = rms(x1, p_n1["scale"], eps)
            flips = jnp.zeros((1,), jnp.int32)
            if kind == "minicpm4":
                out, flips = sparse(p, h, n_p[None], n_pos[None], cfg, mm, count_flips)
            else:
                out = lightning(p, h, cfg, l, mm)
            x1 = x1 + c * out
            x1 = x1 + c * mlp(p_mlp, rms(x1, p_n2["scale"], eps))
            return x1[0], jnp.sum(flips)

        x, flips = jax.lax.map(one, (x, plen, npos))
        return x, jnp.sum(flips)

    @jax.jit
    def head(p_n, p_head, x, rows):
        sel = jnp.take_along_axis(x, rows[:, :, None], axis=1)
        return mm(rms(sel, p_n["scale"], eps) * (cfg["dim_model_base"] / D["h"]), p_head["kernel"])

    return embed, layer, head


def _key(cfg: dict) -> str:
    return json.dumps(cfg, sort_keys=True)


def hidden_states(params, tokens, plen, npos, cfg, precision="highest", count_flips=False):
    """``tokens`` (b, s) int, ``plen`` (b,) prompt lengths -> the last
    layer's output (b, s, hidden) before the final norm, and the summed
    selection flips (``count_flips``) over the positions before ``npos``
    (b,)."""
    embed, layer, _ = _steps(_key(cfg), precision)
    x = embed(params["tok_embed"], tokens)
    flips = 0
    for l, kind in layers(cfg):
        x, f = layer(params[f"l{l}_norm1"], params[mixer_name(l, kind)],
                     params[f"l{l}_norm2"], params[f"l{l}_mlp"], x, plen, npos,
                     l=l, kind=kind, count_flips=count_flips)
        flips += int(f)
    return x, flips


def logits_at(params, tokens, rows, cfg, precision="highest", count_flips=False):
    """Next-token logits (b, r, vocab) at positions ``rows`` (b, r) --
    ``rows[:, 0] + 1`` is each sequence's prompt length, ``rows.max(1)
    + 1`` the positions it ran through -- and the selection flips."""
    with jax.default_matmul_precision("highest"):
        rows = jnp.asarray(rows)
        # whole blocks of positions: what follows a sequence changes
        # nothing before it (causal, and an index entry that reaches past
        # the end is never there for a position before the end)
        tokens = jnp.asarray(tokens)
        blk = cfg["sparse_config"]["block_size"]
        tokens = jnp.pad(tokens, ((0, 0), (0, -tokens.shape[1] % blk)))
        x, flips = hidden_states(params, tokens, rows[:, 0] + 1,
                                 rows.max(axis=1) + 1, cfg, precision, count_flips)
        head = _steps(_key(cfg), precision)[2]
        return head(params["norm_f"], params["lm_head"], x, rows), flips


def served_readings(params, tokens, rows, served, logits, valid, cfg, precision="highest",
                    count_flips=False):
    """For each served token: how far its reference logit lies below the
    reference's best at that position (0 where the reference would have
    picked it too), and how far the program's own logit of it
    (``logits``, or None) lies from the reference's; with
    ``count_flips`` also the (position, K/V head) pairs of the sparse
    layers, over every position, whose selection under bfloat16 query
    and compressed keys differs from float32's.  ``precision`` other
    than ``highest`` is the control: the token judged is then the one
    that precision puts first, and its logit that precision's.  Padded
    entries (``valid`` false) read 0.  Returns ``(gaps, errors or None,
    flips)``.  Not to be wrapped in one ``jax.jit``: the forward frees a
    layer's weights before it makes the next."""
    tokens, rows = jnp.asarray(tokens), jnp.asarray(rows)
    served = jnp.asarray(served).astype(jnp.int32)
    if precision != "highest":
        ctl = logits_at(params, tokens, rows, cfg, precision)[0]
        served, logits = jnp.argmax(ctl, axis=-1), jnp.max(ctl, axis=-1)
    ref, flips = logits_at(params, tokens, rows, cfg, "highest", count_flips)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, served[:, :, None], axis=-1)[..., 0]
    valid = jnp.asarray(valid)
    gaps = jnp.where(valid, best - got, 0.0)
    errs = None if logits is None else jnp.where(
        valid, jnp.abs(jnp.asarray(logits, jnp.float32) - got), 0.0)
    return gaps, errs, flips


def served_gaps(params, tokens, rows, served, valid, cfg, precision="highest"):
    """:func:`served_readings`' gaps alone (every family's reference
    has this)."""
    return served_readings(params, tokens, rows, served, None, valid, cfg, precision)[0]
