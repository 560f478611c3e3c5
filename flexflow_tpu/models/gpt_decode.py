"""KV-cache decode for the GPT family (round-5 verdict #9).

The reference's only incremental-decoding machinery is seq_length
masking (``FFIterationConfig::seq_length``,
``include/flexflow/config.h:162-167``) — every step re-runs the full
forward over the whole prefix, so step time grows with prefix length.
:func:`flexflow_tpu.models.transformer.gpt_generate` reproduces that
behavior for parity.  This module goes beyond it the TPU way: ONE jitted
single-token step whose inputs are static-shape K/V caches
``(L, B, heads, S_max, head_dim)``; each step projects q/k/v for one
position, ``dynamic_update_slice``s the caches at ``t`` (donated, so XLA
updates in place), and attends the single query row against the cache
under an ``iota <= t`` mask.  Per step that is O(S_max·hidden) attention
reads + O(1-token) FFN work — independent of how long the prefix is —
and the trace is position-independent, so the whole generation runs on
one compiled program (the parity/no-retrace tests pin both properties).

Prompt ingestion is phase-separated (docs/SERVING.md): :meth:`
GPTDecodeSession.prefill` feeds the WHOLE prompt in one batched call —
P query rows against the same cache, causal-masked — instead of the
token-at-a-time warmup loop.  Per row the math is element-for-element
the per-token step's (same cache layout, same mask width, same cast
rules), so the cache contents and next-token probs are bit-identical to
the loop (pinned by tests/test_serve.py for fp32 and bf16); the win is
P positions per dispatch instead of P dispatches.

Works on any model built by
:func:`flexflow_tpu.models.transformer.gpt_decoder` (the layer names are
the contract).  Under a sharded strategy the step jit inherits the
executor's parameter shardings and GSPMD inserts the collectives, same
as the full forward.  The production serving layer
(:mod:`flexflow_tpu.serve`) reuses :class:`GPTSpec` and the same math
over a paged/block cache.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import numpy as np

__all__ = ["GPTSpec", "GPTDecodeSession", "gpt_generate_cached"]


@dataclasses.dataclass(frozen=True)
class GPTSpec:
    """Shapes + attrs a compiled :func:`gpt_decoder` model implies —
    the ONE extraction rule, shared by the dense session here and the
    paged serving programs (:mod:`flexflow_tpu.serve.programs`)."""

    num_layers: int
    heads: int
    head_dim: int
    hidden: int
    has_bias: bool
    eps: float
    batch: int
    seq: int

    @classmethod
    def from_model(cls, model) -> "GPTSpec":
        assert model.executor is not None, "call compile() first"
        names = {l.name: l for l in model.layers}
        assert "tok_embed" in names and "lm_head" in names, (
            "requires a gpt_decoder-built model "
            "(tok_embed/dec{i}_*/final_ln/lm_head layer names)"
        )
        num_layers = sum(
            1 for n in names if n.startswith("dec") and n.endswith("_attn")
        )
        attn = names["dec0_attn"].attrs
        heads = attn["num_heads"]
        e = attn["embed_dim"]
        batch, seq = model.graph_inputs[0].shape
        return cls(
            num_layers=num_layers,
            heads=heads,
            head_dim=attn.get("kdim") or e // heads,
            hidden=e,
            has_bias=bool(attn.get("bias")),
            eps=names["final_ln"].attrs.get("eps", 1e-5),
            batch=batch,
            seq=seq,
        )


def make_cast(jnp, dt):
    """Mixed-precision rule shared by every decode/prefill program
    (mirrors ``FFConfig.compute_dtype`` in the executor): float32 master
    params cast at use, caches/activations in the compute dtype,
    probabilities back in float32."""
    mixed = dt != jnp.float32

    def cast(x):
        if mixed and x.dtype == jnp.float32:
            return x.astype(dt)
        return x

    return cast


def quantize_weights_int8(jnp, params):
    """Weight-only int8 for the weight-streaming-bound decode roofline
    (``ServeSpec.weight_dtype`` — docs/SERVING.md): every float leaf
    with >= 2 axes is stored int8 with a per-output-channel (last axis)
    symmetric float32 scale; 1-D leaves (biases, layer-norm params) and
    integer leaves stay as-is with scale 1.  Returns ``(qparams,
    scales)`` — two trees of identical structure that
    :func:`dequantize_weights_int8` folds back at the matmul edge, so
    HBM streams 1-byte elements and the dequant happens in-register."""
    import numpy as np

    def q(x):
        xa = np.asarray(x)
        if xa.ndim < 2 or not np.issubdtype(xa.dtype, np.floating):
            return x, jnp.asarray(1.0, jnp.float32)
        xf = xa.astype(np.float32)
        amax = np.max(np.abs(xf), axis=tuple(range(xa.ndim - 1)))
        scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
        qx = np.clip(np.round(xf / scale), -127, 127).astype(np.int8)
        return jnp.asarray(qx), jnp.asarray(scale)

    import jax

    pairs = jax.tree.map(q, params)
    qparams = jax.tree.map(lambda p: p[0], pairs, is_leaf=lambda p: isinstance(p, tuple))
    scales = jax.tree.map(lambda p: p[1], pairs, is_leaf=lambda p: isinstance(p, tuple))
    return qparams, scales


def dequantize_weights_int8(jax, jnp, qparams, scales):
    """The read-side rule of :func:`quantize_weights_int8`: int8 leaves
    become ``w.astype(f32) * scale`` (scale broadcasts on the last
    axis); everything else passes through.  Traced inside each serve
    program, so the lowered HLO reads int8 from HBM and widens next to
    the consuming matmul."""
    return jax.tree.map(
        lambda w, s: w.astype(jnp.float32) * s
        if w.dtype == jnp.int8 else w,
        qparams, scales,
    )


def layer_norm(jax, jnp, p, x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


class GPTDecodeSession:
    """Compiled single-token decode step + cache state for one model."""

    def __init__(self, model) -> None:
        import jax
        import jax.numpy as jnp

        self.model = model
        spec = GPTSpec.from_model(model)
        self.spec = spec
        self.num_layers = spec.num_layers
        self.heads = spec.heads
        self.kd = spec.head_dim
        self.hidden = spec.hidden
        self.has_bias = spec.has_bias
        self.batch, self.seq = spec.batch, spec.seq
        self.eps = spec.eps
        self._trace_count = 0  # exposed for the no-retrace test

        L, B, H, S, D = (
            self.num_layers, self.batch, self.heads, self.seq, self.kd,
        )
        eps = self.eps
        has_bias = self.has_bias
        scale = 1.0 / math.sqrt(D)
        # mirror the executor's mixed-precision rule (FFConfig.compute_dtype)
        dt = model.executor.compute_dtype
        cast = make_cast(jnp, dt)
        unstack = model.executor.unstack_tree

        def ln(p, x):
            return layer_norm(jax, jnp, p, x, eps)

        def step(params, cache_k, cache_v, tok, t):
            # tok (B,) int32; t () int32; caches (L, B, H, S, D)
            self._trace_count += 1  # traced once; calls replay the jit
            # per-layer view of scan-stacked chains, then cast-at-use
            params = jax.tree.map(cast, unstack(params))
            x = params["tok_embed"]["kernel"][tok]  # (B, hidden)
            x = x + params["pos_embed"]["value"][t]
            mask = (jnp.arange(S) <= t)[None, None, :]
            for i in range(L):
                p_at = params[f"dec{i}_attn"]
                h = ln(params[f"dec{i}_ln0"], x)
                q = h @ p_at["wq"]
                k = h @ p_at["wk"]
                v = h @ p_at["wv"]
                if has_bias:
                    q, k, v = q + p_at["bq"], k + p_at["bk"], v + p_at["bv"]
                q = q.reshape(B, H, D)
                k = k.reshape(B, H, 1, D)
                v = v.reshape(B, H, 1, D)
                cache_k = jax.lax.dynamic_update_slice(
                    cache_k, k[None], (i, 0, 0, t, 0)
                )
                cache_v = jax.lax.dynamic_update_slice(
                    cache_v, v[None], (i, 0, 0, t, 0)
                )
                # scores as multiply+reduce, NOT dot_general: the batched
                # prefill computes the same contraction with a P dim in
                # the operands, and XLA's dot kernels accumulate
                # differently across those shapes (1-ulp drift) while the
                # fused mul+sum lowers identically — this is what makes
                # prefill-vs-step bit-identity hold (tests/test_serve.py)
                scores = (q[:, :, None, :] * cache_k[i]).sum(-1) * scale
                scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
                w = jax.nn.softmax(scores, axis=-1)
                o = jnp.einsum("bhs,bhsd->bhd", w, cache_v[i])
                o = o.reshape(B, H * D) @ p_at["wo"]
                if has_bias:
                    o = o + p_at["bo"]
                x = x + o
                h = ln(params[f"dec{i}_ln1"], x)
                p0, p1 = params[f"dec{i}_ff0"], params[f"dec{i}_ff1"]
                f = jax.nn.gelu(h @ p0["kernel"] + p0["bias"])
                f = f @ p1["kernel"] + p1["bias"]
                x = x + f
            # barrier before the head: pins the SAME fusion boundary in
            # step and prefill, so the trailing ln+head+softmax (identical
            # shapes in both) compiles identically — without it XLA fuses
            # the last FFN into the head differently per program and bf16
            # probs drift by an ulp (the prefill parity tests pin this)
            x = jax.lax.optimization_barrier(x)
            x = ln(params["final_ln"], x)
            logits = x @ params["lm_head"]["kernel"]
            # probabilities in float32, like the executor's fp32 loss head
            probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
            return probs, cache_k, cache_v

        def prefill(params, cache_k, cache_v, toks, start):
            # toks (B, P) int32, start () int32 — ALL P rows in one call.
            # Per row this is exactly ``step`` at t = start + p: same
            # cache layout, same S-wide ``iota <= t`` mask (masked lanes
            # get weight exactly 0.0, and 0.0 * v sums are exact), same
            # cast points — so cache contents and the last row's probs
            # are bit-identical to the per-token loop (pinned in tests).
            P = toks.shape[1]
            params = jax.tree.map(cast, unstack(params))
            pos = start + jnp.arange(P)  # (P,)
            x = params["tok_embed"]["kernel"][toks]  # (B, P, hidden)
            x = x + params["pos_embed"]["value"][pos]
            # mask[p, s]: key position s visible to query row p, shaped
            # (1, P, 1, S) against the (B, P, H, S) score tensor
            mask = (jnp.arange(S)[None, :] <= pos[:, None])[None, :, None, :]
            for i in range(L):
                p_at = params[f"dec{i}_attn"]
                h = ln(params[f"dec{i}_ln0"], x)
                q = h @ p_at["wq"]
                k = h @ p_at["wk"]
                v = h @ p_at["wv"]
                if has_bias:
                    q, k, v = q + p_at["bq"], k + p_at["bk"], v + p_at["bv"]
                q = q.reshape(B, P, H, D)
                # cache layout (L, B, H, S, D): one contiguous P-wide write
                k = k.reshape(B, P, H, D).transpose(0, 2, 1, 3)
                v = v.reshape(B, P, H, D).transpose(0, 2, 1, 3)
                cache_k = jax.lax.dynamic_update_slice(
                    cache_k, k[None], (i, 0, 0, start, 0)
                )
                cache_v = jax.lax.dynamic_update_slice(
                    cache_v, v[None], (i, 0, 0, start, 0)
                )
                # same mul+reduce contraction as ``step`` (see note there):
                # (B,P,H,1,D)*(B,1,H,S,D) -> sum over D -> (B,P,H,S)
                scores = (
                    q[:, :, :, None, :] * cache_k[i][:, None]
                ).sum(-1) * scale
                scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
                w = jax.nn.softmax(scores, axis=-1)
                o = jnp.einsum("bphs,bhsd->bphd", w, cache_v[i])
                o = o.reshape(B, P, H * D) @ p_at["wo"]
                if has_bias:
                    o = o + p_at["bo"]
                x = x + o
                h = ln(params[f"dec{i}_ln1"], x)
                p0, p1 = params[f"dec{i}_ff0"], params[f"dec{i}_ff1"]
                f = jax.nn.gelu(h @ p0["kernel"] + p0["bias"])
                f = f @ p1["kernel"] + p1["bias"]
                x = x + f
            # only the LAST prompt row's distribution feeds generation —
            # skip the (P-1) dead vocab matmuls.  The barrier (see step)
            # also keeps the row slice from back-fusing into the decoder
            # stack, which would regroup the last FFN's accumulation.
            x = jax.lax.optimization_barrier(x)
            x = ln(params["final_ln"], x[:, -1])
            logits = x @ params["lm_head"]["kernel"]
            probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
            return probs, cache_k, cache_v

        # donate the caches: XLA reuses their buffers for the in-place
        # dynamic_update_slice instead of copying (L*B*H*S*D*2 floats)
        self._step = jax.jit(step, donate_argnums=(1, 2))
        # one compiled prefill per distinct prompt length P (static shape)
        self._prefill = jax.jit(prefill, donate_argnums=(1, 2))
        self._dtype = dt
        self._cache_shape = (L, B, H, S, D)
        ck = jnp.zeros(self._cache_shape, dt)
        cv = jnp.zeros(self._cache_shape, dt)
        # warmup: the step's OUTPUT cache layout/sharding can differ from
        # a fresh jnp.zeros (params may be mesh-sharded), which would cost
        # one extra trace on the second call — stabilize it here and pin
        # the sharding so every real step replays ONE compiled program
        tok0 = jnp.zeros((B,), jnp.int32)
        _, ck, cv = self._step(
            model.executor.params, ck, cv, tok0, jnp.asarray(0, jnp.int32)
        )
        _, ck, cv = self._step(
            model.executor.params, ck, cv, tok0, jnp.asarray(0, jnp.int32)
        )
        self._cache_sharding = (ck.sharding, cv.sharding)
        self._jax = jax
        self._jnp = jnp
        self.reset()
        self._trace_count = 0  # warmup traces don't count

    def reset(self) -> None:
        jax, jnp = self._jax, self._jnp
        sk, sv = self._cache_sharding
        self.cache_k = jax.device_put(
            jnp.zeros(self._cache_shape, self._dtype), sk
        )
        self.cache_v = jax.device_put(
            jnp.zeros(self._cache_shape, self._dtype), sv
        )

    def step(self, tok: np.ndarray, t: int) -> np.ndarray:
        """Feed token ``tok`` (B,) at position ``t``; returns next-token
        probabilities (B, vocab).  O(S_max) per call, prefix-independent."""
        import jax.numpy as jnp

        # dynamic_update_slice CLAMPS out-of-range starts — an oversized t
        # would silently overwrite position seq-1 instead of erroring
        assert 0 <= int(t) < self.seq, (
            f"position {t} outside the compiled sequence length {self.seq}"
        )
        probs, self.cache_k, self.cache_v = self._step(
            self.model.executor.params, self.cache_k, self.cache_v,
            jnp.asarray(tok, jnp.int32), jnp.asarray(t, jnp.int32),
        )
        return probs

    def prefill(self, toks: np.ndarray, start: int = 0) -> np.ndarray:
        """Feed ``toks`` (B, P) at positions ``start..start+P-1`` in ONE
        batched call (the phase-separated prompt ingestion — replaces P
        :meth:`step` dispatches); returns next-token probabilities
        (B, vocab) after the last row.  Each distinct P compiles once;
        the caches come back pinned to the session's sharding so the
        decode step's no-retrace guarantee survives a prefill."""
        import jax.numpy as jnp

        toks = jnp.asarray(toks, jnp.int32)
        assert toks.ndim == 2 and toks.shape[0] == self.batch, toks.shape
        P = toks.shape[1]
        assert P >= 1 and 0 <= int(start) and int(start) + P <= self.seq, (
            f"prefill [{start}, {start + P}) outside the compiled "
            f"sequence length {self.seq}"
        )
        probs, ck, cv = self._prefill(
            self.model.executor.params, self.cache_k, self.cache_v,
            toks, jnp.asarray(start, jnp.int32),
        )
        sk, sv = self._cache_sharding
        self.cache_k = self._jax.device_put(ck, sk)
        self.cache_v = self._jax.device_put(cv, sv)
        return probs


def gpt_generate_cached(
    model,
    prompt_ids,
    max_new_tokens: int,
    temperature: float = 0.0,
    seed: int = 0,
    session: GPTDecodeSession | None = None,
    top_k: int = 0,
    top_p: float = 1.0,
    batched_prefill: bool = True,
) -> Tuple[np.ndarray, GPTDecodeSession]:
    """Cache-carrying generation — same contract as
    :func:`flexflow_tpu.models.transformer.gpt_generate` (greedy at
    temperature 0, softmax sampling otherwise) but each step costs
    O(S_max), not a full-prefix forward.  Returns ``(ids, session)``;
    pass ``session`` back in to reuse the compiled step across calls.

    ``batched_prefill=True`` (default) ingests the whole prompt in ONE
    :meth:`GPTDecodeSession.prefill` call; ``False`` keeps the original
    token-at-a-time warmup loop (the two are bit-identical — pinned by
    tests/test_serve.py — so the flag exists for that pin and for
    A/B-ing dispatch counts, not because outputs differ).
    """
    assert session is None or session.model is model, (
        "session was built for a different model"
    )
    sess = session or GPTDecodeSession(model)
    sess.reset()
    p = np.asarray(prompt_ids, np.int32)
    batch, start = p.shape
    assert batch == sess.batch, (batch, sess.batch)
    end = start + max_new_tokens
    assert 1 <= start and end <= sess.seq, (
        f"prompt_len + max_new_tokens = {end} exceeds the compiled "
        f"sequence length {sess.seq}"
    )
    out = np.zeros((batch, end), np.int32)
    out[:, :start] = p
    rng = np.random.default_rng(seed)
    if batched_prefill:
        probs = sess.prefill(p, 0)
    else:
        probs = None
        for t in range(start):  # prefill: feed prompt tokens one at a time
            probs = sess.step(out[:, t], t)
    from flexflow_tpu.models.transformer import sample_next

    for t in range(start, end):
        nxt = sample_next(
            np.asarray(probs), temperature, rng, top_k=top_k, top_p=top_p
        )
        out[:, t] = nxt
        if t + 1 < end:
            probs = sess.step(nxt, t)
    return out, sess
