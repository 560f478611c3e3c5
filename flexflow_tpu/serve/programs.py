"""The serve programs: ONE decoder trunk behind decode, prefill, draft
and verify (docs/SERVING.md).

All four are one forward over the paged K/V pools
``(L, num_blocks * block_size, H * D)``: ``G`` consecutive positions a
lane (row ``g`` of lane ``b`` at ``start[b] + g``) are embedded, run
through ``layers`` unrolled blocks that write the rows' K/V into the
pools and attend over each lane's pages, and a head turns rows into a
float32 distribution and its argmax.  They differ in rows a lane
(``G`` = 1, ``P``, 1, ``k + 1``), layers run (all, or the first
``spec_draft_layers``), whether ``n_valid`` masks padded rows, and
which rows reach the head.  Inactive lanes carry an all-zero table row,
so their writes land in the trash block (kvcache.py): no masking, no
recompile when the active set changes.  Every matmul runs 2-D at
``(B * G, ...)``, so a row's arithmetic is the same in every program,
bit for bit (the bit-identity tests pin it).  No ``lax.scan`` and no
``jax.jit`` boundary around the block: a scan over depth is another
program with another compile time (ROADMAP S4).

What reads the programs from outside, and so may not move: the jitted
functions' names (``jit_decode`` ... in a trace and in the compile
cache), their arguments ``(params, ck, cv[, sk, sv], ...)`` and outputs
``(..., ck, cv[, sk, sv])`` (analysis/capture.py, the benchmark's tests),
and the paged kernel's two names — ``prefill`` calls it as
``%prefill.N``, the other three as ``%decode.N``, which is how the
benchmark's ``paged_attention_roofline.*`` tells a chunk's call from a
decode-width one: ``trunk`` takes the entry point to call.
"""

from __future__ import annotations

import math
from typing import Any, Callable, NamedTuple, Optional, Tuple

from flexflow_tpu.models.gpt_decode import (
    GPTSpec,
    dequantize_weights_int8,
    layer_norm,
    make_cast,
    quantize_weights_int8,
)
from flexflow_tpu.serve.kvcache import PagedKVCache, quantize_kv

__all__ = ["ServePrograms", "build_serve_programs"]


class ServePrograms(NamedTuple):
    """What :func:`build_serve_programs` hands the engine."""

    decode: Callable  # (params, *pools, tok, pos, bt) -> nxt, probs, *pools
    prefill: Callable  # (.., toks, start, n_valid, bt) -> nxt, probs, *pools
    draft: Optional[Callable]  # (.., tok, pos, bt) -> nxt, *pools
    verify: Optional[Callable]  # (.., toks, pos0, bt) -> n, acc, cur, pos, *pools
    params_arg: Any  # what every program takes as ``params``
    donate: Tuple[int, ...]  # the pools' (and scale pools') positions


def build_serve_programs(
    model,
    kv: PagedKVCache,
    *,
    attn_kernel: str,
    weight_dtype: str = "fp32",
    spec_k: int = 0,
    spec_draft_layers: int = 0,
) -> ServePrograms:
    """Jit the serve programs of a compiled ``gpt_decoder`` model over
    the pool geometry of ``kv``.  ``attn_kernel`` is the engine's
    resolved decision (``paged`` | ``gather``); ``draft`` and ``verify``
    are built only with ``spec_k``.  Nothing is compiled here: each
    program traces at its first call."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops.pallas.paged_attention import (
        paged_decode_attention,
        paged_kv_write,
        paged_prefill_attention,
    )

    spec = GPTSpec.from_model(model)
    L, H, D = spec.num_layers, spec.heads, spec.head_dim
    B, MB, BS = kv.slots, kv.max_blocks_per_seq, kv.block_size
    SV = MB * BS  # virtual (paged) sequence length
    S_pos = spec.seq  # pos_embed table height
    has_bias, eps = spec.has_bias, spec.eps
    scale = 1.0 / math.sqrt(D)
    cast = make_cast(jnp, model.executor.compute_dtype)
    # quantized-pool trace-time switch: with ``quant`` the programs
    # take/donate/return the two scale pools beside the K/V pools and
    # every write runs the shared quantize_kv rule
    quant, kvdt = kv.quantized, kv.kv_dtype
    paged = attn_kernel == "paged"
    # weight-only int8: the params ARGUMENT becomes the (qparams, scales)
    # pair and every program folds the scales back first thing — the
    # jitted signature changes, the math after the dequant edge does not
    wq = weight_dtype == "int8"
    # the programs index params by LAYER name; a model whose blocks the
    # executor scan-stacked (--stack-blocks, any chain of depth >= 4
    # under "auto") stores one (depth, ...) array per template layer.
    # The per-layer view is taken INSIDE the programs (static slices XLA
    # reads in place, no second copy of the weights at rest); int8
    # quantizes that view on the host, so scales stay per layer
    unstack = model.executor.unstack_tree
    if wq:
        params_arg = quantize_weights_int8(jnp, unstack(model.executor.params))
    else:
        params_arg = model.executor.params

    def prep_params(params):
        if wq:
            qp, qs = params
            params = dequantize_weights_int8(jax, jnp, qp, qs)
        else:
            params = unstack(params)
        return jax.tree.map(cast, params)

    def ln(p, x):
        return layer_norm(jax, jnp, p, x, eps)

    def attend(q, keys, vals, mask):
        # q (..., H, D) vs keys/vals (..., H, SV, D); mul+reduce
        # scores — the same contraction form as the dense session
        # (models/gpt_decode.py), so paged and dense decode agree
        # to the ulp the shared formulation allows
        scores = (q[..., None, :] * keys).sum(-1) * scale
        scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
        w = jax.nn.softmax(scores, axis=-1)
        return (w[..., None] * vals).sum(-2)

    def write_kv(ck, cv, sk, sv, i, k, v, start, bt, n_valid):
        # THE write of a chunk's new K/V into layer i of the pools: k / v
        # are (B, G, H, D), row g of lane b sits at position
        # start[b] + g, and rows at or past n_valid[b] (the padded tail
        # of a prefill chunk, a whole padded lane) belong to the trash
        # block.  It rides the engine's attention decision
        # (``ServeEngine.kv_write``): paged programs write through the
        # Pallas page-write kernel on the aliased pools, so that both
        # users of the pool want it in ONE layout (an XLA scatter wants
        # a third, and cost two more re-layouts of a layer, every
        # layer); gather programs keep the XLA scatter, which is in
        # place on the CPU.  A quantized pool stores ints plus a
        # per-position scale; the (L, NB, BS) scale pools are small and
        # scatter on adjacent index dimensions either way.  A pool row
        # is one position, all heads: block ``blk`` row ``off`` is pool
        # row ``blk * BS + off``.
        G = k.shape[1]
        if quant or not paged:
            pos = start[:, None] + jnp.arange(G)[None, :]
            blk = bt[
                jnp.arange(B)[:, None], jnp.clip(pos // BS, 0, MB - 1)
            ]
            off = jnp.clip(pos % BS, 0, BS - 1)
            if n_valid is not None:
                valid = jnp.arange(G)[None, :] < n_valid[:, None]
                blk = jnp.where(valid, blk, 0)
                off = jnp.where(valid, off, 0)
        if quant:
            k, ksc = quantize_kv(jnp, k, kvdt)  # scales (B, G)
            v, vsc = quantize_kv(jnp, v, kvdt)
            sk = sk.at[i, blk, off].set(ksc)
            sv = sv.at[i, blk, off].set(vsc)
        if paged:
            ck, cv = paged_kv_write(
                ck, cv, i, k, v, start, bt, n_valid, block_size=BS
            )
        else:
            ck = ck.at[i, blk * BS + off].set(k.reshape(B, G, H * D))
            cv = cv.at[i, blk * BS + off].set(v.reshape(B, G, H * D))
        return ck, cv, sk, sv

    def gather_kv(ck, cv, sk, sv, i, bt):
        # the dense arm's read of layer i: each lane's pages,
        # (B, MB, BS, H, D), as (B, H, SV, D) keys and values in
        # logical position order — a buffer at the full virtual
        # length, which is what the paged kernel exists to delete
        def lanes(pool, sc):
            x = pool[i].reshape(-1, BS, H, D)[bt]
            if quant:
                # the kernel's exact dequant rule, pre-gather
                x = x.astype(jnp.float32) * sc[i][bt][..., None, None]
            return x.transpose(0, 3, 1, 2, 4).reshape(B, H, SV, D)

        return lanes(ck, sk), lanes(cv, sv)

    def embed(params, toks, pos):
        # toks / pos (B, G) -> rows (B * G, hidden)
        x = params["tok_embed"]["kernel"][toks]
        x = x + params["pos_embed"]["value"][jnp.clip(pos, 0, S_pos - 1)]
        return x.reshape(-1, x.shape[-1])

    def block(i, params, x, pools, start, bt, n_valid, G, attn):
        # layer i over rows x (B * G, hidden)
        p_at = params[f"dec{i}_attn"]
        h = ln(params[f"dec{i}_ln0"], x)
        q = h @ p_at["wq"]
        k = h @ p_at["wk"]
        v = h @ p_at["wv"]
        if has_bias:
            q, k, v = q + p_at["bq"], k + p_at["bk"], v + p_at["bv"]
        q = q.reshape(B, G, H, D)
        # write all G rows, THEN attend: row g's mask reaches rows 0..g
        # of this same program, freshly written — and under prefix
        # sharing a chunk never writes a still-shared block (commit
        # happens post-chunk, CoW-audited by serve_cow)
        ck, cv, sk, sv = write_kv(
            *pools, i, k.reshape(B, G, H, D), v.reshape(B, G, H, D),
            start, bt, n_valid,
        )
        if paged:
            # fused paged attention (docs/PERF.md), one call for all G
            # rows: the kernel walks each lane's block table in SMEM and
            # its visible-page clamp fetches ceil((start + G) / BS) pages
            # a lane — no dense gather, no (H, SV, D) buffer in the
            # lowered program (ffcheck ``paged_attn``).  Same mask rule
            # as ``attend``, online softmax in f32: it agrees to a
            # float32 tolerance and the greedy argmax streams are
            # identical (pinned by tests/test_paged_attention.py)
            o = attn(
                q, ck, cv, start, bt, scale=scale,
                scale_k=sk, scale_v=sv, layer=i, block_size=BS,
            )
        else:
            pos = start[:, None] + jnp.arange(G)[None, :]
            mask = (
                jnp.arange(SV)[None, None, :] <= pos[..., None]
            )[:, :, None, :]  # (B, G, 1, SV)
            keys, vals = gather_kv(ck, cv, sk, sv, i, bt)
            o = attend(q, keys[:, None], vals[:, None], mask)
        o = o.reshape(B * G, H * D) @ p_at["wo"]
        if has_bias:
            o = o + p_at["bo"]
        x = x + o
        h = ln(params[f"dec{i}_ln1"], x)
        p0, p1 = params[f"dec{i}_ff0"], params[f"dec{i}_ff1"]
        f = jax.nn.gelu(h @ p0["kernel"] + p0["bias"])
        f = f @ p1["kernel"] + p1["bias"]
        return x + f, (ck, cv, sk, sv)

    def trunk(params, pools, toks, start, bt, *, layers, n_valid=None, attn):
        # toks (B, G) int32, start / n_valid (B,), bt (B, MB) block
        # tables -> rows (B * G, hidden) after ``layers`` blocks, pools
        G = toks.shape[1]
        x = embed(params, toks, start[:, None] + jnp.arange(G)[None, :])
        for i in range(layers):
            x, pools = block(i, params, x, pools, start, bt, n_valid, G, attn)
        # same boundary as the dense session
        return jax.lax.optimization_barrier(x), pools

    def head(params, rows):
        x = ln(params["final_ln"], rows)
        logits = x @ params["lm_head"]["kernel"]
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        return jnp.argmax(probs, axis=-1).astype(jnp.int32), probs

    def decode(params, pools, tok, pos, bt, layers=L):
        x, pools = trunk(
            params, pools, tok[:, None], pos, bt,
            layers=layers, attn=paged_decode_attention,
        )
        return head(params, x), pools

    def prefill(params, pools, toks, start, n_valid, bt):
        # ALL mid-prefill slots' chunks in ONE dispatch (r20): toks
        # (B, P).  Lanes with n_valid == 0 (no mid-prefill request in
        # that slot) ride with an all-zero table row and write the
        # trash block — the idle-lane discipline at chunk width.  The
        # weight-streaming win: the window streams the decode weights
        # ONCE per chunk-batch instead of once per slot.
        P = toks.shape[1]
        x, pools = trunk(
            params, pools, toks, start, bt,
            layers=L, n_valid=n_valid, attn=paged_prefill_attention,
        )
        # distribution after each lane's LAST VALID row (layer norm
        # is per-row, so select-then-ln == ln-then-select)
        last = jnp.clip(n_valid - 1, 0, P - 1)
        return head(params, x.reshape(B, P, -1)[jnp.arange(B), last]), pools

    # --- speculative decoding (docs/SERVING.md): the chain layout makes
    # a depth-Ld draft model a SLICE of the params (layers 0..Ld-1 plus
    # the shared final_ln/lm_head, no second set of weights); verify
    # rewrites ALL layers over W = k+1 positions a slot and computes, ON
    # DEVICE, the longest draft prefix the full model agrees with.  Both
    # return their successors as device arrays, so macro steps chain
    # device-to-device like plain decode: no sync is added.
    def draft(params, pools, tok, pos, bt):
        # decode through the first Ld layers, no probabilities out; the
        # rejected-position K/V this writes is rewritten by whichever
        # program next processes those positions before any row's causal
        # mask can expose it (see SERVING.md)
        (nxt, _), pools = decode(
            params, pools, tok, pos, bt, layers=spec_draft_layers
        )
        return (nxt,), pools

    def verify(params, pools, toks, pos0, bt):
        # toks (B, W): [current, draft_1..draft_k]; row j of slot b sits
        # at position pos0[b] + j, and its argmax is the full model's
        # decode step at that position, bit for bit
        x, pools = trunk(
            params, pools, toks, pos0, bt,
            layers=L, attn=paged_decode_attention,
        )
        n = head(params, x)[0].reshape(toks.shape)
        # accept the longest agreeing prefix: draft j survives iff
        # every draft before it did AND the full model's argmax at
        # its predecessor row reproduces it
        agree = (toks[:, 1:] == n[:, :-1]).astype(jnp.int32)  # (B, k)
        acc = jnp.cumprod(agree, axis=1).sum(axis=1)  # (B,) in [0, k]
        next_cur = n[jnp.arange(B), acc]  # the first token NOT yet fed
        return (n, acc, next_cur, pos0 + acc + 1), pools

    n_pools = 4 if quant else 2
    donate = tuple(range(1, 1 + n_pools))

    def program(body):
        # the jitted signature of every program: a quantized pool threads
        # its two scale pools right after the K/V pools, donated and
        # returned with them
        def run(params, *args):
            pools = args[:n_pools] + (None,) * (4 - n_pools)
            outs, pools = body(prep_params(params), pools, *args[n_pools:])
            return (*outs, *pools[:n_pools])

        run.__name__ = run.__qualname__ = body.__name__
        return jax.jit(run, donate_argnums=donate)

    return ServePrograms(
        decode=program(decode),
        prefill=program(prefill),
        draft=program(draft) if spec_k else None,
        verify=program(verify) if spec_k else None,
        params_arg=params_arg,
        donate=donate,
    )
