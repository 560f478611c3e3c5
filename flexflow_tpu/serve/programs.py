"""The serve programs: ONE decoder trunk behind decode, prefill, draft
and verify (docs/SERVING.md).

All four are one forward over the paged K/V pools
``(L, num_blocks * block_size, H * D)``: ``G`` consecutive positions a
lane (row ``g`` of lane ``b`` at ``start[b] + g``) are embedded, run
through ``layers`` unrolled blocks that write the rows' K/V into the
pools and attend over each lane's pages, and a head turns rows into
their argmax (and, where sampling asks, a float32 distribution).  What a
block IS comes from the decoder spec (``models/gpt_decode.py::GPTSpec``,
read off the compiled model's layers): a layer is a SEQUENCE OF RESIDUAL
BRANCHES ``x + [norm](mixer(norm(x)))`` -- two in GPT-2 and Trinity (an
attention and an FFN), one in Nemotron-H -- LayerNorm or RMSNorm; the
mixer an attention with one head count or grouped K/V heads, with or
without per-head q/k norm, rotary positions by absolute position and a
sigmoid output gate, seeing every earlier key or a window (then the
layer lives in the pool's WINDOW group and its walk starts at the first
position a row still sees); an FFN: GELU, gated dense or routed experts
(``ops/moe.py``'s sorted rows and grouped matmuls, dropless, gated or
``relu2``, all held or the share the layer's own ``first_expert`` and
weights say -- the router stays as wide as published and what absent
experts would add is left out; rows of idle lanes and past ``n_valid``
routed nowhere); or a Mamba-2 state-space mixer
(``ops/ssm.py::mamba2_mixer``, the op's own function) whose two states
live in the pool's STATE group, a row a slot: a decode step is one step
of the recurrence, a prefill chunk the chunked scan from the slot's
state (zero where the chunk starts at position 0), the arrays donated
and updated in place, rows past ``n_valid`` and idle lanes changing
neither.  They differ in rows a lane
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
cache), their arguments ``(params, ck, cv[, sk, sv][, wk, wv][, conv
states, ssm states], ...)`` and outputs ``(nxt, probs | None, [moe
stats,] ck, cv[, sk, sv][, wk, wv][, conv states, ssm states])``
(analysis/capture.py, the benchmark's tests),
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

__all__ = ["ServePrograms", "build_serve_programs", "MOE_STATS"]

# weights an op declares float32 stay so in a program: the router's,
# and a state-space layer's decay, step bias and skip
KEEP_F32 = ("router", "router_bias")
KEEP_F32_STATE = ("A_log", "dt_bias", "D")


def keep_float32(spec: GPTSpec):
    """``(layer name, weight name)`` of every leaf the programs take in
    float32 whatever the compute dtype."""
    kept = {"moe": KEEP_F32, "mamba2": KEEP_F32_STATE}
    return [
        (br.mixer[0], w) for br in spec.branches for w in kept.get(br.kind, ())
    ]

# what a program of a model with routed experts returns after its
# tokens, one float32 each, summed over the call's expert layers
MOE_STATS = ("rows", "experts_touched", "load_max_over_mean", "layer_calls")


class ServePrograms(NamedTuple):
    """What :func:`build_serve_programs` hands the engine."""

    decode: Callable  # (params, *pools, tok, pos, bt) -> nxt, probs, *pools
    prefill: Callable  # (.., toks, start, n_valid, bt) -> nxt, probs, *pools
    draft: Optional[Callable]  # (.., tok, pos, bt) -> nxt, *pools
    verify: Optional[Callable]  # (.., toks, pos0, bt) -> n, acc, cur, pos, *pools
    params_arg: Any  # what every program takes as ``params``
    donate: Tuple[int, ...]  # the pools' (and scale pools') positions
    n_head: int  # outputs of decode / prefill in front of the pools


def serve_pass_rows(rows: int) -> int:
    """Sorted expert rows one pass of a serve program takes (``rows`` =
    positions x top-k): all of a decode step's, an eighth of a prefill
    dispatch's -- the passes are a loop of the length the device finds
    (``ops/moe.py::_held_passes``), so a dispatch in which one lane of
    many is mid-prompt pays for the rows it routes, not for ``slots x
    chunk``."""
    return rows if rows <= 8192 else -(-rows // 64) * 8


def weights_as_consumed(executor, spec: GPTSpec):
    """``executor.params`` as the serve programs multiply them: every
    leaf ``prep_params`` would cast (:func:`make_cast`: float32 -> the
    compute dtype where the two differ; the router's leaves stay
    float32) cast ONCE, here, leaf by leaf on the tree as stored -- a
    scan-stacked bucket stays one ``(depth, ...)`` array, the cast is
    elementwise.  A leaf that needs no cast is the executor's own array,
    never a copy, and when none does the result IS ``executor.params``
    (bfloat16 at rest, float32 compute).  Casts are remembered on the
    executor by the source array's identity (``executor.serve_cast``),
    so engines over one model share one cast tree and a second call
    casts only the leaves ``set_weights`` or a training step replaced
    since: :meth:`ServeEngine.run` calls this again before each run."""
    import weakref

    import jax.numpy as jnp

    cast = make_cast(jnp, executor.compute_dtype)
    # by the name a leaf is stored under (a stacked bucket's, or the layer's)
    keep = {
        (loc[1], w) for lname, w in keep_float32(spec)
        if (loc := executor.locate_weight(lname, w)) is not None
    }
    memo = executor.serve_cast  # (bucket, weight) -> (ref(source), cast)
    out, changed = {}, False
    for bname, ws in executor.params.items():
        out[bname] = {}
        for w, src in ws.items():
            hit = memo.get((bname, w))
            if hit is not None and hit[0]() is src:
                leaf = hit[1]
            else:
                leaf = src if (bname, w) in keep else cast(src)
                if leaf is not src:
                    memo[(bname, w)] = (weakref.ref(src), leaf)
            changed |= leaf is not src
            out[bname][w] = leaf
    return out if changed else executor.params


def build_serve_programs(
    model,
    kv: PagedKVCache,
    *,
    attn_kernel: str,
    weight_dtype: str = "fp32",
    spec_k: int = 0,
    spec_draft_layers: int = 0,
    return_probs: bool = True,
) -> ServePrograms:
    """Jit the serve programs of a compiled decoder (anything
    ``GPTSpec.from_model`` reads: ``gpt_decoder``, ``afmoe_decoder``,
    ``nemotron_h_decoder`` -- layers of one or two residual branches,
    attention over the full or the window group, state-space layers over
    the state group, routed experts all held or a share) over the pool
    geometry of ``kv``.  ``draft`` / ``verify``, int8 weights and a
    quantized pool are built for ``gpt_decoder``-shaped models only
    (``ServeEngine`` refuses the rest by name).  ``attn_kernel`` is the engine's
    resolved decision (``paged`` | ``gather``); ``draft`` and ``verify``
    are built only with ``spec_k``; ``return_probs`` false (greedy
    decoding) leaves the float32 distribution out of the outputs
    (``None`` in its place).  ``params_arg``, what every program takes
    first, is the weights as the programs multiply them
    (:func:`weights_as_consumed`): cast here, once, where the compute
    dtype is not the dtype at rest -- ``prep_params`` inside a program
    applies the same rule and finds nothing to do, so a caller may
    still hand ``executor.params`` and get the same bits with the cast
    paid in every call.  The int8 arm's argument is the ``(qparams,
    scales)`` pair.  No serve program is compiled here: each traces at
    its first call."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops.attention import rotate_half_rope_at
    from flexflow_tpu.ops.moe import (
        gated_ffn,
        held_experts_part,
        route_top_k,
        router_rule,
        shared_expert_part,
    )
    from flexflow_tpu.ops.norm import rms_norm_f32, rms_norm_zero_centered
    from flexflow_tpu.ops.ssm import mamba2_mixer
    from flexflow_tpu.ops.pallas.paged_attention import (
        paged_decode_attention,
        paged_kv_write,
        paged_prefill_attention,
    )

    spec = GPTSpec.from_model(model)
    L, H, D = spec.num_layers, spec.heads, spec.head_dim
    KVH = spec.kv_heads
    rep = H // KVH
    B, MB, BS = kv.slots, kv.max_blocks_per_seq, kv.block_size
    SV = MB * BS  # virtual (paged) sequence length
    S_pos = spec.seq  # pos_embed table height
    eps = spec.eps
    scale = 1.0 / math.sqrt(D)
    cdt = model.executor.compute_dtype  # matmul operands, K/V, q
    cast = make_cast(jnp, cdt)
    # the residual stream: the compute dtype for gpt_decoder models (their
    # programs are pinned bit for bit), float32 for the rest -- a stream
    # of |x| up to sqrt(hidden) loses three digits at every bfloat16 add,
    # and a routed layer turns that into another choice of experts.  The
    # norms read it in float32 and hand the matmuls the compute dtype
    rdt = cdt if spec.is_gpt else jnp.float32
    # which pool a branch's memory lives in, and where: a window layer's
    # K/V in the window group's ring, every other attention's in the full
    # group, and a state-space layer's two states in the state group
    where, n_full, n_win, n_state = {}, 0, 0, 0
    for i, ls in enumerate(spec.layers):
        for j, br in enumerate(ls.branches):
            if br.kind == "mamba2":
                where[(i, j)] = ("state", n_state)
                n_state += 1
            elif br.is_attention and br.window:
                where[(i, j)] = ("window", n_win)
                n_win += 1
            elif br.is_attention:
                where[(i, j)] = ("full", n_full)
                n_full += 1
            else:
                where[(i, j)] = None
    assert (n_full, n_win, n_state) == (kv.num_layers, kv.window_layers, kv.state_layers), (
        "the pool's layer groups are not the model's",
        (n_full, n_win, n_state), (kv.num_layers, kv.window_layers, kv.state_layers),
    )
    R = kv.ring_blocks
    # quantized-pool trace-time switch: with ``quant`` the programs
    # take/donate/return the two scale pools beside the K/V pools and
    # every write runs the shared quantize_kv rule
    quant, kvdt = kv.quantized, kv.kv_dtype
    paged = attn_kernel == "paged"
    # weight-only int8: the params ARGUMENT becomes the (qparams, scales)
    # pair and every program folds the scales back first thing — the
    # jitted signature changes, the math after the dequant edge does not
    wq = weight_dtype == "int8"
    assert spec.is_gpt or not (wq or quant or spec_k), (
        "int8 weights, a quantized pool and speculation are built for "
        "gpt_decoder-shaped models (ServeEngine refuses the rest by name)"
    )
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
        # the weights in the dtype the matmuls take them: cast here, once,
        # not inside every call (on this tree ``prep_params``' cast below
        # is the identity and the compiled programs hold no ``convert``
        # of a weight: ``ServeEngine.weight_casts``)
        params_arg = weights_as_consumed(model.executor, spec)
    keep_f32 = keep_float32(spec)

    def prep_params(params):
        if wq:
            qp, qs = params
            params = dequantize_weights_int8(jax, jnp, qp, qs)
        else:
            params = unstack(params)
        out = jax.tree.map(cast, params)
        for lname, w in keep_f32:
            if w in params[lname]:
                out[lname][w] = params[lname][w]
        return out

    if spec.norm == "layer":
        def norm(p, x, dtype=cdt):
            return layer_norm(jax, jnp, p, x, eps).astype(dtype)
    elif spec.norm == "rms":
        def norm(p, x, dtype=cdt):  # statistics in float32
            return rms_norm_f32(x, p["scale"], eps).astype(dtype)
    else:
        def norm(p, x, dtype=cdt):
            return rms_norm_zero_centered(x, p["weight"], eps).astype(dtype)

    def attend(q, keys, vals, mask):
        # q (..., H, D) vs keys/vals (..., H, SV, D); mul+reduce
        # scores — the same contraction form as the dense session
        # (models/gpt_decode.py), so paged and dense decode agree
        # to the ulp the shared formulation allows
        scores = (q[..., None, :] * keys).sum(-1) * scale
        scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
        w = jax.nn.softmax(scores, axis=-1)
        return (w[..., None] * vals).sum(-2)

    def write_kv(ck, cv, sk, sv, i, k, v, start, bt, n_valid, ring=False):
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
        # row ``blk * BS + off``.  ``ring``: the window group's table,
        # logical page j at entry j % R.
        G = k.shape[1]
        if quant or not paged:
            pos = start[:, None] + jnp.arange(G)[None, :]
            page = (pos // BS) % R if ring else jnp.clip(pos // BS, 0, MB - 1)
            blk = bt[jnp.arange(B)[:, None], page]
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
                ck, cv, i, k, v, start, bt, n_valid, block_size=BS, ring=ring,
            )
        else:
            ck = ck.at[i, blk * BS + off].set(k.reshape(B, G, KVH * D))
            cv = cv.at[i, blk * BS + off].set(v.reshape(B, G, KVH * D))
        return ck, cv, sk, sv

    def gather_kv(ck, cv, sk, sv, i, bt):
        # the dense arm's read of layer i: each lane's pages,
        # (B, MB, BS, H, D), as (B, H, SV, D) keys and values in
        # logical position order — a buffer at the full virtual
        # length, which is what the paged kernel exists to delete
        def lanes(pool, sc):
            x = pool[i].reshape(-1, BS, KVH, D)[bt]
            if quant:
                # the kernel's exact dequant rule, pre-gather
                x = x.astype(jnp.float32) * sc[i][bt][..., None, None]
            return x.transpose(0, 3, 1, 2, 4).reshape(B, KVH, -1, D)

        return lanes(ck, sk), lanes(cv, sv)

    def ring_positions(last):
        # the position each row of a lane's ring holds once positions up
        # to ``last`` (B,) are written: the newest p <= last with
        # (p // BS) % R == its page and p % BS == its offset (< 0: none yet)
        r = jnp.arange(R * BS)[None, :]
        page_now = (last // BS)[:, None]
        page = page_now - (page_now - r // BS) % R
        p = page * BS + r % BS
        return jnp.where(p > last[:, None], p - R * BS, p)

    def embed(params, toks, pos):
        # toks / pos (B, G) -> rows (B * G, hidden)
        x = params[spec.embed]["kernel"][toks]
        if spec.pos_embed is not None:
            x = x + params[spec.pos_embed]["value"][jnp.clip(pos, 0, S_pos - 1)]
        x = x.astype(rdt)
        if spec.embed_scale != 1.0:
            x = x * jnp.asarray(spec.embed_scale, x.dtype)
        return x.reshape(-1, x.shape[-1])

    def project(br, p_at, h, pos, G):
        # rows h (B * G, hidden) -> q (B, G, H, D), k, v (B, G, KVH, D),
        # the output gate (B * G, H * D) or None
        if br.kind == "mha":
            q = h @ p_at["wq"]
            k = h @ p_at["wk"]
            v = h @ p_at["wv"]
            if br.has_bias:
                q, k, v = q + p_at["bq"], k + p_at["bk"], v + p_at["bv"]
            return (q.reshape(B, G, H, D), k.reshape(B, G, KVH, D),
                    v.reshape(B, G, KVH, D), None)
        # gated: per head the query's columns, then the gate's
        qg = (h @ p_at["wq"]).reshape(B, G, H, 2 * D)
        q, gate = qg[..., :D], qg[..., D:].reshape(B * G, H * D)
        k = (h @ p_at["wk"]).reshape(B, G, KVH, D)
        v = (h @ p_at["wv"]).reshape(B, G, KVH, D)
        qk_norm = rms_norm_zero_centered if br.qk_norm_zero_centered else rms_norm_f32
        q = qk_norm(q, p_at["q_norm"], br.qk_eps)
        k = qk_norm(k, p_at["k_norm"], br.qk_eps)
        if br.rotary_dim:
            q = rotate_half_rope_at(q, pos, br.rotary_dim, br.rope_theta)
            k = rotate_half_rope_at(k, pos, br.rotary_dim, br.rope_theta)
        return q.astype(h.dtype), k.astype(h.dtype), v, gate

    def experts(br, p, h32, valid):
        # the routed block over rows h32 (T, hidden), the normed stream in
        # float32: the router reads it as it is (an expert choice that
        # flips against the reference moves a whole position's output),
        # the experts in the compute dtype; ``valid`` (T,): rows of idle
        # lanes and past n_valid are routed to no expert
        a = br.attrs
        n, k = a["n_experts"], a["top_k"]
        h = h32.astype(cdt)
        with jax.named_scope("ff.moe.route"):
            w, idx = route_top_k(h32, p["router"], k, **router_rule(a, p))
            idx = jnp.where(valid[:, None], idx, n)
        with jax.named_scope("ff.moe.experts"):
            # the op's own share: the router is n wide, the experts
            # held are those the weights hold, and what the rest would
            # add is left out (no code stands in for their chip)
            out, counts, _, _ = held_experts_part(
                h, w, idx, a["first_expert"], serve_pass_rows(h.shape[0] * k),
                p.get("w_gate"), p["w_up"], p["w_down"],
                a.get("expert_form", "gated"),
            )
        if a["shared_hidden"]:
            with jax.named_scope("ff.moe.shared"):
                out = out + shared_expert_part(a, p, h)
        load = counts.astype(jnp.float32)
        rows = jnp.sum(load)
        stats = jnp.stack([
            rows, jnp.sum(load > 0).astype(jnp.float32),
            jnp.max(load) / jnp.maximum(jnp.mean(load), 1e-9),
            (rows > 0).astype(jnp.float32),
        ])
        return out, stats  # float32

    def attention_branch(br, at, params, x, pools, start, bts, n_valid, G, attn):
        # an attention branch over rows x (B * G, hidden) -> (its output
        # before the residual add, pools)
        group, gi = at
        ring = group == "window"
        bt = bts[1] if ring else bts[0]
        ck, cv, sk, sv, wk, wv = pools[:6]
        pk, pv = (wk, wv) if ring else (ck, cv)
        p_at = params[br.mixer[0]]
        pos = start[:, None] + jnp.arange(G)[None, :]
        with jax.named_scope("ff.attn_window" if ring else "ff.attn_full"):
            h = norm(params[br.norm_in], x)
            q, k, v, gate = project(br, p_at, h, pos, G)
            # write all G rows, THEN attend: row g's mask reaches rows 0..g
            # of this same program, freshly written — and under prefix
            # sharing a chunk never writes a still-shared block (commit
            # happens post-chunk, CoW-audited by serve_cow)
            pk, pv, sk, sv = write_kv(
                pk, pv, sk, sv, gi, k, v, start, bt, n_valid, ring=ring,
            )
            if paged:
                # fused paged attention (docs/PERF.md), one call for all G
                # rows: the kernel walks each lane's block table in SMEM and
                # its visible-page clamp fetches ceil((start + G) / BS) pages
                # a lane (from the first page a row still sees on, behind a
                # window) — no dense gather, no (H, SV, D) buffer in the
                # lowered program (ffcheck ``paged_attn``).  Same mask rule
                # as ``attend``, online softmax in f32: it agrees to a
                # float32 tolerance and the greedy argmax streams are
                # identical (pinned by tests/test_paged_attention.py)
                o = attn(
                    q, pk, pv, start, bt, scale=scale,
                    scale_k=sk, scale_v=sv, layer=gi, block_size=BS,
                    window=br.window,
                )
            else:
                keys, vals = gather_kv(pk, pv, sk, sv, gi, bt)
                if ring:
                    k_pos = ring_positions(start + G - 1)[:, None, :]  # (B, 1, R*BS)
                    mask = (
                        (k_pos <= pos[..., None]) & (k_pos >= 0)
                        & (k_pos > pos[..., None] - br.window)
                    )[:, :, None, :]
                else:
                    mask = (
                        jnp.arange(SV)[None, None, :] <= pos[..., None]
                    )[:, :, None, :]  # (B, G, 1, SV)
                if rep > 1:
                    keys = jnp.repeat(keys, rep, axis=1)
                    vals = jnp.repeat(vals, rep, axis=1)
                o = attend(q, keys[:, None], vals[:, None], mask)
            o = o.reshape(B * G, H * D)
            if gate is not None:
                o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)
            o = o @ p_at["wo"]
            if br.has_bias:
                o = o + p_at["bo"]
            if br.norm_post is not None:
                o = norm(params[br.norm_post], o, rdt)
        kvs = (ck, cv, sk, sv, pk, pv) if ring else (pk, pv, sk, sv, wk, wv)
        return o, kvs + pools[6:]

    def live_rows(bts, n_valid, G):
        # (B, G) bool: the rows that belong to a request.  A decode-width
        # program has no n_valid: a live lane holds a reservation, so its
        # first page is not the trash block
        if n_valid is None:
            return jnp.broadcast_to(bts[0][:, :1] > 0, (B, G))
        return jnp.arange(G)[None, :] < n_valid[:, None]

    def state_branch(br, si, params, x, pools, start, bts, n_valid, G):
        # a state-space branch (ops/ssm.py::mamba2_mixer, the op's own
        # function) from layer si's two states, which it hands back: a
        # lane whose chunk starts at position 0 reads them as zero (a
        # slot is recycled without touching the device), rows past
        # n_valid change neither, and a lane with no live row keeps both
        # bit for bit
        conv, ssm = pools[6][si], pools[7][si]
        if n_valid is None:  # decode width: a live lane's one row
            n_live = (bts[0][:, 0] > 0).astype(jnp.int32)
            conv_in, ssm_in = conv, ssm
        else:
            n_live = n_valid
            fresh = (start == 0) & (n_live > 0)
            conv_in = jnp.where(fresh[:, None, None], jnp.zeros_like(conv), conv)
            ssm_in = jnp.where(fresh[:, None, None, None], jnp.zeros_like(ssm), ssm)
        h = norm(params[br.norm_in], x).reshape(B, G, -1)
        o, conv_out, ssm_out = mamba2_mixer(
            params[br.mixer[0]], h, br.attrs, conv_in, ssm_in, n_live,
        )
        ssm_out = jnp.where((n_live > 0)[:, None, None, None], ssm_out, ssm)
        o = o.reshape(B * G, -1)
        if br.norm_post is not None:
            o = norm(params[br.norm_post], o, rdt)
        convs, ssms = list(pools[6]), list(pools[7])
        convs[si], ssms[si] = conv_out.astype(conv.dtype), ssm_out
        return o, pools[:6] + (tuple(convs), tuple(ssms))

    def ffn_branch(br, params, x, stats, bts, n_valid, G):
        moe = br.kind == "moe"
        h = norm(params[br.norm_in], x, jnp.float32 if moe else cdt)
        if br.kind == "gelu":
            p0, p1 = params[br.mixer[0]], params[br.mixer[1]]
            f = jax.nn.gelu(h @ p0["kernel"] + p0["bias"])
            f = f @ p1["kernel"] + p1["bias"]
        elif br.kind == "gated_ffn":
            p0 = params[br.mixer[0]]
            with jax.named_scope("ff.ffn_dense"):
                f = gated_ffn(h, p0["w_gate"], p0["w_up"], p0["w_down"])
        else:
            valid = live_rows(bts, n_valid, G)
            f, st = experts(br, params[br.mixer[0]], h, valid.reshape(-1))
            stats = stats + st
        if br.norm_post is not None:
            f = norm(params[br.norm_post], f, rdt)
        return f, stats

    def block(i, params, x, pools, stats, start, bts, n_valid, G, attn):
        # layer i over rows x (B * G, hidden): its residual branches, in order
        for j, br in enumerate(spec.layers[i].branches):
            at = where[(i, j)]
            if br.is_attention:
                f, pools = attention_branch(
                    br, at, params, x, pools, start, bts, n_valid, G, attn)
            elif br.kind == "mamba2":
                f, pools = state_branch(
                    br, at[1], params, x, pools, start, bts, n_valid, G)
            else:
                f, stats = ffn_branch(br, params, x, stats, bts, n_valid, G)
            x = x + f.astype(rdt)
        return x, pools, stats

    def trunk(params, pools, toks, start, bt, *, layers, n_valid=None, attn):
        # toks (B, G) int32, start / n_valid (B,), bt (B, MB) block
        # tables — with a window group the pair (bt, ring tables (B, R))
        # -> rows (B * G, hidden) after ``layers`` blocks, pools, stats
        G = toks.shape[1]
        bts = bt if isinstance(bt, (tuple, list)) else (bt, None)
        x = embed(params, toks, start[:, None] + jnp.arange(G)[None, :])
        stats = jnp.zeros((len(MOE_STATS),), jnp.float32)
        for i in range(layers):
            x, pools, stats = block(
                i, params, x, pools, stats, start, bts, n_valid, G, attn
            )
        # same boundary as the dense session
        return jax.lax.optimization_barrier(x), pools, stats

    def head(params, rows, stats=None):
        x = norm(params[spec.final_norm], rows)
        logits = (x @ params[spec.head]["kernel"]).astype(jnp.float32)
        if return_probs:
            probs = jax.nn.softmax(logits, axis=-1)
            nxt = jnp.argmax(probs, axis=-1).astype(jnp.int32)
        else:
            # greedy: the argmax is all the host needs, and a
            # (slots, vocab) float32 array does not leave the program
            probs, nxt = None, jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if spec.has_moe and stats is not None:
            return nxt, probs, stats
        return nxt, probs

    def decode(params, pools, tok, pos, bt, layers=L):
        x, pools, stats = trunk(
            params, pools, tok[:, None], pos, bt,
            layers=layers, attn=paged_decode_attention,
        )
        return head(params, x, stats), pools

    def prefill(params, pools, toks, start, n_valid, bt):
        # ALL mid-prefill slots' chunks in ONE dispatch (r20): toks
        # (B, P).  Lanes with n_valid == 0 (no mid-prefill request in
        # that slot) ride with an all-zero table row and write the
        # trash block — the idle-lane discipline at chunk width.  The
        # weight-streaming win: the window streams the decode weights
        # ONCE per chunk-batch instead of once per slot.
        P = toks.shape[1]
        x, pools, stats = trunk(
            params, pools, toks, start, bt,
            layers=L, n_valid=n_valid, attn=paged_prefill_attention,
        )
        # distribution after each lane's LAST VALID row (layer norm
        # is per-row, so select-then-ln == ln-then-select)
        last = jnp.clip(n_valid - 1, 0, P - 1)
        return head(params, x.reshape(B, P, -1)[jnp.arange(B), last], stats), pools

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
        out, pools = decode(
            params, pools, tok, pos, bt, layers=spec_draft_layers
        )
        return (out[0],), pools

    def verify(params, pools, toks, pos0, bt):
        # toks (B, W): [current, draft_1..draft_k]; row j of slot b sits
        # at position pos0[b] + j, and its argmax is the full model's
        # decode step at that position, bit for bit
        x, pools, _ = trunk(
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

    # which of (ck, cv, sk, sv, wk, wv) this engine's programs thread
    have = (True, True, quant, quant, bool(n_win), bool(n_win))
    n_kv = sum(have)
    n_pools = n_kv + 2 * n_state
    donate = tuple(range(1, 1 + n_pools))

    def program(body):
        # the jitted signature of every program: a quantized pool threads
        # its two scale pools right after the K/V pools, a model with
        # window layers its window group's pools after those, one with
        # state layers every layer's conv state and then every layer's
        # ssm state last; all donated and returned in that order
        def run(params, *args):
            given = iter(args[:n_kv])
            pools = tuple(next(given) if h else None for h in have) + (
                tuple(args[n_kv:n_kv + n_state]), tuple(args[n_kv + n_state:n_pools]),
            )
            outs, pools = body(prep_params(params), pools, *args[n_pools:])
            return (*outs, *(x for x, h in zip(pools, have) if h), *pools[6], *pools[7])

        run.__name__ = run.__qualname__ = body.__name__
        return jax.jit(run, donate_argnums=donate)

    return ServePrograms(
        decode=program(decode),
        prefill=program(prefill),
        draft=program(draft) if spec_k else None,
        verify=program(verify) if spec_k else None,
        params_arg=params_arg,
        donate=donate,
        n_head=3 if spec.has_moe else 2,
    )
