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
routed nowhere); or a Mamba-2 or Mamba-1 state-space mixer
(``ops/ssm.py::mamba2_mixer`` / ``mamba1_mixer``, the op's own function)
whose two states live in the pool's STATE group, a row a slot: a decode
step is one step of the recurrence, a prefill chunk the scan from the
slot's state (zero where the chunk starts at position 0), the arrays
donated and updated in place, rows past ``n_valid`` and idle lanes
changing neither; a Mamba-1 layer may export its gated scan output, a
memory the trunk carries beside the stream to the gated memory units
that read it; or lightning attention (``ops/linear_attention.py``, SSD's
functions with a fixed decay a head), a (heads, d, d) state a slot in
the same group with no conv.  A sparse (InfLLM-v2) attention layer
writes a pool layer a K/V head, keeps an index of compressed keys a slot
(the pool's INDEX group), selects its blocks on the device -- a query at
a time in a decode, a block of queries at a time in a prefill -- and has
the kernel walk each unit's selected pages as its table
(``sparse_branch``).  Differential attention rides the kernel with a K/V pair
one pool head and a query pair two heads (``project``); a cross layer
reads its source layer's rows of the full group and writes none.  The
layers after the last one that writes a pool (``GPTSpec.self_layers``)
run, in a prefill, on each lane's last valid row only.  They differ in
rows a lane
(``G`` = 1, ``P``, 1, ``k + 1``), layers run (all, or the first
``spec_draft_layers``), whether ``n_valid`` masks padded rows, and
which rows reach the head.  Inactive lanes carry an all-zero table row,
so their writes land in the trash block (kvcache.py): no masking, no
recompile when the active set changes.  Every matmul runs 2-D at
``(B * G, ...)``, so a row's arithmetic is the same in every program,
bit for bit (the bit-identity tests pin it).  ``B`` is read off the
arrays: prefill runs the trunk over groups of the mid-prompt lanes
(:func:`prefill_group_lanes`), in a loop on the device.  No ``lax.scan`` and no
``jax.jit`` boundary around the block: a scan over depth is another
program with another compile time (ROADMAP S4).

What reads the programs from outside, and so may not move: the jitted
functions' names (``jit_decode`` ... in a trace and in the compile
cache), their arguments ``(params, ck, cv[, sk, sv][, wk, wv][, conv
states, ssm states][, indexes], ...)`` and outputs ``(nxt, probs | logit
| None, [moe stats,] [sparse keys,] ck, cv[, sk, sv][, wk, wv][, conv
states, ssm states][, indexes])``
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
# a state-space layer's decay, step bias and skip, and differential
# attention's lambda vectors
KEEP_F32 = ("router", "router_bias")
KEEP_F32_STATE = ("A_log", "dt_bias", "D")
KEEP_F32_DIFF = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")


def keep_float32(spec: GPTSpec):
    """``(layer name, weight name)`` of every leaf the programs take in
    float32 whatever the compute dtype."""
    kept = {"moe": KEEP_F32, "mamba2": KEEP_F32_STATE, "mamba1": KEEP_F32_STATE,
            "diff": KEEP_F32_DIFF}
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
    positions x top-k of ``slots`` lanes): all of a decode step's, an
    eighth of a prefill dispatch's -- the passes are a loop of the length
    the device finds (``ops/moe.py::_held_passes``), so a dispatch in
    which one lane of many is mid-prompt pays for the rows it routes, not
    for ``slots x chunk``.  A group of a prefill dispatch
    (:func:`prefill_group_lanes`) takes passes of this size too, or all
    its rows in one where they are fewer: a smaller pass would stream the
    held experts' weights once more."""
    return rows if rows <= 8192 else -(-rows // 64) * 8


# rows a group of a prefill dispatch computes at least: about 8 x the
# v5e's ridge point for bfloat16 weights (197 TFLOP/s / 819 GB/s = 240
# rows), where a group's dense products stay compute-bound
PREFILL_GROUP_ROWS = 2048


def prefill_group_lanes(slots: int, chunk: int) -> int:
    """Lanes a prefill dispatch computes at once: the fewest, a power of
    two, whose ``lanes x chunk`` rows reach :data:`PREFILL_GROUP_ROWS`,
    and never more than ``slots``.  At ``slots`` the program is one
    full-width pass as it always was; below it the dispatch loops over
    groups of the mid-prompt lanes, as many as the device finds."""
    lanes = 1
    while lanes * chunk < PREFILL_GROUP_ROWS and lanes < slots:
        lanes *= 2
    return min(lanes, slots)


def prefill_lanes(n_mid, slots: int, chunk: int):
    """Lanes a prefill dispatch with ``n_mid`` mid-prompt lanes computes:
    its groups of :func:`prefill_group_lanes`, padded whole.  The program
    takes its loop's trip count from it (``n_mid`` traced) and the engine
    its counter (``n_mid`` an int), so the two cannot disagree."""
    bp = prefill_group_lanes(slots, chunk)
    return -(-n_mid // bp) * bp


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
    return_logit: bool = False,
) -> ServePrograms:
    """Jit the serve programs of a compiled decoder (anything
    ``GPTSpec.from_model`` reads: ``gpt_decoder``, ``afmoe_decoder``,
    ``nemotron_h_decoder``, ``phi4flash_decoder`` -- layers of one or two residual branches,
    attention over the full or the window group, state-space layers over
    the state group, routed experts all held or a share) over the pool
    geometry of ``kv``.  ``draft`` / ``verify``, int8 weights and a
    quantized pool are built for ``gpt_decoder``-shaped models only
    (``ServeEngine`` refuses the rest by name).  ``attn_kernel`` is the engine's
    resolved decision (``paged`` | ``gather``); ``draft`` and ``verify``
    are built only with ``spec_k``; ``return_probs`` false (greedy
    decoding) leaves the float32 distribution out of the outputs
    (``None`` in its place, or with ``return_logit`` the served token's
    own logit, (slots,) float32).  ``params_arg``, what every program takes
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

    from flexflow_tpu.ops.attention import diff_combine, rotate_half_rope_at
    from flexflow_tpu.ops.linear_attention import lightning_mixer
    from flexflow_tpu.ops.moe import (
        gated_ffn,
        held_experts_part,
        route_top_k,
        router_rule,
        shared_expert_part,
    )
    from flexflow_tpu.ops.norm import rms_norm_f32, rms_norm_zero_centered
    from flexflow_tpu.ops.ssm import gated_memory, mamba1_mixer, mamba2_mixer, state_shapes
    from flexflow_tpu.ops.pallas.paged_attention import (
        paged_decode_attention,
        paged_kv_write,
        paged_prefill_attention,
        paged_sparse_attention,
    )
    from flexflow_tpu.ops.sparse_attention import (
        block_scores,
        kernels_at,
        select_blocks,
        selected_table,
    )

    spec = GPTSpec.from_model(model)
    L, H, D = spec.num_layers, spec.heads, spec.head_dim
    L_self = spec.self_layers  # layers a prefill runs on every row
    KVH = kv.heads  # the pool's K/V heads a row (one a pool layer for sparse layers)
    rep = H // spec.kv_heads
    slots, MB, BS = kv.slots, kv.max_blocks_per_seq, kv.block_size
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
    # group, and a state-space layer's two states in the state group; a
    # cross layer reads its source layer's rows of the full group
    # (a sparse layer's K/V take a full-group layer a K/V head, and its
    # index of compressed keys a place in the index group)
    where, n_full, n_win, n_state, n_index = {}, 0, 0, 0, 0
    full_of, index_of = {}, {}  # layer -> its index in the full / index group
    for i, ls in enumerate(spec.layers):
        for j, br in enumerate(ls.branches):
            if br.kind in ("mamba2", "mamba1", "lightning"):
                where[(i, j)] = ("state", n_state)
                n_state += 1
            elif br.is_attention and br.kv_source >= 0:
                where[(i, j)] = ("cross", full_of[br.kv_source])
            elif br.is_attention and br.window:
                where[(i, j)] = ("window", n_win)
                n_win += 1
            elif br.is_attention:
                where[(i, j)] = ("full", n_full)
                full_of[i] = n_full
                n_full += br.pool_layers
                if br.kind == "sparse":
                    index_of[(i, j)] = n_index
                    n_index += 1
            else:
                where[(i, j)] = None
    groups = (kv.num_layers, kv.window_layers, kv.state_layers, kv.index_layers)
    assert (n_full, n_win, n_state, n_index) == groups, (
        "the pool's layer groups are not the model's", (n_full, n_win, n_state, n_index), groups,
    )
    # state layers with a conv (a lightning layer has none: no argument)
    has_conv = [state_shapes(b.kind, b.attrs)[0] is not None for b in spec.state_layers]
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

    def attend(q, keys, vals, mask, scale=scale):
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
        B, G = k.shape[:2]
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
            return x.transpose(0, 3, 1, 2, 4).reshape(bt.shape[0], KVH, -1, D)

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
        B, KVH = pos.shape[0], br.kv_heads
        if br.differential:
            # a pair's two queries as two heads of the pool's head size,
            # [q1 | 0] and [0 | q2]: over the pair's one K/V head [k1 | k2]
            # they score q1 k1 and q2 k2, and both read [v1 | v2]
            d = D // 2
            if br.kv_source >= 0:
                q, k, v = h @ p_at["wq"], None, None
            else:
                qkv = h @ p_at["wqkv"]
                q, k, v = qkv[:, :H * d], qkv[:, H * d:H * d + KVH * D], qkv[:, H * d + KVH * D:]
                k, v = k.reshape(B, G, KVH, D), v.reshape(B, G, KVH, D)
            q = q.reshape(B, G, H // 2, 2, d)
            z = jnp.zeros_like(q[..., 0, :])
            q = jnp.stack([jnp.concatenate([q[..., 0, :], z], -1),
                           jnp.concatenate([z, q[..., 1, :]], -1)], axis=3)
            return q.reshape(B, G, H, D), k, v, None
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

    def experts(br, p, h32, valid, G):
        # the routed block over rows h32 (T, hidden), the normed stream in
        # float32: the router reads it as it is (an expert choice that
        # flips against the reference moves a whole position's output),
        # the experts in the compute dtype; ``valid`` (T,): rows of idle
        # lanes and past n_valid are routed to no expert.  Returns the
        # output and every held expert's load (int32)
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
                h, w, idx, a["first_expert"],
                min(serve_pass_rows(slots * G * k), h.shape[0] * k),
                p.get("w_gate"), p["w_up"], p["w_down"],
                a.get("expert_form", "gated"),
            )
        if a["shared_hidden"]:
            with jax.named_scope("ff.moe.shared"):
                out = out + shared_expert_part(a, p, h)
        return out, counts  # float32, int32

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
        # a differential layer's scores are over the published head size,
        # half the pool's
        sc = 1.0 / math.sqrt(D // 2) if br.differential else scale
        with jax.named_scope({"window": "ff.attn_window", "cross": "ff.attn_cross"}.get(
                group, "ff.attn_full")):
            h = norm(params[br.norm_in], x)
            q, k, v, gate = project(br, p_at, h, pos, G)
            # write all G rows, THEN attend: row g's mask reaches rows 0..g
            # of this same program, freshly written — and under prefix
            # sharing a chunk never writes a still-shared block (commit
            # happens post-chunk, CoW-audited by serve_cow).  A cross
            # layer writes nothing: it reads its source layer's rows
            if group != "cross":
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
                    q, pk, pv, start, bt, scale=sc,
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
                o = attend(q, keys[:, None], vals[:, None], mask, sc)
            if br.differential:
                # A1 - lambda A2 a pair, normed: (rows, H / 2 pairs x D)
                with jax.named_scope("ff.attn_diff"):
                    o = o.reshape(-1, H // 2, 2, D)
                    o = diff_combine(o[:, :, 0], o[:, :, 1], p_at, br.lambda_init,
                                     br.qk_eps, br.lambda_scale)
                    o = o.astype(cdt).reshape(-1, H // 2 * D)
            else:
                o = o.reshape(-1, H * D)
            if gate is not None:
                o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)
            o = o @ p_at["wo"]
            if br.has_bias:
                o = o + p_at["bo"]
            if br.norm_post is not None:
                o = norm(params[br.norm_post], o, rdt)
        kvs = (ck, cv, sk, sv, pk, pv) if ring else (pk, pv, sk, sv, wk, wv)
        return o, kvs + pools[6:]

    def sparse_branch(br, at, ii, params, x, pools, start, bts, n_valid, G):
        # a sparse (InfLLM-v2) branch over rows x (B * G, hidden): its K/V
        # into the full group, a pool layer a K/V head from ``at``; the
        # index of compressed keys ``pools[8][ii]`` brought up to the rows
        # written; each unit of queries -- a decode row, or an aligned
        # block of a prefill chunk's rows, which share one selection --
        # selects its blocks; the paged kernel then walks, a K/V head at a
        # time, a unit's selected pages in order as if they were its
        # whole table, the unit's rows at their place in that order.
        # Returns the branch, the pools and the call's keys read
        a = br.attrs
        blk, st, kern = a["block"], a["stride"], a["kernel"]
        kvh, hd = br.kv_heads, br.head_dim
        qh = br.heads // kvh  # query heads a K/V head
        gi = at[1]
        bt = bts[0]
        ck, cv, sk, sv = pools[:4]
        B = start.shape[0]
        pos = start[:, None] + jnp.arange(G)[None, :]
        n_rows = (bt[:, 0] > 0).astype(jnp.int32) if n_valid is None else n_valid
        width = min(max(a["topk"], a["dense_len"] // blk), MB)  # a unit's table
        with jax.named_scope("ff.sparse"):
            h = norm(params[br.norm_in], x)
            q, k, v, gate = project(br, params[br.mixer[0]], h, pos, G)
            for g in range(kvh):
                ck, cv, sk, sv = write_kv(ck, cv, sk, sv, gi + g, k[:, :, g:g + 1],
                                          v[:, :, g:g + 1], start, bt, n_valid)
            with jax.named_scope("ff.sparse.index"):
                # the entries whose last position this call wrote: the mean
                # of their ``kern`` keys, read back from the pool
                nj = G // st + 1
                j = (start - kern + st) // st
                j = j[:, None] + jnp.arange(nj)[None, :]  # (B, nj)
                end = st * j + kern - 1
                ok = (j >= 0) & (end >= start[:, None]) & (end < (start + n_rows)[:, None])
                p = jnp.maximum(st * j[..., None] + jnp.arange(kern), 0)  # (B, nj, kern)
                page = bt[jnp.arange(B)[:, None, None], jnp.clip(p // BS, 0, MB - 1)]
                rows = page * BS + p % BS
                kbar = jnp.stack([
                    jnp.mean(ck[gi + g, rows].astype(jnp.float32), axis=2) for g in range(kvh)
                ], axis=2)  # (B, nj, kvh, hd)
                index = pools[8][ii]  # (B, J, kvh, hd): an entry's heads together
                J = index.shape[1]
                index = index.at[jnp.arange(B)[:, None], jnp.where(ok, j, J)].set(
                    kbar.astype(index.dtype), mode="drop")
            with jax.named_scope("ff.sparse.select"):
                nk = kernels_at(pos, kern, st)
                score = block_scores(q, index, nk, kernel=kern, stride=st, block=blk)
                sizes = dict(block=blk, topk=a["topk"], window=a["window"],
                             dense_len=a["dense_len"])
                if n_valid is None:  # a unit a row: (B, G = 1, kvh, NB)
                    t_first = t_last = pos[..., None]
                    off = pos % blk
                else:  # a unit a block of rows: their scores summed
                    nt = G // blk
                    live = (jnp.arange(G)[None, :] < n_valid[:, None]).astype(score.dtype)
                    score = (score * live[..., None, None]).reshape(
                        B, nt, blk, kvh, -1).sum(2)
                    t_first = start[:, None] + blk * jnp.arange(nt)[None, :]
                    t_last = jnp.maximum(
                        start[:, None] + jnp.minimum(blk * jnp.arange(1, nt + 1)[None, :],
                                                     n_valid[:, None]) - 1, t_first)
                    t_first, t_last = t_first[..., None], t_last[..., None]
                    off = jnp.zeros_like(t_first[..., 0])
                sel = select_blocks(score, t_first, t_last, **sizes)
                table, n_sel = selected_table(sel, width)  # (B, U, kvh, width)
                table = jnp.where(jnp.arange(width) < n_sel[..., None],
                                  bt[jnp.arange(B)[:, None, None, None], table], 0)
                # where a unit's first row sits among its selected keys
                vpos = (n_sel - 1) * blk + off[..., None]  # (B, U, kvh)
            U = table.shape[1]
            gr = G // U  # rows a unit
            # the keys each live row reads (row r of a unit: its first
            # row's place + r + 1), and the keys at and before it, a K/V
            # head each: the call's (attended, visible)
            f32 = jnp.float32
            live = (jnp.arange(G).reshape(1, U, gr) < n_rows[:, None, None]).astype(f32)
            seen = vpos[..., None].astype(f32) + 1.0 + jnp.arange(gr, dtype=f32)
            n_keys = jnp.stack([
                jnp.sum(seen * live[:, :, None, :]),
                kvh * jnp.sum((pos.reshape(B, U, gr) + 1).astype(f32) * live),
            ])
            outs = []
            with jax.named_scope("ff.sparse.attend"):
                for g in range(kvh):
                    qg = q[:, :, g * qh:(g + 1) * qh].reshape(B * U, gr, qh, hd)
                    tg, vg = table[:, :, g].reshape(B * U, width), vpos[:, :, g].reshape(B * U)
                    if paged:
                        o = paged_sparse_attention(qg, ck, cv, vg, tg, scale=scale,
                                                   layer=gi + g, block_size=BS)
                    else:
                        keys = ck[gi + g].reshape(-1, BS, hd)[tg].reshape(B * U, -1, hd)
                        vals = cv[gi + g].reshape(-1, BS, hd)[tg].reshape(B * U, -1, hd)
                        row = vg[:, None] + jnp.arange(gr)[None, :]
                        mask = (jnp.arange(keys.shape[1])[None, None, :] <= row[..., None])
                        o = attend(qg, keys[:, None, None], vals[:, None, None],
                                   mask[:, :, None, :])
                    outs.append(o.reshape(B, G, qh, hd))
            o = jnp.concatenate(outs, axis=2).reshape(-1, br.heads * hd)
            o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)
            o = o @ params[br.mixer[0]]["wo"]
        idx = list(pools[8])
        idx[ii] = index
        return o, (ck, cv, sk, sv) + pools[4:8] + (tuple(idx),), n_keys

    def live_rows(bts, n_valid, G):
        # (B, G) bool: the rows that belong to a request.  A decode-width
        # program has no n_valid: a live lane holds a reservation, so its
        # first page is not the trash block
        if n_valid is None:
            return jnp.broadcast_to(bts[0][:, :1] > 0, (bts[0].shape[0], G))
        return jnp.arange(G)[None, :] < n_valid[:, None]

    def state_branch(br, si, params, x, pools, start, bts, n_valid, G):
        # a state-space branch (ops/ssm.py::mamba2_mixer | mamba1_mixer,
        # the op's own function) from layer si's two states, which it
        # hands back: a lane whose chunk starts at position 0 reads them
        # as zero (a slot is recycled without touching the device), rows
        # past n_valid change neither, and a lane with no live row keeps
        # both bit for bit.  Also the memory a Mamba-1 layer exports
        # (rows, d_inner), else None
        conv, ssm = pools[6][si], pools[7][si]

        def lanes(v, like):  # (B,) -> broadcast over a state's dims
            return v[(slice(None),) + (None,) * (like.ndim - 1)]

        def fresh_or(state, fresh):
            if state is None:
                return None
            return jnp.where(lanes(fresh, state), jnp.zeros_like(state), state)

        if n_valid is None:  # decode width: a live lane's one row
            n_live = (bts[0][:, 0] > 0).astype(jnp.int32)
            conv_in, ssm_in = conv, ssm
        else:
            n_live = n_valid
            fresh = (start == 0) & (n_live > 0)
            conv_in, ssm_in = fresh_or(conv, fresh), fresh_or(ssm, fresh)
        h = norm(params[br.norm_in], x).reshape(start.shape[0], G, -1)
        mem = None
        if br.kind == "lightning":
            # rows past n_valid and idle lanes: dt 0, the state as it was
            o, ssm_out = lightning_mixer(
                params[br.mixer[0]], h, br.attrs, ssm_in, n_live,
                start[:, None] + jnp.arange(G)[None, :],
            )
            conv_out = None
        elif br.kind == "mamba1":
            o, conv_out, ssm_out, m = mamba1_mixer(
                params[br.mixer[0]], h, br.attrs, conv_in, ssm_in, n_live,
            )
            if br.attrs.get("exports_memory"):
                mem = m.reshape(x.shape[0], -1)
        else:
            o, conv_out, ssm_out = mamba2_mixer(
                params[br.mixer[0]], h, br.attrs, conv_in, ssm_in, n_live,
            )
        ssm_out = jnp.where(lanes(n_live > 0, ssm), ssm_out, ssm)
        o = o.reshape(x.shape[0], -1)
        if br.norm_post is not None:
            o = norm(params[br.norm_post], o, rdt)
        convs, ssms = list(pools[6]), list(pools[7])
        convs[si] = None if conv is None else conv_out.astype(conv.dtype)
        ssms[si] = ssm_out
        return o, pools[:6] + (tuple(convs), tuple(ssms)) + pools[8:], mem

    def ffn_branch(br, params, x, loads, bts, n_valid, G):
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
            f, counts = experts(br, params[br.mixer[0]], h, valid.reshape(-1), G)
            loads = loads + (counts,)
        if br.norm_post is not None:
            f = norm(params[br.norm_post], f, rdt)
        return f, loads

    def gmu_branch(br, params, x, mem):
        # a gated memory unit over the memory a Mamba-1 layer exported at
        # the same rows
        p = params[br.mixer[0]]
        h = norm(params[br.norm_in], x)
        m = jnp.ones_like(mem) if br.attrs.get("memory_ones") else mem
        with jax.named_scope("ff.gmu"):
            return gated_memory(h, m, p["w_in"], p["w_out"])

    def block(i, params, x, mem, pools, loads, start, bts, n_valid, G, attn):
        # layer i over rows x (B * G, hidden): its residual branches, in
        # order; ``mem`` the memory the layers carry beside the stream
        for j, br in enumerate(spec.layers[i].branches):
            at = where[(i, j)]
            if br.kind == "sparse":
                f, pools, n_keys = sparse_branch(br, at, index_of[(i, j)], params, x, pools,
                                                 start, bts, n_valid, G)
                loads = loads + (n_keys,)
            elif br.is_attention:
                f, pools = attention_branch(
                    br, at, params, x, pools, start, bts, n_valid, G, attn)
            elif at is not None:
                f, pools, m = state_branch(
                    br, at[1], params, x, pools, start, bts, n_valid, G)
                mem = mem if m is None else m
            elif br.kind == "gmu":
                f = gmu_branch(br, params, x, mem)
            else:
                f, loads = ffn_branch(br, params, x, loads, bts, n_valid, G)
            f = f.astype(rdt)
            x = x + (f * jnp.asarray(br.scale, rdt) if br.scale != 1.0 else f)
        return x, mem, pools, loads

    def run_layers(lo, hi, params, x, mem, pools, loads, start, bts, n_valid, G, attn):
        for i in range(lo, hi):
            x, mem, pools, loads = block(
                i, params, x, mem, pools, loads, start, bts, n_valid, G, attn
            )
        return x, mem, pools, loads

    def trunk(params, pools, toks, start, bt, *, layers, n_valid=None, attn):
        # toks (B, G) int32, start / n_valid (B,), bt (B, MB) block
        # tables — with a window group the pair (bt, ring tables (B, R))
        # -> rows (B * G, hidden) after ``layers`` blocks, the memory the
        # layers carry (or None), pools, and the held experts' loads of
        # each expert layer run
        G = toks.shape[1]
        bts = bt if isinstance(bt, (tuple, list)) else (bt, None)
        x = embed(params, toks, start[:, None] + jnp.arange(G)[None, :])
        x, mem, pools, loads = run_layers(
            0, layers, params, x, None, pools, (), start, bts, n_valid, G, attn)
        # same boundary as the dense session
        return jax.lax.optimization_barrier(x), mem, pools, loads

    # what a call's ``loads`` hold, in the order its layers add them: an
    # expert layer's rows an expert, a sparse layer's (keys its live rows
    # read, keys before those rows) -- both summed over a prefill's groups
    load_kinds = tuple(br.kind for br in spec.branches if br.kind in ("moe", "sparse"))

    def moe_stats(loads):
        # the call's counters (MOE_STATS), summed over its expert layers
        stats = jnp.zeros((len(MOE_STATS),), jnp.float32)
        for counts in loads:
            load = counts.astype(jnp.float32)
            rows = jnp.sum(load)
            stats = stats + jnp.stack([
                rows, jnp.sum(load > 0).astype(jnp.float32),
                jnp.max(load) / jnp.maximum(jnp.mean(load), 1e-9),
                (rows > 0).astype(jnp.float32),
            ])
        return stats

    def head(params, rows):
        x = norm(params[spec.final_norm], rows)
        if spec.head_scale != 1.0:
            x = x * jnp.asarray(spec.head_scale, x.dtype)
        if spec.head_tied:  # x E^T, contracting the table's rows as they rest
            logits = jax.lax.dot_general(
                x, params[spec.embed]["kernel"], (((1,), (1,)), ((), ()))
            ).astype(jnp.float32)
        else:
            logits = (x @ params[spec.head]["kernel"]).astype(jnp.float32)
        if return_probs:
            probs = jax.nn.softmax(logits, axis=-1)
            nxt = jnp.argmax(probs, axis=-1).astype(jnp.int32)
        else:
            # greedy: the argmax is all the host needs, and a
            # (slots, vocab) float32 array does not leave the program --
            # at most the argmax's own logit does
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            probs = jnp.max(logits, axis=-1) if return_logit else None
        return nxt, probs

    def head_out(nxt, probs, loads):
        # a program's outputs before its pools: the expert layers'
        # counters, then the sparse layers' keys read and keys before them
        out = (nxt, probs)
        if spec.has_moe:
            out += (moe_stats([c for c, k in zip(loads, load_kinds) if k == "moe"]),)
        if index_of:
            out += (sum(c for c, k in zip(loads, load_kinds) if k == "sparse"),)
        return out

    def decode(params, pools, tok, pos, bt, layers=L):
        x, _, pools, loads = trunk(
            params, pools, tok[:, None], pos, bt,
            layers=layers, attn=paged_decode_attention,
        )
        return head_out(*head(params, x), loads), pools

    def prefill_rows(params, pools, toks, start, n_valid, bt):
        # the lanes' chunks through the trunk -> each lane's next token
        # (and distribution), the layers' expert loads, pools.  The
        # layers after the last one that writes a pool (``self_layers``)
        # run on each lane's last valid row only, at decode width: no
        # later row reads what they compute
        B, P = toks.shape
        x, mem, pools, loads = trunk(
            params, pools, toks, start, bt,
            layers=L_self, n_valid=n_valid, attn=paged_prefill_attention,
        )
        # distribution after each lane's LAST VALID row (layer norm
        # is per-row, so select-then-ln == ln-then-select)
        last = jnp.clip(n_valid - 1, 0, P - 1)
        rows = x.reshape(B, P, -1)[jnp.arange(B), last]
        if L_self < L:
            if mem is not None:
                mem = mem.reshape(B, P, -1)[jnp.arange(B), last]
            bts = bt if isinstance(bt, (tuple, list)) else (bt, None)
            rows, _, pools, loads = run_layers(
                L_self, L, params, rows, mem, pools, loads, start + last, bts,
                (n_valid > 0).astype(jnp.int32), 1, paged_decode_attention)
            rows = jax.lax.optimization_barrier(rows)
        out = head(params, rows)
        return out, loads, pools

    def prefill(params, pools, toks, start, n_valid, bt):
        # ALL mid-prefill slots' chunks in ONE dispatch (r20): toks
        # (slots, P).  Lanes with n_valid == 0 (no mid-prefill request in
        # that slot) ride with an all-zero table row and write the
        # trash block — the idle-lane discipline at chunk width.  The
        # weight-streaming win: the window streams the decode weights
        # ONCE per chunk-batch instead of once per slot.  Where
        # ``prefill_group_lanes`` is under ``slots``, the dispatch
        # computes only the mid-prompt lanes, Bp at a time: a loop of as
        # many groups as the device finds, each gathering its lanes'
        # inputs, tables and state rows, running the trunk at width Bp
        # (K/V written through the tables into the pools the loop
        # carries) and scattering state rows and tokens back by slot.  A
        # group's padding lanes (index ``slots``) read slot ``slots - 1``
        # with n_valid 0 and a zero table row, and their scatters drop.
        P = toks.shape[1]
        Bp = prefill_group_lanes(slots, P)
        if Bp == slots:
            (nxt, probs), loads, pools = prefill_rows(
                params, pools, toks, start, n_valid, bt)
            return head_out(nxt, probs, loads), pools
        n_mid = jnp.sum(n_valid > 0)
        lanes = jnp.argsort((n_valid <= 0).astype(jnp.int32), stable=True)  # mid-prompt first
        lanes = jnp.where(jnp.arange(slots) < n_mid, lanes, slots)
        n_groups = -(-slots // Bp)
        lanes = jnp.pad(lanes, (0, n_groups * Bp - slots), constant_values=slots)
        lanes = lanes.reshape(n_groups, Bp)

        def group(g, carry):
            kvs, slot_rows, nxt, probs, loads = carry
            idx = lanes[g]
            live = idx < slots
            at = jnp.minimum(idx, slots - 1)

            def take(a):  # a group's rows of a (slots, ...) input
                rows = a[at]
                return jnp.where(live.reshape((-1,) + (1,) * (a.ndim - 1)), rows, 0)

            def put(a, rows):  # scatter a group's rows back by slot
                return a.at[idx].set(rows, mode="drop")

            bt_g = tuple(map(take, bt)) if isinstance(bt, (tuple, list)) else take(bt)
            # the group's rows of every array kept a slot (states, indexes)
            pools_g = kvs + tuple(
                tuple(None if c is None else c[at] for c in arrs) for arrs in slot_rows)
            (nxt_g, probs_g), loads_g, pools_g = prefill_rows(
                params, pools_g, take(toks), take(start), take(n_valid), bt_g)
            return (
                pools_g[:6],
                tuple(
                    tuple(None if c is None else put(c, r) for c, r in zip(arrs, rows))
                    for arrs, rows in zip(slot_rows, pools_g[6:])
                ),
                put(nxt, nxt_g),
                None if probs is None else put(probs, probs_g),
                tuple(a + b for a, b in zip(loads, loads_g)),
            )

        vocab = spec.vocab
        carry = (
            pools[:6], pools[6:], jnp.zeros((slots,), jnp.int32),
            jnp.zeros((slots, vocab), jnp.float32) if return_probs
            else jnp.zeros((slots,), jnp.float32) if return_logit else None,
            tuple(
                jnp.zeros((2,), jnp.float32) if br.kind == "sparse"
                else jnp.zeros(params[br.mixer[0]]["w_up"].shape[:1], jnp.int32)
                for br in spec.branches if br.kind in ("moe", "sparse")
            ),
        )
        kvs, slot_rows, nxt, probs, loads = jax.lax.fori_loop(
            0, prefill_lanes(n_mid, slots, P) // Bp, group, carry)
        return head_out(nxt, probs, loads), kvs + slot_rows

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
        x, _, pools, _ = trunk(
            params, pools, toks, pos0, bt,
            layers=L, attn=paged_decode_attention,
        )
        n = head(params, x)[0].reshape(toks.shape)
        # accept the longest agreeing prefix: draft j survives iff
        # every draft before it did AND the full model's argmax at
        # its predecessor row reproduces it
        agree = (toks[:, 1:] == n[:, :-1]).astype(jnp.int32)  # (B, k)
        acc = jnp.cumprod(agree, axis=1).sum(axis=1)  # (B,) in [0, k]
        next_cur = n[jnp.arange(slots), acc]  # the first token NOT yet fed
        return (n, acc, next_cur, pos0 + acc + 1), pools

    # which of (ck, cv, sk, sv, wk, wv) this engine's programs thread
    have = (True, True, quant, quant, bool(n_win), bool(n_win))
    n_kv, n_conv = sum(have), sum(has_conv)
    n_pools = n_kv + n_conv + n_state + n_index
    donate = tuple(range(1, 1 + n_pools))

    def program(body):
        # the jitted signature of every program: a quantized pool threads
        # its two scale pools right after the K/V pools, a model with
        # window layers its window group's pools after those, one with
        # state layers every layer's conv state (those that have one) and
        # then every layer's ssm state, one with sparse layers every
        # layer's index last; all donated and returned in that order
        def run(params, *args):
            given = iter(args[:n_kv])
            convs = iter(args[n_kv:n_kv + n_conv])
            at = n_kv + n_conv
            pools = tuple(next(given) if h else None for h in have) + (
                tuple(next(convs) if c else None for c in has_conv),
                tuple(args[at:at + n_state]), tuple(args[at + n_state:n_pools]),
            )
            outs, pools = body(prep_params(params), pools, *args[n_pools:])
            return (*outs, *(x for x, h in zip(pools, have) if h),
                    *(c for c in pools[6] if c is not None), *pools[7], *pools[8])

        run.__name__ = run.__qualname__ = body.__name__
        return jax.jit(run, donate_argnums=donate)

    return ServePrograms(
        decode=program(decode),
        prefill=program(prefill),
        draft=program(draft) if spec_k else None,
        verify=program(verify) if spec_k else None,
        params_arg=params_arg,
        donate=donate,
        n_head=2 + spec.has_moe + bool(index_of),
    )
