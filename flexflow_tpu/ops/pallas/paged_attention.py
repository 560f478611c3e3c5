"""Pallas paged decode attention — block-table-native K/V reads, and
the page-write kernel that lands new K/V rows in the pool in place.

The serving hot path (``serve/engine.py``) keeps each slot's K/V in a
:class:`~flexflow_tpu.serve.kvcache.PagedKVCache` pool of fixed-size
blocks named by a per-slot block table.  The dense decode step
materializes a gather every layer, every step::

    keys = ck[i][bt].transpose(0, 2, 1, 3, 4).reshape(B, H, SV, D)

— a (B, MB, H, BS, D) buffer at the FULL virtual length ``SV = MB *
BS`` per lane, even for a request three tokens in.  That is pure HBM
traffic and peak-memory overhead: the pages are then read *again* by
the attention contraction.

This kernel deletes the gather.  The grid walks the block table
directly: block indices and per-lane positions ride as scalar-prefetch
operands (SMEM), the K/V BlockSpec index_map resolves ``table[b, i]``
per grid step, and Mosaic's DMA pipeline fetches each page straight
from the pool — an online-softmax (running max/sum) carry accumulates
the attention output page by page, so no virtual-length buffer ever
exists.  Three structural guarantees:

* **per-slot virtual length** — the page index is clamped to the
  lane's last live page (``min(i, last)``); a clamped (repeated) index
  means Mosaic skips the DMA and ``pl.when`` skips the compute, so a
  short request reads only its own pages;
* **trash-block-0 never contributes** — inactive table rows are zero
  (the allocator's trash block); the per-position causal mask
  ``k_pos <= row_pos`` zeroes every position past the lane's write
  head, which is exactly the set of rows that could alias block 0;
* **read-only on shared pages** — the kernel only loads K/V; CoW
  prefix sharing needs no new ``serve_cow`` hazard class.

Query rows generalize to ``G`` consecutive positions per lane (``q``
is (B, G, H, D), row ``g`` of lane ``b`` sits at ``positions[b] + g``)
so ONE kernel serves plain decode / draft (G=1), the speculative
verify program (G = k+1), and prefill-sized chunks
(:func:`paged_prefill_attention`, G = the prefill chunk P).  The
clamp is what makes the prefill case cheap: a chunk starting at
position ``s`` visits only ``ceil((s + G) / BS)`` live pages — the
grid still spans MB steps, but every step past ``last`` repeats the
clamped index (no DMA) and skips the compute, so per-layer traffic is
O(chunk x visible) instead of the dense gather's O(chunk x SV), and
the O(S^2)-in-SV prefill materialization never exists.

The write side is :func:`paged_kv_write`: the serve programs hand it
the WHOLE aliased pools and each lane's new rows, and it rewrites only
the pages those rows fall in (decode G=1, verify G=k+1, prefill G=P
with a padded tail).  Write, then attend — row ``g`` sees rows
``0..g`` of its own chunk.  Both kernels take the pools row-major and
whole, with the layer a static index in their index_maps, so nothing
between a serve program's boundary and its kernels wants the pool in
another layout or copies a layer out of it (an XLA scatter and a
``pool[i]`` slice each did; PERF.md PR 27).

Off-TPU the kernels run in interpreter mode only (``INTERPRET``,
default from ``FFTPU_PALLAS_INTERPRET`` — see ``__init__.py``);
:func:`supported` is the predicate ``ServeEngine``'s ``attn="auto"``
consults before declining to the dense gather.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flexflow_tpu.ops.pallas import env_interpret

__all__ = [
    "INTERPRET",
    "paged_decode_attention",
    "paged_kv_write",
    "paged_prefill_attention",
    "supported",
    "resolve_serve_attn",
]

# Flip to True (tests/bench) to run in interpreter mode on CPU; the
# FFTPU_PALLAS_INTERPRET env var sets the import-time default.
INTERPRET = env_interpret()


def supported() -> bool:
    """Can the paged kernel run here?  TPU backends lower natively;
    anything else needs interpreter mode."""
    return INTERPRET or jax.default_backend() == "tpu"


def resolve_serve_attn(mode: str) -> str:
    """Resolve the ``--serve-attn`` knob to a concrete kernel.

    ``auto`` picks ``paged`` whenever :func:`supported` says the kernel
    can run (TPU, or interpreter mode forced) and declines to
    ``gather`` otherwise — so a plain CPU run is byte-identical to the
    pre-paged engine.  An explicit ``paged`` on an unsupported backend
    raises truthfully instead of silently falling back."""
    m = (mode or "auto").strip().lower()
    if m == "auto":
        return "paged" if supported() else "gather"
    if m == "gather":
        return "gather"
    if m == "paged":
        if not supported():
            raise ValueError(
                "--serve-attn paged: Pallas paged attention needs a TPU "
                "backend or interpreter mode (set "
                "FFTPU_PALLAS_INTERPRET=1 to force interpret on "
                f"{jax.default_backend()!r})"
            )
        return "paged"
    raise ValueError(
        f"--serve-attn {mode!r}: expected auto | gather | paged"
    )


def _kernel(
    pos_ref,  # SMEM (B,) int32 — row-0 position per lane
    bt_ref,  # SMEM (B, MB) int32 — block tables
    q_ref,  # VMEM (1, G, H, D)
    k_ref,  # VMEM (1, H, BS, D) — page table[b, min(i, last)]
    v_ref,  # VMEM (1, H, BS, D)
    *rest,  # [sk_ref, sv_ref (VMEM (1, BS, 1) f32)], o_ref, 3 scratch refs
    G: int,
    BS: int,
    MB: int,
    scale: float,
    quantized: bool,
):
    if quantized:
        sk_ref, sv_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        sk_ref = sv_ref = None
        o_ref, acc_ref, m_ref, l_ref = rest
    H = q_ref.shape[2]
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos0 = pos_ref[b]
    last = jnp.minimum((pos0 + G - 1) // BS, MB - 1)

    @pl.when(i <= last)
    def _step():
        q = q_ref[0].astype(jnp.float32)  # (G, H, D)
        k = k_ref[0].astype(jnp.float32)  # (H, BS, D)
        v = v_ref[0].astype(jnp.float32)
        if quantized:
            # in-register dequant of the DMA'd page: the SAME
            # ``int.astype(f32) * scale`` rule as the gather fallback
            # (kvcache.dequantize_kv), applied before the f32 online-
            # softmax carry — elementwise, so the two paths agree
            # bit-for-bit
            k = k * sk_ref[0][None]  # scales (BS, 1) per position
            v = v * sv_ref[0][None]
        # the dense path's mul+reduce contraction, one page at a time
        s = (q[:, :, None, :] * k[None]).sum(-1) * scale  # (G, H, BS)
        k_pos = i * BS + jax.lax.broadcasted_iota(
            jnp.int32, (G, H, BS), 2
        )
        row_pos = pos0 + jax.lax.broadcasted_iota(
            jnp.int32, (G, H, BS), 0
        )
        s = jnp.where(
            k_pos <= row_pos, s, jnp.finfo(jnp.float32).min
        )
        sf = s.reshape(G * H, BS)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, sf.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sf - m_new[:, None])  # (G*H, BS)
        l_ref[:, 0] = l_ref[:, 0] * alpha + p.sum(axis=-1)
        pv = (p.reshape(G, H, BS)[..., None] * v[None]).sum(axis=2)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv.reshape(
            G * H, -1
        )
        m_ref[:, 0] = m_new

    @pl.when(i == MB - 1)
    def _finalize():
        out = acc_ref[...] / l_ref[:, 0][:, None]
        o_ref[0] = out.reshape(G, *o_ref.shape[2:]).astype(o_ref.dtype)


def _paged_call(q, pool_k, pool_v, positions, block_tables, scale,
                scale_k=None, scale_v=None, layer=0):
    # NOT jitted here: the callers (the serve programs) are jitted
    # closures, and an own-cache jit would pin the INTERPRET flag at
    # first trace — tests flip it per engine build.
    #
    # The pools come in WHOLE, (L, N, H, BS, D), and ``layer`` is a
    # static index inside the index_maps: a ``pool[layer]`` slice in
    # front of a custom call is a copy of the layer (76 MB a layer at
    # GPT-2-small width and 24 slots), not a view.
    B, G, H, D = q.shape
    _, N, _, BS, _ = pool_k.shape
    MB = block_tables.shape[1]
    quantized = scale_k is not None

    def q_map(b, i, pos_ref, bt_ref):
        return (b, 0, 0, 0)

    def kv_map(b, i, pos_ref, bt_ref):
        # clamp to the lane's last live page: a repeated block index is
        # an unchanged DMA (Mosaic skips it) and the i > last compute
        # is pl.when-gated off, so masked pages are never fetched
        last = jnp.minimum((pos_ref[b] + G - 1) // BS, MB - 1)
        return (layer, bt_ref[b, jnp.minimum(i, last)], 0, 0, 0)

    def sc_map(b, i, pos_ref, bt_ref):
        # the scale row rides the same physical-block index as its page
        last = jnp.minimum((pos_ref[b] + G - 1) // BS, MB - 1)
        return (layer, bt_ref[b, jnp.minimum(i, last)], 0, 0)

    in_specs = [
        pl.BlockSpec((1, G, H, D), q_map),
        pl.BlockSpec((None, 1, H, BS, D), kv_map),
        pl.BlockSpec((None, 1, H, BS, D), kv_map),
    ]
    operands = [positions, block_tables, q, pool_k, pool_v]
    if quantized:
        # one scale row per page, as a (BS, 1) column: a (1, BS) block of
        # the (N, BS) array breaks Mosaic's (8, 128) block rule, while
        # trailing block dims that EQUAL the array's are always legal —
        # and the column broadcasts across the page's lanes as it is
        in_specs += [
            pl.BlockSpec((None, 1, BS, 1), sc_map),
            pl.BlockSpec((None, 1, BS, 1), sc_map),
        ]
        operands += [scale_k[..., None], scale_v[..., None]]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, MB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, G, H, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((G * H, D), jnp.float32),
            pltpu.VMEM((G * H, 128), jnp.float32),
            pltpu.VMEM((G * H, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _kernel, G=G, BS=BS, MB=MB, scale=scale, quantized=quantized
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, H, D), q.dtype),
        # pages chain a carry per lane: both grid dims are sequential
        compiler_params=None if INTERPRET else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=INTERPRET,
    )(*operands)


def paged_decode_attention(
    q, pool_k, pool_v, positions, block_tables, scale=None,
    scale_k=None, scale_v=None, layer=None,
):
    """Fused paged decode attention over one layer's K/V pool.

    Args:
      q: (B, G, H, D) query rows — ``G`` consecutive positions per
        lane (decode/draft G=1; speculative verify G=k+1).
      pool_k / pool_v: (num_blocks, H, BS, D) — the layer's paged pool
        (physical block 0 is the allocator's trash block); or, with
        ``layer`` given, the WHOLE (L, num_blocks, H, BS, D) pools, of
        which the kernel reads layer ``layer`` (a static int) in place.
        The serve programs pass the whole pools: a ``pool[i]`` slice in
        front of the kernel is a copy of the layer, every call.
      positions: (B,) int32 — row 0's position per lane; row ``g``
        attends positions ``0 .. positions[b] + g`` inclusive (the
        freshly written page rows included, matching the dense
        path's ``k_pos <= pos`` mask).
      block_tables: (B, MB) int32 — logical page -> physical block.
      scale: score scale; default ``1/sqrt(D)``.
      scale_k / scale_v: optional (num_blocks, BS) float32 per-position
        dequant scales for an int8/fp8 pool (``PagedKVCache.scale_k[i]``
        for layer ``i``; the whole (L, num_blocks, BS) pools with
        ``layer``); when given each DMA'd page is dequantized
        in-register via the shared ``int.astype(f32) * scale`` rule
        before the f32 online-softmax carry, so kernel and gather
        fallback stay bit-identical.  Pass both or neither.

    Returns (B, G, H, D) in ``q.dtype``.  Numerics: online softmax in
    float32 — agrees with the dense gather path to reordering ulp
    (the greedy argmax streams are bit-identical; tests pin both).
    """
    if (scale_k is None) != (scale_v is None):
        raise ValueError("pass both scale_k and scale_v, or neither")
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    positions = jnp.asarray(positions, jnp.int32)
    block_tables = jnp.asarray(block_tables, jnp.int32)
    if layer is None:
        # one layer's pool is a pool of one layer (a reshape, no copy)
        layer = 0
        pool_k, pool_v = pool_k[None], pool_v[None]
        if scale_k is not None:
            scale_k, scale_v = scale_k[None], scale_v[None]
    return _paged_call(
        q, pool_k, pool_v, positions, block_tables, float(scale),
        scale_k=scale_k, scale_v=scale_v, layer=int(layer),
    )


def paged_prefill_attention(
    q, pool_k, pool_v, start, block_tables, scale=None,
    scale_k=None, scale_v=None, layer=None,
):
    """Fused paged CHUNKED-PREFILL attention over one layer's K/V pool.

    The prefill-sized row group: ``q`` is (B, P, H, D) — P consecutive
    prompt positions per lane, row ``g`` of lane ``b`` at position
    ``start[b] + g``.  The caller writes the chunk's K/V into the
    pool FIRST (:func:`paged_kv_write`; padded rows belong to the trash
    block), then attends: row
    ``g``'s causal mask reaches positions ``0 .. start[b] + g``, which
    includes the chunk's own freshly written rows — the same
    write-then-attend discipline as the speculative verify program,
    at chunk width.

    What makes this the O(S^2) fix (docs/PERF.md): the kernel's
    visible-page DMA clamp.  The grid walks MB logical pages but the
    page index is clamped to ``last = (start[b] + P - 1) // BS``, so a
    chunk at start ``s`` fetches only ``ceil((s + P) / BS)`` pages —
    a repeated (clamped) index is a skipped DMA and ``pl.when`` skips
    the compute.  The dense gather fallback materializes (H, SV, D) at
    the FULL virtual length for every chunk of every slot; here no
    virtual-length buffer ever exists and traffic is proportional to
    the visible prefix only.

    Padded lanes (an idle slot in the batched prefill dispatch) ride
    with ``start = 0`` and an all-zero table row: every page index
    clamps/maps to the allocator's trash block 0, the per-lane DMAs
    degenerate to one repeated page, and the garbage output rows are
    discarded by the caller.

    ``scale_k``/``scale_v`` are the quantized pool's per-position
    dequant scale rows ((num_blocks, BS) float32), riding the same
    block-table scalar-prefetch as the pages with in-register dequant
    — paged and gather prefill stay bit-identical per kv_dtype, the
    decode contract at chunk width (tests pin fp32/int8/fp8).

    Returns (B, P, H, D) in ``q.dtype``.
    """
    # the decode entry point already generalizes to G consecutive rows;
    # prefill IS that kernel at G = P — one shared lowering, one parity
    # contract, no second code path to drift
    return paged_decode_attention(
        q, pool_k, pool_v, start, block_tables, scale=scale,
        scale_k=scale_k, scale_v=scale_v, layer=layer,
    )


def _write_kernel(
    phys_ref,  # SMEM (B, NP) int32 — physical block of lane b's page j
    lo_ref,  # SMEM (B, NP) int32 — first new row of that page
    hi_ref,  # SMEM (B, NP) int32 — one past its last new row
    new_ref,  # VMEM (2, H, BS, D) — the lane's new K / V rows, page-shaped
    k_ref,  # VMEM (H, BS, D) — page phys[b, j] of layer i
    v_ref,
    ko_ref,  # the same pages of the aliased pools
    vo_ref,
):
    b = pl.program_id(0)
    j = pl.program_id(1)
    row = jax.lax.broadcasted_iota(jnp.int32, k_ref.shape, 1)
    fresh = (row >= lo_ref[b, j]) & (row < hi_ref[b, j])
    ko_ref[...] = jnp.where(fresh, new_ref[0], k_ref[...])
    vo_ref[...] = jnp.where(fresh, new_ref[1], v_ref[...])


def _write_plan(start, block_tables, G, BS, n_valid=None):
    """Which pages ``G`` consecutive rows a lane touch, and which rows of
    each are new.  Returns (phys, lo, hi, page), all (B, NP) int32 with
    ``NP = (G + BS - 2) // BS + 1``: lane b's j-th page is logical page
    ``page[b, j]`` = physical block ``phys[b, j]``, and takes the rows
    ``lo <= r < hi``.  A page that takes no row (past ``n_valid``, past
    the table) names the trash block 0 with an empty range."""
    MB = block_tables.shape[1]
    NP = (G + BS - 2) // BS + 1
    start = jnp.asarray(start, jnp.int32)[:, None]  # (B, 1)
    block_tables = jnp.asarray(block_tables, jnp.int32)
    end = start + (
        G if n_valid is None else jnp.asarray(n_valid, jnp.int32)[:, None]
    )
    page = start // BS + jnp.arange(NP, dtype=jnp.int32)  # (B, NP)
    lo = jnp.clip(start - page * BS, 0, BS)
    hi = jnp.clip(end - page * BS, 0, BS)
    live = (hi > lo) & (page < MB)
    phys = jnp.where(
        live,
        jnp.take_along_axis(block_tables, jnp.clip(page, 0, MB - 1), axis=1),
        0,
    )
    return phys, jnp.where(live, lo, 0), jnp.where(live, hi, 0), page


def paged_kv_write(
    pool_k, pool_v, layer, k, v, start, block_tables, n_valid=None
):
    """Write each lane's new K/V rows into layer ``layer`` of the paged
    pools, in place: the device writer of the serve programs.

    Args:
      pool_k / pool_v: (L, num_blocks, H, BS, D) — the WHOLE pools.  They
        go in and come out of one ``pallas_call`` aliased onto themselves
        (``input_output_aliases``); ``layer`` is a static index inside
        the ``index_map``, so no per-layer slice goes in or comes back
        and XLA sees no operation that wants the pool in a layout other
        than the attention kernel's.
      k / v: (B, G, H, D) — row ``g`` of lane ``b`` belongs at position
        ``start[b] + g`` (decode / draft G=1, verify G=k+1, prefill
        G=P); cast to the pool's dtype like ``.at[...].set`` would.
      start: (B,) int32.  block_tables: (B, MB) int32.
      n_valid: (B,) int32 or None — only rows ``g < n_valid[b]`` are
        written (a prefill chunk's padded tail); None writes all G.

    G consecutive positions touch at most ``NP = (G + BS - 2) // BS + 1``
    pages.  The grid is (B, NP): each step brings one (H, BS, D) page of
    K and of V into VMEM, replaces the rows ``lo <= r < hi`` that are
    new (a select against an iota over BS, which lowers for every pool
    dtype) and writes the page back.  A page of the lane that takes no
    row — past ``n_valid``, past the table, an idle lane — is steered to
    the allocator's trash block 0 with an empty range.  Two grid steps
    name the same page only there, so the pipeline's read-ahead can
    never see a live page stale.  Every other byte of the pools is
    untouched.

    Returns the two pools.
    """
    _, _, H, BS, D = pool_k.shape
    B, G = k.shape[:2]
    phys, lo, hi, page = _write_plan(start, block_tables, G, BS, n_valid)
    NP = phys.shape[1]
    # the new rows in page shape: row r of page j is chunk row
    # page * BS + r - start (clamped; rows outside [lo, hi) are not read)
    kv = jnp.stack([k, v]).astype(pool_k.dtype)  # (2, B, G, H, D)
    if G == 1:
        # the decode step, every step: one row fills its page, no gather
        new = jnp.broadcast_to(
            kv[:, :, :, :, None, :], (2, B, NP, H, BS, D)
        )
    else:
        first = jnp.asarray(start, jnp.int32)[:, None, None]
        g = page[:, :, None] * BS + jnp.arange(BS, dtype=jnp.int32) - first
        g = jnp.clip(g, 0, G - 1).reshape(1, B, NP * BS, 1, 1)
        new = jnp.take_along_axis(kv, g, axis=2).reshape(
            2, B, NP, BS, H, D
        ).transpose(0, 1, 2, 4, 3, 5)

    def new_map(b, j, phys_ref, lo_ref, hi_ref):
        return (0, b, j, 0, 0, 0)

    def page_map(b, j, phys_ref, lo_ref, hi_ref):
        return (layer, phys_ref[b, j], 0, 0, 0)

    page_spec = pl.BlockSpec((None, None, H, BS, D), page_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, NP),
        in_specs=[
            pl.BlockSpec((2, None, None, H, BS, D), new_map),
            page_spec,
            page_spec,
        ],
        out_specs=[page_spec, page_spec],
    )
    return pl.pallas_call(
        _write_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(pool_k.shape, pool_k.dtype),
            jax.ShapeDtypeStruct(pool_v.shape, pool_v.dtype),
        ],
        # operands: phys, lo, hi, new, pool_k, pool_v
        input_output_aliases={4: 0, 5: 1},
        compiler_params=None if INTERPRET else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=INTERPRET,
        name="kv_page_write",
    )(phys, lo, hi, new, pool_k, pool_v)
