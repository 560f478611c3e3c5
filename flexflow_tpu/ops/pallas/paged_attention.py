"""Pallas paged decode attention — block-table-native K/V reads.

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

Off-TPU the kernel runs in interpreter mode only (``INTERPRET``,
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
                scale_k=None, scale_v=None):
    # NOT jitted here: the callers (the serve programs) are jitted
    # closures, and an own-cache jit would pin the INTERPRET flag at
    # first trace — tests flip it per engine build.
    B, G, H, D = q.shape
    N, _, BS, _ = pool_k.shape
    MB = block_tables.shape[1]
    quantized = scale_k is not None

    def q_map(b, i, pos_ref, bt_ref):
        return (b, 0, 0, 0)

    def kv_map(b, i, pos_ref, bt_ref):
        # clamp to the lane's last live page: a repeated block index is
        # an unchanged DMA (Mosaic skips it) and the i > last compute
        # is pl.when-gated off, so masked pages are never fetched
        last = jnp.minimum((pos_ref[b] + G - 1) // BS, MB - 1)
        return (bt_ref[b, jnp.minimum(i, last)], 0, 0, 0)

    def sc_map(b, i, pos_ref, bt_ref):
        # the scale row rides the same physical-block index as its page
        last = jnp.minimum((pos_ref[b] + G - 1) // BS, MB - 1)
        return (bt_ref[b, jnp.minimum(i, last)], 0, 0)

    in_specs = [
        pl.BlockSpec((1, G, H, D), q_map),
        pl.BlockSpec((1, H, BS, D), kv_map),
        pl.BlockSpec((1, H, BS, D), kv_map),
    ]
    operands = [positions, block_tables, q, pool_k, pool_v]
    if quantized:
        # one scale row per page, as a (BS, 1) column: a (1, BS) block of
        # the (N, BS) array breaks Mosaic's (8, 128) block rule, while
        # trailing block dims that EQUAL the array's are always legal —
        # and the column broadcasts across the page's lanes as it is
        in_specs += [
            pl.BlockSpec((1, BS, 1), sc_map),
            pl.BlockSpec((1, BS, 1), sc_map),
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
    scale_k=None, scale_v=None,
):
    """Fused paged decode attention over one layer's K/V pool.

    Args:
      q: (B, G, H, D) query rows — ``G`` consecutive positions per
        lane (decode/draft G=1; speculative verify G=k+1).
      pool_k / pool_v: (num_blocks, H, BS, D) — the layer's paged pool
        (physical block 0 is the allocator's trash block).
      positions: (B,) int32 — row 0's position per lane; row ``g``
        attends positions ``0 .. positions[b] + g`` inclusive (the
        freshly scattered page rows included, matching the dense
        path's ``k_pos <= pos`` mask).
      block_tables: (B, MB) int32 — logical page -> physical block.
      scale: score scale; default ``1/sqrt(D)``.
      scale_k / scale_v: optional (num_blocks, BS) float32 per-position
        dequant scales for an int8/fp8 pool (``PagedKVCache.scale_k[i]``
        for layer ``i``); when given each DMA'd page is dequantized
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
    return _paged_call(
        q, pool_k, pool_v, positions, block_tables, float(scale),
        scale_k=scale_k, scale_v=scale_v,
    )


def paged_prefill_attention(
    q, pool_k, pool_v, start, block_tables, scale=None,
    scale_k=None, scale_v=None,
):
    """Fused paged CHUNKED-PREFILL attention over one layer's K/V pool.

    The prefill-sized row group: ``q`` is (B, P, H, D) — P consecutive
    prompt positions per lane, row ``g`` of lane ``b`` at position
    ``start[b] + g``.  The caller scatters the chunk's K/V into the
    pool FIRST (padded rows to the trash block), then attends: row
    ``g``'s causal mask reaches positions ``0 .. start[b] + g``, which
    includes the chunk's own freshly written rows — the same
    scatter-then-attend discipline as the speculative verify program,
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
        scale_k=scale_k, scale_v=scale_v,
    )
