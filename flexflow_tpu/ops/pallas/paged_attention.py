"""Pallas paged decode attention — block-table-native K/V reads, and
the page-write kernel that lands new K/V rows in the pool in place.

The serving hot path (``serve/programs.py``) keeps each slot's K/V in a
:class:`~flexflow_tpu.serve.kvcache.PagedKVCache` pool of fixed-size
blocks named by a per-slot block table.  The pool is position-major,
``(L, num_blocks * BS, H * D)``: a page is ``BS`` consecutive rows, a
row holds every head of one position.  The dense decode step
materializes a gather every layer, every step::

    keys = ck[i].reshape(NB, BS, H, D)[bt].transpose(0, 3, 1, 2, 4)

— a (B, MB, BS, H, D) buffer at the FULL virtual length ``SV = MB *
BS`` per lane, even for a request three tokens in.  That is pure HBM
traffic and peak-memory overhead: the pages are then read *again* by
the attention contraction.

This kernel deletes the gather.  Its grid is one step a lane.  The
pools come in whole and stay in HBM (``memory_space=pl.ANY``); positions,
block tables and the layer ride as scalar-prefetch operands (SMEM), and
the body walks the lane's block table itself: a ``fori_loop`` whose trip
count is the lane's own — ``n_pages = (pos + G - 1) // BS + 1`` live
pages, ``pages_per_block`` of them a compute block
(:func:`attention_walk`: the pages that make about 128 key positions, 8
at 16 rows a page, read off the page size).  A block's pages are copied
from the pool into one of two VMEM buffers by ``make_async_copy``, page
by page as the table names them, and block ``j + 1``'s copies are
started before block ``j``'s are waited on, so a block is contracted
while the next one arrives.  An online-softmax (running max/sum) carry
accumulates the output block by block, so no virtual-length buffer ever
exists.  Three structural guarantees:

* **per-slot virtual length** — the loop ends at the lane's last live
  page: a request three tokens in takes one block, an idle lane
  (position 0, an all-zero table row) one block of the trash block, and
  table entries past the last live page are never read at all.  A call's
  time follows the tokens its lanes hold, not the table's length times
  the slot count;
* **trash-block-0 never contributes** — inactive table rows are zero
  (the allocator's trash block); the per-position causal mask
  ``k_pos <= row_pos`` zeroes every position past the lane's write
  head, which is exactly the set of rows that could alias block 0, and
  the rows of a last block's buffer that no live page filled;
* **read-only on shared pages** — the kernel only loads K/V; CoW
  prefix sharing needs no new ``serve_cow`` hazard class.

Query rows generalize to ``G`` consecutive positions per lane (``q``
is (B, G, H, D), row ``g`` of lane ``b`` sits at ``positions[b] + g``)
so ONE kernel serves plain decode / draft (G=1), the speculative
verify program (G = k+1), and prefill-sized chunks
(:func:`paged_prefill_attention`, G = the prefill chunk P).  A chunk
starting at position ``s`` walks only ``ceil((s + G) / BS)`` live pages,
so per-layer traffic is O(chunk x visible) instead of the dense
gather's O(chunk x SV), and the O(S^2)-in-SV prefill materialization
never exists.  A quantized pool's scale rows are a sub-tile of a page:
they are gathered by the block tables in front of the call (a few
hundred KB) and the kernel applies a key's scale to its score and a
value's to its probability.

The write side is :func:`paged_kv_write`: the serve programs hand it
the WHOLE aliased pools and each lane's new rows, and it rewrites only
the pages those rows fall in (decode G=1, verify G=k+1, prefill G=P
with a padded tail).  Write, then attend — row ``g`` sees rows
``0..g`` of its own chunk.  Both kernels take the pools whole, with
the layer a scalar read from SMEM (so a program's
layers share one trace and one lowering of each), and in the layout the
TPU keeps them in at rest: the minor dimension is the whole ``H * D``
row (768 at GPT-2-small width, six vregs of lanes) and a page's ``BS``
rows fill whole sublane tiles (:func:`page_rows_tile`), so the default
tiled layout IS the row-major one Mosaic reads.  Nothing between a
serve program's boundary and its kernels copies, slices or re-lays a
pool out (a (..., BS, D) page with D = 64 cost four whole-pool layout
copies a call, an XLA scatter and a ``pool[i]`` slice more; PERF.md
PR 27 and PR 29).

Heads are 64-lane groups of one row here, not a leading dimension, so
the attention kernel contracts over the whole row against a
block-diagonal query: row ``(g, h)`` of ``q_bd`` holds ``q[g, h, :]``
in head ``h``'s lanes and zeros elsewhere; ``q_bd @ k.T`` is the
(G*H, PPB*BS) scores of a block, ``p @ v`` a (G*H, H*D) accumulator of
which only the row's own head's lanes mean anything, picked once when
the lane ends.  Two MXU products a block, ``p @ v`` contracting over the
block's ~128 key positions; float32 accumulation, softmax and carry.

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
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flexflow_tpu.ops.pallas import env_interpret

__all__ = [
    "INTERPRET",
    "attention_walk",
    "rows_tile",
    "lane_blocks",
    "page_rows_tile",
    "paged_decode_attention",
    "paged_kv_write",
    "paged_prefill_attention",
    "supported",
    "resolve_serve_attn",
]

# Flip to True (tests/bench) to run in interpreter mode on CPU; the
# FFTPU_PALLAS_INTERPRET env var sets the import-time default.
INTERPRET = env_interpret()


def page_rows_tile(dtype) -> int:
    """Rows of ``dtype``'s sublane tile on the TPU: 8 rows of 32-bit
    words, so 8 for float32, 16 for bfloat16, 32 for a one-byte pool.
    A page block ``(BS, H * D)`` of the ``(L, num_blocks * BS, H * D)``
    pool is whole tiles exactly when ``BS`` is a multiple of it."""
    return 32 // jnp.dtype(dtype).itemsize


def supported(block_size=None, pool_dtype=None) -> bool:
    """Can the paged kernels run here?  Interpreter mode takes any page;
    a TPU backend lowers natively, for pages of whole sublane tiles
    (``block_size`` a multiple of :func:`page_rows_tile`; not asked
    when ``block_size`` is None)."""
    if INTERPRET:
        return True
    if jax.default_backend() != "tpu":
        return False
    return block_size is None or block_size % page_rows_tile(pool_dtype) == 0


def resolve_serve_attn(mode: str, block_size=None, pool_dtype=None) -> str:
    """Resolve the ``--serve-attn`` knob to a concrete kernel.

    ``auto`` picks ``paged`` whenever :func:`supported` says the kernel
    can run (TPU with pages of whole tiles, or interpreter mode forced)
    and declines to ``gather`` otherwise — so a plain CPU run is
    byte-identical to the pre-paged engine.  An explicit ``paged``
    where it cannot run raises truthfully instead of silently falling
    back."""
    m = (mode or "auto").strip().lower()
    if m == "auto":
        return "paged" if supported(block_size, pool_dtype) else "gather"
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
        if not supported(block_size, pool_dtype):
            raise ValueError(
                f"--serve-attn paged: a page of {block_size} rows is not "
                f"whole sublane tiles of a {jnp.dtype(pool_dtype).name} "
                f"pool on the TPU — block_size must be a multiple of "
                f"{page_rows_tile(pool_dtype)}"
            )
        return "paged"
    raise ValueError(
        f"--serve-attn {mode!r}: expected auto | gather | paged"
    )


# key positions a compute block aims at: the depth of the MXU that
# contracts over them in ``p @ v``
_BLOCK_KEYS = 128


# rows of the block-diagonal query and of the accumulator one grid step
# holds in VMEM when query heads share K/V heads: 512 rows of 512 lanes
# are 0.5 MB (bfloat16) and 1 MB (float32)
_TILE_ROWS = 512


def rows_tile(G, q_heads, kv_heads, tile_rows=None):
    """Query positions of a lane one grid step takes.  With one head
    count (``q_heads == kv_heads``) all ``G``: the geometry the kernel
    has always had.  With grouped heads the ``(G * q_heads, kv_heads *
    D)`` query and accumulator of a whole chunk pass VMEM (64 MB at 256
    positions of 32 / 4 heads of 128), so a lane's rows are cut into
    tiles of at most ``tile_rows`` rows (the largest divisor of ``G``
    that fits), each a grid step with its own walk."""
    if q_heads == kv_heads:
        return G
    limit = max(1, (tile_rows or _TILE_ROWS) // q_heads)
    return max(g for g in range(1, min(G, limit) + 1) if G % g == 0)


def attention_walk(slots, block_size, max_blocks_per_seq, tiles=1):
    """How the attention kernel walks a ``(slots, max_blocks_per_seq)``
    block table of ``block_size``-row pages: one grid step a lane (and a
    tile of its rows, :func:`rows_tile`), whose
    loop takes ``pages_per_block`` pages a compute block — the pages that
    make about ``_BLOCK_KEYS`` key positions, read off the page size
    (8 at 16 rows, 4 at 32, 1 at 128 and above), never more than the
    table holds — over at most ``max_blocks`` blocks, and over a lane's
    live pages only.  ``_paged_call`` builds its grid and its buffers
    from this; ``ServeEngine.attn_walk`` reports it."""
    ppb = max(1, min(_BLOCK_KEYS // block_size, max_blocks_per_seq))
    return {
        "grid": [slots] if tiles == 1 else [slots, tiles],
        "pages_per_block": ppb,
        "max_blocks": -(-max_blocks_per_seq // ppb),
    }


def _live(xp, pos, G, BS, MB, PPB, window=0):
    """(first page, pages, compute blocks) a lane whose ``G`` rows start
    at ``pos`` has to read: up to its last row's page, within the table;
    from page 0, or with ``window`` from the page of the first position
    its first row still sees, ``pos - window + 1`` (the table is then a
    ring of ``MB`` pages: never more than that many).  ``xp`` is
    ``jnp`` inside the kernel and ``np`` on the host."""
    if window:
        first = xp.maximum(pos - (window - 1), 0) // BS
        n_pages = xp.minimum((pos + G - 1) // BS - first + 1, MB)
    else:
        first = 0 * pos
        n_pages = xp.minimum((pos + G - 1) // BS, MB - 1) + 1
    return first, n_pages, (n_pages + PPB - 1) // PPB


def lane_blocks(positions, G, *, block_size, max_blocks_per_seq, window=0):
    """Compute blocks the kernel's loop takes for lanes whose ``G`` rows
    start at ``positions`` (numpy, any shape), for the engine's report."""
    ppb = attention_walk(1, block_size, max_blocks_per_seq)["pages_per_block"]
    return _live(
        np, np.asarray(positions), G, block_size, max_blocks_per_seq, ppb,
        window,
    )[2]


def _kernel(
    layer_ref,  # SMEM (1,) int32 — the layer of the pools to read
    pos_ref,  # SMEM (B,) int32 — row-0 position per lane
    bt_ref,  # SMEM (B, MB) int32 — block tables
    q_ref,  # VMEM (1, G, H*D); grouped heads: (1, G*QH, D)
    k_hbm,  # the WHOLE pool (L, N * BS, H*D), where it lies (HBM)
    v_hbm,
    *rest,  # [sk_ref, sv_ref (VMEM (NB, PPB*BS) f32)], o_ref, scratch refs
    G: int,  # query positions this grid step takes (a tile of the lane's)
    QH: int,
    H: int,  # K/V heads: the pool's row is H * D
    BS: int,
    MB: int,
    PPB: int,
    scale: float,
    quantized: bool,
    window: int,
    tiled: bool,
):
    if quantized:
        sk_ref, sv_ref, *rest = rest
    o_ref, kbuf, vbuf, sem, qbd_ref, acc_ref, m_ref, l_ref = rest
    GH, HD = acc_ref.shape  # G * QH rows of H * D lanes
    D = HD // H
    rep = QH // H  # query heads a K/V head serves
    W = PPB * BS  # key positions a compute block
    b = pl.program_id(0)
    layer = layer_ref[0]
    pos0 = pos_ref[b]
    first_call = b == 0
    if tiled:
        # this step's rows are positions pos0 + t * G .. of the lane: to
        # the walk, a lane of G rows that starts there
        pos0 = pos0 + pl.program_id(1) * G
        first_call &= pl.program_id(1) == 0
    # the walk ends at the lane's last live page: a lane three tokens in
    # takes one block, and so does an idle lane (position 0, the trash
    # block); table entries past ``n_pages`` are never read.  With a
    # window it also STARTS at the first page a row still sees
    first_page, n_pages, n_blocks = _live(jnp, pos0, G, BS, MB, PPB, window)
    # both products accumulate in float32.  bfloat16 rows against a
    # bfloat16 page multiply exactly in one MXU pass; anything wider (a
    # float32 pool, a dequantized page, the float32 probabilities) takes
    # the full-precision passes
    exact = jax.lax.Precision.HIGHEST
    qk_exact = None if qbd_ref.dtype == jnp.bfloat16 else exact

    def own_head():
        # (G*QH, H*D): lane c lies in the K/V head of row (g, h) = g * QH + h
        r = jax.lax.broadcasted_iota(jnp.int32, (GH, HD), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (GH, HD), 1)
        return c // D == (r % QH) // rep

    def own_row():
        # (G*H, G) 0/1: row (g, h) belongs to query row g
        r = jax.lax.broadcasted_iota(jnp.int32, (GH, G), 0)
        g = jax.lax.broadcasted_iota(jnp.int32, (GH, G), 1)
        return (r // H == g).astype(jnp.float32)

    def block_copies(j, slot, go):
        # block j's live pages, K and V, pool -> buffer ``slot``; ``go``
        # starts them or waits for them.  A page past the lane's last
        # live one is not copied: its rows keep what the buffer held (an
        # earlier page, or the zeros below) and the causal mask drops
        # them
        def page(i, carry):
            at = first_page + j * PPB + i
            if window:
                at = at % MB  # the window group's table is a ring
            blk = bt_ref[b, at]
            rows = pl.ds(pl.multiple_of(blk * BS, BS), BS)
            into = pl.ds(pl.multiple_of(i * BS, BS), BS)
            go(pltpu.make_async_copy(
                k_hbm.at[layer, rows], kbuf.at[slot, into], sem.at[0, slot]
            ))
            go(pltpu.make_async_copy(
                v_hbm.at[layer, rows], vbuf.at[slot, into], sem.at[1, slot]
            ))
            return carry

        jax.lax.fori_loop(0, jnp.minimum(PPB, n_pages - j * PPB), page, None)

    @pl.when(first_call)
    def _():
        # ``p @ v`` multiplies the masked positions' zeros by whatever
        # the value buffer holds there: make that finite once a call
        vbuf[...] = jnp.zeros_like(vbuf)

    block_copies(0, 0, lambda c: c.start())

    # while the first block is in flight — the block-diagonal query,
    # once a lane: every query row H times over, each copy keeping one
    # head's lanes
    q = q_ref[0].astype(jnp.float32)
    if QH != H:
        # grouped heads: the rows come in as (G*QH, D), a query head a
        # row; each goes into its K/V head's D lanes
        r = jax.lax.broadcasted_iota(jnp.int32, q.shape, 0)
        for kvh in range(H):
            qbd_ref[:, kvh * D:(kvh + 1) * D] = jnp.where(
                (r % QH) // rep == kvh, q, 0.0
            ).astype(qbd_ref.dtype)
    else:
        if G == 1:
            rows = jnp.broadcast_to(q, (GH, HD))  # q is (G, H*D)
        else:
            rows = jax.lax.dot_general(
                own_row(), q, (((1,), (0,)), ((), ())),
                precision=exact, preferred_element_type=jnp.float32,
            )
        qbd_ref[...] = jnp.where(own_head(), rows, 0.0).astype(qbd_ref.dtype)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)

    def block(j, carry):
        slot = j % 2

        @pl.when(j + 1 < n_blocks)
        def _():
            block_copies(j + 1, 1 - slot, lambda c: c.start())

        block_copies(j, slot, lambda c: c.wait())
        k = kbuf[slot]  # (PPB*BS, H*D)
        v = vbuf[slot].astype(jnp.float32)
        if qk_exact is not None:
            k = k.astype(jnp.float32)
        s = jax.lax.dot_general(
            qbd_ref[...], k, (((1,), (1,)), ((), ())),
            precision=qk_exact, preferred_element_type=jnp.float32,
        ) * scale  # (G*QH, PPB*BS)
        if quantized:
            # the pool's ``int.astype(f32) * scale`` rule
            # (kvcache.dequantize_kv) with the positions' scales applied
            # to the products instead of the pages: a key's scale to its
            # score, a value's to its probability
            s = s * sk_ref[pl.ds(j, 1), :]
        k_pos = first_page * BS + j * W + jax.lax.broadcasted_iota(
            jnp.int32, (GH, W), 1
        )
        # (a chunk's padded rows may lie past the table's end: they see
        # no further than its last page, as when the grid ended there)
        row_pos = jnp.minimum(
            pos0 + jax.lax.broadcasted_iota(jnp.int32, (GH, W), 0) // QH,
            (first_page + n_pages) * BS - 1,
        )
        seen = k_pos <= row_pos
        if window:
            # a row sees its last ``window`` keys, its own among them.
            # (A block may hold none of a late row's keys: its running
            # max is then the mask's floor, and the first block that
            # holds one scales what came before to exactly nothing.)
            seen &= k_pos > row_pos - window
        s = jnp.where(seen, s, jnp.finfo(jnp.float32).min)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])  # (G*QH, PPB*BS) float32
        l_ref[:, 0] = l_ref[:, 0] * alpha + p.sum(axis=-1)
        if quantized:
            p = jnp.where(seen, p * sv_ref[pl.ds(j, 1), :], 0.0)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            precision=exact, preferred_element_type=jnp.float32,
        )  # (G*QH, H*D); row (g, h) means something in its K/V head's lanes
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        m_ref[:, 0] = m_new
        return carry

    jax.lax.fori_loop(0, n_blocks, block, None)

    out = jnp.where(own_head(), acc_ref[...] / l_ref[:, 0][:, None], 0.0)
    if QH != H:
        # a row keeps its own K/V head's D lanes (the others hold zeros)
        o_ref[0] = sum(
            out[:, kvh * D:(kvh + 1) * D] for kvh in range(H)
        ).astype(o_ref.dtype)
        return
    # each query row's H copies back into one row: head h's lanes come
    # from copy h, every other copy holds zeros there
    if G == 1:
        out = out.sum(axis=0, keepdims=True)
    else:
        out = jax.lax.dot_general(
            own_row(), out, (((0,), (0,)), ((), ())),
            precision=exact, preferred_element_type=jnp.float32,
        )
    o_ref[0] = out.astype(o_ref.dtype)


def _lane_scales(scales, layer, block_tables, ppb):
    """A quantized pool's ``(L, N, BS)`` scale rows of ``layer``, gathered
    by the block tables into each lane's key order and cut into the
    kernel's compute blocks: ``(B, max_blocks, ppb * BS)`` float32, a few
    hundred KB, which the kernel takes a lane at a time in VMEM (a scale
    row is a sub-tile of a page: not worth a DMA of its own)."""
    B, MB = block_tables.shape
    rows = scales[layer][block_tables]  # (B, MB, BS)
    nb = -(-MB // ppb)
    rows = jnp.pad(rows, ((0, 0), (0, nb * ppb - MB), (0, 0)))
    return rows.reshape(B, nb, -1)


def _paged_call(q, pool_k, pool_v, positions, block_tables, layer,
                scale_k, scale_v, *, BS, scale, interpret, window=0,
                tile_rows=None):
    # Called through ``_JITTED``: jitted on its own, with the
    # interpreter flag among the static arguments (tests flip it per
    # engine build).  A serve program calls this once a layer with the
    # same shapes, so the kernel is traced, lowered and compiled once a
    # program and not once a layer.
    #
    # The pools come in WHOLE, (L, N * BS, H * D), and stay where they
    # lie: the kernel copies the pages it wants itself, and ``layer`` is
    # a scalar it reads from SMEM.  A ``pool[layer]`` slice in front of
    # a custom call is a copy of the layer (76 MB a layer at GPT-2-small
    # width and 24 slots), not a view.
    B, G, QH, D = q.shape
    HD = pool_k.shape[-1]
    H = HD // D  # K/V heads
    assert H * D == HD and QH % H == 0, (q.shape, pool_k.shape)
    MB = block_tables.shape[1]
    GT = rows_tile(G, QH, H, tile_rows)
    NT = G // GT
    walk = attention_walk(B, BS, MB, NT)
    PPB = walk["pages_per_block"]
    quantized = scale_k is not None
    assert not (quantized and window), "no quantized pool behind a window"
    # the block-diagonal query multiplies a bfloat16 page as bfloat16
    # (the kernel reads the choice off the scratch's dtype)
    qbd_dtype = (
        jnp.bfloat16 if q.dtype == pool_k.dtype == jnp.bfloat16
        else jnp.float32
    )
    grouped = QH != H
    # grouped heads go in and come out a query head a row, (G*QH, D):
    # both are the caller's (B, G, QH, D) seen otherwise, no copy
    width, rows = (D, G * QH) if grouped else (QH * D, G)
    tile = GT * QH if grouped else G

    def lane_map(b, *rest):
        return (b, 0, 0)

    def tile_map(b, *rest):
        return (b, rest[0], 0) if NT > 1 else (b, 0, 0)

    in_specs = [
        pl.BlockSpec((1, tile, width), tile_map),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [
        layer.reshape(1), positions, block_tables, q.reshape(B, rows, width),
        pool_k, pool_v,
    ]
    if quantized:
        lane_scales = pl.BlockSpec(
            (None, walk["max_blocks"], PPB * BS), lane_map
        )
        in_specs += [lane_scales, lane_scales]
        operands += [
            _lane_scales(s, layer, block_tables, PPB)
            for s in (scale_k, scale_v)
        ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=tuple(walk["grid"]),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, tile, width), tile_map),
        scratch_shapes=[
            # two compute blocks of K and of V: one contracted while the
            # next one's pages arrive
            pltpu.VMEM((2, PPB * BS, HD), pool_k.dtype),
            pltpu.VMEM((2, PPB * BS, HD), pool_v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),  # (K | V, buffer)
            pltpu.VMEM((GT * QH, HD), qbd_dtype),
            pltpu.VMEM((GT * QH, HD), jnp.float32),
            pltpu.VMEM((GT * QH, 128), jnp.float32),
            pltpu.VMEM((GT * QH, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _kernel, G=GT, QH=QH, H=H, BS=BS, MB=MB, PPB=PPB, scale=scale,
        quantized=quantized, window=window, tiled=NT > 1,
    )
    # no ``name=``: a compiled program and the profiler's trace name the
    # call after the jitted function around it (``_jitted_as`` below)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, rows, width), q.dtype),
        # lanes run in turn: the value buffer is cleared by the first
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(walk["grid"])
        ),
        interpret=interpret,
    )(*operands).reshape(B, G, QH, D)


def _jitted_as(name):
    """``_paged_call`` jitted under ``name``: the kernel's custom call
    is ``%<name>.N`` in a compiled program and in the profiler's trace,
    which is how a reader of either tells a decode-width call from a
    prefill chunk's (the benchmark's ``paged_attention_roofline.*``
    reads ``%decode.N`` and ``%prefill.N``)."""
    def call(*args, **kwargs):
        return _paged_call(*args, **kwargs)

    call.__name__ = call.__qualname__ = name
    return jax.jit(call, static_argnames=(
        "BS", "scale", "interpret", "window", "tile_rows",
    ))


_JITTED = {name: _jitted_as(name) for name in ("decode", "prefill")}


def paged_decode_attention(
    q, pool_k, pool_v, positions, block_tables, scale=None,
    scale_k=None, scale_v=None, layer=None, *, block_size, window=0,
    tile_rows=None,
):
    """Fused paged decode attention over one layer's K/V pool.

    Args:
      q: (B, G, QH, D) query rows — ``G`` consecutive positions per
        lane (decode/draft G=1; speculative verify G=k+1).  ``QH`` may
        be a multiple of the pool's ``H`` K/V heads (grouped-query
        attention: ``QH // H`` query heads read one K/V head, and a
        lane's rows are then taken :func:`rows_tile` positions a grid
        step).
      pool_k / pool_v: (num_blocks * BS, H * D) — the layer's paged pool,
        position-major (physical block ``n`` is rows ``n * BS ..``; block
        0 is the allocator's trash block); or, with ``layer`` given, the
        WHOLE (L, num_blocks * BS, H * D) pools, of which the kernel
        reads layer ``layer`` in place.  The serve programs pass the
        whole pools: a ``pool[i]`` slice in front of the kernel is a
        copy of the layer, every call.
      positions: (B,) int32 — row 0's position per lane; row ``g``
        attends positions ``0 .. positions[b] + g`` inclusive (the
        freshly written page rows included, matching the dense
        path's ``k_pos <= pos`` mask).
      block_tables: (B, MB) int32 — logical page -> physical block.
      scale: score scale; default ``1/sqrt(D)``.
      scale_k / scale_v: optional (num_blocks, BS) float32 per-position
        dequant scales for an int8/fp8 pool (``PagedKVCache.scale_k[i]``
        for layer ``i``; the whole (L, num_blocks, BS) pools with
        ``layer``); when given, the shared ``int.astype(f32) * scale``
        rule is applied in the kernel (a key's scale to its score, a
        value's to its probability) inside the f32 online-softmax
        carry.  Pass both or neither.
      block_size: ``BS``, the rows of a page (the pool's shape does not
        say).
      window: 0, or the positions a row sees back, its own among them
        (row at ``p`` attends ``p - window + 1 .. p``).  The walk then
        starts at the page of the first row's first visible position,
        and the block table is read as a RING of its ``MB`` pages
        (logical page ``j`` at entry ``j % MB``: the window group of
        ``PagedKVCache``), which has to hold ``window + G`` positions
        and a page.

    Returns (B, G, QH, D) in ``q.dtype``.  Numerics: float32 scores,
    online softmax and accumulation; the two contractions run on the
    MXU at full precision, so the result agrees with the dense gather
    path to a float32 tolerance, not to the bit (the greedy argmax
    streams are identical; tests pin both).
    """
    return _attention(
        "decode", q, pool_k, pool_v, positions, block_tables, scale,
        scale_k, scale_v, layer, block_size, window, tile_rows,
    )


def _attention(name, q, pool_k, pool_v, positions, block_tables, scale,
               scale_k, scale_v, layer, block_size, window=0, tile_rows=None):
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
    return _JITTED[name](
        q, pool_k, pool_v, positions, block_tables,
        jnp.asarray(layer, jnp.int32), scale_k, scale_v,
        BS=int(block_size), scale=float(scale), interpret=bool(INTERPRET),
        window=int(window), tile_rows=tile_rows,
    )


def paged_prefill_attention(
    q, pool_k, pool_v, start, block_tables, scale=None,
    scale_k=None, scale_v=None, layer=None, *, block_size, window=0,
    tile_rows=None,
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

    What makes this the O(S^2) fix (docs/PERF.md): the walk ends at the
    lane's last live page, ``(start[b] + P - 1) // BS``, so a chunk at
    start ``s`` copies only ``ceil((s + P) / BS)`` pages.  The dense
    gather fallback materializes (H, SV, D) at the FULL virtual length
    for every chunk of every slot; here no virtual-length buffer ever
    exists and traffic is proportional to the visible prefix only.

    Padded lanes (an idle slot in the batched prefill dispatch) ride
    with ``start = 0`` and an all-zero table row: they walk one block,
    of the allocator's trash block 0, and the garbage output rows are
    discarded by the caller.

    ``scale_k``/``scale_v`` are the quantized pool's per-position
    dequant scale rows ((num_blocks, BS) float32), gathered by the same
    block tables and applied in the kernel — the decode contract at
    chunk width (tests pin fp32/int8/fp8).

    Returns (B, P, H, D) in ``q.dtype``.
    """
    # the decode entry point already generalizes to G consecutive rows;
    # prefill IS that kernel at G = P — one shared lowering, one parity
    # contract, no second code path to drift
    return _attention(
        "prefill", q, pool_k, pool_v, start, block_tables, scale,
        scale_k, scale_v, layer, block_size, window, tile_rows,
    )


def _write_kernel(
    layer_ref,  # SMEM (1,) int32 — the layer the index_maps write
    phys_ref,  # SMEM (B, NP) int32 — physical block of lane b's page j
    lo_ref,  # SMEM (B, NP) int32 — first new row of that page
    hi_ref,  # SMEM (B, NP) int32 — one past its last new row
    new_ref,  # VMEM (2, BS, H*D) — the lane's new K / V rows, page-shaped
    k_ref,  # VMEM (BS, H*D) — page phys[b, j] of layer i
    v_ref,
    ko_ref,  # the same pages of the aliased pools
    vo_ref,
):
    b = pl.program_id(0)
    j = pl.program_id(1)
    row = jax.lax.broadcasted_iota(jnp.int32, k_ref.shape, 0)
    fresh = (row >= lo_ref[b, j]) & (row < hi_ref[b, j])
    ko_ref[...] = jnp.where(fresh, new_ref[0], k_ref[...])
    vo_ref[...] = jnp.where(fresh, new_ref[1], v_ref[...])


def _write_plan(start, block_tables, G, BS, n_valid=None, ring=False):
    """Which pages ``G`` consecutive rows a lane touch, and which rows of
    each are new.  Returns (phys, lo, hi, page), all (B, NP) int32 with
    ``NP = (G + BS - 2) // BS + 1``: lane b's j-th page is logical page
    ``page[b, j]`` = physical block ``phys[b, j]``, and takes the rows
    ``lo <= r < hi``.  A page that takes no row (past ``n_valid``, past
    the table) names the trash block 0 with an empty range.  With
    ``ring`` the table is the window group's ring: logical page ``j`` is
    its entry ``j % MB``, and no page lies past it."""
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
    if ring:
        live, at = hi > lo, page % MB
    else:
        live, at = (hi > lo) & (page < MB), jnp.clip(page, 0, MB - 1)
    phys = jnp.where(live, jnp.take_along_axis(block_tables, at, axis=1), 0)
    return phys, jnp.where(live, lo, 0), jnp.where(live, hi, 0), page


def paged_kv_write(
    pool_k, pool_v, layer, k, v, start, block_tables, n_valid=None,
    *, block_size, ring=False,
):
    """Write each lane's new K/V rows into layer ``layer`` of the paged
    pools, in place: the device writer of the serve programs.

    Args:
      pool_k / pool_v: (L, num_blocks * BS, H * D) — the WHOLE pools.
        They go in and come out of one ``pallas_call`` aliased onto
        themselves (``input_output_aliases``); ``layer`` is a scalar
        the ``index_map`` reads from SMEM, so no per-layer slice goes in or
        comes back and XLA sees no operation that wants the pool in a
        layout other than the attention kernel's.
      k / v: (B, G, H, D) — row ``g`` of lane ``b`` belongs at position
        ``start[b] + g`` (decode / draft G=1, verify G=k+1, prefill
        G=P); cast to the pool's dtype like ``.at[...].set`` would.
      start: (B,) int32.  block_tables: (B, MB) int32.
      n_valid: (B,) int32 or None — only rows ``g < n_valid[b]`` are
        written (a prefill chunk's padded tail); None writes all G.
      block_size: ``BS``, the rows of a page.
      ring: the table is the window group's ring (``_write_plan``).

    G consecutive positions touch at most ``NP = (G + BS - 2) // BS + 1``
    pages.  The grid is (B, NP): each step brings one (BS, H * D) page
    of K and of V into VMEM, replaces the rows ``lo <= r < hi`` that are
    new (a select against an iota over BS, which lowers for every pool
    dtype) and writes the page back.  A page of the lane that takes no
    row — past ``n_valid``, past the table, an idle lane — is steered to
    the allocator's trash block 0 with an empty range.  Two grid steps
    name the same page only there, so the pipeline's read-ahead can
    never see a live page stale.  Every other byte of the pools is
    untouched.

    Returns the two pools.
    """
    return _kv_write(
        pool_k, pool_v, jnp.asarray(layer, jnp.int32), k, v, start,
        block_tables, n_valid, BS=int(block_size), interpret=bool(INTERPRET),
        ring=bool(ring),
    )


@functools.partial(jax.jit, static_argnames=("BS", "interpret", "ring"))
def _kv_write(pool_k, pool_v, layer, k, v, start, block_tables, n_valid,
              *, BS, interpret, ring=False):
    # jitted on its own like ``_paged_call``: one trace and one lowering
    # a program, whatever its depth
    HD = pool_k.shape[-1]
    B, G = k.shape[:2]
    phys, lo, hi, page = _write_plan(start, block_tables, G, BS, n_valid, ring)
    NP = phys.shape[1]
    # the new rows in page shape: row r of page j is chunk row
    # page * BS + r - start (clamped; rows outside [lo, hi) are not read)
    kv = jnp.stack([k, v]).astype(pool_k.dtype).reshape(2, B, G, HD)
    if G == 1:
        # the decode step, every step: one row fills its page, no gather
        new = jnp.broadcast_to(kv[:, :, :, None, :], (2, B, NP, BS, HD))
    else:
        first = jnp.asarray(start, jnp.int32)[:, None, None]
        g = page[:, :, None] * BS + jnp.arange(BS, dtype=jnp.int32) - first
        g = jnp.clip(g, 0, G - 1).reshape(1, B, NP * BS, 1)
        new = jnp.take_along_axis(kv, g, axis=2).reshape(2, B, NP, BS, HD)

    def new_map(b, j, layer_ref, phys_ref, lo_ref, hi_ref):
        return (0, b, j, 0, 0)

    def page_map(b, j, layer_ref, phys_ref, lo_ref, hi_ref):
        return (layer_ref[0], phys_ref[b, j], 0)

    page_spec = pl.BlockSpec((None, BS, HD), page_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, NP),
        in_specs=[
            pl.BlockSpec((2, None, None, BS, HD), new_map),
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
        # operands: layer, phys, lo, hi, new, pool_k, pool_v
        input_output_aliases={5: 0, 6: 1},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
        name="kv_page_write",
    )(layer.reshape(1), phys, lo, hi, new, pool_k, pool_v)
