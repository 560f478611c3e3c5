"""The paged attention kernel with grouped heads (``q_heads !=
kv_heads``), block-diagonal and a K/V head at a time, a first visible
position (``window``) and a ring table, and the page-write kernel
through a ring -- in the Pallas interpreter, against a dense masked
softmax."""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from flexflow_tpu.ops.pallas import paged_attention as pa


@pytest.fixture(autouse=True)
def interpret():
    old = pa.INTERPRET
    pa.INTERPRET = True
    yield
    pa.INTERPRET = old


def _dense(q, keys, vals, pos, window):
    """q (B, G, QH, D); keys / vals (B, S, KVH, D) by logical position."""
    B, G, QH, D = q.shape
    S, KVH = keys.shape[1], keys.shape[2]
    k = np.repeat(keys, QH // KVH, axis=2).astype(np.float64)
    v = np.repeat(vals, QH // KVH, axis=2).astype(np.float64)
    s = np.einsum("bghd,bshd->bghs", q.astype(np.float64), k) / np.sqrt(D)
    row = pos[:, None] + np.arange(G)[None, :]  # (B, G)
    kp = np.arange(S)[None, None, :]
    seen = kp <= row[:, :, None]
    if window:
        seen &= kp > row[:, :, None] - window
    s = np.where(seen[:, :, None, :], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bghs,bshd->bghd", p, v)


def _case(rng, *, B, G, QH, KVH, D, BS, window, pos, dtype, chunk):
    """Pools written position by position through a (ring) table, then
    one attention call for rows ``pos .. pos + G - 1`` a lane."""
    pos = np.asarray(pos, np.int32)
    S = int(pos.max()) + G
    ring = bool(window)
    if ring:
        R = -(-(window + chunk) // BS) + 1
    else:
        R = -(-S // BS)
    keys = rng.standard_normal((B, S, KVH, D)).astype(np.float32)
    vals = rng.standard_normal((B, S, KVH, D)).astype(np.float32)
    # scrambled physical pages, block 0 the trash block; an idle lane
    # (pos < 0 in the caller's list is not used: idle = all-zero table)
    perm = 1 + rng.permutation(B * R)
    tables = perm.reshape(B, R).astype(np.int32)
    N = B * R + 1
    pk = rng.standard_normal((2, N * BS, KVH * D)).astype(np.float32) * 3.0  # garbage
    pv = rng.standard_normal((2, N * BS, KVH * D)).astype(np.float32) * 3.0
    layer = 1
    for b in range(B):
        # what a ring holds when the call runs: the newest write a slot
        for p in range(int(pos[b]) + G):
            page = (p // BS) % R if ring else p // BS
            row = tables[b, page] * BS + p % BS
            pk[layer, row] = keys[b, p].reshape(-1)
            pv[layer, row] = vals[b, p].reshape(-1)
    q = rng.standard_normal((B, G, QH, D)).astype(np.float32)
    return q, keys, vals, jnp.asarray(pk, dtype), jnp.asarray(pv, dtype), tables, layer


def _as_held(dtype, *xs):
    """What a pool (and a query) of ``dtype`` hold of float32 ``xs``."""
    return [np.asarray(jnp.asarray(x, dtype).astype(jnp.float32)) for x in xs]


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("G,pos,tile_rows", [
    (1, [0, 5, 7, 8, 30], None),        # decode: below, at, above the window
    (8, [0, 3, 5, 16, 29], None),       # a chunk that straddles the window's edge
    (8, [0, 3, 5, 16, 29], 8),          # the same, two positions a grid step
    (6, [2, 9, 0, 21, 13], 4),          # a tile of one position (QH = 4)
])
@pytest.mark.parametrize("window", [0, 8])
def test_grouped_heads_and_window_against_dense(dtype, tol, G, pos, tile_rows, window):
    rng = np.random.default_rng(hash((G, window, tile_rows or 0)) % 2**31)
    QH, KVH, D, BS = 4, 2, 16, 4
    q, keys, vals, pk, pv, tables, layer = _case(
        rng, B=len(pos), G=G, QH=QH, KVH=KVH, D=D, BS=BS, window=window,
        pos=pos, dtype=dtype, chunk=8,
    )
    if dtype == jnp.bfloat16:
        # the reference sees what the pool holds
        keys, vals, q = _as_held(dtype, keys, vals, q)
    # ``tile_rows`` block-diagonal rows of KVH * D float32 lanes a step
    tile_bytes = tile_rows and tile_rows * KVH * D * 4
    assert pa.rows_tile(G, QH, KVH, D, tile_bytes)[1] == "block_diagonal"
    fn = pa.paged_prefill_attention if G > 1 else pa.paged_decode_attention
    out = fn(
        jnp.asarray(q, dtype), pk, pv, jnp.asarray(pos, jnp.int32), jnp.asarray(tables),
        layer=layer, block_size=BS, window=window, tile_bytes=tile_bytes,
    )
    want = _dense(q, keys, vals, np.asarray(pos), window)
    np.testing.assert_allclose(np.asarray(out, np.float64), want, atol=tol, rtol=tol)


# a K/V head at a time at a small cost: 8 query over 2 K/V heads of 128
# lanes, so that 32 positions bring a K/V head the MXU's 128 rows
HQH, HKVH, HD, HBS = 8, 2, 128, 16


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("G,pos,tile_bytes,tiles", [
    (32, [37, 5], None, 1),              # the whole chunk a grid step
    (64, [21, 0], 32 * HQH * HD * 4, 2),  # two tiles of 32 positions
])
@pytest.mark.parametrize("window", [0, 24])
def test_per_head_arm_against_dense(dtype, tol, G, pos, tile_bytes, tiles, window):
    """A chunk a K/V head at a time, and a third lane idle on the trash
    block: its former pages hold NaN, which reaches no output."""
    assert pa.rows_tile(G, HQH, HKVH, HD, tile_bytes) == (G // tiles, "per_head")
    rng = np.random.default_rng(G + window)
    q, keys, vals, pk, pv, tables, layer = _case(
        rng, B=3, G=G, QH=HQH, KVH=HKVH, D=HD, BS=HBS, window=window,
        pos=pos + [0], dtype=jnp.float32, chunk=G,
    )
    tables = tables.copy()
    pk = np.asarray(pk).copy()
    for blk in tables[2]:  # the idle lane's former pages: poison
        pk[:, blk * HBS:(blk + 1) * HBS] = np.nan
    tables[2] = 0
    pk[:, :HBS] = 0.5  # the trash block holds something finite
    pk, pv = jnp.asarray(pk, dtype), jnp.asarray(pv, dtype)
    if dtype == jnp.bfloat16:
        keys, vals, q = _as_held(dtype, keys, vals, q)
    out = pa.paged_prefill_attention(
        jnp.asarray(q, dtype), pk, pv, jnp.asarray(pos + [0], jnp.int32),
        jnp.asarray(tables), layer=layer, block_size=HBS, window=window,
        tile_bytes=tile_bytes,
    )
    out = np.asarray(out, np.float64)
    assert np.isfinite(out).all()
    want = _dense(q[:2], keys[:2], vals[:2], np.asarray(pos), window)
    np.testing.assert_allclose(out[:2], want, atol=tol, rtol=tol)


@pytest.mark.parametrize("G,tile_bytes,rows", [
    (16, None, 16),               # 64 rows a K/V head: under the MXU's 128
    (32, 32 * HQH * HD * 2, 8),   # a budget of 16 positions a head: the same
])
def test_below_the_mxus_rows_the_block_diagonal_arm_is_taken(G, tile_bytes, rows):
    """Where a K/V head's query rows would not fill an MXU pass the call
    stays block-diagonal (its tile then counted in H * D lanes), and
    agrees with the reference as well."""
    assert pa.rows_tile(G, HQH, HKVH, HD, tile_bytes) == (rows, "block_diagonal")
    rng = np.random.default_rng(G)
    pos = [3, 40]
    q, keys, vals, pk, pv, tables, layer = _case(
        rng, B=2, G=G, QH=HQH, KVH=HKVH, D=HD, BS=HBS, window=0, pos=pos,
        dtype=jnp.float32, chunk=G,
    )
    out = pa.paged_prefill_attention(
        jnp.asarray(q), pk, pv, jnp.asarray(pos, jnp.int32), jnp.asarray(tables),
        layer=layer, block_size=HBS, tile_bytes=tile_bytes,
    )
    want = _dense(q, keys, vals, np.asarray(pos), 0)
    np.testing.assert_allclose(np.asarray(out, np.float64), want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("G,q_heads,kv_heads,head_dim,positions,heads", [
    (128, 32, 4, 128, 64, "per_head"),        # trinity_mini prefill: 2 walks
    (256, 32, 2, 128, 64, "per_head"),        # nemotron3_nano prefill: 4 walks
    (256, 40, 10, 128, 32, "per_head"),       # phi4 prefill: 8 walks, 128 rows a head
    (64, 16, 1, 128, 64, "per_head"),         # minicpm_sala sparse_attend, a unit: 1 walk
    (1, 32, 4, 128, 1, "block_diagonal"),     # trinity_mini decode: 8 rows a head
    (1, 32, 2, 128, 1, "block_diagonal"),     # nemotron3_nano decode: 16
    (1, 40, 10, 128, 1, "block_diagonal"),    # phi4 decode: 4
    (1, 16, 1, 128, 1, "block_diagonal"),     # minicpm_sala sparse_attend decode
    (256, 12, 12, 64, 256, "block_diagonal"),  # gpt2_small: one head count
    (4, 12, 12, 64, 4, "block_diagonal"),      # ... and its verify width
    (256, 32, 4, 64, 32, "block_diagonal"),   # heads of half a vreg: 1 MB of 256 lanes
])
def test_each_cells_calls_take_their_arm_and_tiles(
    G, q_heads, kv_heads, head_dim, positions, heads
):
    """``rows_tile`` at each served geometry: a prefill chunk of grouped
    heads of 128 a K/V head at a time, in tiles whose float32
    accumulator is at most 1 MB; every decode width block-diagonal."""
    assert pa.rows_tile(G, q_heads, kv_heads, head_dim) == (positions, heads)
    lanes = head_dim if heads == "per_head" else kv_heads * head_dim
    assert q_heads == kv_heads or positions * q_heads * lanes * 4 <= 1 << 20


def test_idle_lanes_read_the_trash_block_and_nothing_else():
    """An idle lane (position 0, an all-zero table) walks one block of
    block 0; a NaN anywhere else in the pool never reaches an output."""
    rng = np.random.default_rng(0)
    QH, KVH, D, BS, W = 4, 2, 16, 4, 8
    q, keys, vals, pk, pv, tables, layer = _case(
        rng, B=3, G=4, QH=QH, KVH=KVH, D=D, BS=BS, window=W, pos=[11, 0, 6],
        dtype=jnp.float32, chunk=8,
    )
    tables = tables.copy()
    idle_pages = tables[1].copy()
    tables[1] = 0
    pk = np.asarray(pk).copy()
    for blk in idle_pages:  # the idle lane's former pages: poison
        pk[:, blk * BS:(blk + 1) * BS] = np.nan
    pk[:, :BS] = 0.5  # the trash block holds something finite
    out = pa.paged_prefill_attention(
        jnp.asarray(q), jnp.asarray(pk), pv, jnp.asarray([11, 0, 6], jnp.int32),
        jnp.asarray(tables), layer=layer, block_size=BS, window=W,
    )
    out = np.asarray(out)
    assert np.isfinite(out).all()
    want = _dense(q, keys, vals, np.asarray([11, 0, 6]), W)
    np.testing.assert_allclose(out[[0, 2]], want[[0, 2]], atol=2e-5, rtol=2e-5)


def test_walk_starts_at_the_first_visible_page():
    """``_live`` with a window: the pages before the first row's window
    are not walked (and a NaN there is never read)."""
    BS, W, R = 4, 8, 5
    first, n_pages, n_blocks = pa._live(np, np.asarray([0, 7, 8, 30, 100]), 1, BS, R, 32, W)
    assert first.tolist() == [0, 0, 0, 5, 23]
    assert n_pages.tolist() == [1, 2, 3, 3, 3] and n_blocks.tolist() == [1] * 5
    # a chunk of 8 rows at 100: first row sees from 93 (page 23), last row is 107 (page 26)
    f, n, _ = pa._live(np, np.asarray([100]), 8, BS, R, 32, W)
    assert (f[0], n[0]) == (23, 4)
    # no window: from page 0, within the table
    f, n, _ = pa._live(np, np.asarray([100]), 8, BS, 40, 32, 0)
    assert (f[0], n[0]) == (0, 27)
    # a budget of one block-diagonal position: a tile a position
    assert pa.rows_tile(6, 4, 2, 16, 4 * 32 * 4) == (1, "block_diagonal")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_page_write_through_a_ring_that_has_wrapped(dtype):
    """Chunks written through the window group's ring, far past its
    length: every position lands at ring page ``(p // BS) % R``, padded
    rows and idle lanes in the trash block, nothing else is touched."""
    rng = np.random.default_rng(3)
    B, KVH, D, BS, R, G, L = 3, 2, 16, 4, 5, 8, 2
    HD = KVH * D
    tables = (1 + rng.permutation(B * R)).reshape(B, R).astype(np.int32)
    tables[2] = 0  # an idle lane
    N = B * R + 1
    pk = jnp.asarray(rng.standard_normal((L, N * BS, HD)), dtype)
    pv = jnp.asarray(rng.standard_normal((L, N * BS, HD)), dtype)
    want_k, want_v = np.asarray(pk, np.float32).copy(), np.asarray(pv, np.float32).copy()
    layer = 1
    for start, n_valid in [((0, 3, 0), (8, 8, 0)), ((8, 11, 0), (8, 5, 0)),
                           ((16, 16, 0), (8, 8, 0)), ((24, 24, 0), (3, 8, 0))]:
        k = rng.standard_normal((B, G, KVH, D)).astype(np.float32)
        v = rng.standard_normal((B, G, KVH, D)).astype(np.float32)
        pk, pv = pa.paged_kv_write(
            pk, pv, layer, jnp.asarray(k), jnp.asarray(v), jnp.asarray(start, jnp.int32),
            jnp.asarray(tables), jnp.asarray(n_valid, jnp.int32), block_size=BS, ring=True,
        )
        for b in range(B):
            for g in range(n_valid[b]):
                p = start[b] + g
                row = tables[b, (p // BS) % R] * BS + p % BS
                want_k[layer, row] = np.asarray(jnp.asarray(k[b, g].reshape(-1), dtype), np.float32)
                want_v[layer, row] = np.asarray(jnp.asarray(v[b, g].reshape(-1), dtype), np.float32)
    got_k, got_v = np.asarray(pk, np.float32), np.asarray(pv, np.float32)
    # the trash block may hold anything; every other row is pinned
    np.testing.assert_array_equal(got_k[:, BS:], want_k[:, BS:])
    np.testing.assert_array_equal(got_v[:, BS:], want_v[:, BS:])
