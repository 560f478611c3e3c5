"""InfLLM-v2 block-sparse attention (MiniCPM4's ``sparse_config``).

No reference analog (FlexFlow 2022 attends every earlier key).  A query
reads the keys of at most ``topk`` blocks of ``block`` positions, chosen
per K/V head from a compressed-key index::

    K_j   = mean(k[stride j .. stride j + kernel - 1])   (complete kernels only)
    r_b   = sum over the K/V head's query heads of
            max over the kernels j that overlap block b of softmax_j(q . K_j / sqrt(d))
    I     = block 0, the blocks that hold positions t - window + 1 .. t,
            and the best-scoring other complete blocks, ``topk`` in all
    out   = softmax over the keys of I up to t of (q . k / sqrt(d)) v

A query whose context (its own position included) is at most
``dense_len`` attends densely.  The projections, the per-head q/k RMS
norm and the sigmoid output gate are :class:`GatedAttention`'s; the
layer carries no positions.

The functions here are the ONE rule the op's forward, the serve programs
(``serve/programs.py``: the index a lane keeps, decode one query at a
time, prefill an aligned block of queries at a time) and the tests use:

* :func:`compress_keys` -- the index of whole sequences;
* :func:`kernels_at` -- how many index entries exist once position ``t``
  is written;
* :func:`block_scores` -- ``r_b`` for query rows against an index;
* :func:`select_blocks` -- the selected set, as a mask over blocks, of a
  unit of queries (one query, or a block of them sharing one selection);
* :func:`selected_table` -- a mask's blocks in order, the table the paged
  kernel walks (``ops/pallas/paged_attention.py::paged_sparse_attention``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from flexflow_tpu.fftype import OperatorType
from flexflow_tpu.ops.attention import GatedAttention
from flexflow_tpu.ops.base import OpContext, register_op
from flexflow_tpu.ops.norm import rms_norm_f32

def check_sizes(a: dict) -> None:
    """The relations between the sparse sizes that the rule relies on."""
    k, s, b = a["kernel"], a["stride"], a["block"]
    for ok, why in (
        (k % s == 0 and b % s == 0, "kernel and block whole strides"),
        (a["window"] % b == 0 and a["dense_len"] % b == 0, "window and dense_len whole blocks"),
        (a["topk"] > a["window"] // b + 1, "a top-k that leaves room past the forced blocks"),
        (a["dense_len"] >= a["topk"] * b, "more blocks before a sparse query than it reads"),
    ):
        if not ok:
            raise ValueError(f"sparse sizes {a}: need {why}")


def kernels_at(t, kernel: int, stride: int):
    """Index entries complete once position ``t`` is written."""
    return jnp.maximum((t + 1 - kernel) // stride + 1, 0)


def compress_keys(k, kernel: int, stride: int, n: int):
    """``k`` (b, s, g, d) -> the first ``n`` index entries (b, n, g, d)
    float32: entry ``j`` the mean of ``k`` over positions ``stride j ..
    stride j + kernel - 1`` (positions past ``s`` read zero: such an
    entry is never complete, so never read)."""
    b, s, g, d = k.shape
    m = kernel // stride
    k = jnp.pad(k.astype(jnp.float32), ((0, 0), (0, (n + m) * stride - s), (0, 0), (0, 0)))
    c = k[:, :(n + m) * stride].reshape(b, n + m, stride, g, d).sum(2)
    return sum(c[:, i:i + n] for i in range(m)) / kernel


def block_scores(q, index, n_kernels, *, kernel: int, stride: int, block: int):
    """``q`` (B, R, H, d) query rows, ``index`` (B, J, g, d) their lane's
    compressed keys, ``n_kernels`` (B, R) entries that exist for each row.
    Returns ``r`` (B, R, g, J * stride / block) float32: a block's score
    is the sum over the K/V head's query heads of the largest
    probability among the kernels that overlap it (absent ones read 0)."""
    B, R, H, d = q.shape
    J, g = index.shape[1], index.shape[2]
    rep, r, m = H // g, block // stride, kernel // stride
    s = jnp.einsum(
        "brgkd,bjgd->brgkj", q.reshape(B, R, g, rep, d), index.astype(q.dtype),
        preferred_element_type=jnp.float32,
    ) * (1.0 / math.sqrt(d))
    live = jnp.arange(J) < n_kernels[:, :, None, None, None]
    p = jnp.where(live, jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1), 0.0)
    # block b overlaps kernels r b - (m - 1) .. r b + r - 1: index o of the
    # padded row is kernel o - (m - 1)
    nb = J // r
    pp = jnp.pad(p, ((0, 0),) * 4 + ((m - 1, r),))
    mx = functools.reduce(jnp.maximum, [pp[..., o:o + r * nb:r] for o in range(r + m - 1)])
    return mx.sum(axis=3)


def select_blocks(score, t_first, t_last, *, block: int, topk: int, window: int,
                  dense_len: int):
    """The selected blocks, a mask (..., NB), of a unit of queries at
    positions ``t_first .. t_last`` (...,) in one block that share a
    selection (one query: both the same) scored ``score`` (..., NB): block
    0, the blocks that hold ``t_first - window + 1 .. t_last``, and the
    best-scoring complete blocks before them, ``topk`` in all; every block
    up to ``t_last``'s where ``t_last + 1 <= dense_len``."""
    nb = score.shape[-1]
    b = jnp.arange(nb)
    c = (t_last // block)[..., None]
    lo = (jnp.maximum(t_first - window + 1, 0) // block)[..., None]
    sel = (b == 0) | ((b >= lo) & (b <= c))
    k = topk - 1 - window // block  # the most a unit takes by score
    n_top = topk - jnp.sum(sel, axis=-1, keepdims=True)
    vals, idx = jax.lax.top_k(jnp.where((b >= 1) & (b < lo), score, -jnp.inf), k)
    keep = (jnp.arange(k) < n_top) & (vals > -jnp.inf)
    sel |= jnp.any((idx[..., :, None] == b) & keep[..., :, None], axis=-2)
    return jnp.where((t_last + 1 <= dense_len)[..., None], b <= c, sel)


def selected_table(sel, width: int):
    """A mask over blocks (..., NB) -> ``(table (..., width) int32, n)``:
    the selected blocks in increasing order, then zeros; ``n`` of them."""
    order = jnp.argsort((~sel).astype(jnp.int32), axis=-1, stable=True)[..., :width]
    n = jnp.sum(sel, axis=-1).astype(jnp.int32)
    return jnp.where(jnp.arange(width) < n[..., None], order, 0).astype(jnp.int32), n


def sparse_attention(q, k, v, a: dict):
    """The rule over whole sequences, a selection per query: ``q`` (b, s,
    H, d), ``k``, ``v`` (b, s, g, d) -> (b, s, H, d) float32.  Plain XLA,
    every score held: for the op's forward and the tests at small sizes."""
    b, s, H, d = q.shape
    g = k.shape[2]
    blk, st = a["block"], a["stride"]
    nb = -(-s // blk)
    index = compress_keys(k, a["kernel"], st, nb * blk // st)
    t = jnp.broadcast_to(jnp.arange(s), (b, s))
    score = block_scores(q, index, kernels_at(t, a["kernel"], st),
                         kernel=a["kernel"], stride=st, block=blk)
    sel = select_blocks(score, t[..., None], t[..., None], block=blk, topk=a["topk"],
                        window=a["window"], dense_len=a["dense_len"])  # (b, s, g, nb)
    kpos = jnp.arange(s)
    seen = jnp.take(sel, kpos // blk, axis=-1) & (kpos <= t[..., None, None])  # (b, s, g, s)
    rep = H // g
    scores = jnp.einsum("bsgrd,btgd->bgrst", q.reshape(b, s, g, rep, d).astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(d)
    scores = jnp.where(jnp.moveaxis(seen, 2, 1)[:, :, None], scores, -jnp.inf)
    o = jnp.einsum("bgrst,btgd->bsgrd", jax.nn.softmax(scores, axis=-1), v.astype(jnp.float32))
    return o.reshape(b, s, H, d)


class SparseBlockAttention(GatedAttention):
    """Causal grouped-query self-attention whose queries read the blocks
    :func:`select_blocks` picks (the ``minicpm4`` mixer).  Input (B, S,
    E) -> (B, S, E).  Weights and projections :class:`GatedAttention`'s
    (``wq`` per head the query's columns and then the gate's; no
    rotary); attrs ``num_heads``, ``num_kv_heads``, ``head_dim``,
    ``eps``, ``zero_centered`` and the sparse sizes ``kernel``,
    ``stride``, ``block``, ``topk``, ``window``, ``dense_len``."""

    op_type = OperatorType.SPARSE_ATTENTION

    def forward(self, layer, params, inputs, ctx: OpContext):
        x = inputs[0]
        a = layer.attrs
        h, kv, d = a["num_heads"], a["num_kv_heads"], a["head_dim"]
        b, s, _ = x.shape
        with jax.named_scope("ff.sparse"):
            qg = (x @ params["wq"]).reshape(b, s, h, 2 * d)
            q, gate = qg[..., :d], qg[..., d:]
            q = rms_norm_f32(q, params["q_norm"], a["eps"]).astype(x.dtype)
            k = rms_norm_f32((x @ params["wk"]).reshape(b, s, kv, d), params["k_norm"],
                             a["eps"]).astype(x.dtype)
            v = (x @ params["wv"]).reshape(b, s, kv, d)
            out = sparse_attention(q, k, v, a).reshape(b, s, h * d)
            out = out * jax.nn.sigmoid(gate.reshape(b, s, h * d).astype(jnp.float32))
            return [out.astype(x.dtype) @ params["wo"]]

    def flops(self, layer) -> float:
        b, s, e = layer.inputs[0].shape
        a = layer.attrs
        h, kv, d = a["num_heads"], a["num_kv_heads"], a["head_dim"]
        proj = 2.0 * b * s * e * (2 * h * d + 2 * kv * d + h * d)
        seen = min(s, a["topk"] * a["block"])  # keys a late query reads
        index = 2.0 * b * s * h * d * (s // a["stride"])  # scores over the index
        return proj + index + 4.0 * b * h * s * seen * d / 2


register_op(SparseBlockAttention())
