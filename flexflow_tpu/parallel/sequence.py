"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference has **no** sequence parallelism (SURVEY §2.4: only
``FFIterationConfig::seq_length`` masking, ``include/flexflow/config.h:162``).
The TPU build treats the sequence dim as a first-class shardable dim — the
same ``Repartition``-over-seq the PCG machinery could in principle express —
and supplies the two standard attention realizations:

* **Ring attention** (`ring_attention`): Q stays put; K/V blocks rotate
  around the ICI ring via ``ppermute`` while each step folds one block into
  a running online-softmax (flash-style m/l/o accumulators).  O(S/P) memory
  per chip, P-1 hops of K/V over ICI, compute/comm overlap left to XLA's
  async collective scheduling.
* **Ulysses** (`ulysses_attention`): ``all_to_all`` swaps the sharded dim
  from sequence to heads, runs *local* full-sequence attention on H/P heads,
  and swaps back.  Two all-to-alls, needs ``num_heads % P == 0``.

Both are pure jax (differentiable; the ring scan is wrapped in
``jax.checkpoint`` so the backward pass re-rotates K/V instead of saving
every block — the memory property that makes ring attention worth it).
Both compose with DP and TP: ``batch_axis``/``head_axis`` keep the batch
and head dims sharded inside the shard_map region, and attention-prob
dropout is supported (per-shard independent masks; any i.i.d. mask is a
valid dropout sample, so shard-locality does not change semantics).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec


_NEG = -1e30  # finite mask value: keeps online-softmax nan-free


def _local_sdpa(q, k, v, rng=None, *, causal: bool, dropout_rate: float = 0.0):
    import jax as _jax

    from flexflow_tpu.ops.attention import _flash_ok

    # the Ulysses local step sees FULL sequence length per device — at
    # long context its (S, S) einsum scores hit the same memory wall the
    # global path dispatches around, so apply the same flash policy.
    # Hardware-only: pallas-inside-shard_map is exercised on chip, while
    # CPU test meshes keep the einsum reference path.
    sq, sk, d = q.shape[2], k.shape[2], q.shape[3]
    if _jax.default_backend() == "tpu" and _flash_ok(
        sq, sk, d, q.shape[0] * q.shape[1]
    ):
        from flexflow_tpu.ops.pallas.flash_attention import flash_attention

        seed = (
            _jax.random.randint(rng, (), 0, 2**31 - 1)
            if (rng is not None and dropout_rate > 0.0)
            else 0
        )
        return flash_attention(
            q, k, v, causal=causal, dropout_rate=dropout_rate, seed=seed
        )
    """Full-sequence SDPA on local blocks — same math as the global path
    (ops.attention.sdpa: scale, end-aligned causal tril, prob dropout)."""
    from flexflow_tpu.ops.attention import sdpa

    return sdpa(q, k, v, causal=causal, dropout_rate=dropout_rate, rng=rng)


def _ring_local(q, k, v, rng, *, axis_name: str, axis_size: int, causal: bool,
                dropout_rate: float = 0.0, other_axes=()):
    """Per-shard ring attention body (runs under shard_map).

    q/k/v: (B, H, S_local, D).  Rotates K/V blocks ``axis_size`` times with
    ``ppermute``; block arriving at step i originated on device
    (my_index - i) mod P, which fixes its global key positions for the
    causal mask.  Dropout (flash-style): the softmax denominator ``l``
    accumulates undropped probabilities; only the value accumulation ``o``
    sees the dropped/rescaled ones.
    """
    my = jax.lax.axis_index(axis_name)
    scale = 1.0 / math.sqrt(q.shape[-1])
    sq, sk = q.shape[2], k.shape[2]
    # end-aligned global causal positions (matches ops.attention.sdpa's
    # tril(k=Sk-Sq)): query i attends key j <= i + (Sk_global - Sq_global)
    q_pos = my * sq + jnp.arange(sq) + (sk - sq) * axis_size
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
    if rng is not None:
        rng = _fold_shard(rng, axis_name, other_axes)

    def fold(o, m, l, kb, vb, i):
        """Fold one K/V block into the online-softmax accumulators."""
        src = (my - i) % axis_size
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kb) * scale
        keep = None
        if causal:
            k_pos = src * sk + jnp.arange(sk)
            keep = (q_pos[:, None] >= k_pos[None, :])[None, None]
            s = jnp.where(keep, s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if causal:
            p = jnp.where(keep, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        p_o = p
        if dropout_rate > 0.0 and rng is not None:
            kr = 1.0 - dropout_rate
            p_o = p * jax.random.bernoulli(
                jax.random.fold_in(rng, i), kr, p.shape
            ) / kr
        o = o * alpha + jnp.einsum("bhqk,bhkd->bhqd", p_o, vb)
        return o, m_new, l

    def step(carry, i):
        o, m, l, kb, vb = carry
        o, m, l = fold(o, m, l, kb, vb, i)
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        return (o, m, l, kb, vb), None

    b, h, _, d = q.shape
    dv = v.shape[-1]
    o0 = jnp.zeros((b, h, sq, dv), dtype=jnp.float32)
    m0 = jnp.full((b, h, sq, 1), _NEG, dtype=jnp.float32)
    l0 = jnp.zeros((b, h, sq, 1), dtype=jnp.float32)
    # scan does axis_size-1 (fold + rotate) rounds; the last arriving block
    # is folded outside so no dead final K/V rotation rides the ICI ring
    (o, m, l, kb, vb), _ = jax.lax.scan(
        step, (o0, m0, l0, k, v), jnp.arange(axis_size - 1)
    )
    o, _, l = fold(o, m, l, kb, vb, axis_size - 1)
    # belt-and-braces NaN guard: l == 0 requires a causal row with zero
    # attendable keys, which the attention op excludes from this path
    # (causal implies sq == sk there); guarded rows would yield zeros,
    # which differs from global sdpa's uniform-softmax limit — hence the
    # exclusion rather than reliance on this guard (round-1 advisor finding)
    return (o / jnp.maximum(l, jnp.finfo(jnp.float32).tiny)).astype(q.dtype)


def _specs(batch_axis, head_axis, axis):
    return PartitionSpec(batch_axis, head_axis, axis, None)


def _fold_shard(rng, axis_name, other_axes):
    """Distinct dropout key per device: fold in the coordinate along the
    seq axis AND every other mesh axis sharding this tensor (batch/head) —
    shards that differ only in DP/TP position must not share masks."""
    rng = jax.random.fold_in(rng, jax.lax.axis_index(axis_name))
    for a in other_axes:
        rng = jax.random.fold_in(rng, jax.lax.axis_index(a))
    return rng


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh,
    axis: str,
    causal: bool = False,
    head_axis: Optional[str] = None,
    batch_axis: Optional[str] = None,
    dropout_rate: float = 0.0,
    rng: Optional[jax.Array] = None,
) -> jax.Array:
    """Sequence-sharded attention over (B, H, S, D) global arrays.

    Shards S (dim 2) over mesh axis ``axis``; K/V blocks ride the ICI ring.
    ``head_axis``/``batch_axis``: mesh axes already sharding the head/batch
    dims (TP/DP composition — keeps them sharded inside the shard_map
    region instead of gathering).  Falls back to local SDPA when the axis
    has size 1.
    """
    axis_size = mesh.shape[axis]
    if axis_size == 1:
        return _local_sdpa(q, k, v, rng, causal=causal, dropout_rate=dropout_rate)
    spec = _specs(batch_axis, head_axis, axis)
    body = jax.checkpoint(
        functools.partial(
            _ring_local, axis_name=axis, axis_size=axis_size, causal=causal,
            dropout_rate=dropout_rate,
            other_axes=tuple(a for a in (batch_axis, head_axis) if a),
        )
    )
    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, spec, PartitionSpec()),
        out_specs=spec, check_vma=False,
    )
    return f(q, k, v, rng)


def _ulysses_local(q, k, v, rng, *, axis_name: str, axis_size: int,
                   causal: bool, dropout_rate: float = 0.0, other_axes=()):
    """all_to_all: (B, H, S/P, D) -> (B, H/P, S, D), local full-seq SDPA,
    then back.  The two transposes are the only collectives."""
    a2a = functools.partial(jax.lax.all_to_all, axis_name=axis_name, tiled=True)
    qh = a2a(q, split_axis=1, concat_axis=2)
    kh = a2a(k, split_axis=1, concat_axis=2)
    vh = a2a(v, split_axis=1, concat_axis=2)
    if rng is not None:
        rng = _fold_shard(rng, axis_name, other_axes)
    out = _local_sdpa(qh, kh, vh, rng, causal=causal, dropout_rate=dropout_rate)
    return a2a(out, split_axis=2, concat_axis=1)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh,
    axis: str,
    causal: bool = False,
    head_axis: Optional[str] = None,
    batch_axis: Optional[str] = None,
    dropout_rate: float = 0.0,
    rng: Optional[jax.Array] = None,
) -> jax.Array:
    """DeepSpeed-Ulysses-style all-to-all sequence parallelism over
    (B, H, S, D): swap seq-sharding for head-sharding, attend locally,
    swap back.  Requires local head count divisible by axis_size."""
    axis_size = mesh.shape[axis]
    if axis_size == 1:
        return _local_sdpa(q, k, v, rng, causal=causal, dropout_rate=dropout_rate)
    h_local = q.shape[1] // (mesh.shape[head_axis] if head_axis else 1)
    if h_local % axis_size != 0:
        raise ValueError(
            f"ulysses needs local heads ({h_local}) divisible by seq-axis size {axis_size}"
        )
    spec = _specs(batch_axis, head_axis, axis)
    body = functools.partial(
        _ulysses_local, axis_name=axis, axis_size=axis_size, causal=causal,
        dropout_rate=dropout_rate,
        other_axes=tuple(a for a in (batch_axis, head_axis) if a),
    )
    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, spec, PartitionSpec()),
        out_specs=spec, check_vma=False,
    )
    return f(q, k, v, rng)
