"""Decoder-hybrid-decoder of the ``phi4flash`` family (Microsoft
Phi-4-mini-flash-reasoning, the SambaY architecture of "Decoder-Hybrid-
Decoder Architecture for Efficient Reasoning with Long Generation").

No reference app (FlexFlow 2022 has none of these layers).  Every layer
is pre-norm, LayerNorm with a bias, with two branches::

    x0 = E[id];   x <- x + Mixer_l(N1_l(x));   x <- x + MLP_l(N2_l(x))
    logits = N_f(x_L) E^T                       (head tied, no positions)

``MLP`` is the dense gated FFN (:class:`~flexflow_tpu.ops.moe.GatedFFN`).
With ``half = num_layers / 2`` the mixer of layer ``l`` is

* ``l <= half``, every ``mb_per_layer``-th layer from 0: Mamba-1
  (:class:`~flexflow_tpu.ops.ssm.Mamba1Mixer`); the one at ``half``
  exports its gated scan output ``m``, the memory;
* ``l < half`` otherwise: differential attention over a window of
  ``sliding_window`` keys;
* ``l = half + 1``: differential attention over every earlier key, whose
  K/V are the one cache the layers after it read;
* ``l > half + 1``, even: a gated memory unit over ``m``
  (:class:`~flexflow_tpu.ops.ssm.GatedMemoryUnit`);
* ``l > half + 1``, odd: differential cross-attention, its own query
  and output projections over layer ``half + 1``'s K/V
  (:class:`~flexflow_tpu.ops.attention.DifferentialAttention`).

Layers ``0 .. half + 1`` are the self-decoder, the rest the cross-
decoder: it writes no cache, so a prefill runs it on the rows whose
logits are needed only (``serve/programs.py``).

Three planted faults of the benchmark come through the arguments:
``lambda_scale`` 0 (no second softmax map subtracted), ``sliding_window``
0 (the window layers see every key) and ``gmu_ones`` (the memory units
read ones instead of ``m``).

``ServeEngine`` serves what this builds (``models/gpt_decode.py``'s
decoder spec reads the layers and their attrs, not these names).
"""

from __future__ import annotations

from flexflow_tpu.fftype import DataType
from flexflow_tpu.model import FFModel
from flexflow_tpu.tensor import Tensor


def layer_kind(l: int, num_layers: int, mb_per_layer: int = 2) -> str:
    """The mixer of layer ``l``: ``M`` (Mamba-1; ``m`` where it exports
    the memory), ``S`` (window attention), ``F`` (full attention, the
    shared cache), ``G`` (gated memory unit) or ``C`` (cross-attention)."""
    half = num_layers // 2
    if l <= half:
        if l % mb_per_layer == 0:
            return "m" if l == half else "M"
        return "S"
    if l == half + 1:
        return "F"
    return "G" if l % 2 == 0 else "C"


def phi4flash_decoder(
    model: FFModel,
    batch: int,
    seq: int,
    hidden: int = 2560,
    heads: int = 40,
    kv_heads: int = 20,
    ff_dim: int = 10240,
    num_layers: int = 32,
    vocab: int = 200064,
    sliding_window: int = 512,
    d_inner: int = 5120,
    state_size: int = 16,
    dt_rank: int = 160,
    conv_kernel: int = 4,
    mb_per_layer: int = 2,
    eps: float = 1e-5,
    lambda_scale: float = 1.0,
    gmu_ones: bool = False,
) -> Tensor:
    """Build the causal LM into ``model``; returns next-token softmax
    (batch, seq, vocab).  Head size is ``hidden / heads``."""
    assert num_layers % 2 == 0 and num_layers >= 4, num_layers
    hd = hidden // heads
    ids = model.create_tensor((batch, seq), DataType.INT32, name="token_ids")
    x = model.embedding(ids, vocab, hidden, name="tok_embed")
    embedded, mem, kv = x, None, None
    for l in range(num_layers):
        kind = layer_kind(l, num_layers, mb_per_layer)
        h = model.layer_norm(x, [-1], eps=eps, name=f"l{l}_ln1")
        if kind in "Mm":
            h = model.mamba1_mixer(h, d_inner, state_size, dt_rank, conv_kernel,
                                   exports_memory=kind == "m", name=f"l{l}_mamba")
            if kind == "m":
                h, mem = h
        elif kind == "G":
            h = model.gated_memory(h, mem, memory_ones=gmu_ones, name=f"l{l}_gmu")
        else:
            diff = dict(num_heads=heads, num_kv_heads=kv_heads, head_dim=hd, layer_idx=l,
                        eps=eps, lambda_scale=lambda_scale)
            if kind == "S":
                h = model.differential_attention(h, window=sliding_window, **diff,
                                                 name=f"l{l}_attn")
            elif kind == "F":
                h, kv = model.differential_attention(h, exports_kv=True, **diff,
                                                     name=f"l{l}_attn")
            else:
                h = model.differential_attention(h, kv=kv, **diff, name=f"l{l}_xattn")
        x = model.add(h, x, name=f"l{l}_res0")
        h = model.layer_norm(x, [-1], eps=eps, name=f"l{l}_ln2")
        h = model.gated_ffn(h, ff_dim, name=f"l{l}_mlp")
        x = model.add(h, x, name=f"l{l}_res1")
    x = model.layer_norm(x, [-1], eps=eps, name="final_ln")
    x = model.tied_head(x, embedded, name="lm_head")
    return model.softmax(x, name="lm_softmax")
