"""The ``minicpm_sala`` decoder (OpenBMB MiniCPM-SALA): sparse and
linear attention side by side, MiniCPM's muP scalings on the stream.

No reference app (FlexFlow 2022 has none of these layers).  Every layer
is pre-norm (RMSNorm) with two branches, each scaled before it joins the
stream::

    x0 = scale_emb * E[id]
    x <- x + (scale_depth / sqrt(L)) Mixer_l(N1_l(x))
    x <- x + (scale_depth / sqrt(L)) MLP_l(N2_l(x))      MLP: SiLU-gated
    logits = W_head(N_f(x) / (hidden / dim_model_base))

``L`` is the PUBLISHED depth, whatever share of it is built.  The mixer
of layer ``l`` is ``mixer_types[l]``: ``minicpm4`` -- InfLLM-v2 block-
sparse attention (:class:`~flexflow_tpu.ops.sparse_attention.
SparseBlockAttention`, no positions) -- or ``lightning-attn`` -- linear
attention with a fixed decay a head that depends on ``l`` and ``L``
(:class:`~flexflow_tpu.ops.linear_attention.LightningAttention`, rotary
positions).  ``layer_ids`` are the published indices of the layers
built, in order: a cut keeps each layer's kind and decay.

``ServeEngine`` serves what this builds (``models/gpt_decode.py``'s
decoder spec reads the layers and their attrs, not these names).
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

from flexflow_tpu.fftype import DataType
from flexflow_tpu.model import FFModel
from flexflow_tpu.tensor import Tensor

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def minicpm_sala_decoder(
    model: FFModel,
    batch: int,
    seq: int,
    hidden: int = 4096,
    heads: int = 32,
    kv_heads: int = 2,
    head_dim: int = 128,
    ff_dim: int = 16384,
    vocab: int = 73448,
    num_layers: int = 32,
    mixer_types: Sequence[str] = (),
    layer_ids: Optional[Sequence[int]] = None,
    lightning_heads: int = 32,
    lightning_head_dim: int = 128,
    rope_theta: float = 10000.0,
    eps: float = 1e-6,
    scale_emb: float = 12.0,
    scale_depth: float = 1.4,
    dim_model_base: int = 256,
    kernel: int = 32,
    stride: int = 16,
    block: int = 64,
    topk: int = 64,
    window: int = 2048,
    dense_len: int = 8192,
) -> Tensor:
    """Build the causal LM into ``model``; returns next-token softmax
    (batch, seq, vocab).  ``num_layers`` is the published depth, of which
    ``mixer_types`` names every layer's kind."""
    L = num_layers
    assert len(mixer_types) == L, (len(mixer_types), L)
    ids_ = list(range(L)) if layer_ids is None else [int(l) for l in layer_ids]
    assert ids_ and all(0 <= l < L for l in ids_), (ids_, L)
    branch = scale_depth / math.sqrt(L)
    ids = model.create_tensor((batch, seq), DataType.INT32, name="token_ids")
    x = model.embedding(ids, vocab, hidden, name="tok_embed")
    x = model.scalar_multiply(x, scale_emb, name="embed_scale")
    for l in ids_:
        h = model.rms_norm(x, eps, name=f"l{l}_norm1")
        if mixer_types[l] == SPARSE:
            h = model.sparse_attention(
                h, heads, kv_heads, head_dim, kernel=kernel, stride=stride, block=block,
                topk=topk, window=window, dense_len=dense_len, eps=eps, name=f"l{l}_attn",
            )
        elif mixer_types[l] == LIGHTNING:
            h = model.lightning_attention(
                h, lightning_heads, lightning_head_dim, l, L, rope_theta=rope_theta,
                eps=eps, name=f"l{l}_lightning",
            )
        else:
            raise ValueError(f"mixer type {mixer_types[l]!r} of layer {l}: {SPARSE} | {LIGHTNING}")
        h = model.scalar_multiply(h, branch, name=f"l{l}_mix_scale")
        x = model.add(h, x, name=f"l{l}_res0")
        h = model.rms_norm(x, eps, name=f"l{l}_norm2")
        h = model.gated_ffn(h, ff_dim, name=f"l{l}_mlp")
        h = model.scalar_multiply(h, branch, name=f"l{l}_mlp_scale")
        x = model.add(h, x, name=f"l{l}_res1")
    x = model.rms_norm(x, eps, name="norm_f")
    x = model.scalar_multiply(x, dim_model_base / hidden, name="head_scale")
    x = model.dense(x, vocab, use_bias=False, name="lm_head")
    return model.softmax(x, name="lm_softmax")
