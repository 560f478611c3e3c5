"""Hybrid decoder of the Qwen3-Next family: Gated-DeltaNet linear
attention beside gated softmax attention, a sparse-MoE block after
every mixer.

No reference app (FlexFlow 2022 has neither layer).  Layer ``i`` is
pre-norm with zero-centred RMSNorm::

    h = x + Mixer_i(N(x));   y = h + MoE(N(h))

``Mixer_i`` is full (gated, grouped-query, partly rotary) attention
where ``(i + 1) % full_attention_interval == 0`` and the linear layer
otherwise.  The MoE block is one *share* of the published one: the
router covers ``router_experts``, this builder holds ``held_experts``
of them from ``first_expert`` on, plus the shared expert
(``ops/moe.py::RoutedExperts``).  ``vocab`` likewise is the rows of the
embedding and of the (untied) head held here.  The multi-token-
prediction module of the published model is not built.
"""

from __future__ import annotations

from typing import Optional

from flexflow_tpu.fftype import DataType
from flexflow_tpu.model import FFModel
from flexflow_tpu.tensor import Tensor


def qwen3_next_decoder(
    model: FFModel,
    batch: int,
    seq: int,
    hidden: int = 2048,
    heads: int = 16,
    ff_dim: int = 512,
    num_layers: int = 48,
    vocab: int = 151936,
    kv_heads: int = 2,
    head_dim: int = 256,
    rotary_dim: int = 64,
    rope_theta: float = 1e7,
    linear_k_heads: int = 16,
    linear_v_heads: int = 32,
    linear_k_dim: int = 128,
    linear_v_dim: int = 128,
    conv_kernel: int = 4,
    router_experts: int = 512,
    first_expert: int = 0,
    held_experts: Optional[int] = None,
    top_k: int = 10,
    shared_ff_dim: int = 512,
    full_attention_interval: int = 4,
    eps: float = 1e-6,
    use_flash: bool = True,
) -> Tensor:
    """Build the causal LM into ``model``; returns next-token softmax
    (batch, seq, vocab).  ``fit`` takes labels (n, seq): the ids shifted
    by one.  ``ff_dim`` is one routed expert's width."""
    ids = model.create_tensor((batch, seq), DataType.INT32, name="token_ids")
    t = model.embedding(ids, vocab, hidden, name="tok_embed")
    for i in range(num_layers):
        h = model.rms_norm(t, eps, zero_centered=True, name=f"l{i}_ln0")
        if (i + 1) % full_attention_interval == 0:
            h = model.gated_attention(
                h, heads, kv_heads, head_dim, rotary_dim, rope_theta, eps,
                use_flash=use_flash, name=f"l{i}_attn",
            )
        else:
            h = model.gated_delta_net(
                h, linear_k_heads, linear_v_heads, linear_k_dim, linear_v_dim,
                conv_kernel, eps, name=f"l{i}_gdn",
            )
        t = model.add(h, t, name=f"l{i}_res0")
        h = model.rms_norm(t, eps, zero_centered=True, name=f"l{i}_ln1")
        h = model.routed_experts(
            h, router_experts, top_k, ff_dim, first_expert=first_expert,
            held=held_experts, shared_hidden=shared_ff_dim, name=f"l{i}_moe",
        )
        t = model.add(h, t, name=f"l{i}_res1")
    t = model.rms_norm(t, eps, zero_centered=True, name="final_norm")
    t = model.dense(t, vocab, use_bias=False, name="lm_head")
    return model.softmax(t, name="lm_softmax")
