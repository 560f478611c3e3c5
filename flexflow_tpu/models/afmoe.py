"""Decoder of the ``afmoe`` family (Arcee Trinity): gated grouped-query
attention with window and full layers mixed, a sigmoid-routed mixture
of experts beside a shared expert.

No reference app (FlexFlow 2022 has none of these layers).  Layer ``i``
is sandwich-normed with plain RMSNorm (four norms a layer)::

    x0 = E[id] * sqrt(hidden)
    h  = x + N(Attn_i(N(x)));   y = h + N(FFN_i(N(h)))

``Attn_i`` is :class:`~flexflow_tpu.ops.attention.GatedAttention` with
per-head q/k norm; a ``sliding_attention`` layer carries rotary
positions over the whole head and sees its last ``sliding_window`` keys,
a ``full_attention`` layer carries no positions and sees every earlier
key.  ``FFN_i`` is the dense gated FFN for ``i < num_dense_layers`` and
after that :class:`~flexflow_tpu.ops.moe.RoutedExperts`: sigmoid scores,
top-k of ``score + bias``, the chosen scores renormalised and scaled by
``route_scale``, an ungated shared expert.  The head is untied.

``ServeEngine`` serves what this builds (``models/gpt_decode.py``'s
decoder spec reads the layers and their attrs, not these names).
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

from flexflow_tpu.fftype import DataType
from flexflow_tpu.model import FFModel
from flexflow_tpu.tensor import Tensor


def afmoe_decoder(
    model: FFModel,
    batch: int,
    seq: int,
    hidden: int = 2048,
    heads: int = 32,
    ff_dim: int = 1024,
    num_layers: int = 32,
    vocab: int = 200192,
    kv_heads: int = 4,
    head_dim: int = 128,
    dense_ff_dim: int = 6144,
    num_dense_layers: int = 2,
    num_experts: int = 128,
    top_k: int = 8,
    shared_ff_dim: int = 1024,
    layer_types: Optional[Sequence[str]] = None,
    sliding_window: int = 2048,
    global_attn_every_n_layers: int = 4,
    rope_theta: float = 10000.0,
    score_func: str = "sigmoid",
    route_norm: bool = True,
    route_scale: float = 2.826,
    eps: float = 1e-5,
    use_flash: bool = True,
) -> Tensor:
    """Build the causal LM into ``model``; returns next-token softmax
    (batch, seq, vocab).  ``ff_dim`` is one routed expert's width;
    ``layer_types`` defaults to ``global_attn_every_n_layers - 1``
    sliding layers before every full one.  ``sliding_window`` 0 makes
    every layer attend its whole context (a planted fault of the
    benchmark: the sliding layers keep their rotary positions)."""
    if layer_types is None:
        layer_types = [
            "full_attention" if (i + 1) % global_attn_every_n_layers == 0
            else "sliding_attention" for i in range(num_layers)
        ]
    assert len(layer_types) == num_layers, (len(layer_types), num_layers)
    ids = model.create_tensor((batch, seq), DataType.INT32, name="token_ids")
    t = model.embedding(ids, vocab, hidden, name="tok_embed")
    t = model.scalar_multiply(t, math.sqrt(hidden), name="embed_scale")
    for i, kind in enumerate(layer_types):
        assert kind in ("sliding_attention", "full_attention"), kind
        sliding = kind == "sliding_attention"
        h = model.rms_norm(t, eps, name=f"l{i}_ln_in")
        h = model.gated_attention(
            h, heads, kv_heads, head_dim, head_dim if sliding else 0, rope_theta,
            eps, use_flash=use_flash, window=sliding_window if sliding else 0,
            zero_centered=False, name=f"l{i}_attn",
        )
        h = model.rms_norm(h, eps, name=f"l{i}_ln_post_attn")
        t = model.add(h, t, name=f"l{i}_res0")
        h = model.rms_norm(t, eps, name=f"l{i}_ln_pre_mlp")
        if i < num_dense_layers:
            h = model.gated_ffn(h, dense_ff_dim, name=f"l{i}_ffn")
        else:
            h = model.routed_experts(
                h, num_experts, top_k, ff_dim, shared_hidden=shared_ff_dim,
                score=score_func, route_norm=route_norm, route_scale=route_scale,
                router_bias=True, shared_gated=False, name=f"l{i}_moe",
            )
        h = model.rms_norm(h, eps, name=f"l{i}_ln_post_mlp")
        t = model.add(h, t, name=f"l{i}_res1")
    t = model.rms_norm(t, eps, name="final_norm")
    t = model.dense(t, vocab, use_bias=False, name="lm_head")
    return model.softmax(t, name="lm_softmax")
