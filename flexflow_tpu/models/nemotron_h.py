"""Hybrid decoder of the ``nemotron_h`` family (NVIDIA Nemotron-H /
Nemotron 3): Mamba-2 state-space layers, grouped-query attention layers
and sparse-MoE layers, each layer ONE mixer.

No reference app (FlexFlow 2022 has none of these layers).  Layer ``i``
is pre-norm with plain RMSNorm and holds one mixer, by the letter of
``pattern`` at ``i``::

    x0 = E[id];   x <- x + Mixer_i(N_i(x));   logits = N_f(x_L) W_head

``M``: :class:`~flexflow_tpu.ops.ssm.Mamba2Mixer`.  ``*``: causal
grouped-query attention with no biases, no gate, no q/k norm and no
positional encoding (:class:`~flexflow_tpu.ops.attention.MultiHeadAttention`
with ``num_kv_heads``).  ``E``: one *share* of the published MoE block
(:class:`~flexflow_tpu.ops.moe.RoutedExperts`): sigmoid scores over
``router_experts``, top-k of ``score + bias``, the chosen scores
renormalised and scaled by ``route_scale``, ungated ``relu2`` experts
(``expert_act`` ``relu``: plain ReLU, a planted fault of the benchmark)
of which this builder holds ``held_experts`` from ``first_expert`` on,
and an ungated shared expert of the same form.  ``vocab`` is the rows of the embedding and of the (untied)
head held here.

``ServeEngine`` serves what this builds (``models/gpt_decode.py``'s
decoder spec reads the layers and their attrs, not these names).
"""

from __future__ import annotations

from typing import Optional

from flexflow_tpu.fftype import DataType
from flexflow_tpu.model import FFModel
from flexflow_tpu.tensor import Tensor


def nemotron_h_decoder(
    model: FFModel,
    batch: int,
    seq: int,
    hidden: int = 2688,
    heads: int = 32,
    ff_dim: int = 1856,
    num_layers: int = 52,
    vocab: int = 131072,
    pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    kv_heads: int = 2,
    head_dim: int = 128,
    mamba_heads: int = 64,
    mamba_head_dim: int = 64,
    n_groups: int = 8,
    state_size: int = 128,
    conv_kernel: int = 4,
    chunk: int = 128,
    router_experts: int = 128,
    first_expert: int = 0,
    held_experts: Optional[int] = None,
    top_k: int = 6,
    shared_ff_dim: int = 3712,
    route_norm: bool = True,
    route_scale: float = 2.5,
    expert_act: str = "relu2",
    eps: float = 1e-5,
    use_flash: bool = True,
) -> Tensor:
    """Build the causal LM into ``model``; returns next-token softmax
    (batch, seq, vocab).  ``ff_dim`` is one routed expert's width;
    ``pattern`` has one letter a layer."""
    assert len(pattern) == num_layers, (pattern, num_layers)
    assert expert_act in ("relu2", "relu"), expert_act
    ids = model.create_tensor((batch, seq), DataType.INT32, name="token_ids")
    t = model.embedding(ids, vocab, hidden, name="tok_embed")
    for i, kind in enumerate(pattern):
        h = model.rms_norm(t, eps, name=f"l{i}_norm")
        if kind == "M":
            h = model.mamba2_mixer(
                h, mamba_heads, mamba_head_dim, n_groups, state_size,
                conv_kernel, chunk, eps, name=f"l{i}_mamba",
            )
        elif kind == "*":
            h = model.multihead_attention(
                h, h, h, hidden, heads, kdim=head_dim, vdim=head_dim, causal=True,
                use_flash=use_flash, num_kv_heads=kv_heads, name=f"l{i}_attn",
            )
        elif kind == "E":
            h = model.routed_experts(
                h, router_experts, top_k, ff_dim, first_expert=first_expert,
                held=held_experts, shared_hidden=shared_ff_dim, score="sigmoid",
                route_norm=route_norm, route_scale=route_scale, router_bias=True,
                shared_gated=False, expert_form=expert_act, name=f"l{i}_moe",
            )
        else:
            raise ValueError(f"pattern letter {kind!r} at layer {i}: M | * | E")
        t = model.add(h, t, name=f"l{i}_res")
    t = model.rms_norm(t, eps, name="norm_f")
    t = model.dense(t, vocab, use_bias=False, name="lm_head")
    return model.softmax(t, name="lm_softmax")
