"""Transformer encoder (BERT-style).

Reference app: ``examples/cpp/Transformer/transformer.cc:33-75`` —
``create_attention_encoder``: per layer MultiHeadAttention + two dense
layers; the reference feeds a (batch, seq, hidden) input tensor directly
(no tokenizer) and trains with MSE against random labels; we default to a
token-embedding front end + classifier head so the model is also usable for
real LM-style tasks, with ``raw_input=True`` matching the reference shape
exactly.
"""

from __future__ import annotations

from typing import Optional

from flexflow_tpu.fftype import ActiMode, DataType
from flexflow_tpu.model import FFModel
from flexflow_tpu.tensor import Tensor


def encoder_layer(
    model: FFModel,
    t: Tensor,
    hidden: int,
    heads: int,
    ff_dim: int,
    dropout: float = 0.0,
    causal: bool = False,
    use_flash: bool = True,
    name: str = "enc",
) -> Tensor:
    """Post-LN encoder block (attention -> add&norm -> FFN -> add&norm),
    matching the reference's attention+dense+dense structure
    (``transformer.cc:33-55``) plus the layer norms BERT requires."""
    attn = model.multihead_attention(
        t, t, t, hidden, heads, dropout=dropout, causal=causal,
        use_flash=use_flash, name=f"{name}_attn",
    )
    t = model.add(attn, t, name=f"{name}_res0")
    t = model.layer_norm(t, axes=[-1], name=f"{name}_ln0")
    ff = model.dense(t, ff_dim, ActiMode.GELU, name=f"{name}_ff0")
    ff = model.dense(ff, hidden, name=f"{name}_ff1")
    if dropout > 0.0:
        ff = model.dropout(ff, dropout, name=f"{name}_drop")
    t = model.add(ff, t, name=f"{name}_res1")
    t = model.layer_norm(t, axes=[-1], name=f"{name}_ln1")
    return t


def transformer_encoder(
    model: FFModel,
    batch: int,
    seq: int,
    hidden: int = 768,
    heads: int = 12,
    ff_dim: int = 3072,
    num_layers: int = 12,
    vocab: int = 32000,
    num_classes: Optional[int] = None,
    dropout: float = 0.0,
    causal: bool = False,
    use_flash: bool = True,
    raw_input: bool = False,
) -> Tensor:
    """Build a full encoder into ``model``; returns the logits tensor
    (pre-softmax output of the classifier / LM head)."""
    if raw_input:
        t = model.create_tensor((batch, seq, hidden), name="embeddings")
    else:
        ids = model.create_tensor((batch, seq), DataType.INT32, name="token_ids")
        t = model.embedding(ids, vocab, hidden, name="tok_embed")
        pos = model.create_tensor((batch, seq, hidden), name="pos_embed")
        t = model.add(t, pos, name="embed_add")
    for i in range(num_layers):
        t = encoder_layer(
            model, t, hidden, heads, ff_dim, dropout, causal, use_flash, name=f"enc{i}"
        )
    if num_classes is not None:
        # pooled classification head (BERT CLS-style: mean-pool)
        t = model.reduce_mean(t, axes=[1], name="pool")
        t = model.dense(t, num_classes, name="cls_head")
        t = model.softmax(t, name="cls_softmax")
    else:
        # LM head over vocab (reshaped to (batch*seq, vocab) for the loss)
        t = model.dense(t, vocab, name="lm_head")
        t = model.reshape(t, (batch * seq, vocab), name="lm_flatten")
        t = model.softmax(t, name="lm_softmax")
    return t


def decoder_layer(
    model: FFModel,
    t: Tensor,
    hidden: int,
    heads: int,
    ff_dim: int,
    dropout: float = 0.0,
    use_flash: bool = True,
    name: str = "dec",
) -> Tensor:
    """Pre-LN causal decoder block (GPT-2 style: ln -> attn -> res,
    ln -> FFN -> res).  Same op vocabulary as the reference's encoder
    (``transformer.cc:33-55``) with causal masking — the causal core
    dispatches to the flash kernel / ring attention like any other
    attention, so the long-context path covers decoders too."""
    h = model.layer_norm(t, axes=[-1], name=f"{name}_ln0")
    attn = model.multihead_attention(
        h, h, h, hidden, heads, dropout=dropout, causal=True,
        use_flash=use_flash, name=f"{name}_attn",
    )
    t = model.add(attn, t, name=f"{name}_res0")
    h = model.layer_norm(t, axes=[-1], name=f"{name}_ln1")
    ff = model.dense(h, ff_dim, ActiMode.GELU, name=f"{name}_ff0")
    ff = model.dense(ff, hidden, name=f"{name}_ff1")
    if dropout > 0.0:
        ff = model.dropout(ff, dropout, name=f"{name}_drop")
    return model.add(ff, t, name=f"{name}_res1")


def gpt_decoder(
    model: FFModel,
    batch: int,
    seq: int,
    hidden: int = 768,
    heads: int = 12,
    ff_dim: int = 3072,
    num_layers: int = 12,
    vocab: int = 50257,
    dropout: float = 0.0,
    use_flash: bool = True,
) -> Tensor:
    """Causal LM (GPT-2 style): token embedding + learned positional
    parameter, pre-LN causal blocks, final LN, tied-shape LM head.
    Returns next-token softmax reshaped to (batch*seq, vocab) for the
    sparse-CCE loss."""
    ids = model.create_tensor((batch, seq), DataType.INT32, name="token_ids")
    t = model.embedding(ids, vocab, hidden, name="tok_embed")
    pos = model.parameter((seq, hidden), name="pos_embed")
    t = model.add(t, pos, name="embed_add")  # (B,S,H) + (S,H) broadcast
    for i in range(num_layers):
        t = decoder_layer(
            model, t, hidden, heads, ff_dim, dropout, use_flash, name=f"dec{i}"
        )
    t = model.layer_norm(t, axes=[-1], name="final_ln")
    t = model.dense(t, vocab, use_bias=False, name="lm_head")
    t = model.reshape(t, (batch * seq, vocab), name="lm_flatten")
    return model.softmax(t, name="lm_softmax")


# BERT configs (the training cells of chip_smoke.py and bench.py)
BERT_BASE = dict(hidden=768, heads=12, ff_dim=3072, num_layers=12)
BERT_LARGE = dict(hidden=1024, heads=16, ff_dim=4096, num_layers=24)
# GPT-2 configs (causal-LM family for the decoder path)
GPT2_SMALL = dict(hidden=768, heads=12, ff_dim=3072, num_layers=12)
GPT2_MEDIUM = dict(hidden=1024, heads=16, ff_dim=4096, num_layers=24)


def sample_next(probs, temperature: float, rng, top_k: int = 0,
                top_p: float = 1.0):
    """Next-token selection shared by :func:`gpt_generate` and the
    KV-cache path (``models.gpt_decode``): greedy at temperature 0, else
    temperature-scaled softmax sampling, optionally truncated to the
    ``top_k`` highest-probability tokens and/or the ``top_p`` nucleus
    (smallest prefix of the sorted distribution with cumulative mass
    >= top_p) — beyond the reference, which has no generation path."""
    import numpy as np

    if temperature <= 0.0:
        return probs.argmax(-1).astype(np.int32)
    # float64 throughout: rng.choice re-checks sum(p) == 1 at ~1e-8
    # tolerance, which float32 normalization misses
    logp = np.log(np.maximum(probs.astype(np.float64), 1e-30)) / temperature
    z = np.exp(logp - logp.max(-1, keepdims=True))
    z /= z.sum(-1, keepdims=True)
    if top_k and top_k < z.shape[-1]:
        kth = np.sort(z, axis=-1)[:, -top_k][:, None]
        z = np.where(z >= kth, z, 0.0)
        z /= z.sum(-1, keepdims=True)
    if top_p < 1.0:
        order = np.argsort(-z, axis=-1)
        sorted_z = np.take_along_axis(z, order, axis=-1)
        cum = np.cumsum(sorted_z, axis=-1)
        # keep the smallest prefix reaching top_p (the first token always
        # survives so the distribution never empties)
        keep_sorted = cum - sorted_z < top_p
        keep = np.zeros_like(z, dtype=bool)
        np.put_along_axis(keep, order, keep_sorted, axis=-1)
        z = np.where(keep, z, 0.0)
        z /= z.sum(-1, keepdims=True)
    return np.array(
        [rng.choice(z.shape[-1], p=z[b]) for b in range(z.shape[0])],
        np.int32,
    )


def gpt_generate(
    model,
    prompt_ids,
    max_new_tokens: int,
    temperature: float = 0.0,
    seed: int = 0,
    top_k: int = 0,
    top_p: float = 1.0,
):
    """Iterative decoding for a compiled :func:`gpt_decoder` model, the
    reference's own NMT-style scheme (``FFIterationConfig::seq_length``,
    ``include/flexflow/config.h:162-167``: decode = re-run the forward per
    step; the reference has no KV cache either).  The causal mask makes
    every position < t invariant to whatever sits beyond t, so ONE
    fixed-shape compiled forward serves every step — no per-length
    retrace.

    ``prompt_ids``: (batch, prompt_len) int tokens, prompt_len >= 1.
    Returns (batch, prompt_len + max_new_tokens) ids (greedy at
    temperature 0, else softmax sampling with ``seed``).
    """
    import numpy as np

    batch, seq = model.graph_inputs[0].shape
    p = np.asarray(prompt_ids, np.int32)
    assert p.ndim == 2 and p.shape[0] == batch, p.shape
    start = p.shape[1]
    end = start + max_new_tokens
    assert 1 <= start <= seq
    assert end <= seq, (
        f"prompt_len + max_new_tokens = {end} exceeds the compiled "
        f"sequence length {seq}; rebuild gpt_decoder with a longer seq"
    )
    cur = np.zeros((batch, seq), np.int32)
    cur[:, :start] = p
    rng = np.random.default_rng(seed)
    for t in range(start, end):
        probs = np.asarray(model.eval_batch([cur]))
        cur[:, t] = sample_next(
            probs.reshape(batch, seq, -1)[:, t - 1], temperature, rng,
            top_k=top_k, top_p=top_p,
        )
    return cur[:, :end]
