"""Operations a training step of the Qwen3-Next share needs, from the
configuration's ``model`` (the source's key names) and the batch's shape.

Same rules as ``work.py``: what the algorithm requires, a multiply-add
is two operations, recomputed work is never counted, backward is twice
the forward's matmuls (x 3).  Two counts are the algorithm's own choice
and are stated here: the delta rule is counted in its chunked form at
``CHUNK`` tokens (the token-by-token form has no matrix products to
count against a matmul peak), and the held experts at the rows a
uniform router sends them, ``tokens * top_k * held / routed``, not at
the rows a run happened to route.
"""

from __future__ import annotations

CHUNK = 64


def linear_mixer_flops_per_token(m: dict) -> float:
    """One Gated-DeltaNet mixer, forward, one token."""
    h = m["hidden_size"]
    hk, hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    qkv = 2 * hk * dk + hv * dv
    proj = 2 * h * (qkv + hv * dv + 2 * hv) + 2 * hv * dv * h
    conv = 2 * qkv * m["linear_conv_kernel_dim"]
    # per value head: a token meets (CHUNK + 1) / 2 tokens of its chunk
    # (k.k and q.k: 2 dk each; the unit-triangular solve against
    # [v | k]: 2 (dv + dk); scores times values: 2 dv) and the carried
    # state three times (w S, q S, k^T v: 2 dk dv each)
    pairs = (CHUNK + 1) / 2 * (4 * dk + 2 * (dv + dk) + 2 * dv)
    return proj + conv + hv * (pairs + 6 * dk * dv)


def full_mixer_flops_per_token(m: dict, seq: int) -> float:
    """The gated attention mixer, forward, one token of a causal
    sequence of ``seq`` (a token sees (seq + 1) / 2 keys on average)."""
    h, H, KV, d = (m["hidden_size"], m["num_attention_heads"],
                   m["num_key_value_heads"], m["head_dim"])
    proj = 2 * h * (2 * H * d + 2 * KV * d) + 2 * H * d * h
    return proj + (seq + 1) / 2 * H * 4 * d


def moe_flops_per_token(m: dict) -> float:
    """Router over its full width, the shared expert and its gate, and
    the expected rows of the held experts."""
    h = m["hidden_size"]
    held_rows = m["num_experts_per_tok"] * m["num_experts"] / m["router_num_experts"]
    return (
        2 * h * m["router_num_experts"]
        + 6 * h * m["shared_expert_intermediate_size"] + 2 * h
        + held_rows * 6 * h * m["moe_intermediate_size"]
    )


def full_layers(m: dict) -> int:
    return sum(
        (i + 1) % m["full_attention_interval"] == 0 for i in range(m["num_hidden_layers"])
    )


def forward_flops_per_token(m: dict, seq: int) -> dict:
    """Forward operations a token, by part (the embedding is a lookup)."""
    n, full = m["num_hidden_layers"], full_layers(m)
    return {
        "linear_mixers": (n - full) * linear_mixer_flops_per_token(m),
        "full_mixers": full * full_mixer_flops_per_token(m, seq),
        "moe": n * moe_flops_per_token(m),
        "head": 2 * m["hidden_size"] * m["vocab_size"],
    }


def decoder_train_flops_per_step(m: dict, *, batch: int, seq: int) -> float:
    return 3 * batch * seq * sum(forward_flops_per_token(m, seq).values())


def held_parameters(m: dict) -> int:
    """Parameters this share holds (the configuration's ``deployment``)."""
    h, f = m["hidden_size"], m["moe_intermediate_size"]
    hk, hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    H, KV, d = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    qkv = 2 * hk * dk + hv * dv
    linear = h * (qkv + hv * dv + 2 * hv) + qkv * m["linear_conv_kernel_dim"] + 2 * hv + dv + hv * dv * h
    full = h * (2 * H * d + 2 * KV * d) + H * d * h + 2 * d
    fs = m["shared_expert_intermediate_size"]
    moe = h * m["router_num_experts"] + 3 * h * fs + h + m["num_experts"] * 3 * h * f
    n, n_full = m["num_hidden_layers"], full_layers(m)
    return (n - n_full) * linear + n_full * full + n * (moe + 2 * h) + h + 2 * h * m["vocab_size"]
