"""Operations and bytes serving the ``afmoe`` decoder needs, from the
configuration's ``model`` (the source's key names) and the requests'
lengths.

Same rules as ``work.py``: what the algorithm requires, a multiply-add
is two operations, nothing recomputed is counted.  A window layer's
query at position p needs its last ``min(p + 1, window)`` keys; a
kernel call must read each K/V row some row of it sees ONCE (the rows a
chunk ``[lo, hi)`` sees are ``max(0, lo - window + 1) .. hi - 1``),
however often an implementation re-reads them: so a roofline share
that rests on these bytes cannot pass 100 %.  A routed position goes
through its ``num_experts_per_tok`` experts, whatever the router's
balance.
"""

from __future__ import annotations

import math


def layer_windows(m: dict) -> list:
    """A layer's window (0: it sees every earlier key)."""
    return [
        int(m["sliding_window"]) if t == "sliding_attention" else 0
        for t in m["layer_types"][: m["num_hidden_layers"]]
    ]


def layer_matmul_flops_per_position(m: dict, i: int) -> int:
    """Projections and FFN of layer ``i`` for one position, attention's
    scores left out: q and gate (2 H d), k, v (2 KV d), o; the dense
    gated FFN, or router + shared expert + the chosen experts."""
    h = m["hidden_size"]
    H, KV, d = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    proj = 2 * h * (2 * H * d + 2 * KV * d) + 2 * H * d * h
    if i < m["num_dense_layers"]:
        return proj + 6 * h * m["intermediate_size"]
    f = m["moe_intermediate_size"]
    return proj + 2 * h * m["num_experts"] + 6 * h * f * (
        m["num_shared_experts"] + m["num_experts_per_tok"]
    )


def attention_flops(m: dict, pairs: int) -> int:
    """q.k and p.v for ``pairs`` (query row, visible key) pairs, summed
    over the query heads: 2 H d each."""
    return 4 * m["num_attention_heads"] * m["head_dim"] * pairs


def served_request_work(*, prompt_len: int, prefill_pos: int, new_tokens: int,
                        prefill_chunk: int, window: int) -> dict:
    """What one request has asked of a full and of a window attention
    layer so far (``work.served_request_work`` with the window applied):
    K/V rows its calls had to read and (row, key) pairs attended."""
    out = dict.fromkeys(
        ("positions", "logit_rows", "kv_reads_full", "kv_reads_window",
         "pairs_full", "pairs_window"), 0,
    )
    chunks = math.ceil(prefill_pos / prefill_chunk) if prefill_pos else 0
    for c in range(chunks):
        lo = c * prefill_chunk
        hi = min(lo + prefill_chunk, prefill_pos)
        out["kv_reads_full"] += hi
        out["kv_reads_window"] += hi - max(0, lo - window + 1)
        out["pairs_full"] += sum(range(lo + 1, hi + 1))
        out["pairs_window"] += sum(min(p + 1, window) for p in range(lo, hi))
        out["positions"] += hi - lo
    steps = max(0, new_tokens - 1)
    for k in range(steps):
        p = prompt_len + k
        out["kv_reads_full"] += p + 1
        out["pairs_full"] += p + 1
        out["kv_reads_window"] += min(p + 1, window)
        out["pairs_window"] += min(p + 1, window)
    out["positions"] += steps
    out["logit_rows"] = steps + (1 if prefill_pos >= prompt_len else 0)
    return out


def _by_layer(m: dict, tot: dict, full_key: str, window_key: str) -> list:
    return [tot[window_key] if w else tot[full_key] for w in layer_windows(m)]


def serve_flops(m: dict, tot: dict) -> int:
    """Forward work of everything served: every position through every
    layer's matmuls, the attended pairs by the layer's kind, the head at
    the rows whose logits were needed."""
    matmuls = sum(
        layer_matmul_flops_per_position(m, i) for i in range(m["num_hidden_layers"])
    )
    pairs = sum(_by_layer(m, tot, "pairs_full", "pairs_window"))
    return (tot["positions"] * matmuls + attention_flops(m, pairs)
            + 2 * tot["logit_rows"] * m["hidden_size"] * m["vocab_size"])


def paged_attention_bytes(m: dict, tot: dict, itemsize: int) -> int:
    """Bytes the attention kernel's calls must move, all layers: each
    visible K and V row once a call (KV heads x head_dim wide), the query
    rows in and the output rows out (query heads x head_dim wide)."""
    kv_w = m["num_key_value_heads"] * m["head_dim"] * itemsize
    q_w = m["num_attention_heads"] * m["head_dim"] * itemsize
    reads = sum(_by_layer(m, tot, "kv_reads_full", "kv_reads_window"))
    return 2 * reads * kv_w + m["num_hidden_layers"] * 2 * tot["positions"] * q_w


def paged_attention_flops(m: dict, tot: dict) -> int:
    return attention_flops(m, sum(_by_layer(m, tot, "pairs_full", "pairs_window")))
