"""Operations and bytes a step or a kernel call needs, from shapes only.

Nothing here looks at the program: the counts are what the algorithm
requires, so a roofline or MFU share reads the same work whatever
implements it.  A multiply-add is two operations.  Recomputed work is
never counted.
"""

from __future__ import annotations

import math


def block_matmul_flops_per_token(hidden: int, ff_dim: int) -> int:
    """Forward matmuls of one transformer block for one token, attention
    scores left out: q, k, v, o projections (4 * 2*h*h) and the two FFN
    matmuls (2 * 2*h*ff)."""
    return 8 * hidden * hidden + 4 * hidden * ff_dim


def attention_flops(q_rows_times_keys: int, hidden: int) -> int:
    """Scores and probabilities-times-values for ``q_rows_times_keys``
    (query row, key) pairs summed over the heads: 2*h for q.k, 2*h for p.v."""
    return 4 * hidden * q_rows_times_keys


def encoder_train_flops_per_step(
    *, batch: int, seq: int, hidden: int, ff_dim: int, num_layers: int,
    num_classes: int,
) -> int:
    """Forward + backward of the encoder classifier as built (raw float
    embeddings in, mean-pool, one dense head): backward is twice the
    forward's matmuls, so 3x; full (non-causal) attention."""
    tokens = batch * seq
    fwd = num_layers * (
        tokens * block_matmul_flops_per_token(hidden, ff_dim)
        + attention_flops(batch * seq * seq, hidden)
    )
    fwd += 2 * batch * hidden * num_classes
    return 3 * fwd


def decoder_serve_flops(
    *, positions: int, attended: int, logit_rows: int, hidden: int,
    ff_dim: int, num_layers: int, vocab: int,
) -> int:
    """Forward work of a served decoder: ``positions`` token positions
    through every block, ``attended`` (query row, visible key) pairs per
    layer, and ``logit_rows`` rows through the output head."""
    return (
        num_layers * (
            positions * block_matmul_flops_per_token(hidden, ff_dim)
            + attention_flops(attended, hidden)
        )
        + 2 * logit_rows * hidden * vocab
    )


def served_request_work(
    *, prompt_len: int, prefill_pos: int, new_tokens: int, prefill_chunk: int,
) -> dict:
    """What one request has asked of the attention layer so far.

    Prefill goes chunk by chunk: a chunk covering prompt positions
    ``[lo, hi)`` reads keys and values ``0..hi-1`` once and its row at
    position p sees p+1 keys.  The first new token comes out of the last
    prefill chunk; every further one is a decode step at position
    ``prompt_len + k`` that reads that many + 1 keys and values.
    """
    kv_reads = pairs = rows = calls_rows = 0
    chunks = math.ceil(prefill_pos / prefill_chunk) if prefill_pos else 0
    for c in range(chunks):
        lo = c * prefill_chunk
        hi = min(lo + prefill_chunk, prefill_pos)
        kv_reads += hi
        pairs += sum(range(lo + 1, hi + 1))
        rows += hi - lo
    decode_steps = max(0, new_tokens - 1)
    for k in range(decode_steps):
        kv_reads += prompt_len + k + 1
        pairs += prompt_len + k + 1
    rows += decode_steps
    return {
        "positions": rows,
        "kv_token_reads": kv_reads,
        "attended_pairs": pairs,
        "prefill_chunks": chunks,
        "decode_steps": decode_steps,
        "logit_rows": decode_steps + (1 if prefill_pos >= prompt_len else 0),
    }


def paged_attention_bytes(
    *, kv_token_reads: int, q_rows: int, heads: int, head_dim: int,
    kv_itemsize: int, q_itemsize: int,
) -> int:
    """Bytes one layer's paged-attention calls must move: each visible
    key and value token once per call that reads it, plus the query rows
    in and the output rows out."""
    width = heads * head_dim
    return 2 * kv_token_reads * width * kv_itemsize + 2 * q_rows * width * q_itemsize


def roofline_seconds(flops: float, bytes_moved: float, peaks: dict) -> tuple:
    """Least time the chip could take and which resource sets it."""
    t_flops = flops / peaks["flops_bf16"]
    t_bytes = bytes_moved / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "bandwidth")
