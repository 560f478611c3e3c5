"""Operations and bytes serving the ``nemotron_h`` decoder needs, from
the configuration's ``model`` (the source's key names) and the requests'
lengths.

Same rules as ``work.py``: what the algorithm requires, a multiply-add
is two operations, nothing recomputed is counted.  A layer is ONE mixer,
by its letter:

* ``M``: the projections (``[z | xBC | dt]`` in, ``d`` out), the conv's
  taps, and the scan -- a position's state update and read-out are
  ``d * N`` products each; a prefill chunk runs the chunked form, whose
  chunk-local products (``C B^T`` a group and its product with the
  values, over the causal pairs inside a scan chunk) are counted where
  prefill made them;
* ``*``: q, k, v, o and the attended (row, key) pairs;
* ``E``: the router over all its outputs, the shared expert, and the
  HELD chosen experts -- the rows the program's counter says were
  routed to an expert held here (top-k x held / routed under an even
  router), two matrices each;

the head at the rows whose logits were needed.  A kernel call must read
each K/V row some row of it sees once; a state layer's call must read
and write every live lane's state once.
"""

from __future__ import annotations

import math


def kinds(m: dict) -> str:
    return m["hybrid_override_pattern"][: m["num_hidden_layers"]]


def mamba_dims(m: dict):
    d = m["mamba_num_heads"] * m["mamba_head_dim"]
    return d, d + 2 * m["n_groups"] * m["ssm_state_size"]


def layer_flops_per_position(m: dict, kind: str) -> int:
    """One position through a layer of ``kind``, without what depends on
    where the position sits (attended pairs, chunk-local products) or on
    the router (the chosen experts)."""
    h = m["hidden_size"]
    if kind == "M":
        d, cw = mamba_dims(m)
        proj = 2 * h * (d + cw + m["mamba_num_heads"]) + 2 * d * h
        return proj + 2 * cw * m["conv_kernel"] + 4 * d * m["ssm_state_size"]
    if kind == "*":
        H, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
        return 2 * h * (H * hd + 2 * KV * hd) + 2 * H * hd * h
    E = m.get("router_num_experts", m["n_routed_experts"])
    return 2 * h * E + 4 * h * m["moe_shared_expert_intermediate_size"] * m["n_shared_experts"]


def expert_flops_per_row(m: dict) -> int:
    return 4 * m["hidden_size"] * m["moe_intermediate_size"]


def chunk_local_flops(m: dict, pairs: int) -> int:
    """``C_i . B_j`` a group and ``w_ij x_j`` a head for ``pairs``
    causal (i, j) pairs inside scan chunks."""
    d, _ = mamba_dims(m)
    return 2 * pairs * (m["n_groups"] * m["ssm_state_size"] + d)


def attention_flops(m: dict, pairs: int) -> int:
    return 4 * m["num_attention_heads"] * m["head_dim"] * pairs


def served_request_work(*, prompt_len: int, prefill_pos: int, new_tokens: int,
                        prefill_chunk: int, window: int = 0, scan_chunk: int = 128) -> dict:
    """What one request has asked so far: positions, rows whose logits
    were needed, K/V rows its attention calls had to read and (row, key)
    pairs attended, causal pairs inside the scan chunks of its prefill
    dispatches, and lane-calls (a program call in which it was live)."""
    out = dict.fromkeys(
        ("positions", "logit_rows", "kv_reads_full", "pairs_full", "scan_pairs",
         "lane_calls"), 0,
    )
    chunks = math.ceil(prefill_pos / prefill_chunk) if prefill_pos else 0
    for c in range(chunks):
        lo = c * prefill_chunk
        hi = min(lo + prefill_chunk, prefill_pos)
        out["kv_reads_full"] += hi
        out["pairs_full"] += sum(range(lo + 1, hi + 1))
        out["positions"] += hi - lo
        for s in range(lo, hi, scan_chunk):  # the scan's own chunks, from the dispatch's start
            n = min(s + scan_chunk, hi) - s
            out["scan_pairs"] += n * (n + 1) // 2
    steps = max(0, new_tokens - 1)
    p = prompt_len + steps  # the position after the last step
    out["kv_reads_full"] += (prompt_len + 1 + p) * steps // 2
    out["pairs_full"] += (prompt_len + 1 + p) * steps // 2
    out["positions"] += steps
    out["lane_calls"] = chunks + steps
    out["logit_rows"] = steps + (1 if prefill_pos >= prompt_len else 0)
    return out


def serve_flops(m: dict, tot: dict) -> int:
    """Forward work of everything served.  ``tot["held_rows"]``: rows
    routed to held experts, all layers (the program's counter); without
    it, what an even router sends."""
    ks = kinds(m)
    per_position = sum(layer_flops_per_position(m, k) for k in ks)
    held_rows = tot.get("held_rows")
    if held_rows is None:
        E = m.get("router_num_experts", m["n_routed_experts"])
        held_rows = tot["positions"] * ks.count("E") * m["num_experts_per_tok"] * (
            m["n_routed_experts"] / E)
    return int(
        tot["positions"] * per_position
        + held_rows * expert_flops_per_row(m)
        + ks.count("M") * chunk_local_flops(m, tot["scan_pairs"])
        + ks.count("*") * attention_flops(m, tot["pairs_full"])
        + 2 * tot["logit_rows"] * m["hidden_size"] * m["vocab_size"]
    )


def paged_attention_bytes(m: dict, tot: dict, itemsize: int) -> int:
    """Bytes the attention kernel's calls must move, all attention
    layers: each visible K and V row once a call (KV heads x head_dim
    wide), the query rows in and the output rows out."""
    kv_w = m["num_key_value_heads"] * m["head_dim"] * itemsize
    q_w = m["num_attention_heads"] * m["head_dim"] * itemsize
    return kinds(m).count("*") * (2 * tot["kv_reads_full"] * kv_w + 2 * tot["positions"] * q_w)


def paged_attention_flops(m: dict, tot: dict) -> int:
    return kinds(m).count("*") * attention_flops(m, tot["pairs_full"])


def state_bytes_per_slot_layer(m: dict, conv_itemsize: int = 2) -> int:
    """A slot's recurrent state in one state layer: the float32 state
    and the conv's last inputs."""
    d, cw = mamba_dims(m)
    return d * m["ssm_state_size"] * 4 + cw * (m["conv_kernel"] - 1) * conv_itemsize


def ssm_state_bytes(m: dict, tot: dict, conv_itemsize: int = 2) -> int:
    """The least the state layers' updates must move: every lane-call
    reads and writes the lane's state once a state layer."""
    return tot["lane_calls"] * kinds(m).count("M") * 2 * state_bytes_per_slot_layer(
        m, conv_itemsize)
