"""Operations and bytes serving the ``phi4flash`` decoder needs, from the
configuration's ``model`` (the source's key names) and the requests'
lengths.

Same rules as ``work.py``: what the algorithm requires, a multiply-add
is two operations, nothing recomputed is counted.  The layers split in
two (``reference/phi4flash.py::layer_kinds``):

* the SELF-DECODER, layers ``0 .. half + 1`` (Mamba-1, window and full
  differential attention, their FFNs), runs on every position served;
* the CROSS-DECODER, the layers after it (memory units, cross-attention
  over layer ``half + 1``'s K/V, their FFNs), writes nothing a later row
  reads, so it runs on the rows whose logits are needed: a decode step's
  row and a prefill chunk's last row (``cross_rows``);

the head at the rows whose logits were needed.  Differential attention
of a query row and a key: two score products of ``hd`` and two value
products of ``2 hd`` a query pair -- ``6 H hd`` operations (``H``
query heads of ``hd``).  Each paged-kernel call (one a layer that reads
a cache a program call: the full layer and every cross layer read the
full cache, the window layers their rings) must read each K/V row some
row of it sees once, take its query rows in and write its two maps
out.  A state layer's call must read and write every live lane's state
once.
"""

from __future__ import annotations

import math

from benchmarks.reference.phi4flash import dims, layer_kinds


def self_layers(m: dict) -> int:
    return layer_kinds(m).index("F") + 1


def layer_flops_per_row(m: dict, kind: str) -> int:
    """One row through a layer of ``kind`` and its FFN, attention's
    (row, key) products left out."""
    D = dims(m)
    h, H, KV, hd, d, N, R = D["h"], D["H"], D["KV"], D["hd"], D["d"], D["N"], D["R"]
    mlp = 6 * h * D["f"]
    if kind in "Mm":
        proj = 2 * h * 2 * d + 2 * d * (R + 2 * N) + 2 * R * d + 2 * d * h
        # the conv's taps; the state's update and read-out, d x N each
        return mlp + proj + 2 * d * D["K"] + 4 * d * N
    if kind == "G":
        return mlp + 4 * h * d
    if kind == "C":
        return mlp + 2 * h * H * hd + 2 * H * hd * h
    return mlp + 2 * h * (H + 2 * KV) * hd + 2 * H * hd * h


def attention_flops(m: dict, pairs: int) -> int:
    D = dims(m)
    return 6 * D["H"] * D["hd"] * pairs


def served_request_work(*, prompt_len: int, prefill_pos: int, new_tokens: int,
                        prefill_chunk: int, window: int) -> dict:
    """What one request has asked so far: positions, rows whose logits
    were needed, rows the cross-decoder ran (one a prefill chunk, one a
    decode step), K/V rows the full cache's readers and the window
    layers had to read a call and the (row, key) pairs they attended,
    and lane-calls (a program call in which it was live)."""
    out = dict.fromkeys(
        ("positions", "logit_rows", "cross_rows", "kv_reads_full", "pairs_full",
         "kv_reads_win", "pairs_win", "cross_kv_reads", "cross_pairs", "lane_calls"), 0,
    )
    chunks = math.ceil(prefill_pos / prefill_chunk) if prefill_pos else 0
    for c in range(chunks):
        lo = c * prefill_chunk
        hi = min(lo + prefill_chunk, prefill_pos)
        out["positions"] += hi - lo
        out["kv_reads_full"] += hi
        out["pairs_full"] += sum(range(lo + 1, hi + 1))
        out["kv_reads_win"] += hi - max(0, lo - window + 1)
        out["pairs_win"] += sum(min(p + 1, window) for p in range(lo, hi))
        # the chunk's last row through the cross-decoder, over keys 0 .. hi - 1
        out["cross_rows"] += 1
        out["cross_kv_reads"] += hi
        out["cross_pairs"] += hi
    steps = max(0, new_tokens - 1)
    p = prompt_len + steps  # the position after the last step
    seen = (prompt_len + 1 + p) * steps // 2  # keys the steps' rows see
    out["positions"] += steps
    out["cross_rows"] += steps
    out["kv_reads_full"] += seen
    out["pairs_full"] += seen
    out["cross_kv_reads"] += seen
    out["cross_pairs"] += seen
    win = sum(min(q + 1, window) for q in range(prompt_len, p))
    out["kv_reads_win"] += win
    out["pairs_win"] += win
    out["lane_calls"] = chunks + steps
    out["logit_rows"] = steps + (1 if prefill_pos >= prompt_len else 0)
    return out


def counts(m: dict):
    ks = layer_kinds(m)
    return dict(window=ks.count("S"), full=ks.count("F"), cross=ks.count("C"),
                state=ks.count("M") + ks.count("m"))


def serve_flops(m: dict, tot: dict) -> int:
    """Forward work of everything served."""
    ks = layer_kinds(m)
    n, c = self_layers(m), counts(m)
    D = dims(m)
    per_self = sum(layer_flops_per_row(m, k) for k in ks[:n])
    per_cross = sum(layer_flops_per_row(m, k) for k in ks[n:])
    return int(
        tot["positions"] * per_self + tot["cross_rows"] * per_cross
        + c["window"] * attention_flops(m, tot["pairs_win"])
        + c["full"] * attention_flops(m, tot["pairs_full"])
        + c["cross"] * attention_flops(m, tot["cross_pairs"])
        + 2 * tot["logit_rows"] * D["h"] * D["v"]
    )


def paged_attention_bytes(m: dict, tot: dict, itemsize: int) -> int:
    """Bytes the paged kernel's calls must move, every layer that reads
    a cache: each visible K and V row once a call (``KV hd`` wide), the
    query rows in (``H hd``) and the two maps out (``2 H hd``)."""
    D = dims(m)
    c = counts(m)
    kv_w = D["KV"] * D["hd"] * itemsize
    q_w = D["H"] * D["hd"] * itemsize
    reads = (c["full"] * tot["kv_reads_full"] + c["window"] * tot["kv_reads_win"]
             + c["cross"] * tot["cross_kv_reads"])
    rows = (c["full"] + c["window"]) * tot["positions"] + c["cross"] * tot["cross_rows"]
    return 2 * reads * kv_w + 3 * rows * q_w


def paged_attention_flops(m: dict, tot: dict) -> int:
    c = counts(m)
    return (c["full"] * attention_flops(m, tot["pairs_full"])
            + c["window"] * attention_flops(m, tot["pairs_win"])
            + c["cross"] * attention_flops(m, tot["cross_pairs"]))


def kernel_layers(m: dict) -> int:
    """Paged-kernel calls a program call makes (a prefill's: a group)."""
    c = counts(m)
    return c["full"] + c["window"] + c["cross"]


def state_bytes_per_slot_layer(m: dict, conv_itemsize: int = 2) -> int:
    """A slot's recurrent state in one Mamba-1 layer: the float32 (d, N)
    state and the conv's last ``K - 1`` inputs."""
    D = dims(m)
    return D["d"] * D["N"] * 4 + D["d"] * (D["K"] - 1) * conv_itemsize


def ssm_state_bytes(m: dict, tot: dict, conv_itemsize: int = 2) -> int:
    """The least the state layers' updates must move: every lane-call
    reads and writes the lane's state once a state layer."""
    return tot["lane_calls"] * counts(m)["state"] * 2 * state_bytes_per_slot_layer(
        m, conv_itemsize)
