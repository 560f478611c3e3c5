"""Operations and bytes serving the ``minicpm_sala`` decoder needs, from
the configuration's ``model`` (the source's key names) and the
requests' lengths.

Same rules as ``work.py``: what the algorithm requires, a multiply-add
is two operations, nothing recomputed is counted.  Every position runs
every layer (``reference/minicpm_sala.py::layers``):

* a LIGHTNING layer: five projections, the output norm and gate, and a
  head's state update and read-out, ``2 d^2`` operations each (the
  recurrence's least; the chunked form a prefill runs trades some of it
  for products over a chunk, not counted);
* a SPARSE layer: four projections (the query's and the gate's one
  matrix), the scores against the index's entries there
  (``2 H d`` an entry), and the attended keys (``4 H d`` a key: the
  score and the value's product);
* the MLP ``6 hidden ff`` a position; the head at the rows whose logits
  were needed.

The sparse layers' kernel (``%sparse_attend.N``, one call a layer and a
K/V head a decode step or a prefill group): a unit of queries -- a
decoded position, or a block of a prompt's positions that share a
selection -- must read the K and V rows of its selected blocks its
queries see (one K/V head's ``d``), bring its query rows in and write
them out (``H / KV`` heads of ``d`` a row).  A lightning layer's call
must read and write every live lane's state once.
"""

from __future__ import annotations

import numpy as np

from benchmarks.reference.minicpm_sala import dims, layers


def counts(m: dict) -> dict:
    kinds = [k for _, k in layers(m)]
    return dict(sparse=kinds.count("minicpm4"), lightning=kinds.count("lightning-attn"))


def attended(m: dict, t):
    """Keys a query at positions ``t`` (array) reads: every key up to it
    while its context is at most ``dense_len``, else its ``topk``
    selected blocks up to itself."""
    D = dims(m)
    t = np.asarray(t, np.int64)
    cap = (D["topk"] - 1) * D["B"] + t % D["B"] + 1
    return np.where(t + 1 <= D["dense"], t + 1, np.minimum(t + 1, cap))


def served_request_work(m: dict, *, prompt_len: int, prefill_pos: int, new_tokens: int,
                        prefill_chunk: int) -> dict:
    """What one request has asked so far: positions, rows whose logits
    were needed, the sparse layers' attended keys (``pairs``), the K/V
    rows their units must read (``unit_rows``), query rows, index
    entries scored, and lane-calls (a program call in which it was
    live)."""
    D = dims(m)
    B = D["B"]
    steps = max(0, new_tokens - 1)
    t_pre = np.arange(prefill_pos)
    t_dec = prompt_len + np.arange(steps)
    t = np.concatenate([t_pre, t_dec])
    # a prompt block's unit reads its selected blocks' rows up to its last row
    lo = np.arange(0, prefill_pos, B)
    hi = np.minimum(lo + B, prefill_pos) - 1
    unit = np.where(hi + 1 <= D["dense"], hi + 1, (D["topk"] - 1) * B + hi - lo + 1)
    kernels = np.maximum((t + 1 - D["K"]) // D["s"] + 1, 0)
    chunks = -(-prefill_pos // prefill_chunk) if prefill_pos else 0
    return {
        "positions": int(t.size),
        "logit_rows": steps + (1 if prefill_pos >= prompt_len else 0),
        "pairs": int(attended(m, t).sum()),
        "unit_rows": int(unit.sum() + attended(m, t_dec).sum()),
        "index_entries": int(kernels.sum()),
        "lane_calls": chunks + steps,
    }


def layer_flops_per_row(m: dict, kind: str) -> int:
    """One position through a layer of ``kind`` and its MLP, the sparse
    layer's scores and attended keys left out."""
    D = dims(m)
    h = D["h"]
    mlp = 6 * h * D["f"]
    if kind == "minicpm4":
        return mlp + 2 * h * (2 * D["H"] * D["d"] + 2 * D["KV"] * D["d"]) + 2 * D["H"] * D["d"] * h
    w = D["LH"] * D["Ld"]
    return mlp + 2 * h * w * 5 + D["LH"] * 4 * D["Ld"] ** 2


def serve_flops(m: dict, tot: dict) -> int:
    """Forward work of everything served."""
    D = dims(m)
    c = counts(m)
    per_row = sum(layer_flops_per_row(m, k) for _, k in layers(m))
    return int(
        tot["positions"] * per_row
        + c["sparse"] * (4 * D["H"] * D["d"] * tot["pairs"]
                         + 2 * D["H"] * D["d"] * tot["index_entries"])
        + 2 * tot["logit_rows"] * D["h"] * D["v"]
    )


def kernel_layers(m: dict) -> int:
    """Sparse-kernel calls a program call makes (a prefill's: a group):
    one a sparse layer and a K/V head."""
    return counts(m)["sparse"] * dims(m)["KV"]


def sparse_attention_bytes(m: dict, tot: dict, itemsize: int) -> int:
    """Bytes the sparse kernel's calls must move: each unit's K and V
    rows once (one K/V head's ``d``), its query rows in and out."""
    D = dims(m)
    c = counts(m)
    rows = 2 * tot["unit_rows"] * D["d"] * itemsize * D["KV"]
    queries = 2 * tot["positions"] * D["H"] * D["d"] * itemsize
    return c["sparse"] * (rows + queries)


def sparse_attention_flops(m: dict, tot: dict) -> int:
    D = dims(m)
    return counts(m)["sparse"] * 4 * D["H"] * D["d"] * tot["pairs"]


def state_bytes_per_slot_layer(m: dict) -> int:
    """A slot's lightning state in one layer: float32 (heads, d, d)."""
    D = dims(m)
    return D["LH"] * D["Ld"] ** 2 * 4


def lightning_state_bytes(m: dict, tot: dict) -> int:
    """The least the lightning layers' updates must move: every
    lane-call reads and writes the lane's state once a layer."""
    return tot["lane_calls"] * counts(m)["lightning"] * 2 * state_bytes_per_slot_layer(m)
