"""The serve programs without an engine (ISSUE 30, docs/SERVING.md).

``build_serve_programs`` writes the decoder once and hands back four
jitted programs.  Three trace-time switches shape them: the attention
arm (``gather`` | ``paged``), the pool (full precision | quantized, which
threads two scale pools through every program) and the weights (as
stored | int8, which makes the params argument a pair).  Each of the
eight combinations is traced here, with speculation on, against what the
window loop unpacks and what it donates; nothing is compiled.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)))
)

from flexflow_tpu import FFConfig, FFModel, MachineMesh  # noqa: E402
from flexflow_tpu.models.transformer import gpt_decoder  # noqa: E402
from flexflow_tpu.ops.pallas import paged_attention as pa  # noqa: E402
from flexflow_tpu.serve.kvcache import PagedKVCache  # noqa: E402
from flexflow_tpu.serve.programs import build_serve_programs  # noqa: E402

SLOTS, SEQ, VOCAB, LAYERS, HEADS, HIDDEN = 3, 64, 29, 4, 4, 32
BS, CHUNK, SPEC_K, DRAFT_LAYERS = 8, 5, 2, 1


@pytest.fixture(scope="module")
def model():
    # four blocks: the executor scan-stacks them, so the programs' own
    # per-layer view of the parameters is traced too
    m = FFModel(FFConfig(batch_size=SLOTS))
    gpt_decoder(
        m, SLOTS, SEQ, hidden=HIDDEN, heads=HEADS, ff_dim=64,
        num_layers=LAYERS, vocab=VOCAB, use_flash=False,
    )
    m.compile(seed=0, mesh=MachineMesh((1, 1), ("data", "model")))
    return m


def _pool(kv_dtype):
    return PagedKVCache(
        LAYERS, HEADS, HIDDEN // HEADS, slots=SLOTS, block_size=BS,
        max_seq_len=SEQ, dtype=jnp.float32, kv_dtype=kv_dtype,
    )


@pytest.mark.parametrize("weight_dtype", ["fp32", "int8"])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("attn", ["gather", "paged"])
def test_programs_trace_to_what_the_window_unpacks(
    model, monkeypatch, attn, kv_dtype, weight_dtype
):
    # the paged kernels run here only in the interpreter; the flag is
    # read when ``resolve_serve_attn`` decides and when a program traces
    monkeypatch.setattr(pa, "INTERPRET", attn == "paged")
    kv = _pool(kv_dtype)
    progs = build_serve_programs(
        model, kv, attn_kernel=pa.resolve_serve_attn(attn, BS, kv.dtype),
        weight_dtype=weight_dtype, spec_k=SPEC_K,
        spec_draft_layers=DRAFT_LAYERS,
    )
    pools = [kv.cache_k, kv.cache_v]
    if kv_dtype == "int8":
        assert kv.cache_k.dtype == jnp.int8
        pools += [kv.scale_k, kv.scale_v]
    if weight_dtype == "int8":
        qparams, scales = progs.params_arg
        assert jax.tree.structure(qparams) == jax.tree.structure(scales)
        assert any(x.dtype == jnp.int8 for x in jax.tree.leaves(qparams))
    else:
        assert progs.params_arg is model.executor.params

    z = jnp.zeros((SLOTS,), jnp.int32)
    bt = jnp.zeros((SLOTS, kv.max_blocks_per_seq), jnp.int32)
    W = SPEC_K + 1
    lane = jax.ShapeDtypeStruct((SLOTS,), jnp.int32)
    probs = jax.ShapeDtypeStruct((SLOTS, VOCAB), jnp.float32)
    cases = {
        "decode": ((z, z, bt), [lane, probs]),
        "prefill": (
            (jnp.zeros((SLOTS, CHUNK), jnp.int32), z, z + 1, bt),
            [lane, probs],
        ),
        "draft": ((z, z, bt), [lane]),
        "verify": (
            (jnp.zeros((SLOTS, W), jnp.int32), z, bt),
            [jax.ShapeDtypeStruct((SLOTS, W), jnp.int32), lane, lane, lane],
        ),
    }
    assert progs.donate == tuple(range(1, 1 + len(pools)))
    for name, (inputs, heads) in cases.items():
        traced = getattr(progs, name).trace(progs.params_arg, *pools, *inputs)
        # the program's name in a trace and in the compile cache
        assert traced.fun_name == name
        # heads first, then the pools as they went in
        want = heads + [jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools]
        assert list(traced.out_info) == want, name
        params_info, *args_info = traced.args_info[0]
        donated = [i + 1 for i, a in enumerate(args_info) if a.donated]
        assert tuple(donated) == progs.donate, name
        assert not any(a.donated for a in jax.tree.leaves(params_info))


def test_no_speculation_no_speculative_programs(model):
    progs = build_serve_programs(model, _pool("fp32"), attn_kernel="gather")
    assert progs.draft is None and progs.verify is None
