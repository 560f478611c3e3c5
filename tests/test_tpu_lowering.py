"""Chip-free guard for the Pallas kernels: lower every entry point for
``platforms=["tpu"]`` on the CPU, at the shapes ``chip_smoke.py`` runs.

``jax.export`` runs the Pallas -> Mosaic lowering without a device, which
is where a bad BlockSpec or an unsupported cast fails.  Where libtpu can
describe a chip-less v5e topology the same function is also compiled by
the real TPU compiler, which is where Mosaic refuses a layout.  Neither
step executes anything: numerics are ``chip_smoke.py``'s job.
"""

from __future__ import annotations

import functools
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from flexflow_tpu.ops.pallas import flash_attention as fa  # noqa: E402
from flexflow_tpu.ops.pallas import paged_attention as pa  # noqa: E402
from flexflow_tpu.serve.engine import count_pool_relayouts  # noqa: E402
from flexflow_tpu.serve.kvcache import kv_pool_dtype  # noqa: E402

# GPT-2-small serving geometry: 8 slots, 12 heads of 64, 16-position
# blocks, 64 blocks per 1024-token sequence, full provisioning + trash;
# the pool is position-major, (L, N * BS, H * D)
B, H, D, BS, MB = 8, 12, 64, 16, 64
N = B * MB + 1


def _page_rows(kv_dtype):
    """(dtype, BS, MB) of a pool the kernels lower for: a page is whole
    sublane tiles of the pool's dtype (32 rows for a one-byte pool), the
    virtual length stays 1024."""
    pool_dt = kv_pool_dtype(jnp, kv_dtype, fallback=jnp.float32)
    bs = max(BS, pa.page_rows_tile(pool_dt))
    return pool_dt, bs, MB * BS // bs


@functools.lru_cache(maxsize=None)
def _v5e_sharding():
    """Single-device sharding on a compile-only v5e topology, or None
    when this libtpu cannot describe one without a chip."""
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception:  # noqa: BLE001 — any libtpu refusal means "skip tier 2"
        return None
    mesh = Mesh(np.array(topo.devices[:1]), ("x",))
    return NamedSharding(mesh, PartitionSpec())


def _lower_for_tpu(fn, *avals):
    jax.export.export(jax.jit(fn), platforms=["tpu"])(*avals)
    sh = _v5e_sharding()
    if sh is not None:
        placed = [
            jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh) for a in avals
        ]
        jax.jit(fn).lower(*placed).compile()


@pytest.fixture(autouse=True)
def _compiled_not_interpreted(monkeypatch):
    monkeypatch.setattr(pa, "INTERPRET", False)
    monkeypatch.setattr(fa, "INTERPRET", False)


@pytest.mark.parametrize("kv_dtype", ["fp32", "bf16", "int8", "fp8"])
@pytest.mark.parametrize("G", [1, 32])
def test_paged_attention_lowers_for_tpu(G, kv_dtype):
    pool_dt, bs, mb = _page_rows(kv_dtype)
    n = B * mb + 1
    sds = jax.ShapeDtypeStruct
    avals = [
        sds((B, G, H, D), jnp.bfloat16),
        sds((n * bs, H * D), pool_dt),
        sds((n * bs, H * D), pool_dt),
        sds((B,), jnp.int32),
        sds((B, mb), jnp.int32),
    ]
    if kv_dtype in ("int8", "fp8"):
        avals += [sds((n, bs), jnp.float32)] * 2

        def fn(q, k, v, pos, bt, sk, sv):
            return pa.paged_decode_attention(
                q, k, v, pos, bt, scale_k=sk, scale_v=sv, block_size=bs
            )
    else:
        fn = functools.partial(pa.paged_decode_attention, block_size=bs)
    _lower_for_tpu(fn, *avals)


L = 2  # layers of the pools below: enough to chain two writes


@pytest.mark.parametrize("kv_dtype", ["fp32", "bf16", "int8", "fp8"])
@pytest.mark.parametrize("G", [1, 5, 32])
def test_kv_page_write_lowers_for_tpu(G, kv_dtype):
    """The page-write kernel at decode / verify / prefill width: the
    select against the iota over BS has to lower for every pool dtype,
    packed ones included."""
    pool_dt, bs, mb = _page_rows(kv_dtype)
    n = B * mb + 1
    sds = jax.ShapeDtypeStruct

    def fn(ck, cv, k, v, start, bt, n_valid):
        for i in range(L):
            ck, cv = pa.paged_kv_write(
                ck, cv, i, k, v, start, bt, n_valid, block_size=bs
            )
        return ck, cv

    pool = sds((L, n * bs, H * D), pool_dt)
    _lower_for_tpu(
        fn, pool, pool,
        sds((B, G, H, D), pool_dt), sds((B, G, H, D), pool_dt),
        sds((B,), jnp.int32), sds((B, mb), jnp.int32), sds((B,), jnp.int32),
    )


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("G", [1, 32])
def test_serve_layers_leave_the_pool_to_the_kernels(G, kv_dtype):
    """What the serve programs do with the pools, compiled for a v5e: a
    layer writes its new rows through ``kv_page_write`` and attends
    through the paged kernel, both on the WHOLE pools.  Between the
    program's boundary and its kernels XLA then makes NO pool-sized
    array but the page writer's aliased outputs, however many layers
    there are: no layout copy at the boundary (ISSUE 29: the pool's
    minor dimension is the whole H * D row, so the layout the TPU keeps
    it in at rest is the one Mosaic reads; a (..., 16, 64) page cost
    four whole-pool copies a call), no scatter, no per-layer slice or
    re-layout (ISSUE 27: each cost a copy of the pool or of a layer,
    every layer of every call)."""
    import re

    sh = _v5e_sharding()
    if sh is None:
        pytest.skip("no v5e topology can be described here")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    pool_dt, bs, mb = _page_rows(kv_dtype)
    n = B * mb + 1
    quant = kv_dtype == "int8"

    def fn(ck, cv, sk, sv, q, k, v, start, bt):
        o = q
        for i in range(L):
            ck, cv = pa.paged_kv_write(
                ck, cv, i, k, v, start, bt, block_size=bs
            )
            o = o + pa.paged_decode_attention(
                o, ck, cv, start, bt, layer=i, block_size=bs,
                scale_k=sk if quant else None, scale_v=sv if quant else None,
            )
        return o, ck, cv

    pool = sds((L, n * bs, H * D), pool_dt)
    scales = sds((L, n, bs), jnp.float32)
    rows = sds((B, G, H, D), jnp.bfloat16)
    new = sds((B, G, H, D), pool_dt)
    txt = jax.jit(fn, donate_argnums=(0, 1)).lower(
        pool, pool, scales, scales, rows, new, new,
        sds((B,), jnp.int32), sds((B, mb), jnp.int32),
    ).compile().as_text()
    name = {"bf16": "bf16", "int8": "s8"}[kv_dtype]
    pool_sized = re.compile(
        rf"^\s*(?:ROOT )?%(\S+) = \(?{name}\[{L},{n * bs},{H * D}\]\S* "
        rf"(?:{name}\S+ )?([\w-]+)\(", re.M,
    )
    made = [
        (name, op) for name, op in pool_sized.findall(txt)
        if op not in ("parameter", "get-tuple-element", "tuple", "bitcast")
    ]
    writers = [n for n, op in made if n.startswith("kv_page_write")]
    assert len(writers) == L, made
    if not quant:
        # (a one-byte pool this small is staged whole through faster
        # memory, copy-start / copy-done and a ConcatBitcast, which the
        # cell-sized pools never are)
        assert all(op == "custom-call" for _, op in made), made
        assert len(made) == L, made
    # no operation under any shape copies or transposes a pool's bytes
    # (the count ``ServeEngine.pool_relayouts`` makes on the chip)
    assert count_pool_relayouts(
        txt, L * n * bs * H * D * jnp.dtype(pool_dt).itemsize
    ) == 0
    # the attention kernel keeps the name the benchmark's regex reads:
    # that of the jitted entry point around it, whatever the program
    assert len(re.findall(r"^\s*%decode[.\d]* = \S+ custom-call\(", txt, re.M)) == L


def test_serve_programs_at_the_cell_geometry_hold_no_weight_cast():
    """The ``gpt2_small`` cells' serve programs (12 blocks of 768 / 3072
    scan-stacked, 24 slots, chunks of 32 -- so a chunk's normed rows are
    float32 ``(768, 768)`` like a weight; a small vocabulary), compiled
    for a v5e: handed the executor's float32 tree, ``jit_decode``
    converts every weight stack to bfloat16 before it multiplies
    anything (ISSUE 33: 27.5 / 36.9 % of the cells' device time); handed
    ``params_arg``, the tree cast once at build, neither program holds
    such a convert, nor a whole-pool copy, and the temporaries shrink by
    the bfloat16 copies no longer made."""
    from flexflow_tpu import FFConfig, FFModel, MachineMesh
    from flexflow_tpu.models.transformer import gpt_decoder
    from flexflow_tpu.serve.engine import count_weight_casts
    from flexflow_tpu.serve.kvcache import PagedKVCache
    from flexflow_tpu.serve.programs import build_serve_programs

    sh = _v5e_sharding()
    if sh is None:
        pytest.skip("no v5e topology can be described here")
    slots, chunk, seq, depth = 24, 32, 1024, 12
    m = FFModel(FFConfig(batch_size=slots, compute_dtype="bfloat16"))
    gpt_decoder(
        m, slots, seq, hidden=H * D, heads=H, ff_dim=4 * H * D,
        num_layers=depth, vocab=512, use_flash=False,
    )
    m.compile(seed=0, mesh=MachineMesh((1, 1), ("data", "model")))
    kv = PagedKVCache(
        depth, H, D, slots=slots, block_size=BS, max_seq_len=seq,
        dtype=jnp.bfloat16, chunk=chunk,
    )
    progs = build_serve_programs(m, kv, attn_kernel="paged", return_probs=False)

    def sds(x, dtype=None):
        return jax.ShapeDtypeStruct(
            getattr(x, "shape", x), dtype or x.dtype, sharding=sh
        )

    stored = m.executor.params
    shapes = {tuple(x.shape) for x in jax.tree.leaves(stored)}
    assert (depth, H * D, 4 * H * D) in shapes  # the stacks, as stored
    pools = [sds(kv.cache_k), sds(kv.cache_v)]
    lane, bt = sds((slots,), jnp.int32), sds((slots, MB), jnp.int32)
    decode_args = (lane, lane, bt)
    prefill_args = (sds((slots, chunk), jnp.int32), lane, lane, bt)

    def compiled(prog, tree, args):
        c = prog.lower(jax.tree.map(sds, tree), *pools, *args).compile()
        return c.as_text(), c.memory_analysis().temp_size_in_bytes

    text, temp_f32 = compiled(progs.decode, stored, decode_args)
    # a convert a stack and more (17 here, jax 0.9.0 / libtpu 0.0.34)
    assert count_weight_casts(text, shapes, jnp.bfloat16) >= 9
    nbytes = kv.cache_k.size * kv.cache_k.dtype.itemsize
    for prog, args in ((progs.prefill, prefill_args), (progs.decode, decode_args)):
        text, temp = compiled(prog, progs.params_arg, args)
        assert count_weight_casts(text, shapes, jnp.bfloat16) == 0
        assert count_pool_relayouts(text, nbytes) == 0
    # decode's: 173 MB of temporaries handed float32, 3 MB handed bfloat16
    assert temp_f32 - temp > 100e6, (temp_f32, temp)


@pytest.mark.parametrize("dropout", [0.0, 0.1])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_lowers_for_tpu(causal, dropout):
    # b2 h12 s8192 d64: 6 GiB of f32 scores, past the dispatcher's 4 GiB
    # threshold (ops/attention.py::_flash_ok) — a shape flash really gets
    q = jax.ShapeDtypeStruct((2, 12, 8192, 64), jnp.bfloat16)

    def fwd(q, k, v):
        return fa.flash_attention(
            q, k, v, causal=causal, dropout_rate=dropout, seed=7
        )

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    _lower_for_tpu(fwd, q, q, q)
    _lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)


def test_flash_attention_lowers_at_the_gated_attention_shape():
    # b1 h16 s8192 d256, causal: the full-attention layer of the
    # qwen3_next_80b_a3b cell (exactly 4 GiB of f32 scores, the
    # dispatcher's threshold), forward and backward
    q = jax.ShapeDtypeStruct((1, 16, 8192, 256), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True).astype(jnp.float32))

    _lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)


def test_grouped_expert_matmul_and_chunk_solve_lower_for_tpu():
    """The two XLA pieces of the same cell whose TPU lowering is not a
    plain fusion: ``lax.ragged_dot`` over 32 held experts (10,240 rows)
    with its backward, and the unit-triangular solve of the delta rule
    over 4,096 chunks of 64."""
    from flexflow_tpu.ops import linear_attention as la

    x = jax.ShapeDtypeStruct((10240, 2048), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((32, 2048, 512), jnp.bfloat16)
    sizes = jax.ShapeDtypeStruct((32,), jnp.int32)

    def experts(x, w, sizes):
        return jnp.sum(jax.lax.ragged_dot(x, w, sizes, preferred_element_type=jnp.float32))

    _lower_for_tpu(jax.grad(experts, argnums=(0, 1)), x, w, sizes)

    qk = jax.ShapeDtypeStruct((1, 512, 32, 128), jnp.bfloat16)
    g = jax.ShapeDtypeStruct((1, 512, 32), jnp.float32)

    def rule(q, k, v, g, beta):
        return jnp.sum(la.gated_delta_rule_chunked(q, k, v, g, beta)[0])

    _lower_for_tpu(jax.grad(rule, argnums=(0, 1, 2, 3, 4)), qk, qk, qk, g, g)


# Trinity-Mini's serving geometry (ISSUE 32): 32 query / 4 K/V heads of
# 128, a chunk of 256 rows in tiles of 16 positions, the full group's
# table (528 pages of 16 for 8,448 positions) and the window group's ring
# (145 pages: window 2,048 + chunk 256, and one)
TQH, TKV, TD, TMB, TRING, TWIN = 32, 4, 128, 528, 145, 2048


@pytest.mark.parametrize("G", [1, 256])
@pytest.mark.parametrize("window,mb", [(0, TMB), (TWIN, TRING)])
def test_grouped_head_attention_lowers_for_tpu(G, window, mb):
    slots = 4
    n = slots * mb + 1
    entry = pa.paged_prefill_attention if G > 1 else pa.paged_decode_attention
    dt = jnp.bfloat16

    def fn(q, pk, pv, pos, bt):
        return entry(q, pk, pv, pos, bt, layer=jnp.int32(1), block_size=BS, window=window)

    _lower_for_tpu(
        fn,
        jax.ShapeDtypeStruct((slots, G, TQH, TD), dt),
        jax.ShapeDtypeStruct((2, n * BS, TKV * TD), dt),
        jax.ShapeDtypeStruct((2, n * BS, TKV * TD), dt),
        jax.ShapeDtypeStruct((slots,), jnp.int32),
        jax.ShapeDtypeStruct((slots, mb), jnp.int32),
    )


@pytest.mark.parametrize("G", [1, 256])
def test_page_write_through_a_ring_lowers_for_tpu(G):
    slots = 4
    n = slots * TRING + 1
    dt = jnp.bfloat16

    def fn(pk, pv, k, v, start, bt, n_valid):
        return pa.paged_kv_write(
            pk, pv, jnp.int32(1), k, v, start, bt, n_valid, block_size=BS, ring=True
        )

    _lower_for_tpu(
        fn,
        jax.ShapeDtypeStruct((2, n * BS, TKV * TD), dt),
        jax.ShapeDtypeStruct((2, n * BS, TKV * TD), dt),
        jax.ShapeDtypeStruct((slots, G, TKV, TD), dt),
        jax.ShapeDtypeStruct((slots, G, TKV, TD), dt),
        jax.ShapeDtypeStruct((slots,), jnp.int32),
        jax.ShapeDtypeStruct((slots, TRING), jnp.int32),
        jax.ShapeDtypeStruct((slots,), jnp.int32),
    )


# Nemotron-3-Nano's serving geometry (ISSUE 34): 32 query / 2 K/V heads
# of 128 (a pool row of 256 lanes, 16 query heads a K/V head), a table
# of 136 pages of 16 for 2,176 positions; Mamba-2 with 64 heads of 64,
# state 128, 8 groups, conv 4 over 6,144 channels, hidden 2,688
NQH, NKV, ND, NMB = 32, 2, 128, 136
NH, NP, NG, NN, NE = 64, 64, 8, 128, 2688


@pytest.mark.parametrize("G", [1, 128, 256])
def test_attention_and_page_write_lower_at_16_query_heads_a_kv_head(G):
    slots = 8
    n = slots * NMB + 1
    entry = pa.paged_prefill_attention if G > 1 else pa.paged_decode_attention
    dt = jnp.bfloat16
    pool = jax.ShapeDtypeStruct((1, n * BS, NKV * ND), dt)
    ints = jax.ShapeDtypeStruct((slots,), jnp.int32)
    bt = jax.ShapeDtypeStruct((slots, NMB), jnp.int32)

    def attend(q, pk, pv, pos, bt):
        return entry(q, pk, pv, pos, bt, layer=jnp.int32(0), block_size=BS)

    _lower_for_tpu(attend, jax.ShapeDtypeStruct((slots, G, NQH, ND), dt), pool, pool, ints, bt)

    def write(pk, pv, k, v, start, bt, n_valid):
        return pa.paged_kv_write(pk, pv, jnp.int32(0), k, v, start, bt, n_valid, block_size=BS)

    kv = jax.ShapeDtypeStruct((slots, G, NKV, ND), dt)
    _lower_for_tpu(write, pool, pool, kv, kv, ints, bt, ints)


@pytest.mark.parametrize("G", [1, 128])
def test_state_layer_updates_its_state_in_place_on_the_tpu(G):
    """The Mamba-2 mixer at the published widths, as a serve program
    runs it (one step, or a chunk from a slot's state), both states
    donated: the compiled module updates the state (2.1 MB a slot) in
    place -- no copy of the whole array; the conv's tail (36 KB a slot,
    a shift register that every call rewrites whole) is not held to
    that."""
    from flexflow_tpu.ops import ssm

    sh = _v5e_sharding()
    if sh is None:
        pytest.skip("no compile-only v5e topology")
    slots, dt = 16, jnp.bfloat16
    attrs = dict(num_heads=NH, head_dim=NP, n_groups=NG, state_size=NN, conv_kernel=4,
                 chunk=128, eps=1e-5)
    d, cw = NH * NP, NH * NP + 2 * NG * NN
    shapes = {
        "in_proj": ((NE, d + cw + NH), dt), "conv": ((cw, 4), dt), "conv_bias": ((cw,), dt),
        "A_log": ((NH,), jnp.float32), "dt_bias": ((NH,), jnp.float32),
        "D": ((NH,), jnp.float32), "scale": ((d,), dt), "out_proj": ((d, NE), dt),
    }

    def place(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    params = {k: place(*v) for k, v in shapes.items()}
    conv, state = place((slots, 3, cw), dt), place((slots, NH, NP, NN), jnp.float32)

    def layer(params, conv, state, u, n_valid):
        o, c, s = ssm.mamba2_mixer(params, u, attrs, conv, state, n_valid)
        return o, c, jnp.where((n_valid > 0)[:, None, None, None], s, state)

    text = jax.jit(layer, donate_argnums=(1, 2)).lower(
        params, conv, state, place((slots, G, NE), dt), place((slots,), jnp.int32),
    ).compile().as_text()
    assert "input_output_alias={ {1}: (8, {}, may-alias), {2}: (9, {}, may-alias) }" in text
    assert count_pool_relayouts(text, slots * NH * NP * NN * 4) == 0
