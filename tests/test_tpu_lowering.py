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
    """Lower ``fn`` for the TPU, and compile it for a described v5e where
    libtpu can describe one: the compiled module's text, else None."""
    jax.export.export(jax.jit(fn), platforms=["tpu"])(*avals)
    sh = _v5e_sharding()
    if sh is None:
        return None
    placed = [
        jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh) for a in avals
    ]
    return jax.jit(fn).lower(*placed).compile().as_text()


def _attention_lowers(fn, q, pool, positions, tables, heads):
    """The paged kernel at ``q``'s width contracts its heads as
    ``heads`` says (``rows_tile``), lowers and compiles for the TPU, and
    the compiled module copies and transposes no pool."""
    G, QH, D = q.shape[1:]
    assert pa.rows_tile(G, QH, pool.shape[-1] // D, D)[1] == heads
    text = _lower_for_tpu(fn, q, pool, pool, positions, tables)
    if text is not None:
        nbytes = int(np.prod(pool.shape)) * jnp.dtype(pool.dtype).itemsize
        assert count_pool_relayouts(text, nbytes) == 0


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
    the bfloat16 copies no longer made.  A prefill group is every slot
    here (``prefill_group_lanes``), so prefill is the full-width program,
    as before PR 38: no loop over groups (``while``; a group's gathers and
    scatters live in its body) and no ordering of the lanes (``sort``)."""
    from flexflow_tpu import FFConfig, FFModel, MachineMesh
    from flexflow_tpu.models.transformer import gpt_decoder
    from flexflow_tpu.serve.engine import count_weight_casts
    from flexflow_tpu.serve.kvcache import PagedKVCache
    from flexflow_tpu.serve.programs import build_serve_programs, prefill_group_lanes

    sh = _v5e_sharding()
    if sh is None:
        pytest.skip("no v5e topology can be described here")
    slots, chunk, seq, depth = 24, 32, 1024, 12
    assert prefill_group_lanes(slots, chunk) == slots
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
        low = prog.lower(jax.tree.map(sds, tree), *pools, *args)
        assert "stablehlo.while" not in low.as_text()
        assert "stablehlo.sort" not in low.as_text()
        c = low.compile()
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


def test_nemotron_cell_prefill_loops_over_groups_in_place(monkeypatch):
    """Nemotron-H's cell geometry (128 slots x chunks of 256) at the
    published widths -- three layers, a state, an attention and an
    expert layer, eight experts held --, compiled for a v5e through the
    experts' kernel: the prefill program loops over groups of 8 lanes
    (2,048 rows), and copies no pool or state array a group."""
    from flexflow_tpu import FFConfig, FFModel, MachineMesh
    from flexflow_tpu.models.nemotron_h import nemotron_h_decoder
    from flexflow_tpu.ops.pallas import grouped_matmul as gm
    from flexflow_tpu.serve.kvcache import PagedKVCache
    from flexflow_tpu.serve.programs import build_serve_programs, prefill_group_lanes

    sh = _v5e_sharding()
    if sh is None:
        pytest.skip("no v5e topology can be described here")
    slots, chunk, seq = 128, 256, 2176
    assert prefill_group_lanes(slots, chunk) == 8
    m = FFModel(FFConfig(batch_size=slots, compute_dtype="bfloat16", param_dtype="bfloat16"))
    nemotron_h_decoder(
        m, slots, seq, hidden=NE, heads=32, ff_dim=XW, num_layers=3, vocab=1024,
        pattern="M*E", kv_heads=2, head_dim=128, mamba_heads=NH, mamba_head_dim=NP,
        n_groups=NG, state_size=NN, conv_kernel=4, chunk=128, router_experts=128,
        first_expert=0, held_experts=8, top_k=6, shared_ff_dim=3712, route_norm=True,
        route_scale=2.5, expert_act="relu2", eps=1e-5, use_flash=False,
    )
    m.compile(seed=0, mesh=MachineMesh((1, 1), ("data", "model")))
    cw = NH * NP + 2 * NG * NN
    kv = PagedKVCache(
        1, 2, 128, slots=slots, block_size=16, num_blocks=1025, max_seq_len=seq,
        dtype=jnp.bfloat16, chunk=chunk, state_shapes=[((3, cw), (NH, NP, NN))],
    )
    progs = build_serve_programs(m, kv, attn_kernel="paged", return_probs=False)
    monkeypatch.setattr(gm, "INTERPRET", False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(x, dtype=None):
        return jax.ShapeDtypeStruct(getattr(x, "shape", x), dtype or x.dtype, sharding=sh)

    pools = [sds(x) for x in (kv.cache_k, kv.cache_v, *kv.state_conv, *kv.state_ssm)]
    lane = sds((slots,), jnp.int32)
    text = progs.prefill.lower(
        jax.tree.map(sds, progs.params_arg), *pools, sds((slots, chunk), jnp.int32),
        lane, lane, sds((slots, kv.max_blocks_per_seq), jnp.int32),
    ).compile().as_text()
    assert " while(" in text and "s32[8,256]" in text  # a group's tokens
    assert "ragged-dot-none" in text
    for x in (kv.cache_k, kv.state_ssm[0]):
        assert count_pool_relayouts(text, x.size * x.dtype.itemsize, x.dtype.itemsize) == 0


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


# Nemotron-H's expert stacks (ISSUE 35): 64 held experts, hidden 2688,
# width 1856 -- no factor of 512 in either, so XLA's ``ragged_dot`` walks
# them in 128 x 128 tiles and the repo's own kernel takes them instead
XE, XH, XW = 64, 2688, 1856


def _ragged_dot_calls(text):
    """The ``%ragged-dot-none[.N]`` custom calls of a compiled program's
    text: ``(is the repo's Pallas kernel, [window of each operand and
    result])``, the windows read from the call's Mosaic module."""
    import base64
    import re

    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    ctx = jmlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True
    calls = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%ragged-dot-none[.\d]* = \S+ custom-call\(", line)
        if m is None:
            continue
        assert 'custom_call_target="tpu_custom_call"' in line
        body = re.search(r'"body":"([^"]+)"', line).group(1)
        with ctx:
            module = str(ir.Module.parse(base64.b64decode(body)))
        windows = [
            tuple(int(d) for d in w.split(","))
            for w in re.findall(r"window_bounds = array<i64: ([\d, ]+)>", module)
        ]
        calls.append(("/pallas_call" in line, windows))
    return calls


def _expert_pair(x, w_up, w_down, sizes):
    from flexflow_tpu.ops import moe

    h = jnp.square(jax.nn.relu(moe.grouped(x, w_up, sizes)))
    return moe.grouped(h.astype(x.dtype), w_down, sizes)


def _compiled_for_v5e(fn, *avals):
    sh = _v5e_sharding()
    if sh is None:
        pytest.skip("no compile-only v5e topology")
    placed = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh) for a in avals]
    # a function of its own: jit's trace cache does not know which
    # backend ``grouped`` was told it is on
    return jax.jit(lambda *a: fn(*a)).lower(*placed).compile().as_text()


@pytest.fixture
def on_a_tpu(monkeypatch):
    """``ops/moe.py::grouped`` asks the default backend, which is the
    CPU here: the test steers it, not an option of the program."""
    from flexflow_tpu.ops.pallas import grouped_matmul as gm

    monkeypatch.setattr(gm, "INTERPRET", False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("stack", ["w_up", "w_down"])
@pytest.mark.parametrize("rows", [768, 24576])
def test_expert_matmul_kernel_at_nemotrons_widths(on_a_tpu, rows, stack):
    """A decode step's 768 rows and a prefill pass's 24,576 through each
    of the two stacks: the kernel lowers, compiles on a described v5e,
    takes a whole expert's matrix a weight tile -- no 128 x 128 window --
    and the stack as it rests: no copy of it in the compiled program."""
    from flexflow_tpu.ops import moe
    from flexflow_tpu.serve.engine import count_stack_copies

    k, n = (XH, XW) if stack == "w_up" else (XW, XH)
    sds = jax.ShapeDtypeStruct
    avals = [sds((rows, k), jnp.bfloat16), sds((XE, k, n), jnp.bfloat16), sds((XE,), jnp.int32)]
    jax.export.export(jax.jit(moe.grouped), platforms=["tpu"])(*avals)
    text = _compiled_for_v5e(moe.grouped, *avals)
    (ours, windows), = _ragged_dot_calls(text)
    assert ours
    # rows, one expert's matrix with K minor as both stacks rest, the result
    assert windows == [(128, k), (1, XW, XH), (128, n)]
    assert count_stack_copies(text, [(XE, k, n)]) == 0


def test_ragged_dot_at_nemotrons_widths_is_what_the_kernel_replaces():
    """Why the kernel: XLA's own lowering of the same pair walks both
    stacks in 128 x 128 windows and copies ``w_up`` (it rests with 2688
    minor) whole.  On the CPU's default backend ``grouped`` keeps
    ``lax.ragged_dot``, which is what is compiled here."""
    from flexflow_tpu.serve.engine import count_stack_copies

    sds = jax.ShapeDtypeStruct
    text = _compiled_for_v5e(
        _expert_pair, sds((768, XH), jnp.bfloat16), sds((XE, XH, XW), jnp.bfloat16),
        sds((XE, XW, XH), jnp.bfloat16), sds((XE,), jnp.int32),
    )
    calls = _ragged_dot_calls(text)
    assert len(calls) == 2 and not any(ours for ours, _ in calls)
    assert all(w[1] == (1, 128, 128) for _, w in calls)
    assert count_stack_copies(text, [(XE, XH, XW), (XE, XW, XH)]) == 1


def test_expert_pair_through_the_kernel_copies_no_stack_and_trains(on_a_tpu):
    """The ``relu2`` pair as a serve program runs it -- two kernel
    calls, both named ``ragged-dot-none``, no stack copied -- and its
    gradient, whose backward is ``lax.ragged_dot``'s own, compile."""
    from flexflow_tpu.serve.engine import count_stack_copies

    sds = jax.ShapeDtypeStruct
    avals = [sds((768, XH), jnp.bfloat16), sds((XE, XH, XW), jnp.bfloat16),
             sds((XE, XW, XH), jnp.bfloat16), sds((XE,), jnp.int32)]
    text = _compiled_for_v5e(_expert_pair, *avals)
    calls = _ragged_dot_calls(text)
    assert [ours for ours, _ in calls] == [True, True]
    assert count_stack_copies(text, [(XE, XH, XW), (XE, XW, XH)]) == 0
    text = _compiled_for_v5e(
        jax.grad(lambda *a: jnp.sum(_expert_pair(*a)), argnums=(0, 1, 2)), *avals
    )
    # ``w_up``'s forward (what ``w_down``'s would give, a sum does not
    # need) by the kernel, under the same name; the four backward
    # products by XLA
    assert sorted(ours for ours, _ in _ragged_dot_calls(text)) == [False] * 4 + [True]


@pytest.mark.parametrize("rows,experts,hidden,width", [
    (256, 128, 2048, 1024),  # Trinity-Mini, a decode step
    (4096, 128, 2048, 1024),  # a prefill pass
    (10240, 32, 2048, 512),  # Qwen3-Next, a training pass
])
def test_widths_with_a_factor_of_512_keep_xlas_ragged_dot(monkeypatch, rows, experts, hidden, width):
    """By their widths these stacks get 512-wide tiles from XLA: on a
    TPU too ``grouped`` leaves them to ``lax.ragged_dot``, and their
    compiled program is the one they have without this choice."""
    sds = jax.ShapeDtypeStruct
    avals = [sds((rows, hidden), jnp.bfloat16), sds((experts, hidden, width), jnp.bfloat16),
             sds((experts, width, hidden), jnp.bfloat16), sds((experts,), jnp.int32)]
    texts = []
    for backend in ("cpu", "tpu"):  # the CPU's: ``ragged_dot``, as before this choice
        monkeypatch.setattr(jax, "default_backend", lambda backend=backend: backend)
        texts.append(_compiled_for_v5e(_expert_pair, *avals))
    as_it_was, text = texts
    calls = _ragged_dot_calls(text)
    assert len(calls) == 2 and not any(ours for ours, _ in calls)
    assert all(w[1] == (1, 512, 512) for _, w in calls)
    assert text == as_it_was


# Trinity-Mini's serving geometry (ISSUE 32): 32 query / 4 K/V heads of
# 128, the cell's chunk of 128 rows (and 256) a K/V head at a time in
# tiles of 64 positions, the full group's table (528 pages of 16 for
# 8,448 positions) and the window group's ring (145 pages: window 2,048 +
# chunk 256, and one)
TQH, TKV, TD, TMB, TRING, TWIN = 32, 4, 128, 528, 145, 2048


@pytest.mark.parametrize("G", [1, 128, 256])
@pytest.mark.parametrize("window,mb", [(0, TMB), (TWIN, TRING)])
def test_grouped_head_attention_lowers_for_tpu(G, window, mb):
    slots = 4
    n = slots * mb + 1
    entry = pa.paged_prefill_attention if G > 1 else pa.paged_decode_attention
    dt = jnp.bfloat16

    def fn(q, pk, pv, pos, bt):
        return entry(q, pk, pv, pos, bt, layer=jnp.int32(1), block_size=BS, window=window)

    _attention_lowers(
        fn,
        jax.ShapeDtypeStruct((slots, G, TQH, TD), dt),
        jax.ShapeDtypeStruct((2, n * BS, TKV * TD), dt),
        jax.ShapeDtypeStruct((slots,), jnp.int32),
        jax.ShapeDtypeStruct((slots, mb), jnp.int32),
        "per_head" if G > 1 else "block_diagonal",
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

    # the cell's chunk of 256 a K/V head at a time, in 4 tiles of 64
    _attention_lowers(attend, jax.ShapeDtypeStruct((slots, G, NQH, ND), dt), pool, ints, bt,
                      "per_head" if G > 1 else "block_diagonal")

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


# Phi-4-mini-flash-reasoning's serving geometry: differential attention
# as the pool serves it -- a K/V pair one head of 128 (10 K/V heads), a
# query pair two heads of 128 (40 query heads, 4 a K/V head), the score
# scale 1/8 -- over the full group's table (576 pages of 16 for 9,216
# positions) and a window ring of 49 pages (window 512 + chunk 256, and
# one); Mamba-1 with d_inner 5,120, state 16, conv 4, dt rank 160
PQH, PKV, PD, PMB, PRING, PWIN = 40, 10, 128, 576, 49, 512


@pytest.mark.parametrize("G", [1, 256])
@pytest.mark.parametrize("window,mb", [(0, PMB), (PWIN, PRING)])
def test_differential_attention_lowers_at_40_over_10_heads(G, window, mb):
    slots = 4
    n = slots * mb + 1
    entry = pa.paged_prefill_attention if G > 1 else pa.paged_decode_attention
    dt = jnp.bfloat16

    def fn(q, pk, pv, pos, bt):
        return entry(q, pk, pv, pos, bt, scale=0.125, layer=jnp.int32(0), block_size=BS,
                     window=window)

    # the cell's chunk of 256 a K/V head at a time (4 query heads x 32
    # positions = 128 rows a head), in 8 tiles
    pool = jax.ShapeDtypeStruct((1, n * BS, PKV * PD), dt)
    _attention_lowers(
        fn, jax.ShapeDtypeStruct((slots, G, PQH, PD), dt), pool,
        jax.ShapeDtypeStruct((slots,), jnp.int32), jax.ShapeDtypeStruct((slots, mb), jnp.int32),
        "per_head" if G > 1 else "block_diagonal",
    )


@pytest.mark.parametrize("G", [1, 128])
def test_mamba1_layer_updates_its_state_in_place_on_the_tpu(G):
    """The Mamba-1 mixer at the published widths, one step or a chunk
    from a slot's state, both states donated and handed back in place.  A
    decode step updates the (5,120, 16) float32 state with no copy of the
    whole array; a chunk's scan stages the state it carries in the chip's
    on-chip memory for the loop (a copy there and back, not a relayout),
    which the engine's prefill pays on a group's gathered rows."""
    from flexflow_tpu.ops import ssm

    sh = _v5e_sharding()
    if sh is None:
        pytest.skip("no compile-only v5e topology")
    slots, dt, d, n, r, e = 16, jnp.bfloat16, 5120, 16, 160, 2560
    attrs = dict(d_inner=d, state_size=n, conv_kernel=4, dt_rank=r)
    shapes = {
        "in_proj": ((e, 2 * d), dt), "conv": ((d, 4), dt), "conv_bias": ((d,), dt),
        "x_proj": ((d, r + 2 * n), dt), "dt_proj": ((r, d), dt),
        "dt_bias": ((d,), jnp.float32), "A_log": ((d, n), jnp.float32),
        "D": ((d,), jnp.float32), "out_proj": ((d, e), dt),
    }

    def place(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    params = {k: place(*v) for k, v in shapes.items()}
    conv, state = place((slots, 3, d), dt), place((slots, d, n), jnp.float32)

    def layer(params, conv, state, u, n_valid):
        o, c, s, _ = ssm.mamba1_mixer(params, u, attrs, conv, state, n_valid)
        return o, c, jnp.where((n_valid > 0)[:, None, None], s, state)

    text = jax.jit(layer, donate_argnums=(1, 2)).lower(
        params, conv, state, place((slots, G, e), dt), place((slots,), jnp.int32),
    ).compile().as_text()
    assert "input_output_alias={ {1}: (9, {}, may-alias), {2}: (10, {}, may-alias) }" in text
    assert count_pool_relayouts(text, slots * d * n * 4) == (0 if G == 1 else 1)


# MiniCPM-SALA's serving geometry: a sparse layer's pool row is one K/V
# head of 128 (16 query heads read it), pages of 64 -- a page is a
# selected block -- and a unit's table of at most 128 selected pages (64
# past dense_len, every block before it); a decode call takes 48 lanes a
# K/V head, a prefill group's call 4 lanes x 8 blocks of 64 queries
SQH, SD, SBS, STW = 16, 128, 64, 128


@pytest.mark.parametrize("lanes,G", [(48, 1), (32, 64)])
def test_sparse_kernel_lowers_at_16_query_heads_over_one_kv_head(lanes, G):
    n = 48 * 288 + 1
    dt = jnp.bfloat16

    def fn(q, pk, pv, pos, bt):
        return pa.paged_sparse_attention(q, pk, pv, pos, bt, layer=jnp.int32(1), block_size=SBS)

    # a prefill unit of 64 queries in one walk: 1,024 rows of its one
    # K/V head
    pool = jax.ShapeDtypeStruct((4, n * SBS, SD), dt)
    _attention_lowers(
        fn, jax.ShapeDtypeStruct((lanes, G, SQH, SD), dt), pool,
        jax.ShapeDtypeStruct((lanes,), jnp.int32), jax.ShapeDtypeStruct((lanes, STW), jnp.int32),
        "per_head" if G > 1 else "block_diagonal",
    )


@pytest.mark.parametrize("G", [1, 512])
def test_lightning_layer_updates_its_state_in_place_on_the_tpu(G):
    """The lightning mixer at the published widths (32 heads of 128 over
    4,096), one step or a chunk from a slot's (32, 128, 128) float32
    state, donated: the compiled module hands the state back in place.  A
    decode step updates it with no copy of the whole array; a chunk's
    scan stages the state it carries for the loop (one copy, as the
    Mamba-1 scan's), which the engine's prefill pays on a group's
    gathered rows."""
    from flexflow_tpu.ops.linear_attention import lightning_mixer

    sh = _v5e_sharding()
    if sh is None:
        pytest.skip("no compile-only v5e topology")
    slots, dt, e = 8, jnp.bfloat16, 4096
    attrs = dict(num_heads=32, head_dim=128, layer_idx=10, num_layers=32, rope_theta=1e4,
                 eps=1e-6, chunk=128)

    def place(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    params = {w: place((e, e), dt) for w in ("wq", "wk", "wv", "wg", "wo")}
    params.update(q_norm=place((128,), dt), k_norm=place((128,), dt), o_norm=place((e,), dt))
    state = place((slots, 32, 128, 128), jnp.float32)

    def layer(params, state, u, n_valid, pos):
        o, s = lightning_mixer(params, u, attrs, state, n_valid, pos)
        return o, jnp.where((n_valid > 0)[:, None, None, None], s, state)

    text = jax.jit(layer, donate_argnums=(1,)).lower(
        params, state, place((slots, G, e), dt), place((slots,), jnp.int32),
        place((slots, G), jnp.int32),
    ).compile().as_text()
    assert "input_output_alias={ {1}: (8, {}, may-alias) }" in text
    assert count_pool_relayouts(text, slots * 32 * 128 * 128 * 4) == (0 if G == 1 else 1)
