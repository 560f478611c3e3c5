"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the two main paths once, through the entry points a
user calls, at the full width of models the repo supports, and checks
what comes out:

  1. kernels  — both Pallas modules, compiled natively, against plain
                float32 ``jax.numpy`` references, and the page-write
                kernel against the XLA scatter, byte for byte;
  2. trainer  — BERT-Base b16 s512 bf16 Adam through ``FFModel.compile``
                (searched) and ``FFModel.fit`` on seeded synthetic data;
  3. server   — GPT-2-small s1024 bf16 through ``flexflow_tpu.serve.
                driver.main`` (what ``python -m flexflow_tpu --serve``
                runs), 8 slots, the default ``--serve-attn auto``; then
                once more with an int8 pool (pages of 32) and
                speculation (k = 3).  Both engines' compiled decode and
                prefill programs must hold no whole-pool copy
                (``ServeEngine.pool_relayouts() == 0``) and no convert
                of a float32 weight (``weight_casts() == 0``); the paged
                kernel's walk (``attn_walk()``) is printed beside them.

On a host with several chips the trainer also runs under the default
all-devices mesh and under the searched strategy, asserts where every
array lives, and compares the loss with a one-chip run in the same
process.  Any failed check ends the run non-zero.  Without a TPU, or
without the package beside it, it exits non-zero before doing anything
and prints no result.  Otherwise the last line of stdout is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

(``"ok": false`` and a non-zero exit when a phase failed), with the
device as JAX reports it; the line before it, ``summary: {...}``, holds
what each phase reported.  Compile seconds and step/window times are
printed as information only: this script measures nothing and claims
nothing.

    python chip_smoke.py
"""

from __future__ import annotations

import contextlib
import functools
import gc
import io
import json
import math
import os
import sys
import time
import traceback

# --- written tolerances, each with its reason --------------------------------
# paged attention accumulates in float32 from the stored values, so the
# kernel and the float32 reference differ by reordering only (~1e-6
# relative); the OUTPUT is cast to q's dtype, bfloat16 here, whose 8
# significant bits round a value by up to 2**-8 of its own magnitude.
# 2**-7 of the largest reference magnitude leaves a factor 2 over that
# rounding and still fails on a dropped page, a wrong scale row or a
# shifted causal mask (each moves an output by a large fraction of its
# magnitude).
PAGED_REL_TOL = 2.0 ** -7
# flash attention feeds bfloat16 q/k/v and bfloat16-rounded probabilities
# to the MXU (float32 accumulation) and returns bfloat16, forward and
# backward alike: the worst element stacks two or three roundings of
# 2**-8.  2**-6 of the largest reference magnitude allows four; a wrong
# mask, a missed block or a dropout mask that differs between forward
# and backward is off by a large fraction of the magnitude.
FLASH_REL_TOL = 2.0 ** -6
# data parallelism is the same mathematics as one chip; what differs is
# the order bfloat16 products are summed in (per-shard batches of b/n,
# then an all-reduce), and training amplifies that as the loss falls.
# The bound is therefore on the loss SCALE — 2% of the one-chip run's
# first-epoch mean — for every epoch: a relative bound on a loss that
# has fallen to ~0.03 would measure the noise.  Measured on four v5e
# chips (PR 21): 0.03% of that scale in the first epoch, 0.4% in the
# second.  A dropped shard or a mis-scaled gradient shows in the first
# epoch already, by far more than 2%.
MULTICHIP_LOSS_TOL = 2e-2


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def info(msg: str) -> None:
    print(f"info: {msg}", flush=True)


# =============================================================== kernels
_HIGHEST = "highest"  # jax.lax.Precision for every reference contraction


def _paged_reference(q, pages_k, pages_v, positions, tables, scale_k, scale_v):
    """Plain float32 attention over the gathered pages of each lane;
    ``pages_k`` / ``pages_v`` are (N, BS, H, D)."""
    import jax.numpy as jnp

    B, G, H, D = q.shape
    BS = pages_k.shape[1]
    MB = tables.shape[1]
    keys = pages_k[tables].astype(jnp.float32)  # (B, MB, BS, H, D)
    vals = pages_v[tables].astype(jnp.float32)
    if scale_k is not None:
        keys = keys * scale_k[tables][..., None, None]
        vals = vals * scale_v[tables][..., None, None]
    keys = keys.transpose(0, 3, 1, 2, 4).reshape(B, H, MB * BS, D)
    vals = vals.transpose(0, 3, 1, 2, 4).reshape(B, H, MB * BS, D)
    # HIGHEST: a TPU's default float32 matmul is one bfloat16 pass
    s = jnp.einsum(
        "bghd,bhkd->bghk", q.astype(jnp.float32), keys, precision=_HIGHEST
    ) / math.sqrt(D)
    row_pos = positions[:, None] + jnp.arange(G)[None, :]  # (B, G)
    visible = jnp.arange(MB * BS)[None, None, :] <= row_pos[..., None]
    s = jnp.where(visible[:, :, None, :], s, -jnp.inf)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return jnp.einsum("bghk,bhkd->bghd", p, vals, precision=_HIGHEST)


def _page_geometry(pa, pool_dtype, BS, MB):
    """(BS, MB) for a pool of ``pool_dtype``: a page is whole sublane
    tiles of it on the chip (32 rows for a one-byte pool), the virtual
    length stays ``BS * MB``."""
    bs = max(BS, 1 if pa.INTERPRET else pa.page_rows_tile(pool_dtype))
    return bs, BS * MB // bs


def check_paged_attention(
    *, B=8, H=12, D=64, BS=16, MB=64, groups=(1, 32),
    kv_dtypes=("fp32", "bf16", "int8", "fp8"), seed=0,
) -> dict:
    """``paged_decode_attention`` at GPT-2-small serving geometry (the
    server phase's: 8 slots, 12 heads of 64, 1024 positions in pages of
    16, or of 32 for a one-byte pool; the pool position-major, (N * BS,
    H * D)) for G=1 (decode) and G=prefill_chunk, every ``kv_dtype``
    the engine offers, scrambled block tables, lanes at different
    depths, garbage in the pages past each lane's write head."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.ops.pallas import paged_attention as pa
    from flexflow_tpu.serve.kvcache import kv_pool_dtype, quantize_kv

    cell = (BS, MB)
    out = {}
    for kv_dtype in kv_dtypes:
        dt = kv_pool_dtype(jnp, kv_dtype, fallback=jnp.float32)
        BS, MB = _page_geometry(pa, dt, *cell)
        N = B * MB + 1
        for G in groups:
            rng = np.random.default_rng(seed)
            q = jnp.asarray(rng.normal(size=(B, G, H, D)), jnp.bfloat16)
            kf = jnp.asarray(rng.normal(size=(N, BS, H, D)), jnp.float32)
            vf = jnp.asarray(rng.normal(size=(N, BS, H, D)), jnp.float32)
            sk = sv = None
            if kv_dtype in ("int8", "fp8"):
                kq, sk = quantize_kv(jnp, kf, kv_dtype)  # scales (N, BS)
                vq, sv = quantize_kv(jnp, vf, kv_dtype)
            else:
                kq, vq = kf.astype(dt), vf.astype(dt)
            tables = jnp.asarray(
                rng.permutation(np.arange(1, N)).reshape(B, MB), jnp.int32
            )
            # lanes spread from the first page to the last; row G-1 of
            # the deepest lane sits on the final position
            positions = jnp.asarray(
                np.linspace(0, MB * BS - G, B).astype(np.int32)
            )
            got = jax.jit(
                functools.partial(pa.paged_decode_attention, block_size=BS)
            )(
                q, kq.reshape(N * BS, H * D), vq.reshape(N * BS, H * D),
                positions, tables, None, sk, sv,
            )
            ref = jax.jit(_paged_reference)(
                q, kq, vq, positions, tables, sk, sv
            )
            got = np.asarray(got, np.float32)
            ref = np.asarray(ref, np.float32)
            check(got.shape == (B, G, H, D), f"paged {kv_dtype} G={G}: shape {got.shape}")
            check(np.isfinite(got).all(), f"paged {kv_dtype} G={G}: non-finite output")
            err = float(np.abs(got - ref).max() / np.abs(ref).max())
            out[f"{kv_dtype}/G{G}"] = err
            check(
                err <= PAGED_REL_TOL,
                f"paged {kv_dtype} G={G}: max error {err:.3e} of the largest "
                f"reference magnitude exceeds {PAGED_REL_TOL:.3e}",
            )
    return out


def check_kv_page_write(
    *, L=2, B=8, H=12, D=64, BS=16, MB=64, groups=(1, 32),
    kv_dtypes=("fp32", "bf16", "int8", "fp8"), seed=0,
) -> dict:
    """``paged_kv_write`` at the same geometry against the XLA scatter
    it replaced, byte for byte, for G=1 (decode) and G=prefill_chunk
    with short tails and an idle lane, every pool dtype: the select on
    packed pages has to be exact on the chip, not only to lower."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.ops.pallas import paged_attention as pa
    from flexflow_tpu.serve.kvcache import kv_pool_dtype

    cell = (BS, MB)
    out = {}
    for kv_dtype in kv_dtypes:
        dt = kv_pool_dtype(jnp, kv_dtype, fallback=jnp.float32)
        BS, MB = _page_geometry(pa, dt, *cell)
        N = B * MB + 1
        for G in groups:
            rng = np.random.default_rng(seed)

            def rand(shape):  # small integers: exact in every pool dtype
                return jnp.asarray(rng.integers(-8, 9, size=shape), jnp.float32).astype(dt)

            pk, pv = rand((L, N * BS, H * D)), rand((L, N * BS, H * D))
            k, v = rand((B, G, H, D)), rand((B, G, H, D))
            bt = rng.permutation(np.arange(1, N)).reshape(B, MB).astype(np.int32)
            bt[-1] = 0  # an idle lane
            start = np.linspace(0, MB * BS - G, B).astype(np.int32) + (BS - 3)
            start = np.minimum(start, MB * BS - G)
            n_valid = np.minimum(np.arange(B, dtype=np.int32) * 5 + 1, G)
            n_valid[-1] = 0
            pos = start[:, None] + np.arange(G)[None]
            valid = np.arange(G)[None] < n_valid[:, None]
            blk = np.where(valid, bt[np.arange(B)[:, None], pos // BS], 0)
            row = blk * BS + np.where(valid, pos % BS, 0)
            want = jax.jit(
                lambda a, b, k, v: (
                    a.at[1, row].set(k.reshape(B, G, H * D)),
                    b.at[1, row].set(v.reshape(B, G, H * D)),
                )
            )(pk, pv, k, v)
            got = jax.jit(
                lambda a, b, k, v: pa.paged_kv_write(
                    a, b, 1, k, v, start, bt, n_valid, block_size=BS
                )
            )(pk, pv, k, v)

            def raw(x):  # bytes by block; the device's layout need not be C order
                x = np.ascontiguousarray(np.asarray(x)).view(np.uint8)
                return x.reshape(L, N, -1)

            # block 0 is the trash block: padded rows may land anywhere in it
            diff = sum(
                int((raw(g)[:, 1:] != raw(w)[:, 1:]).sum())
                for g, w in zip(got, want)
            )
            out[f"{kv_dtype}/G{G}"] = diff
            check(diff == 0, f"kv_page_write {kv_dtype} G={G}: {diff} bytes differ from the scatter")
    return out


def _flash_reference(jax, jnp, q, k, v, *, causal, dropout_rate, seed):
    """Float32 attention one (batch, head) at a time, so the (S, S)
    scores never exist for more than one head; the dropout mask is the
    kernel's own counter hash evaluated in plain jax.numpy."""
    from flexflow_tpu.ops.pallas.flash_attention import _uniform01

    b, h, s, d = q.shape

    @jax.checkpoint
    def one(args):
        bh, qi, ki, vi = args
        sc = jnp.matmul(
            qi.astype(jnp.float32), ki.astype(jnp.float32).T, precision=_HIGHEST
        ) / math.sqrt(d)
        q_pos = jnp.arange(s, dtype=jnp.int32)[:, None]
        k_pos = jnp.arange(s, dtype=jnp.int32)[None, :]
        if causal:
            sc = jnp.where(q_pos >= k_pos, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        if dropout_rate > 0.0:
            u = _uniform01(
                jnp.uint32(seed), bh.astype(jnp.uint32),
                jnp.broadcast_to(q_pos, (s, s)), jnp.broadcast_to(k_pos, (s, s)),
            )
            p = jnp.where(u >= dropout_rate, p / (1.0 - dropout_rate), 0.0)
        return jnp.matmul(p, vi.astype(jnp.float32), precision=_HIGHEST)

    flat = lambda x: x.reshape(b * h, s, d)  # noqa: E731
    out = jax.lax.map(
        one, (jnp.arange(b * h, dtype=jnp.int32), flat(q), flat(k), flat(v))
    )
    return out.reshape(b, h, s, d)


def check_flash_attention(*, b=2, h=12, s=8192, d=64, seed=0) -> dict:
    """``flash_attention`` forward and backward at a shape the attention
    dispatcher really sends it (b2 h12 s8192: 6 GiB of float32 scores,
    past ``ops/attention.py``'s 4 GiB threshold), causal and not, with
    and without dropout."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.ops.attention import _flash_ok
    from flexflow_tpu.ops.pallas.flash_attention import flash_attention

    check(_flash_ok(s, s, d, b * h), f"dispatcher would not send b{b} h{h} s{s} to flash")
    rng = np.random.default_rng(seed)
    q, k, v = (
        jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.bfloat16) for _ in range(3)
    )
    # a fixed cotangent, so forward and backward share one scalar loss
    w = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    out = {}
    for causal in (False, True):
        for dropout in (0.0, 0.1):
            kw = dict(causal=causal, dropout_rate=dropout, seed=7)

            def f_kernel(q, k, v):
                return flash_attention(q, k, v, **kw)

            def f_ref(q, k, v):
                return _flash_reference(jax, jnp, q, k, v, **kw)

            def both(f):
                def loss(q, k, v):
                    o = f(q, k, v)
                    return jnp.sum(o.astype(jnp.float32) * w), o

                (_, o), g = jax.jit(
                    jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
                )(q, k, v)
                return [np.asarray(x, np.float32) for x in (o, *g)]

            tag = f"causal={int(causal)}/dropout={dropout}"
            t0 = time.perf_counter()
            got = both(f_kernel)
            t1 = time.perf_counter()
            ref = both(f_ref)
            info(f"flash {tag}: kernel fwd+bwd compiled and run in "
                 f"{t1 - t0:.1f} s, reference in {time.perf_counter() - t1:.1f} s")
            errs = {}
            for name, a, r in zip(("out", "dq", "dk", "dv"), got, ref):
                check(a.shape == r.shape, f"flash {tag} {name}: shape {a.shape}")
                check(np.isfinite(a).all(), f"flash {tag} {name}: non-finite")
                e = float(np.abs(a - r).max() / np.abs(r).max())
                errs[name] = e
                check(
                    e <= FLASH_REL_TOL,
                    f"flash {tag} {name}: max error {e:.3e} of the largest "
                    f"reference magnitude exceeds {FLASH_REL_TOL:.3e}",
                )
            out[tag] = errs
    return out


# =============================================================== trainer
def synthetic_classes(n, seq, hidden, classes, seed):
    """Seeded, learnable: every sample is its class's fixed pattern plus
    unit noise at each position, so the mean-pooled head can separate
    the classes and the loss has somewhere to fall."""
    import numpy as np

    rng = np.random.default_rng(seed)
    y = rng.integers(0, classes, size=(n, 1)).astype(np.int32)
    pattern = rng.standard_normal((classes, hidden), dtype=np.float32)
    x = rng.standard_normal((n, seq, hidden), dtype=np.float32)
    x += pattern[y[:, 0]][:, None, :]
    return x, y


def describe_placement(model) -> dict:
    """Assert that parameters, optimizer state and an input batch live on
    every device of the strategy's mesh under the shardings the strategy
    names; return a short account for the log."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    ex = model.executor
    n = model.strategy.mesh.size
    if ex.mesh is None:
        check(n == 1, "no device mesh under a multi-device strategy")
        return {"devices": 1, "sharded_weights": 0}
    every = set(ex.mesh.devices.flat)
    check(len(every) == n, f"mesh spans {len(every)} devices, strategy says {n}")
    by_name = {l.name: l for l in ex.layers}
    sharded = 0
    account = {}  # tree -> {partition spec: arrays under it}
    trees = {"params": ex.params, "adam.m": ex.opt_state["m"], "adam.v": ex.opt_state["v"]}
    for tname, tree in trees.items():
        for lname, ws in tree.items():
            stacked = lname in ex._bucket_members
            for wname, arr in ws.items():
                where = f"{tname}[{lname}][{wname}]"
                ps = tuple(model.strategy.weight_pspec(
                    by_name[lname], wname, arr.ndim - int(stacked)
                ))
                want = NamedSharding(
                    ex.mesh, PartitionSpec(*((None,) if stacked else ()), *ps)
                )
                check(
                    set(arr.sharding.device_set) == every,
                    f"{where} lives on {len(arr.sharding.device_set)} of {n} devices",
                )
                check(
                    arr.sharding.is_equivalent_to(want, arr.ndim),
                    f"{where}: sharding {arr.sharding} but the strategy "
                    f"names {want.spec}",
                )
                seen = account.setdefault(tname, {})
                seen[str(want.spec)] = seen.get(str(want.spec), 0) + 1
                if any(a is not None for a in ps):
                    sharded += tname == "params"
                    check(
                        not arr.sharding.is_fully_replicated,
                        f"{where} is replicated, the strategy shards it {ps}",
                    )
    b = ex.graph_inputs[0].shape[0]
    xs = [np.zeros(t.shape, t.dtype.to_jnp()) for t in ex.graph_inputs]
    inputs, labels = ex.place_batch(xs + [np.zeros((b, 1), np.int32)])
    specs = {}
    for name, arr, ps in (
        [(t.name, a, ex._input_pspec(t)) for t, a in zip(ex.graph_inputs, inputs)]
        + [("labels", labels, ex._label_pspec())]
    ):
        check(
            set(arr.sharding.device_set) == every,
            f"input {name} lives on {len(arr.sharding.device_set)} of {n} devices",
        )
        check(
            arr.sharding.is_equivalent_to(NamedSharding(ex.mesh, ps), arr.ndim),
            f"input {name}: sharding {arr.sharding}, executor names {ps}",
        )
        specs[name] = str(ps)
    if model.strategy.mesh.axis_size("data") > 1 and ex.pipeline is None:
        check(
            not inputs[0].sharding.is_fully_replicated,
            "the batch is replicated over a data-parallel mesh",
        )
    return {
        "devices": n, "sharded_weights": int(sharded), "arrays": account,
        "inputs": specs,
    }


def train_bert(
    *, mesh_shape=None, search_budget=8, init_weights=None, keep_weights=False,
    batch=16, seq=512, width=None, classes=64, batches=40, epochs=2,
    dtype="bfloat16", seed=0,
):
    """BERT-Base (the BENCH_r02 configuration) through builder -> search
    -> executor, one instrumented step to see the compile, then
    ``FFModel.fit`` with tracing off: the async metrics window, the
    input pipeline and the scan-stacked blocks are all on this path."""
    import jax
    import numpy as np

    from flexflow_tpu import (
        AdamOptimizer, FFConfig, FFModel, LossType, MachineMesh, MetricsType,
    )
    from flexflow_tpu.models.transformer import BERT_BASE, transformer_encoder
    from flexflow_tpu.obs import Tracer, configure, set_tracer

    width = dict(width or BERT_BASE)
    tracer = configure(level="step")
    cfg = FFConfig(
        batch_size=batch, compute_dtype=dtype, search_budget=search_budget,
    )
    model = FFModel(cfg)
    transformer_encoder(
        model, batch=batch, seq=seq, num_classes=classes, raw_input=True, **width
    )
    t0 = time.perf_counter()
    model.compile(
        optimizer=AdamOptimizer(alpha=1e-4),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[MetricsType.ACCURACY, MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY],
        mesh=MachineMesh(mesh_shape, ("data", "model")) if mesh_shape else None,
        seed=seed,
    )
    ex = model.executor
    if init_weights is not None:
        model.set_weights(init_weights)
    w0 = model.get_weights() if keep_weights else None
    build_s = time.perf_counter() - t0

    x, y = synthetic_classes(batches * batch, seq, width["hidden"], classes, seed)
    # step 0 through the instrumented path: AOT compile in its own span,
    # persistent-cache hit counted, memory snapshot taken
    ex.train_step([x[:batch]], y[:batch])
    stats = dict(ex.last_step_stats)
    counters = tracer.summary()["counters"]
    set_tracer(Tracer())  # fit runs the untraced, asynchronous path

    losses = []
    t0 = time.perf_counter()
    for _ in range(epochs):
        pm = model.fit(x, y, epochs=1, verbose=False)
        check(pm.train_all == batches * batch, f"fit saw {pm.train_all} samples")
        losses.append(pm.sparse_cce_loss / pm.train_all)
    fit_s = time.perf_counter() - t0
    check(all(math.isfinite(v) for v in losses), f"loss not finite: {losses}")
    check(
        losses[-1] < losses[0],
        f"loss did not fall over {epochs * batches} steps: {losses}",
    )
    chains = [(c.depth, c.block_len) for c in ex._block_chains]
    check(
        bool(chains) or width["num_layers"] < 4,
        "--stack-blocks auto left a chain of depth >= 4 unrolled",
    )
    placement = describe_placement(model)
    st = model.strategy
    res = {
        "mesh": dict(zip(st.mesh.axis_names, st.mesh.shape)),
        "searched": search_budget > 0,
        "steps": 1 + epochs * batches,
        "epoch_mean_loss": [round(v, 5) for v in losses],
        "stacked_chains": chains,
        "placement": placement,
        "aot_sharding_drifts": ex.aot_sharding_drifts,
        "persistent_cache_hit": bool(counters.get("jit_cache.persistent_hit")),
    }
    info(
        f"train mesh={res['mesh']} searched={res['searched']}: build+search+init "
        f"{build_s:.1f} s, step compile {stats['compile_s']:.1f} s "
        f"(persistent cache hit: {res['persistent_cache_hit']}), "
        f"{epochs * batches} fit steps in {fit_s:.2f} s wall "
        f"({1e3 * fit_s / (epochs * batches):.1f} ms each, input pipeline included), "
        f"epoch-mean loss {res['epoch_mean_loss']}, "
        f"AOT sharding drifts {ex.aot_sharding_drifts}"
    )
    del model, ex, x, y
    gc.collect()
    return res, w0


def train_phase(n_devices: int, **size) -> dict:
    out = {}
    if n_devices == 1:
        out["searched"], _ = train_bert(**size)
        return out
    # several chips: a one-chip run in this process is the reference the
    # all-devices runs must reproduce from the same initial weights
    ref, w0 = train_bert(
        mesh_shape=(1, 1), search_budget=-1, keep_weights=True, **size
    )
    out["one_chip"] = ref
    out["default_mesh"], _ = train_bert(search_budget=-1, init_weights=w0, **size)
    out["searched"], _ = train_bert(init_weights=w0, **size)
    for name in ("default_mesh", "searched"):
        run = out[name]
        check(
            run["placement"]["devices"] == n_devices,
            f"{name}: strategy uses {run['placement']['devices']} of {n_devices} devices",
        )
        bound = MULTICHIP_LOSS_TOL * ref["epoch_mean_loss"][0]
        for a, b in zip(run["epoch_mean_loss"], ref["epoch_mean_loss"]):
            check(
                abs(a - b) <= bound,
                f"{name}: epoch-mean loss {a} vs {b} on one chip "
                f"(tolerance {bound:.4f})",
            )
        run["loss_delta_vs_one_chip"] = [
            round(a - b, 5)
            for a, b in zip(run["epoch_mean_loss"], ref["epoch_mean_loss"])
        ]
    return out


# ================================================================ server
def serve_gpt2(
    *, slots=8, seq=1024, width=None, vocab=50257, requests=24,
    prompt_len=(64, 512), gen_len=(32, 128), dtype="bfloat16", extra=(),
) -> dict:
    """GPT-2-small through the serve driver's ``main`` — the function
    ``python -m flexflow_tpu --serve`` calls — and its JSON summary;
    ``extra`` is more of its command line."""
    from unittest import mock

    import jax

    import flexflow_tpu.serve as serve_pkg
    from flexflow_tpu.models.transformer import GPT2_SMALL
    from flexflow_tpu.serve import TrafficSpec, synthetic_requests
    from flexflow_tpu.serve.driver import main as serve_main

    built = []  # the engine the driver builds, to ask it afterwards

    class Recorded(serve_pkg.ServeEngine):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            built.append(self)

    width = dict(width or GPT2_SMALL)
    argv = [
        "--serve-slots", str(slots), "--dtype", dtype,
        "--hidden", str(width["hidden"]), "--heads", str(width["heads"]),
        "--ff-dim", str(width["ff_dim"]), "--num-layers", str(width["num_layers"]),
        "--vocab", str(vocab), "--seq", str(seq),
        "--requests", str(requests), "--traffic-seed", "0",
        "--prompt-len", "%d:%d" % prompt_len, "--gen-len", "%d:%d" % gen_len,
        *extra,
    ]
    buf = io.StringIO()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(buf), mock.patch.object(
        serve_pkg, "ServeEngine", Recorded
    ):
        rc = serve_main(argv)
    total_s = time.perf_counter() - t0
    check(rc == 0, f"serve driver returned {rc}")
    line = buf.getvalue().strip().splitlines()[-1]
    print(line, flush=True)
    s = json.loads(line)
    # the same seeded traffic the driver generated, clamped the same way
    want = synthetic_requests(TrafficSpec(
        n_requests=requests, seed=0, prompt_len=prompt_len, max_new=gen_len,
        vocab=vocab,
    ))
    want_tokens = sum(
        max(1, min(r.max_new_tokens, seq - r.prompt_len)) for r in want
    )
    check(
        s["requests_finished"] == requests and s["requests_rejected"] == 0,
        f"{s['requests_finished']} of {requests} requests finished, "
        f"{s['requests_rejected']} rejected",
    )
    check(
        s["new_tokens"] == want_tokens,
        f"{s['new_tokens']} tokens generated, the traffic asks for {want_tokens}",
    )
    check(
        s["host_syncs"] == s["windows"],
        f"{s['host_syncs']} host syncs over {s['windows']} windows",
    )
    check(s["attn_kernel"] == "paged", f"attn_kernel {s['attn_kernel']!r}")
    check(s["prefill_attn_kernel"] == "paged", "prefill did not run the paged kernel")
    check(not s["attn_interpret"], "the paged kernel ran in the Pallas interpreter")
    check(s["kv_write"] == "page_kernel", f"kv_write {s['kv_write']!r}")
    check(len(built) == 1, f"the driver built {len(built)} engines")
    relayouts = built[0].pool_relayouts()
    check(
        relayouts == 0,
        f"{relayouts} whole-pool copies or transposes in the compiled decode "
        f"and prefill programs (pool {built[0].kv.cache_k.shape} "
        f"{built[0].kv.cache_k.dtype})",
    )
    casts = built[0].weight_casts()
    check(
        casts == 0 or built[0].weight_dtype == "int8",
        f"{casts} converts of a float32 weight to "
        f"{built[0].model.executor.compute_dtype} in the compiled decode and "
        "prefill programs: the weights were not handed over cast",
    )
    walk = built[0].attn_walk()
    info(
        f"serve: pool_relayouts {relayouts}, weight_casts {casts}, "
        f"attn_walk {walk}"
    )
    check(
        s["prefill_chunks"] > s["prefill_dispatches"] > 0,
        "prefill chunks were not batched over slots",
    )
    check(s["peak_active"] == slots, f"peak_active {s['peak_active']} of {slots} slots")
    dev = s["device"]
    check(
        dev["platform"] == jax.devices()[0].platform
        and dev["device_count"] == len(jax.devices()),
        f"summary names device {dev}",
    )
    check(
        dev["devices_used"] == 1,
        f"one engine spread over {dev['devices_used']} devices",
    )
    info(
        f"serve: {total_s:.1f} s in driver.main of which {s['wall_s']:.1f} s serving "
        f"(the rest is build, init and compile); {s['windows']} windows, "
        f"{1e3 * s['wall_s'] / s['windows']:.1f} ms per window wall, "
        f"{s['decode_steps']} decode steps, {s['prefill_chunks']} prefill chunks in "
        f"{s['prefill_dispatches']} dispatches"
    )
    return {
        **{k: s[k] for k in (
            "model", "requests_finished", "new_tokens", "windows", "host_syncs",
            "decode_steps", "prefill_chunks", "prefill_dispatches",
            "attn_kernel", "attn_interpret", "kv_write", "kv_dtype", "device",
            "block_size", "spec_k", "spec_accepted",
        )},
        "pool_shape": list(built[0].kv.cache_k.shape),
        "pool_relayouts": relayouts,
        "weight_casts": casts,
        "attn_walk": walk,
    }


# ================================================================== main
def run_phases(n_devices: int) -> dict:
    """Kernels, trainer, server, in that order; the first failed check
    raises.  Returns what each phase reported."""
    from flexflow_tpu.config import apply_compile_cache
    from flexflow_tpu.ops.pallas import flash_attention as fa
    from flexflow_tpu.ops.pallas import paged_attention as pa
    from flexflow_tpu.runtime.native import native_available

    cache_dir = apply_compile_cache()

    def cached_programs():
        if not os.path.isdir(cache_dir):
            return set()
        return {f for f in os.listdir(cache_dir) if f.endswith("-cache")}

    cached_before = cached_programs()
    info(f"compile cache {cache_dir}: {len(cached_before)} programs before")
    check(
        not fa.INTERPRET and not pa.INTERPRET,
        "FFTPU_PALLAS_INTERPRET is set: the kernels would be interpreted on the chip",
    )
    info(f"batch loader: {'native (built from native/ffdl.cc)' if native_available() else 'python'}")

    phases = {"kernels": {}}
    for name, fn in (
        ("paged_attention", check_paged_attention),
        ("kv_page_write", check_kv_page_write),
        ("flash_attention", check_flash_attention),
    ):
        t0 = time.perf_counter()
        phases["kernels"][name] = fn()
        info(f"{name}: agrees with its plain reference in "
             f"{time.perf_counter() - t0:.1f} s (compiles and reference "
             f"included): {json.dumps(phases['kernels'][name])}")
    phases["train"] = train_phase(n_devices)
    phases["serve"] = serve_gpt2()
    # the other users of the pool and its kernels: a one-byte pool (a
    # page is 32 rows of it) with its scale pools, draft and verify
    phases["serve_int8_spec"] = serve_gpt2(extra=(
        "--serve-kv-dtype", "int8", "--serve-block-size", "32",
        "--serve-spec-k", "3",
    ))
    check(
        phases["serve_int8_spec"]["spec_accepted"] > 0,
        "speculation accepted no draft token",
    )
    new = cached_programs() - cached_before
    info(f"compile cache: {len(new)} programs added")
    return {
        "phases": phases,
        "compile_cache": {
            "dir": cache_dir, "before": len(cached_before), "added": len(new),
        },
    }


def main() -> int:
    try:
        import flexflow_tpu  # noqa: F401
    except ModuleNotFoundError as e:
        if e.name != "flexflow_tpu":
            raise
        print(
            "chip_smoke: the flexflow_tpu package is not beside this script; "
            "run it from the root of a checkout.",
            file=sys.stderr,
        )
        return 1
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(
            f"chip_smoke: no TPU — jax.default_backend() is {backend!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}).  This "
            "script proves the program runs on the chip and does not "
            "fall back to anything else.",
            file=sys.stderr,
        )
        return 1
    devs = jax.devices()
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    print(f"device: {json.dumps(device)}", flush=True)
    # what the host's environment announces (runtime/distributed.py reads
    # TPU_WORKER_HOSTNAMES and MEGASCALE_COORDINATOR_ADDRESS to tell a pod
    # from one host; the compile cache reads JAX_COMPILATION_CACHE_DIR)
    announced = {
        k: v for k, v in sorted(os.environ.items())
        if k.startswith(("TPU_", "MEGASCALE_", "JAX_", "XLA_", "LIBTPU_", "FFTPU_"))
    }
    info(f"jax {jax.__version__}, processes {jax.process_count()}, "
         f"environment {json.dumps(announced)}")

    try:
        summary = run_phases(len(devs))
    except Exception:
        # a failed phase is a result too: say so, last, and exit non-zero
        traceback.print_exc()
        sys.stderr.flush()
        print(json.dumps({"ok": False, "device": device}), flush=True)
        return 1
    print("summary: " + json.dumps({**summary, "claim": None}), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
