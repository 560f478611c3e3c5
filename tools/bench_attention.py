"""Attention-core micro-benchmark on the real chip.

Times three implementations of the (B, H, S, D) attention core — XLA's
fused sdpa (einsum+softmax), our Pallas flash kernel, and (as a sanity
target only, never shipped) the jax-bundled TPU flash kernel — for
forward and forward+backward, and prints one JSON line per config.  Used
to tune block sizes and validate the dispatch policy in
``flexflow_tpu/ops/attention.py``.

Methodology: per-dispatch overhead would swamp sub-ms kernels, so each
timing chains REPS invocations
inside ONE jitted ``lax.scan`` (each iteration feeds the previous output
back as the query, so nothing can be dead-code-eliminated) and divides.
A null-chain probe measures the residual dispatch overhead, reported as
``overhead_ms`` and subtracted.
"""

from __future__ import annotations

import json
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _chain(core, k, v, reps):
    """jit(q -> scalar) running `core` reps times, each feeding its output
    back as the next query."""

    @jax.jit
    def f(q):
        def body(c, _):
            return core(c, k, v).astype(q.dtype), None

        out, _ = lax.scan(body, q, None, length=reps)
        return jnp.sum(out.astype(jnp.float32))

    return f


def _time(fn, *args, iters=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        r = fn(*args)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / iters * 1000.0  # ms per outer call


def sdpa(q, k, v, causal=False):
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) / math.sqrt(d)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def main():
    import os

    from flexflow_tpu.ops.pallas import flash_attention as fa
    from flexflow_tpu.ops.pallas.flash_attention import flash_attention

    if os.environ.get("FFTPU_FORCE_TILED") == "1":
        fa.ONEPASS_MAX_SK = fa.ONEPASS_MAX_SK_CAUSAL = 0  # A/B the kernels

    try:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as jax_flash,
        )
        have_jax_flash = jax.default_backend() == "tpu"
    except ImportError:
        have_jax_flash = False

    configs = [
        # (b, h, s, d, causal, reps)
        (16, 12, 512, 64, False, 16),
        (16, 12, 512, 64, True, 16),
        (4, 12, 2048, 64, False, 8),
        (4, 12, 2048, 64, True, 8),
        (1, 12, 8192, 64, True, 2),
    ]
    bq = int(sys.argv[1]) if len(sys.argv) > 1 else None
    bk = int(sys.argv[2]) if len(sys.argv) > 2 else None
    only_s = int(sys.argv[3]) if len(sys.argv) > 3 else None

    # dispatch-overhead probe: a null chain of trivial kernels
    z = jnp.zeros((8, 128), jnp.float32)
    probe = jax.jit(lambda x: jnp.sum(x * 1.000001))
    overhead = _time(probe, z, iters=10)
    print(json.dumps({"overhead_ms": round(overhead, 2)}), flush=True)

    for b, h, s, d, causal, reps in configs:
        if only_s and s != only_s:
            continue
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.bfloat16)

        kw = {}
        if bq:
            kw["block_q"] = min(bq, s)
        if bk:
            kw["block_k"] = min(bk, s)

        def ours(q, k, v):
            return flash_attention(q, k, v, causal=causal, **kw)

        def xla(q, k, v):
            return sdpa(q, k, v, causal)

        def grad_core(core):
            g = jax.grad(
                lambda q, k, v: jnp.sum(core(q, k, v).astype(jnp.float32)),
                argnums=(0, 1, 2),
            )

            def f(qq, kk, vv):
                dq, dk, dv = g(qq, kk, vv)
                return dq + dk + dv  # same shape as q -> chainable

            return f

        row = {
            "shape": f"b{b} h{h} s{s} d{d}",
            "causal": causal,
            "reps": reps,
        }
        import os
        impls = {"sdpa": xla, "flash": ours}
        if have_jax_flash:
            impls["jaxflash"] = lambda q, k, v: jax_flash(q, k, v, causal=causal)
        want = os.environ.get("BENCH_IMPLS")
        if want:
            impls = {k: v for k, v in impls.items() if k in want.split(",")}
        for name, core in impls.items():
            try:
                t = _time(_chain(core, k, v, reps), q)
                row[f"fwd_{name}_ms"] = round((t - overhead) / reps, 3)
                t = _time(_chain(grad_core(core), k, v, reps), q)
                row[f"bwd_{name}_ms"] = round((t - overhead) / reps, 3)
            except Exception as e:  # noqa: BLE001 — keep the sweep going
                row[f"{name}_error"] = str(e)[:120]
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
