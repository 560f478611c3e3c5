"""Validate the cost model against measured reality on the virtual CPU mesh.

Two checks (exit-code gated like tools/bench_compare.py):

1. **Collective scaling** — measures real XLA collectives (all-gather /
   all-reduce / all-to-all over an 8-device host mesh) at growing sizes
   and compares their scaling against ``TPUMachineModel``'s analytic
   formulas.  Absolute times differ (host mesh != ICI), but the
   *bytes-scaling exponent* must match: the analytic model is linear in
   bytes past the latency floor.

2. **Rank-correlation gate** (``--rank-gate``) — the property the Unity
   search actually needs is ORDERING, not absolute accuracy: it builds a
   small MLP, prices several mesh factorizations with
   ``estimate_strategy_cost``, MEASURES each strategy's real step time on
   the 8-device mesh, and computes Spearman ρ between predicted and
   measured — before and after fitting a CalibrationStore on those same
   pairs.  Gate: ρ(after) >= ρ(before) (calibration corrections are
   monotone by construction — ``fit_scale_offset`` clamps scale > 0 — so
   they may never invert a ranking the analytic model got right).
   Exit 1 when the gate fails, like bench_compare.

Run: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    PYTHONPATH=. python tools/validate_costmodel.py [--rank-gate]
"""

from __future__ import annotations

import json
import time

import numpy as np


def measure_collectives(sizes_kb=(256, 1024, 4096), n_dev=8, iters=20,
                        collectives=None, windows=1):
    """Time each collective at each size.  ``windows`` > 1 takes the median
    of that many independent timing windows — the scaling exponent from a
    single window is noise-prone on a shared host."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    assert len(jax.devices()) >= n_dev, (
        f"need {n_dev} devices; run under JAX_PLATFORMS=cpu "
        f"XLA_FLAGS=--xla_force_host_platform_device_count={n_dev}"
    )
    devs = np.asarray(jax.devices()[:n_dev])
    mesh = Mesh(devs, ("x",))

    bodies = {
        "all_gather": lambda x: jax.lax.all_gather(x, "x"),
        "all_reduce": lambda x: jax.lax.psum(x, "x"),
        "all_to_all": lambda x: jax.lax.all_to_all(
            x.reshape(n_dev, -1), "x", split_axis=0, concat_axis=0
        ),
    }
    if collectives:
        bodies = {k: v for k, v in bodies.items() if k in collectives}
    results = {}
    for name, body in bodies.items():
        times = []
        for kb in sizes_kb:
            n = kb * 256  # f32 elements per device shard
            if name == "all_to_all":
                n = max(n, n_dev * n_dev)
                n -= n % (n_dev * n_dev)

            f = jax.jit(
                jax.shard_map(
                    lambda x: jnp.sum(body(x)).reshape(1),
                    mesh=mesh, in_specs=P("x"), out_specs=P("x"),
                    check_vma=False,
                )
            )
            x = jnp.ones((n_dev * n,), jnp.float32)
            float(f(x)[0])  # compile + warmup
            samples = []
            for _ in range(windows):
                t0 = time.perf_counter()
                for _ in range(iters):
                    r = f(x)
                float(r[0])
                samples.append((time.perf_counter() - t0) / iters)
            times.append(float(np.median(samples)))
        results[name] = dict(zip(sizes_kb, times))
    return results


def scaling_exponent(times_by_size):
    sizes = sorted(times_by_size)
    t0, t1 = times_by_size[sizes[0]], times_by_size[sizes[-1]]
    import math

    return math.log(t1 / t0) / math.log(sizes[-1] / sizes[0])


def model_exponent(coll: str, sizes_kb=(256, 4096), n=8):
    from flexflow_tpu.search.cost import TPUMachineModel
    import math

    m = TPUMachineModel()
    t0 = getattr(m, coll)(sizes_kb[0] * 1024.0, n)
    t1 = getattr(m, coll)(sizes_kb[-1] * 1024.0, n)
    return math.log(t1 / t0) / math.log(sizes_kb[-1] / sizes_kb[0])


def spearman(a, b):
    """Spearman rank correlation with average ranks for ties (no scipy
    dependency — the container has numpy only)."""
    import numpy as np

    def ranks(v):
        v = np.asarray(v, np.float64)
        order = np.argsort(v, kind="stable")
        r = np.empty(len(v), np.float64)
        i = 0
        while i < len(v):
            j = i
            while j + 1 < len(v) and v[order[j + 1]] == v[order[i]]:
                j += 1
            r[order[i : j + 1]] = 0.5 * (i + j) + 1.0
            i = j + 1
        return r

    ra, rb = ranks(a), ranks(b)
    ra -= ra.mean()
    rb -= rb.mean()
    denom = float(np.sqrt((ra * ra).sum() * (rb * rb).sum()))
    if denom == 0:
        return 0.0
    return float((ra * rb).sum() / denom)


def _measure_step_s(model, x, y, iters: int = 3) -> float:
    """Wall seconds per training step of a compiled model (warmup step
    excluded; value-forced like bench.py's _median_sps)."""
    ex = model.executor
    inputs, labels = ex.place_batch([x, y])
    loss, _ = ex.train_step(inputs, labels)
    float(loss)  # compile + warmup
    t0 = time.perf_counter()
    for _ in range(iters):
        loss, _ = ex.train_step(inputs, labels)
    float(loss)
    return (time.perf_counter() - t0) / iters


def rank_correlation_gate(
    n_dev: int = 8,
    batch: int = 32,
    hidden: int = 64,
    iters: int = 3,
):
    """Spearman ρ(predicted, measured) over per-mesh strategies on the
    virtual mesh, before vs after calibration.  Returns a dict with
    ``rho_before`` / ``rho_after`` / ``ok`` (after >= before) plus the
    per-strategy rows.  See module docstring for why >= is the bound."""
    import numpy as np

    from flexflow_tpu import (
        FFConfig,
        FFModel,
        LossType,
        MachineMesh,
        SGDOptimizer,
    )
    from flexflow_tpu.search.calibration import CalibrationStore
    from flexflow_tpu.search.cost import TPUMachineModel, estimate_strategy_cost

    from flexflow_tpu.parallel.strategy import (
        Strategy,
        data_parallel_strategy,
    )
    from flexflow_tpu.search.candidates import op_candidates

    machine = TPUMachineModel.detect()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, hidden)).astype(np.float32)
    y = rng.integers(0, 8, size=(batch, 1)).astype(np.int32)

    def tensor_parallel_strategy(layers, mesh):
        """Per-layer candidate with the most kernel sharding — the
        Megatron-style column/row split op_candidates enumerates."""
        st = Strategy(mesh)
        for layer in layers:
            if layer.op_type.is_parallel_op:
                continue
            cands = op_candidates(layer, mesh)
            best = max(
                cands,
                key=lambda c: sum(
                    len(ws.used_axes()) for ws in c.weights.values()
                ),
                default=None,
            )
            if best is not None:
                st.ops[int(layer.layer_guid)] = best
        return st

    # five genuinely different placements of the same graph: a tiny-MLP
    # SEARCH would pick replication everywhere (grad-sync latency beats
    # smoke-scale compute), which ties every prediction — the gate needs
    # spread, so the placements are fixed by construction.  The body is
    # a depth-4 UNIFORM dense chain (h0..h3, hidden->hidden) so the
    # scan-stacked collapse and the grad-overlap ring (both keyed on
    # chains of >= 4 identical blocks) are exercisable by the fifth arm.
    arms = [
        ("replicated 8x1", (n_dev, 1), lambda ls, m: Strategy(m), {}),
        ("data-parallel 8x1", (n_dev, 1), data_parallel_strategy, {}),
        ("tensor-parallel 1x8", (1, n_dev), tensor_parallel_strategy, {}),
        ("hybrid 2x4", (2, n_dev // 2), tensor_parallel_strategy, {}),
        # dp + ring overlap (docs/PERF.md "Overlapped gradient sync"):
        # same placement as the dp arm, but the chain's grad sync rings
        # inside the backward scan — predicted with the overlap model's
        # adjustment, measured with --grad-overlap ring on the
        # scan-stacked executor
        ("dp 8x1 + ring overlap", (n_dev, 1), data_parallel_strategy,
         {"stack_blocks": "on", "grad_overlap": "ring"}),
    ]
    rows = []
    for name, shape, make, cfg_kw in arms:
        cfg = FFConfig(batch_size=batch, **cfg_kw)
        model = FFModel(cfg)
        t = model.create_tensor((batch, hidden), name="x")
        for i in range(4):
            t = model.dense(t, hidden, name=f"h{i}")
        model.dense(t, 8, name="head")
        mesh = MachineMesh(shape, ("data", "model"))
        st = make(model.layers, mesh)
        predicted = estimate_strategy_cost(model.layers, st, machine)
        if cfg_kw.get("grad_overlap") == "ring":
            from flexflow_tpu.search.cost import grad_overlap_adjustment

            delta, price = grad_overlap_adjustment(
                model.layers, st, machine, mode="ring"
            )
            if price is not None:
                predicted = max(0.0, predicted - delta)
        model.compile(
            optimizer=SGDOptimizer(lr=0.01),
            loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
            mesh=mesh, strategy=st, seed=0,
        )
        measured = _measure_step_s(model, x, y, iters=iters)
        rows.append({
            "strategy": name,
            "predicted_s": predicted,
            "measured_s": measured,
        })

    preds = [r["predicted_s"] for r in rows]
    meas = [r["measured_s"] for r in rows]
    rho_before = spearman(preds, meas)
    store = CalibrationStore(machine.source)
    for r in rows:
        store.add_step_sample("fit", r["predicted_s"], r["measured_s"])
    cal = [store.correct_step("fit", p) for p in preds]
    for r, c in zip(rows, cal):
        r["calibrated_s"] = c
    rho_after = spearman(cal, meas)
    return {
        "rho_before": round(rho_before, 4),
        "rho_after": round(rho_after, 4),
        "ok": rho_after >= rho_before - 1e-9,
        "step_correction": store.step_correction("fit"),
        "strategies": rows,
    }


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rank-gate", action="store_true",
                    help="run the predicted-vs-measured rank-correlation "
                         "gate (exit 1 on failure)")
    ap.add_argument("--skip-scaling", action="store_true",
                    help="skip the collective-scaling measurement")
    args = ap.parse_args(argv)

    out = {}
    if not args.skip_scaling:
        measured = measure_collectives()
        for coll, times in measured.items():
            out[coll] = {
                "measured_exponent": round(scaling_exponent(times), 3),
                "model_exponent": round(model_exponent(coll), 3),
                "times_ms": {k: round(v * 1e3, 3) for k, v in times.items()},
            }
    rc = 0
    if args.rank_gate:
        gate = rank_correlation_gate()
        out["rank_gate"] = gate
        if not gate["ok"]:
            rc = 1
    print(json.dumps(out, indent=1))
    if rc:
        print(
            "validate_costmodel: rank-correlation gate FAILED "
            f"(rho_after {out['rank_gate']['rho_after']} < "
            f"rho_before {out['rank_gate']['rho_before']})",
            flush=True,
        )
    return rc


if __name__ == "__main__":
    import sys

    sys.exit(main())
