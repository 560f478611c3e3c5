"""The training job: ``FFModel.compile`` (searched) then ``FFModel.fit``.

Set-up builds one model -- builder, search, executor, the compiled step
and its state -- warms the step once, loads the seed's weights, and
drives that same object through its first three steps by the window's
own call (``fit``) and feed (the loader and the device prefetcher), on
rows that all differ.  The window then repeats ``fit`` over the seeded
data set until ``--seconds`` is up and stops the clock after the last
step's outputs are ready.  After the window the peak memory is read,
the program's state is freed, and the plain reference follows the same
three steps from the same weights (``benchmarks/reference``).
"""

from __future__ import annotations

import gc
import importlib
import statistics
import time

import numpy as np

from benchmarks import trace_reduce as TR
from benchmarks import weights as W

PROGRAMS = r"^jit_step"  # the executor's step program on the trace's XLA Modules line


def synthetic_classes(n, seq, hidden, classes, seed):
    """Seeded and learnable: each sample is its class's fixed pattern
    plus unit noise at every position (the data ``chip_smoke.py``
    trains on).  All rows differ, and the first rows are the same
    whatever ``n`` is (labels, patterns and noise have a stream each)."""
    ry, rp, rx = (np.random.default_rng(s) for s in np.random.SeedSequence(seed).spawn(3))
    y = ry.integers(0, classes, size=(n, 1)).astype(np.int32)
    pattern = rp.standard_normal((classes, hidden), dtype=np.float32)
    x = rx.standard_normal((n, seq, hidden), dtype=np.float32)
    x += pattern[y[:, 0]][:, None, :]
    return x, y


def build_model(config: dict, cell: dict, seed: int):
    """Builder -> search -> executor, as the configuration's file says."""
    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, LossType, MetricsType

    mod, _, fn = config["builder"].partition(":")
    builder = getattr(importlib.import_module(mod), fn)
    run = cell["mix"]
    ff = FFConfig(
        batch_size=run["batch"], compute_dtype=config["compute_dtype"],
        search_budget=cell["search_budget"],
    )
    model = FFModel(ff)
    builder(model, batch=run["batch"], seq=run["seq"], **config["builder_args"])
    opt = config["optimizer"]
    model.compile(
        optimizer=AdamOptimizer(
            alpha=opt["alpha"], beta1=opt["beta1"], beta2=opt["beta2"],
            epsilon=opt["epsilon"],
        ),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[MetricsType.ACCURACY, MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY],
        seed=seed & 0x7FFFFFFF,
    )
    return model


def per_layer_norms(tree, executor, shapes):
    """``{layer: {weight: norm}}`` of a tree in the executor's layout."""
    import jax
    import jax.numpy as jnp

    def norms(t):
        out = {}
        for lname in shapes:
            for wname in shapes[lname]:
                _, bname, d = executor.locate_weight(lname, wname)
                a = t[bname][wname]
                a = a if d is None else a[d]
                out.setdefault(lname, {})[wname] = jnp.sqrt(
                    jnp.sum(jnp.square(a.astype(jnp.float32)))
                )
        return out

    return jax.jit(norms)(tree)


def fit_once(model, x, y):
    """One ``fit`` call over ``x``; returns the epoch-mean loss."""
    pm = model.fit(x, y, epochs=1, verbose=False)
    if pm.train_all != len(x):
        raise RuntimeError(f"fit saw {pm.train_all} of {len(x)} samples")
    return pm.sparse_cce_loss / pm.train_all


def compare(prog: dict, ref: dict, limits: dict) -> list:
    """The numbers ``correct`` rests on, each beside its limit.

    Per step the loss's gap as a share of the reference's loss.  For the
    first gradient and for the change after the last step, the worst
    leaf's gap between the program's norm and the reference's (not the
    norm of a difference), over the larger of that leaf's reference norm
    and the median leaf's.  Leaves whose reference gradient is under a
    thousandth of the median leaf's move under Adam by round-off alone
    and are left out of the change.
    """
    checks = []
    for i, (a, b) in enumerate(zip(prog["loss"], ref["loss"]), start=1):
        gap = abs(a - b) / abs(b) if np.isfinite(a) else float("inf")
        checks.append((f"loss_gap_step{i}", gap, limits[f"loss_gap_step{i}"]))

    def flat(t):
        return {(l, w): float(v) for l, ws in t.items() for w, v in ws.items()}

    g_ref, g_prog = flat(ref["grad_norm"]), flat(prog["grad_norm"])
    g_med = statistics.median(g_ref.values())

    def worst(p, r, keys):
        med = statistics.median(r[k] for k in keys)
        gaps = {k: abs(p[k] - r[k]) / max(r[k], med) for k in keys}
        k = max(gaps, key=gaps.get)
        v = gaps[k]
        return (v if np.isfinite(v) else float("inf")), k

    v, _ = worst(g_prog, g_ref, list(g_ref))
    checks.append(("grad_norm_gap_worst_leaf", v, limits["grad_norm_gap_worst_leaf"]))
    moved = [k for k in g_ref if g_ref[k] >= 1e-3 * g_med]
    v, _ = worst(flat(prog["change_norm"]), flat(ref["change_norm"]), moved)
    checks.append(("change_norm_gap_worst_leaf", v, limits["change_norm_gap_worst_leaf"]))
    return checks


def reference_readings(ctx, x3, y3, precision="highest", rows=None):
    import jax.numpy as jnp

    config, cell = ctx.config, ctx.cell
    ref = importlib.import_module(f"benchmarks.reference.{config['family']}")
    shapes = ref.param_shapes(config["model"])
    params = W.make(shapes, ctx.seed)
    b = cell["mix"]["batch"]
    batches = [
        (jnp.asarray(x3[i * b:(i + 1) * b]), jnp.asarray(y3[i * b:(i + 1) * b, 0]))
        for i in range(len(x3) // b)
    ]
    return ref.train_readings(
        params, batches, config["model"], config["optimizer"],
        precision=precision, rows=rows,
    )


def run(ctx) -> dict:
    import jax

    from flexflow_tpu.obs import Tracer, configure, set_tracer

    config, cell, run_ = ctx.config, ctx.cell, ctx.cell["mix"]
    ref = importlib.import_module(f"benchmarks.reference.{config['family']}")
    shapes = ref.param_shapes(config["model"])
    b, s = run_["batch"], run_["seq"]
    hidden, classes = config["model"]["hidden_size"], config["model"]["num_labels"]
    steps_per_fit = run_["steps_per_fit"]
    check_steps = 3

    # ---- set-up ---------------------------------------------------------
    tracer = configure(level="step")
    marks = [("start", time.perf_counter())]

    def mark(name):
        marks.append((name, time.perf_counter()))

    model = build_model(config, cell, ctx.seed)
    ex = model.executor
    mark("build_search_init")
    x, y = synthetic_classes(steps_per_fit * b, s, hidden, classes, ctx.seed)
    mark("host_data")
    # the step program once, through the instrumented path: its compile
    # (or cache load) in a span of its own.  The state it leaves is
    # thrown away: the seed's weights and a fresh optimizer state follow.
    ex.train_step([x[:b]], y[:b])
    stats = dict(ex.last_step_stats)
    summ = tracer.summary()
    set_tracer(Tracer())  # fit runs the untraced, asynchronous path
    mark("warm_step")
    ex.params = W.make_for_executor(shapes, ctx.seed, ex)
    ex.opt_state = ex.optimizer.init_state(ex.params)
    p0 = jax.tree.map(jax.numpy.copy, ex.params)  # the step donates its arguments
    jax.block_until_ready(p0)
    mark("seed_weights")
    # first steps, by the window's own call and feed
    prog = {"loss": []}
    for i in range(check_steps):
        rows = slice(i * b, (i + 1) * b)
        prog["loss"].append(fit_once(model, x[rows], y[rows]))
        if i == 0:
            beta1 = config["optimizer"]["beta1"]
            m1 = per_layer_norms(ex.opt_state["m"], ex, shapes)
            prog["grad_norm"] = jax.tree.map(lambda v: v / (1.0 - beta1), m1)
    delta = jax.jit(lambda a, c: jax.tree.map(lambda u, v: u - v, a, c))(ex.params, p0)
    prog["change_norm"] = per_layer_norms(delta, ex, shapes)
    del p0, delta
    mark("first_steps")
    # a short fit call over several batches warms the loader's ring and
    # the prefetcher beyond one batch
    fit_once(model, x[: 4 * b], y[: 4 * b])
    jax.block_until_ready(ex.params)
    mark("warm_fit")
    syncs0 = ex.host_syncs

    # ---- the window -----------------------------------------------------
    t_start = time.perf_counter()
    steps = 0
    trace = None
    while True:
        elapsed = time.perf_counter() - t_start
        if elapsed >= ctx.seconds:
            break
        tracing = ctx.trace and trace is None and elapsed >= 0.4 * ctx.seconds
        if tracing:
            TR.start_trace(ctx.trace_dir)
            t_tr = time.perf_counter()
        fit_once(model, x, y)
        if tracing:
            jax.block_until_ready(ex.params)
            trace = {"window_s": time.perf_counter() - t_tr, "steps": steps_per_fit,
                     "programs": PROGRAMS, "program_calls": steps_per_fit}
            jax.profiler.stop_trace()
        steps += steps_per_fit
    jax.block_until_ready(ex.params)
    window_s = time.perf_counter() - t_start
    host_syncs = ex.host_syncs - syncs0

    peak = ctx.memory_peak_bytes()
    memory_stats = ctx.memory_stats()
    step_memory = ex.memory_snapshot()
    prog = jax.tree.map(float, prog)
    # ---- free the program, then the reference ---------------------------
    x3, y3 = x[: check_steps * b].copy(), y[: check_steps * b].copy()
    del model, ex, x, y
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    ref_readings = reference_readings(ctx, x3, y3)
    reference_s = time.perf_counter() - t_ref
    checks = compare(prog, ref_readings, cell["correct_limits"])

    spans = {k: v["total_s"] for k, v in summ["spans"].items()}
    tokens = steps * b * s
    return {
        "t_window_start": t_start,
        "metrics": {"train_tokens_per_s": tokens / window_s},
        "attempted": steps,
        "failed": 0,
        "memory_peak_bytes": peak,
        "checks": checks,
        "trace": trace,
        "facts": {
            "window_s": window_s,
            "steps": steps,
            "tokens": tokens,
            "host_syncs": host_syncs,
            "spans": spans,
            "counters": dict(summ["counters"]),
            "step_compile_s": stats.get("compile_s"),
            "step_memory_analysis": step_memory,
            "memory_stats_after_window": memory_stats,
            "setup_parts_s": {
                n: t - marks[i][1] for i, (n, t) in enumerate(marks[1:])
            },
            "reference_s": reference_s,
            "train_flops_per_step": ctx.work.encoder_train_flops_per_step(
                batch=b, seq=s, hidden=hidden,
                ff_dim=config["model"]["intermediate_size"],
                num_layers=config["model"]["num_hidden_layers"],
                num_classes=classes,
            ),
            "loss_first_steps": prog["loss"],
            "reference_loss": ref_readings["loss"],
        },
    }


def prove(ctx, what: str) -> list:
    """Readings that set the limits of ``correct`` (``benchmarks/prove.py``):
    the reference put in the program's place, in a lower precision
    (``control`` = fp8, ``bf16``) or with a fault planted (``half_batch``:
    half of the batch left out, the mean taken over the rest), against
    the float32 reference on the same seed.  No window is needed."""
    b, s = ctx.cell["mix"]["batch"], ctx.cell["mix"]["seq"]
    m = ctx.config["model"]
    x3, y3 = synthetic_classes(3 * b, s, m["hidden_size"], m["num_labels"], ctx.seed)
    truth = reference_readings(ctx, x3, y3)
    if what == "half_batch":
        other = reference_readings(ctx, x3, y3, rows=slice(0, b // 2))
    else:
        other = reference_readings(
            ctx, x3, y3, precision={"control": "fp8"}.get(what, what)
        )
    return compare(other, truth, ctx.cell["correct_limits"])
