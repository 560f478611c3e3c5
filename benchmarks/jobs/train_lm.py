"""The language-model training job: ``FFModel.compile`` (searched) then
``FFModel.fit`` over token ids with next-token labels ``(n, seq)``.

``jobs/train.py``'s order and its comparison: set-up builds one model,
warms the step, loads the seed's weights and drives the first three
steps by the window's own call; the window repeats ``fit`` over the
seeded sequences; then peak memory, free, and the family's plain
reference (``reference/<family>.py``: ``param_shapes(model)`` and
``train_readings(params, [(ids, labels), ...], model, optimizer,
precision, rows)``) follows the same three steps from the same weights.
What differs: ids drawn from the held vocabulary instead of float
embeddings; the cell's ``remat_policy``; the initial weights are made
again for the change's norm rather than kept beside the step (at 10 GB
of state a copy would not fit); the ops' routing counters, which come
with ``fit``'s metric flush; two more numbers, ``rows_over_budget`` and
``grad_norm_gap_mean_leaf``; and a number decides ``correct`` only where
the cell gives it a limit (the rest are reported as ``unlimited_gaps``).

What a family's reference must provide for this job (``README.md``'s
table says ``train_readings`` for ``train``; the same holds here):
``param_shapes(model)`` under the program's layer and weight names;
``train_readings`` as above, ``ids`` and ``labels`` ``(batch, seq)`` int,
``rows`` a slice of the ``batch * seq`` label rows the loss is averaged
over (the planted half-batch fault), returning ``loss``, ``grad_norm``,
``change_norm`` as ``bert_encoder.py`` does; and, for ``prove.py --what
routing``, ``routed_sets(params, ids, model, precision)``.  A model cut to a chip's
share takes the share from ``model`` (``first_expert``, the held
``num_experts``, the held ``vocab_size``) as the program's builder does.
"""

from __future__ import annotations

import collections
import gc
import importlib
import math
import statistics
import time
from unittest import mock

import numpy as np

from benchmarks import trace_reduce as TR
from benchmarks import weights as W
from benchmarks import work_qwen3_next as work_lm
from benchmarks.jobs.train import PROGRAMS, compare, per_layer_norms

CHECK_STEPS = 3


def token_rows(n, seq, vocab, seed):
    """Seeded ids uniform over the held vocabulary, ``(n, seq)``, and
    their next-token labels (row r's label at t is its id at t + 1; the
    last label is one more draw).  The first rows are the same whatever
    ``n`` is."""
    rng = np.random.default_rng(np.random.SeedSequence(seed))
    ids = rng.integers(0, vocab, size=(n, seq + 1), dtype=np.int32)
    return np.ascontiguousarray(ids[:, :-1]), np.ascontiguousarray(ids[:, 1:])


def build_model(config: dict, cell: dict, seed: int):
    """Builder -> search -> executor, as the configuration's file says."""
    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, LossType, MetricsType

    mod, _, fn = config["builder"].partition(":")
    builder = getattr(importlib.import_module(mod), fn)
    run = cell["mix"]
    ff = FFConfig(
        batch_size=run["batch"], compute_dtype=config["compute_dtype"],
        search_budget=cell["search_budget"], remat_policy=cell.get("remat_policy", "none"),
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


class Counted:
    """What the ops counted over the ``fit`` calls so far."""

    def __init__(self):
        self.counters, self.gauge_sum, self.rows = {}, 0.0, 0
        self.held_rows_by_call = []  # one entry a fit call: rows a sample, all layers

    def fit(self, model, x, y):
        """One ``fit`` call over ``x``; returns the epoch-mean loss."""
        pm = model.fit(x, y, epochs=1, verbose=False)
        if pm.train_all != len(x):
            raise RuntimeError(f"fit saw {pm.train_all} of {len(x)} samples")
        for k, v in pm.counters.items():
            self.counters[k] = self.counters.get(k, 0.0) + v
        self.gauge_sum += pm.gauge_sums.get("moe.load_max_over_mean", 0.0)
        self.rows += pm.train_all
        self.held_rows_by_call.append(pm.counters.get("moe.held_rows", 0.0) / pm.train_all)
        return pm.sparse_cce_loss / pm.train_all


def reference_readings(ctx, x3, y3, precision="highest", rows=None):
    import jax.numpy as jnp

    config, b = ctx.config, ctx.cell["mix"]["batch"]
    ref = importlib.import_module(f"benchmarks.reference.{config['family']}")
    params = W.make(ref.param_shapes(config["model"]), ctx.seed)
    batches = [
        (jnp.asarray(x3[i * b:(i + 1) * b]), jnp.asarray(y3[i * b:(i + 1) * b]))
        for i in range(len(x3) // b)
    ]
    return ref.train_readings(
        params, batches, config["model"], config["optimizer"],
        precision=precision, rows=rows,
    )


def grad_gaps(prog, ref_readings) -> dict:
    """Every leaf's gap between the program's and the reference's norm
    of the first gradient, by ``compare``'s measure (over the larger of
    the leaf's reference norm and the median leaf's)."""
    r = {(l, w): v for l, ws in ref_readings["grad_norm"].items() for w, v in ws.items()}
    p = {(l, w): v for l, ws in prog["grad_norm"].items() for w, v in ws.items()}
    med = statistics.median(r.values())
    return {"/".join(k): abs(p[k] - r[k]) / max(r[k], med) for k in r}


def limited(prog, ref_readings, limits):
    """``train.py``'s five numbers and ``grad_norm_gap_mean_leaf`` (the
    mean of :func:`grad_gaps`: a lower precision moves every leaf, so
    the mean tells it from the program more steadily than the worst leaf
    does), split: those the cell gives a limit (they decide ``correct``)
    and the others' values by name."""
    or_none = collections.defaultdict(lambda: math.inf, limits)  # a lookup adds the key
    every = compare(prog, ref_readings, or_none)
    name = "grad_norm_gap_mean_leaf"
    mean = statistics.fmean(grad_gaps(prog, ref_readings).values())  # nan is not correct
    every.insert(4, (name, mean, or_none[name]))
    return (
        [c for c in every if c[0] in limits],
        {n: v for n, v, _ in every if n not in limits},
    )


def worst_leaves(prog, ref_readings, n=3):
    """The ``n`` leaves of :func:`grad_gaps` with the widest gap: which
    layer a gap is in."""
    gaps = grad_gaps(prog, ref_readings)
    return [[k, gaps[k]] for k in sorted(gaps, key=gaps.get, reverse=True)[:n]]


def first_steps(ctx, model, x, y, shapes, counted, mark=lambda name: None):
    """The seed's weights into ``model``'s executor, then ``CHECK_STEPS``
    steps by ``fit`` itself, one batch a call; returns what ``compare``
    wants of the program: each step's loss, the first gradient's and the
    whole change's norm a leaf."""
    import jax

    ex, b = model.executor, ctx.cell["mix"]["batch"]
    # the state the executor holds goes before the seed's comes (only the
    # shapes are needed): 7.5 GB of state is never held twice
    ex.params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding), ex.params
    )
    ex.opt_state = None
    ex.params = W.make_for_executor(shapes, ctx.seed, ex)
    ex.opt_state = ex.optimizer.init_state(ex.params)
    jax.block_until_ready(ex.params)
    mark("seed_weights")
    prog = {"loss": []}
    for i in range(CHECK_STEPS):
        rows = slice(i * b, (i + 1) * b)
        prog["loss"].append(counted.fit(model, x[rows], y[rows]))
        if i == 0:
            beta1 = ctx.config["optimizer"]["beta1"]
            m1 = per_layer_norms(ex.opt_state["m"], ex, shapes)
            prog["grad_norm"] = jax.tree.map(lambda v: v / (1.0 - beta1), m1)
    # the change's norms in one program that makes the initial weights
    # again and keeps nothing: a copy held beside the steps would not fit
    prog["change_norm"] = jax.jit(lambda a: per_layer_norms(
        jax.tree.map(lambda u, v: u - v, a, W.make_for_executor(shapes, ctx.seed, ex)),
        ex, shapes,
    ))(ex.params)
    return jax.tree.map(float, prog)


def run(ctx) -> dict:
    import jax

    from flexflow_tpu.obs import Tracer, configure, set_tracer

    config, cell, run_ = ctx.config, ctx.cell, ctx.cell["mix"]
    ref = importlib.import_module(f"benchmarks.reference.{config['family']}")
    shapes = ref.param_shapes(config["model"])
    b, s = run_["batch"], run_["seq"]
    steps_per_fit = run_["steps_per_fit"]
    moe_layers = config["model"]["num_hidden_layers"]

    # ---- set-up ---------------------------------------------------------
    tracer = configure(level="step")
    marks = [("start", time.perf_counter())]

    def mark(name):
        marks.append((name, time.perf_counter()))

    model = build_model(config, cell, ctx.seed)
    ex = model.executor
    mark("build_search_init")
    x, y = token_rows(steps_per_fit * b, s, config["model"]["vocab_size"], ctx.seed)
    mark("host_data")
    # the step program once, through the instrumented path: its compile
    # (or cache load) in a span of its own.  The state it leaves is
    # thrown away: the seed's weights and a fresh optimizer state follow.
    ex.train_step([x[:b]], y[:b])
    stats = dict(ex.last_step_stats)
    summ = tracer.summary()
    set_tracer(Tracer())  # fit runs the untraced, asynchronous path
    mark("warm_step")
    counted = Counted()
    prog = first_steps(ctx, model, x, y, shapes, counted, mark)
    mark("first_steps")
    # a short fit call over several batches warms the loader's ring and
    # the prefetcher beyond one batch
    counted.fit(model, x[: 4 * b], y[: 4 * b])
    jax.block_until_ready(ex.params)
    mark("warm_fit")
    syncs0 = ex.host_syncs
    window = Counted()

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
        window.fit(model, x, y)
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
    # ---- free the program, then the reference ---------------------------
    x3, y3 = x[: CHECK_STEPS * b].copy(), y[: CHECK_STEPS * b].copy()
    del model, ex, x, y
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    ref_readings = reference_readings(ctx, x3, y3)
    reference_s = time.perf_counter() - t_ref
    over = counted.counters.get("moe.rows_over_budget", 0.0) + window.counters.get(
        "moe.rows_over_budget", 0.0
    )
    checks, unlimited = limited(prog, ref_readings, cell["correct_limits"])
    checks.append(("rows_over_budget", over, cell["correct_limits"]["rows_over_budget"]))

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
            "train_flops_per_step": work_lm.decoder_train_flops_per_step(
                config["model"], batch=b, seq=s
            ),
            # rows one layer's held experts took, over the window's steps
            "held_rows": window.counters.get("moe.held_rows", 0.0) / moe_layers,
            # the same a token and layer, one entry a fit call: the three
            # first steps and the warm call, then the window's calls
            "held_rows_per_token_by_fit_call": [
                r / (moe_layers * s)
                for r in counted.held_rows_by_call + window.held_rows_by_call
            ],
            "rows_over_budget": over,
            # passes over the held rows a layer made a step (1 = within one budget)
            "expert_passes": window.counters.get("moe.passes", 0.0) / max(moe_layers * steps, 1),
            "expert_load_max_over_mean": window.gauge_sum / max(window.rows, 1),
            "loss_first_steps": prog["loss"],
            "reference_loss": ref_readings["loss"],
            "unlimited_gaps": unlimited,
            "grad_norm_gap_worst_leaves": worst_leaves(prog, ref_readings),
        },
    }


def routing_disagreement(ctx) -> list:
    """Per layer, the share of the first batch's tokens whose set of
    chosen experts differs between the reference with bfloat16 matmul
    operands (what the configuration states for the program; the router
    itself stays float32, as the program's does) and the reference
    proper: how far the program's rounding alone moves the routing."""
    import jax
    import jax.numpy as jnp

    config, run_ = ctx.config, ctx.cell["mix"]
    m = config["model"]
    ref = importlib.import_module(f"benchmarks.reference.{config['family']}")
    params = W.make(ref.param_shapes(m), ctx.seed)
    x, _ = token_rows(run_["batch"], run_["seq"], m["vocab_size"], ctx.seed)
    sets = {
        prec: np.asarray(jax.jit(lambda p, t, prec=prec: ref.routed_sets(p, t, m, prec))(
            params, jnp.asarray(x)))
        for prec in ("bf16", "highest")
    }
    differ = np.any(sets["bf16"] != sets["highest"], axis=-1)  # (layers, tokens)
    return [
        (f"tokens_with_other_expert_set.layer{i}", float(np.mean(d)), 1.0)
        for i, d in enumerate(differ)
    ]


# ``prove --what passes``: rows of one pass, as a multiple of a uniform
# router's, small enough that every layer takes three passes at the
# compared steps (the cell's own runs take one there)
SMALL_PASS_FACTOR = 0.4


def small_pass_readings(ctx) -> list:
    """The program itself, built with passes of ``SMALL_PASS_FACTOR``,
    through the three compared steps against the reference under the
    cell's limits: the later passes, the sums over passes in both
    directions and the padded last group, at the cell's size."""
    import jax

    from flexflow_tpu.ops import moe

    config, run_ = ctx.config, ctx.cell["mix"]
    m = config["model"]
    ref = importlib.import_module(f"benchmarks.reference.{config['family']}")
    x, y = token_rows(CHECK_STEPS * run_["batch"], run_["seq"], m["vocab_size"], ctx.seed)
    counted = Counted()
    with mock.patch.object(moe, "PASS_ROWS_FACTOR", SMALL_PASS_FACTOR):
        model = build_model(config, ctx.cell, ctx.seed)
        prog = first_steps(ctx, model, x, y, ref.param_shapes(m), counted)
    del model
    gc.collect()
    jax.clear_caches()
    checks, unlimited = limited(prog, reference_readings(ctx, x, y), ctx.cell["correct_limits"])
    layer_steps = m["num_hidden_layers"] * CHECK_STEPS
    return checks + [
        ("rows_over_budget", counted.counters["moe.rows_over_budget"], 0),
        ("passes_short_of_two_a_layer_and_step",
         max(0.0, 2 * layer_steps - counted.counters["moe.passes"]), 0),
        ("passes_a_layer_and_step", counted.counters["moe.passes"] / layer_steps, math.inf),
    ] + [(n, v, math.inf) for n, v in unlimited.items()]


_TRUTH: dict = {}


def prove(ctx, what: str) -> list:
    """Readings that set the limits of ``correct`` (``benchmarks/prove.py``):
    the reference put in the program's place, in a lower precision
    (``control`` = fp8, ``bf16``) or with a fault planted (``half_batch``:
    the second half of the batch's tokens left out, the mean taken over
    the rest), against the float32 reference on the same seed (numbers
    the cell gives no limit come with the limit ``inf``); and
    ``routing``: :func:`routing_disagreement`; ``passes``:
    :func:`small_pass_readings`.  No window is needed."""
    if what == "routing":
        return routing_disagreement(ctx)
    if what == "passes":
        return small_pass_readings(ctx)
    b, s = ctx.cell["mix"]["batch"], ctx.cell["mix"]["seq"]
    x3, y3 = token_rows(CHECK_STEPS * b, s, ctx.config["model"]["vocab_size"], ctx.seed)
    if ctx.seed not in _TRUTH:  # one seed's readings serve every ``what``
        _TRUTH.clear()
        _TRUTH[ctx.seed] = reference_readings(ctx, x3, y3)
    truth = _TRUTH[ctx.seed]
    if what == "half_batch":
        other = reference_readings(ctx, x3, y3, rows=slice(0, b * s // 2))
    else:
        other = reference_readings(
            ctx, x3, y3, precision={"control": "fp8"}.get(what, what)
        )
    checks, unlimited = limited(other, truth, ctx.cell["correct_limits"])
    return checks + [(n, v, math.inf) for n, v in unlimited.items()]
