"""The training job on a host of several chips: ``jobs/train.py``'s run
and prove as they are -- ``FFModel.compile`` searches over every chip
the host has (``default_mesh()``), ``fit`` feeds the global batch -- with
the reference's readings taken over micro-batches.

The float32 reference runs on one chip.  At the global batch its
activations may not fit there, so each of its steps takes the gradient
of the batch's mean loss as the mean of ``MICRO_BATCH``-row micro-
batches' gradients (and the loss as the mean of theirs): the same
numbers up to float32 summation order, since the mean over the batch is
the mean of the micro-batches' means when they are of one size.  Adam
then steps once, as ``reference/<family>.py::train_readings`` does.
"""

from __future__ import annotations

import importlib

from benchmarks import weights as W
from benchmarks.jobs import train as T

MICRO_BATCH = 16  # rows a reference step holds at once: one chip's batch of the 1-chip cell


def micro_train_readings(ref, params, batches, cfg, opt, precision="highest", rows=None,
                         micro=MICRO_BATCH):
    """``ref.train_readings`` with each step's loss and gradient the mean
    over micro-batches of ``micro`` rows."""
    import jax
    import jax.numpy as jnp

    p0 = params
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    step = jax.jit(lambda p, x, y: jax.value_and_grad(ref.loss)(p, x, y, cfg, precision))
    update = jax.jit(lambda p, g, m, v, a: ref.adam_step(p, g, m, v, a, opt))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    scale = jax.jit(lambda a, s: jax.tree.map(lambda u: u * s, a))
    losses, g1 = [], None
    for t, (x, y) in enumerate(batches, start=1):
        if rows is not None:
            x, y = x[rows], y[rows]
        n = x.shape[0] // micro
        assert n * micro == x.shape[0], (x.shape, micro)
        l_sum, g_sum = 0.0, None
        for i in range(n):
            l, g = step(params, x[i * micro:(i + 1) * micro], y[i * micro:(i + 1) * micro])
            l_sum = l_sum + l
            g_sum = g if g_sum is None else add(g_sum, g)
        g = scale(g_sum, 1.0 / n)
        losses.append(l_sum / n)
        if t == 1:
            g1 = jax.jit(ref.leaf_norms)(g)
        params, m, v = update(params, g, m, v, ref.adam_alpha(t, opt))
    change = jax.jit(
        lambda a, b: ref.leaf_norms(jax.tree.map(lambda p, q: p - q, a, b))
    )(params, p0)
    return {
        "loss": [float(l) for l in losses],
        "grad_norm": jax.tree.map(float, g1),
        "change_norm": jax.tree.map(float, change),
    }


def reference_readings(ctx, x3, y3, precision="highest", rows=None):
    """``jobs/train.py::reference_readings`` over micro-batches."""
    import jax.numpy as jnp

    config, cell = ctx.config, ctx.cell
    ref = importlib.import_module(f"benchmarks.reference.{config['family']}")
    params = W.make(ref.param_shapes(config["model"]), ctx.seed)
    b = cell["mix"]["batch"]
    batches = [
        (jnp.asarray(x3[i * b:(i + 1) * b]), jnp.asarray(y3[i * b:(i + 1) * b, 0]))
        for i in range(len(x3) // b)
    ]
    return micro_train_readings(
        ref, params, batches, config["model"], config["optimizer"],
        precision=precision, rows=rows, micro=min(MICRO_BATCH, b),
    )


def _with_micro_batches(fn, ctx, *args):
    real = T.reference_readings
    T.reference_readings = reference_readings
    try:
        return fn(ctx, *args)
    finally:
        T.reference_readings = real


def run(ctx) -> dict:
    return _with_micro_batches(T.run, ctx)


def prove(ctx, what: str) -> list:
    """``jobs/train.py``'s prove over micro-batches, and a fault of the
    host's own: ``no_allreduce``, a step whose gradient is not summed
    over the chips, so that each chip's copy steps on its own
    ``batch / chips`` rows -- what reading back the first chip's copy
    gives, the reference over each batch's first ``batch / chips`` rows."""
    if what != "no_allreduce":
        return _with_micro_batches(T.prove, ctx, what)
    b, s = ctx.cell["mix"]["batch"], ctx.cell["mix"]["seq"]
    m = ctx.config["model"]
    x3, y3 = T.synthetic_classes(3 * b, s, m["hidden_size"], m["num_labels"], ctx.seed)
    truth = reference_readings(ctx, x3, y3)
    local = reference_readings(ctx, x3, y3, rows=slice(0, b // ctx.cell["chips"]))
    return T.compare(local, truth, ctx.cell["correct_limits"])
