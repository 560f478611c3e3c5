"""Weights from ``--seed`` for the ``phi4flash`` family, leaf by leaf.

``weights_by_leaf.py`` draws every leaf ``0.02 * normal`` (norm scales
``1 + 0.02 * normal``).  On a Mamba-1 layer that would make every
channel forget its state at one rate, and the attention's ``lambda``
near ``0.02^2``: a dropped decay or a dropped second map would barely
show.  Here the leaves that say how the layers remember are drawn as
the family initialises them:

* ``A_log[c, j] = log(j + 1)``, ``j`` = 0 .. state - 1, every channel
  (a pair forgets at rate ``(j + 1) dt`` a position);
* ``dt_bias = softplus^-1(dt)``, ``dt`` log-uniform in [0.001, 0.1]
  a channel;
* ``D = 1``;
* ``lambda_q1``, ``lambda_k1``, ``lambda_q2``, ``lambda_k2``
  ``0.1 * normal``;
* every norm weight (the LayerNorms' ``scale``, the attention's
  ``subln``) 1;

every other leaf is ``weights_by_leaf.leaf``'s.  Keyed by seed and leaf
name, float32 for the reference and cast for the program, the same
numbers on both sides.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks import weights_by_leaf as WL
from benchmarks.weights_nemotron_h import _key

DT_RANGE = (0.001, 0.1)
LAMBDAS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")


def leaf(seed: int, layer: str, weight: str, shape, dtype=jnp.float32):
    """The leaf ``layer/weight``: float32 numbers from the seed and the
    name, returned in ``dtype``."""
    shape = tuple(shape)
    if weight == "A_log":
        x = jnp.broadcast_to(jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)), shape)
    elif weight == "dt_bias":
        u = jax.random.uniform(_key(seed, layer, weight), shape, jnp.float32)
        lo, hi = (math.log(v) for v in DT_RANGE)
        dt = jnp.exp(lo + u * (hi - lo))
        x = dt + jnp.log(-jnp.expm1(-dt))  # softplus(x) = dt
    elif weight in ("D", "subln", "scale"):
        x = jnp.ones(shape, jnp.float32)
    elif weight in LAMBDAS:
        x = 0.1 * jax.random.normal(_key(seed, layer, weight), shape, jnp.float32)
    else:
        return WL.leaf(seed, layer, weight, shape, dtype)
    return x.astype(dtype)


def layer(shapes: dict, seed: int, name: str) -> dict:
    return {w: leaf(seed, name, w, s) for w, s in shapes[name].items()}


def tree(shapes: dict, seed: int) -> dict:
    """The whole float32 tree (small models, tests)."""
    return {name: layer(shapes, seed, name) for name in shapes}


class ByLayer:
    """``params[layer]`` makes that layer's float32 weights when asked
    and keeps nothing: what a layer-by-layer reference reads."""

    def __init__(self, shapes: dict, seed: int) -> None:
        self.shapes, self.seed = shapes, seed

    def __getitem__(self, name: str) -> dict:
        return layer(self.shapes, self.seed, name)


def fill_executor(shapes: dict, seed: int, executor) -> None:
    """``weights_by_leaf.fill_executor`` with this module's ``leaf``:
    replace the program's parameters one leaf at a time, each in the
    dtype and sharding of the array it replaces."""
    have = {(l, w) for l, ws in executor.params.items() for w in ws}
    want = {(l, w) for l, ws in shapes.items() for w in ws}
    if have != want:
        raise KeyError(
            f"program and reference name different weights: only the program "
            f"{sorted(have - want)}, only the reference {sorted(want - have)}"
        )
    for l, w in sorted(want):
        cur = executor.params[l][w]
        if tuple(cur.shape) != tuple(shapes[l][w]):
            raise ValueError(f"{l}/{w}: program {cur.shape}, reference {shapes[l][w]}")
        new = jax.device_put(leaf(seed, l, w, cur.shape, cur.dtype), cur.sharding)
        del cur
        executor.params[l][w] = new
