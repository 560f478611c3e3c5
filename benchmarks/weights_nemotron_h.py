"""Weights from ``--seed`` for the ``nemotron_h`` family, leaf by leaf.

``weights_by_leaf.py`` draws every leaf ``0.02 * normal``.  On a
Mamba-2 layer's ``A_log`` and ``dt_bias`` that makes every head forget
half its state a token (``dt`` = softplus(0) = 0.69, ``A`` = -1), and a
state dropped at a chunk boundary would barely show.  Here those leaves
are drawn as the family initialises them:

* ``A_log = log(a)``, ``a`` uniform in [1, 16];
* ``dt_bias = softplus^-1(dt)``, ``dt`` log-uniform in [``time_step_min``
  0.001, ``time_step_max`` 0.1], floored at ``time_step_floor`` 1e-4
  (what those keys of the source's config are for);
* ``D = 1 + 0.02 * normal``;

a per-token decay between e^-1.6 and e^-0.001: a head remembers a token
or a thousand.  Every other leaf is ``weights_by_leaf.leaf``'s.  Keyed
by seed and leaf name, float32 for the reference and cast for the
program, the same numbers on both sides.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

from benchmarks import weights_by_leaf as WL
from benchmarks.weights import key_from_seed

TIME_STEP = {"time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4}
A_RANGE = (1.0, 16.0)


def _key(seed: int, layer: str, weight: str):
    key = key_from_seed(seed)
    for name in (layer, weight):
        key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return key


def leaf(seed: int, layer: str, weight: str, shape, dtype=jnp.float32, time_step=None):
    """The leaf ``layer/weight``: float32 numbers from the seed and the
    name, returned in ``dtype``."""
    ts = dict(TIME_STEP, **(time_step or {}))
    shape = tuple(shape)
    if weight == "A_log":
        a = jax.random.uniform(_key(seed, layer, weight), shape, jnp.float32, *A_RANGE)
        x = jnp.log(a)
    elif weight == "dt_bias":
        u = jax.random.uniform(_key(seed, layer, weight), shape, jnp.float32)
        lo, hi = math.log(ts["time_step_min"]), math.log(ts["time_step_max"])
        dt = jnp.maximum(jnp.exp(lo + u * (hi - lo)), ts["time_step_floor"])
        x = dt + jnp.log(-jnp.expm1(-dt))  # softplus(x) = dt
    elif weight == "D":
        x = 1.0 + 0.02 * jax.random.normal(_key(seed, layer, weight), shape, jnp.float32)
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
