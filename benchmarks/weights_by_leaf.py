"""Weights from ``--seed``, leaf by leaf.

``weights.py`` draws every number of a model in one float32 vector,
which a configuration whose float32 weights pass the chip's memory
cannot do.  Same rule here (``0.02 * normal``; norm scales ``1 + 0.02 *
normal``, so that a dropped multiply shows), but each leaf's numbers
come from a key folded from the seed and the leaf's ``(layer, weight)``
name: any leaf is made alone, in float32 for the reference and cast for
the program, the same numbers on both sides whatever else was made.
"""

from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp

from benchmarks.weights import key_from_seed

# leaves that multiply a normalised vector: centred on one
ONE_CENTRED = ("scale", "q_norm", "k_norm")


@functools.partial(jax.jit, static_argnames=("shape", "one", "dtype"))
def _draw(key, *, shape, one, dtype):
    x = 0.02 * jax.random.normal(key, shape, jnp.float32)
    return (1.0 + x if one else x).astype(dtype)


def leaf(seed: int, layer: str, weight: str, shape, dtype=jnp.float32):
    """The leaf ``layer/weight``: float32 numbers from the seed and the
    name, returned in ``dtype``."""
    key = key_from_seed(seed)
    for name in (layer, weight):
        key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return _draw(key, shape=tuple(shape), one=weight in ONE_CENTRED,
                 dtype=jnp.dtype(dtype).name)


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
    """Replace the program's parameters, one leaf at a time, each in the
    dtype and sharding of the array it replaces (which is freed as it is
    replaced: the model is never held twice)."""
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
