"""Weights from ``--seed`` for the ``minicpm_sala`` family, leaf by leaf.

``weights_by_leaf.py``'s draw (``0.02 * normal``; a weight that
multiplies a normalised vector ``1 + 0.02 * normal``), with the
lightning layers' output norm ``o_norm`` drawn as a norm weight too: at
``0.02 * normal`` it would scale those layers' branch down fifty-fold,
and a fault in them would barely show.  The decays are not weights
(``reference/minicpm_sala.py::decays``, from the layer's published
index).  Keyed by seed and leaf name, float32 for the reference and cast
for the program, the same numbers on both sides.
"""

from __future__ import annotations

import jax.numpy as jnp

from benchmarks import weights_by_leaf as WL


def leaf(seed: int, layer: str, weight: str, shape, dtype=jnp.float32):
    """The leaf ``layer/weight``: float32 numbers from the seed and the
    name, returned in ``dtype``."""
    if weight == "o_norm":
        return (1.0 + WL.leaf(seed, layer, weight, shape, jnp.float32)).astype(dtype)
    return WL.leaf(seed, layer, weight, shape, dtype)


def tree(shapes: dict, seed: int) -> dict:
    """The whole float32 tree (small models, tests)."""
    return {name: {w: leaf(seed, name, w, s) for w, s in ws.items()}
            for name, ws in shapes.items()}


class ByLayer(WL.ByLayer):
    """``params[layer]`` makes that layer's float32 weights when asked
    and keeps nothing: what a layer-by-layer reference reads."""

    def __getitem__(self, name: str) -> dict:
        return {w: leaf(self.seed, name, w, s) for w, s in self.shapes[name].items()}


def fill_executor(shapes: dict, seed: int, executor) -> None:
    """``weights_by_leaf.fill_executor`` with this module's ``leaf``."""
    import jax

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
