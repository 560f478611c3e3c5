"""Weights from ``--seed``: made by the benchmark, on the device, in one
jitted call, and handed to the program and to the reference alike.

The reference may take nothing the program has made, so the program's
own seeded init does not serve: both sides get this tree.  Every leaf is
``0.02 * normal`` except LayerNorm scales, which are ``1 + 0.02 *
normal`` (a scale or a bias left at its init of one or nought would hide
a dropped multiply or add).  The numbers are one draw, cut into leaves
in sorted (layer, weight) order, so the tree does not depend on how a
side stores it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def key_from_seed(seed: int):
    """A key from any non-negative whole number, also past 2**31."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def per_layer(shapes: dict, key) -> dict:
    """``{layer: {weight: shape}}`` -> the same tree of float32 arrays
    (call under jit).  One draw of all the numbers, cut into the leaves
    in sorted (layer, weight) order: a draw per leaf is the same
    mathematics and a minute of compilation more."""
    order = [(l, w) for l in sorted(shapes) for w in sorted(shapes[l])]
    sizes = [int(math.prod(shapes[l][w])) for l, w in order]
    flat = 0.02 * jax.random.normal(key, (sum(sizes),), jnp.float32)
    out, at = {}, 0
    for (l, w), n in zip(order, sizes):
        x = jax.lax.slice(flat, (at,), (at + n,)).reshape(tuple(shapes[l][w]))
        out.setdefault(l, {})[w] = 1.0 + x if w == "scale" else x
        at += n
    return out


def make(shapes: dict, seed: int) -> dict:
    """The per-layer tree, on the default device, from one jitted call."""
    return jax.jit(lambda k: per_layer(shapes, k))(key_from_seed(seed))


def make_for_executor(shapes: dict, seed: int, executor) -> dict:
    """The same numbers in the layout the program's executor stores:
    members of a scan-stacked chain sit in one ``(depth, ...)`` array
    under the template layer's name (``Executor.locate_weight`` says
    where), each leaf in the dtype and sharding of the array it
    replaces.  One jitted call."""
    route = {}  # (bucket, weight) -> [(depth index or None, layer)]
    for lname in shapes:
        for wname in shapes[lname]:
            loc = executor.locate_weight(lname, wname)
            if loc is None:
                raise KeyError(f"the program has no weight {lname}/{wname}")
            _, bname, d = loc
            route.setdefault((bname, wname), []).append((d, lname))
    have = {(b, w) for b, ws in executor.params.items() for w in ws}
    if have != set(route):
        raise KeyError(f"weights the reference does not know: {sorted(have - set(route))}")

    def build(key):
        flat = per_layer(shapes, key)
        out = {}
        for (bname, wname), members in route.items():
            cur = executor.params[bname][wname]
            if members[0][0] is None:
                arr = flat[members[0][1]][wname]
            else:
                arr = jnp.stack([flat[l][wname] for _, l in sorted(members)])
            assert arr.shape == cur.shape, (bname, wname, arr.shape, cur.shape)
            out.setdefault(bname, {})[wname] = arr.astype(cur.dtype)
        return out

    shardings = jax.tree.map(lambda a: a.sharding, executor.params)
    return jax.jit(build, out_shardings=shardings)(key_from_seed(seed))
