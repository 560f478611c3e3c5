"""The arithmetic a reference runs in.

``highest`` is the reference proper: float32 operands, and on a TPU the
six-pass float32 matmul (a TPU's default float32 matmul is one bfloat16
pass).  ``bf16`` is what the configurations state for the program.
``fp8`` is the control, the nearest precision below bfloat16: both
operands of every matmul are rounded to float8_e4m3fn under a per-tensor
scale (amax -> 448), the products then accumulate in float32, and the
rounding is straight-through for the backward pass -- the usual fp8
recipe a later PR would be tempted by.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "bf16", "fp8")
_E4M3_MAX = 448.0


def _fake_fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _E4M3_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def matmul(precision: str):
    """``mm(a, b)`` = ``a @ b`` (batched like jnp.matmul) in ``precision``."""
    if precision == "highest":
        def mm(a, b):
            return jnp.matmul(
                a.astype(jnp.float32), b.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            )
    elif precision == "bf16":
        def mm(a, b):
            return jnp.matmul(
                a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                preferred_element_type=jnp.float32,
            )
    elif precision == "fp8":
        def mm(a, b):
            return jnp.matmul(
                _fake_fp8(a.astype(jnp.float32)), _fake_fp8(b.astype(jnp.float32)),
                precision=jax.lax.Precision.HIGHEST,
            )
    else:
        raise ValueError(f"precision {precision!r}: one of {PRECISIONS}")
    return mm
