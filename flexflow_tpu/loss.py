"""Loss functions.

Reference: ``src/loss_functions/loss_functions.cc`` + ``.cu`` —
``Loss::backward`` launches a LOSS_BWD index task writing logit gradients
directly (sparse-CCE via softmax-grad trick, CCE, MSE, identity), scaled by
``1/batch`` (``loss_functions.cc`` scale factor).

TPU-native: losses are scalar-valued pure functions; jax.grad produces the
same logit gradients the reference hand-codes (including the 1/batch
scaling, which falls out of ``mean``).  ``sparse_categorical_crossentropy``
expects the *softmax output* as the reference does (the final Softmax op is
part of the graph; we use a numerically-stable log on it).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from flexflow_tpu.fftype import LossType


def sparse_categorical_crossentropy(probs: jax.Array, labels: jax.Array) -> jax.Array:
    """probs: (..., classes) post-softmax; labels: int, one per row of
    ``probs`` in any shape that holds them -- (batch,), (batch, 1), or
    next-token labels (batch, seq) against (batch, seq, classes) or
    (batch*seq, classes)."""
    probs = probs.reshape(-1, probs.shape[-1])
    labels = labels.reshape(probs.shape[0]).astype(jnp.int32)
    p = jnp.take_along_axis(probs, labels[:, None], axis=-1)[:, 0]
    return -jnp.mean(jnp.log(jnp.maximum(p, 1e-12)))


def categorical_crossentropy(probs: jax.Array, labels: jax.Array) -> jax.Array:
    return -jnp.mean(
        jnp.sum(labels * jnp.log(jnp.maximum(probs, 1e-12)), axis=-1)
    )


def mean_squared_error_avg(pred: jax.Array, labels: jax.Array) -> jax.Array:
    return jnp.mean(jnp.sum(jnp.square(pred - labels), axis=-1))


def mean_squared_error_sum(pred: jax.Array, labels: jax.Array) -> jax.Array:
    return jnp.sum(jnp.square(pred - labels)) / pred.shape[0]


def identity_loss(pred: jax.Array, labels: jax.Array) -> jax.Array:
    """Reference ``identity`` loss: gradient of ones/batch — i.e. the model
    output *is* the loss (used e.g. for custom objectives)."""
    return jnp.mean(pred)


_LOSS_FNS = {
    LossType.SPARSE_CATEGORICAL_CROSSENTROPY: sparse_categorical_crossentropy,
    LossType.CATEGORICAL_CROSSENTROPY: categorical_crossentropy,
    LossType.MEAN_SQUARED_ERROR_AVG_REDUCE: mean_squared_error_avg,
    LossType.MEAN_SQUARED_ERROR_SUM_REDUCE: mean_squared_error_sum,
    LossType.IDENTITY: identity_loss,
}


def get_loss_fn(loss_type: LossType):
    return _LOSS_FNS[loss_type]


def parse_loss(name: str) -> LossType:
    return LossType(name)
