"""LayerNorm, RMSNorm, Softmax, Dropout.

Reference: ``src/ops/layer_norm.cc`` (601 LoC, custom Welford kernels,
elementwise_affine flag), ``src/ops/softmax.cc`` (cudnnSoftmaxForward +
custom bwd, dim arg), ``src/ops/dropout.cc`` (cudnnDropout, seed attr).
RMSNorm has no reference analog but is required by modern transformer
parity (LLaMA-style models).

TPU-native: jnp reductions fuse into single VPU passes; dropout uses the
jax threaded-rng from the OpContext (deterministic per step & layer, unlike
the reference's stateful cudnnDropout state).
"""

from __future__ import annotations

import math
from typing import List

import jax
import jax.numpy as jnp

from flexflow_tpu.fftype import OperatorType
from flexflow_tpu.initializer import OnesInitializer, ZeroInitializer
from flexflow_tpu.ops.base import OpContext, OpDef, ShapeDtype, WeightSpec, register_op
from flexflow_tpu.tensor import Layer


class LayerNorm(OpDef):
    op_type = OperatorType.LAYERNORM

    def infer(self, layer: Layer) -> List[ShapeDtype]:
        t = layer.inputs[0]
        return [(t.shape, t.dtype)]

    def _norm_shape(self, layer: Layer):
        return tuple(layer.attrs["axes"])

    def weights(self, layer: Layer) -> List[WeightSpec]:
        if not layer.attrs.get("elementwise_affine", True):
            return []
        t = layer.inputs[0]
        shape = tuple(t.shape[ax] for ax in self._norm_shape(layer))
        return [
            WeightSpec("scale", shape, t.dtype, OnesInitializer()),
            WeightSpec("bias", shape, t.dtype, ZeroInitializer()),
        ]

    def forward(self, layer, params, inputs, ctx: OpContext):
        x = inputs[0]
        axes = self._norm_shape(layer)
        eps = layer.attrs.get("eps", 1e-5)
        mean = jnp.mean(x, axis=axes, keepdims=True)
        var = jnp.var(x, axis=axes, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + eps)
        if "scale" in params:
            bshape = [x.shape[i] if i in axes else 1 for i in range(x.ndim)]
            y = y * params["scale"].reshape(bshape) + params["bias"].reshape(bshape)
        return [y]

    def flops(self, layer: Layer) -> float:
        return 8.0 * math.prod(layer.inputs[0].shape)

    def partitionable_dims(self, layer):
        t = layer.inputs[0]
        axes = set(self._norm_shape(layer))
        d = {}
        for i in range(t.ndim):
            if i in axes:
                continue
            # rank-3 (B,S,H) only: rank-4 NCHW dim 1 is channels
            d[i] = "sample" if i == 0 else ("seq" if i == 1 and t.ndim == 3 else "channel")
        return d


def rms_norm_zero_centered(x, w, eps):
    """``x / rms(x) * (1 + w)`` over the last axis; statistics and
    scaling in float32, returned in float32."""
    x = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * (1.0 + w.astype(jnp.float32))


def rms_norm_f32(x, w, eps):
    """``x / rms(x) * w`` over the last axis; statistics and scaling in
    float32, returned in float32."""
    x = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * w.astype(jnp.float32)


class RMSNorm(OpDef):
    op_type = OperatorType.RMS_NORM

    def infer(self, layer: Layer) -> List[ShapeDtype]:
        t = layer.inputs[0]
        return [(t.shape, t.dtype)]

    def weights(self, layer: Layer) -> List[WeightSpec]:
        t = layer.inputs[0]
        if layer.attrs.get("zero_centered"):
            return [WeightSpec("weight", (t.shape[-1],), t.dtype, ZeroInitializer())]
        return [WeightSpec("scale", (t.shape[-1],), t.dtype, OnesInitializer())]

    def forward(self, layer, params, inputs, ctx: OpContext):
        x = inputs[0]
        eps = layer.attrs.get("eps", 1e-6)
        if layer.attrs.get("zero_centered"):
            return [rms_norm_zero_centered(x, params["weight"], eps).astype(x.dtype)]
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return [x * jax.lax.rsqrt(ms + eps) * params["scale"]]

    def partitionable_dims(self, layer):
        t = layer.inputs[0]
        d = {0: "sample"}
        if t.ndim == 3:  # (B,S,H) only — not NCHW channels
            d[1] = "seq"
        return d


class Softmax(OpDef):
    op_type = OperatorType.SOFTMAX

    def infer(self, layer: Layer) -> List[ShapeDtype]:
        t = layer.inputs[0]
        return [(t.shape, t.dtype)]

    def forward(self, layer, params, inputs, ctx: OpContext):
        dim = layer.attrs.get("dim", -1)
        return [jax.nn.softmax(inputs[0], axis=dim)]

    def flops(self, layer: Layer) -> float:
        return 5.0 * math.prod(layer.inputs[0].shape)

    def partitionable_dims(self, layer):
        t = layer.inputs[0]
        dim = layer.attrs.get("dim", -1) % t.ndim
        return {i: ("sample" if i == 0 else "channel") for i in range(t.ndim) if i != dim}


class Dropout(OpDef):
    op_type = OperatorType.DROPOUT

    def infer(self, layer: Layer) -> List[ShapeDtype]:
        t = layer.inputs[0]
        return [(t.shape, t.dtype)]

    def forward(self, layer, params, inputs, ctx: OpContext):
        x = inputs[0]
        rate = layer.attrs.get("rate", 0.5)
        if not ctx.training or rate == 0.0:
            return [x]
        keep = 1.0 - rate
        mask = jax.random.bernoulli(ctx.next_rng(), keep, x.shape)
        return [jnp.where(mask, x / keep, 0.0)]

    def partitionable_dims(self, layer):
        t = layer.inputs[0]
        d = {i: ("sample" if i == 0 else "channel") for i in range(t.ndim)}
        if t.ndim == 3:
            d[1] = "seq"  # (B, S, H) only — rank-4 NCHW dim 1 is channels
        return d


register_op(LayerNorm())
register_op(RMSNorm())
register_op(Softmax())
register_op(Dropout())
