"""Embedding and Gather.

Reference: ``src/ops/embedding.cc`` (1205 LoC, custom gather/scatter CUDA
kernels, AggrMode SUM/AVG/NONE, vocab-partition parameter parallelism via
replica dims, ``embedding.cc:162-196``) and ``src/ops/gather.cc``.

TPU-native: ``jnp.take`` lowers to a gather HLO (dynamic-slice loop on
TPU).  For vocab-sharded tables (DLRM parameter parallelism,
``embedding.cc:162-196``) the op opens an explicit ``shard_map``: each
device gathers from its local vocab shard with out-of-range ids masked to
zero rows, bags are reduced locally, and one ``psum`` over the vocab axis
completes the lookup — O(batch·dim) bytes on the wire instead of the
table-sized all-gather naive GSPMD gather-on-sharded-dim can fall into.
This replaces the reference's replica-dim region movement with a single
ICI collective.
"""

from __future__ import annotations

import math
from typing import List

import jax
import jax.numpy as jnp

from flexflow_tpu.fftype import AggrMode, DataType, OperatorType
from flexflow_tpu.initializer import NormInitializer
from flexflow_tpu.ops.base import OpContext, OpDef, ShapeDtype, WeightSpec, register_op
from flexflow_tpu.tensor import Layer


class Embedding(OpDef):
    """Input: int ids ``(batch, bag)``; output ``(batch, out_dim)`` under
    SUM/AVG aggregation, or ``(batch, bag, out_dim)`` with AggrMode.NONE —
    matching reference shape rules (``src/ops/embedding.cc`` ctor)."""

    op_type = OperatorType.EMBEDDING

    def infer(self, layer: Layer) -> List[ShapeDtype]:
        t = layer.inputs[0]
        a = layer.attrs
        out_dim = a["out_dim"]
        aggr = a.get("aggr", AggrMode.NONE)
        dt = a.get("dtype", DataType.FLOAT)
        if aggr is AggrMode.NONE:
            return [(t.shape + (out_dim,), dt)]
        return [(t.shape[:-1] + (out_dim,), dt)]

    def weights(self, layer: Layer) -> List[WeightSpec]:
        a = layer.attrs
        dt = a.get("dtype", DataType.FLOAT)
        return [
            WeightSpec(
                name="kernel",
                shape=(a["num_entries"], a["out_dim"]),
                dtype=dt,
                initializer=a.get("kernel_initializer") or NormInitializer(),
                tp_dim=0,  # vocab-partition (embedding.cc:162-196)
            )
        ]

    def forward(self, layer, params, inputs, ctx: OpContext):
        ids = inputs[0]
        table = params["kernel"]
        aggr = layer.attrs.get("aggr", AggrMode.NONE)

        # vocab-sharded (parameter-parallel) path: explicit masked-local-
        # gather + psum instead of trusting GSPMD with a gather whose
        # operand dim 0 is sharded (reference vocab partition,
        # embedding.cc:162-196; SURVEY §7.3 flags this as the one place an
        # explicit collective is required)
        vp_axis = ctx.weight_axis("kernel", 0)
        if vp_axis is not None and ctx.mesh is not None and ctx.mesh.shape[vp_axis] > 1:
            out = self._forward_vocab_sharded(layer, ids, table, aggr, ctx, vp_axis)
            if out is not None:
                return [out]

        # mode="clip": out-of-range ids clamp to the boundary row — a
        # defined, sharding-independent behavior (jnp.take's default fills
        # NaN, and the reference CUDA gather leaves OOB unspecified)
        rows = jnp.take(table, ids, axis=0, mode="clip")
        if aggr is AggrMode.SUM:
            rows = jnp.sum(rows, axis=-2)
        elif aggr is AggrMode.AVG:
            rows = jnp.mean(rows, axis=-2)
        return [rows]

    def _forward_vocab_sharded(self, layer, ids, table, aggr, ctx, vp_axis):
        """Sharded embedding-bag: local gather on the vocab shard, bag
        reduction, one psum over ``vp_axis``.  Wire cost is the output size
        (batch·out_dim), independent of table size.  Returns None when the
        vocab doesn't divide the axis (caller falls back)."""
        from jax.sharding import PartitionSpec as P

        vp = ctx.mesh.shape[vp_axis]
        vocab = layer.attrs["num_entries"]
        if vocab % vp != 0:
            return None
        vshard = vocab // vp
        dp_axis = ctx.batch_axis(exclude=vp_axis)
        if dp_axis is not None and ids.shape[0] % ctx.mesh.shape[dp_axis] != 0:
            dp_axis = None

        def body(ids_l, tab_l):
            # clamp like jnp.take's default clip mode so out-of-range ids
            # resolve to the last row on exactly one shard — identical
            # numerics to the replicated path
            ids_c = jnp.clip(ids_l, 0, vocab - 1)
            lo = jax.lax.axis_index(vp_axis) * vshard
            loc = ids_c - lo
            ok = (loc >= 0) & (loc < vshard)
            rows = jnp.take(tab_l, jnp.clip(loc, 0, vshard - 1), axis=0)
            rows = rows * ok[..., None].astype(rows.dtype)
            if aggr in (AggrMode.SUM, AggrMode.AVG):
                rows = jnp.sum(rows, axis=-2)  # bag-reduce BEFORE the wire
            rows = jax.lax.psum(rows, vp_axis)
            if aggr is AggrMode.AVG:
                rows = rows / ids_l.shape[-1]
            return rows

        ids_spec = P(dp_axis)  # P(None) == replicated
        out_rank = ids.ndim + (1 if aggr is AggrMode.NONE else 0)
        out_spec = P(dp_axis, *([None] * (out_rank - 1)))
        f = jax.shard_map(
            body,
            mesh=ctx.mesh,
            in_specs=(ids_spec, P(vp_axis, None)),
            out_specs=out_spec,
            check_vma=False,
        )
        return f(ids, table)

    def flops(self, layer: Layer) -> float:
        shape, _ = self.infer(layer)[0]
        return float(math.prod(shape))

    def partitionable_dims(self, layer):
        return {0: "sample"}


class Gather(OpDef):
    """``src/ops/gather.cc``: torch.gather semantics along ``dim``."""

    op_type = OperatorType.GATHER

    def infer(self, layer: Layer) -> List[ShapeDtype]:
        data, index = layer.inputs
        return [(index.shape, data.dtype)]

    def forward(self, layer, params, inputs, ctx: OpContext):
        data, index = inputs
        dim = layer.attrs.get("dim", 0)
        return [jnp.take_along_axis(data, index, axis=dim)]


class TiedHead(OpDef):
    """An output head tied to an embedding's table: ``x E^T``, no bias.
    Input (..., E) -> (..., num_entries).  It holds no weights: attr
    ``tied_to`` names the embedding layer whose ``kernel`` it reads (the
    executor hands it that layer's weights), ``num_entries`` the rows."""

    op_type = OperatorType.TIED_HEAD

    def infer(self, layer: Layer) -> List[ShapeDtype]:
        t = layer.inputs[0]
        return [(t.shape[:-1] + (layer.attrs["num_entries"],), t.dtype)]

    def weights(self, layer: Layer) -> List[WeightSpec]:
        return []

    def forward(self, layer, params, inputs, ctx: OpContext):
        x = inputs[0]
        return [jax.lax.dot_general(
            x, params["kernel"].astype(x.dtype), (((x.ndim - 1,), (1,)), ((), ()))
        )]

    def flops(self, layer: Layer) -> float:
        t = layer.inputs[0]
        return 2.0 * math.prod(t.shape) * layer.attrs["num_entries"]


register_op(Embedding())
register_op(Gather())
register_op(TiedHead())
