"""Operator definition base + registry.

Reference pattern (SURVEY §2.3): each op is a graph class (``src/ops/x.cc``,
shape inference + Legion launchers + cost measurement) plus CUDA kernels
(``src/ops/kernels/x_kernels.cu``) behind fwd/bwd wrappers.

TPU-native pattern: each op is an :class:`OpDef` —
  * ``infer`` — shape/dtype inference (replaces the .cc constructors)
  * ``weights`` — weight declarations (shape, initializer, TP-sharding hints)
  * ``forward`` — pure jax lowering (replaces the .cu forward kernel; the
    backward kernel is *gone*: jax autodiff derives it, which eliminates the
    reference's hand-written ``backward_task`` per op)
  * ``flops``/``mem_bytes`` — analytic cost for the simulator (replaces
    on-device ``measure_operator_cost`` as the first-line estimate).

Ops never talk to devices or shardings; strategies apply sharding
constraints *around* op lowerings at step-build time (see
``flexflow_tpu/runtime/executor.py``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax

from flexflow_tpu.fftype import DataType, OperatorType
from flexflow_tpu.initializer import Initializer
from flexflow_tpu.tensor import Layer

ShapeDtype = Tuple[Tuple[int, ...], DataType]


@dataclasses.dataclass
class WeightSpec:
    """Declaration of one trainable (or stateful) parameter.

    ``tp_dim``: which weight dim shards when the op is tensor-parallel along
    its partitionable output dim (None = always replicate).  This encodes the
    reference's per-op ``ParallelDimMappingRecord`` for weights
    (``include/flexflow/operator.h:22-49``) in the only form the TPU build
    needs: weight-dim <-> mesh-axis alignment.
    """

    name: str
    shape: Tuple[int, ...]
    dtype: DataType
    initializer: Initializer
    trainable: bool = True
    tp_dim: Optional[int] = None


class OpContext:
    """Per-trace context handed to ``forward``: training flag, per-layer rng,
    and — for ops that open a ``shard_map`` region (ring/Ulysses attention,
    MoE all-to-all dispatch) — the live mesh plus the incoming distribution
    of each input (``input_shardings``)."""

    def __init__(
        self,
        training: bool,
        rng: Optional[jax.Array] = None,
        mesh: Optional[Any] = None,
        input_shardings: Optional[Sequence[Any]] = None,
        op_sharding: Optional[Any] = None,
        seq_length: Optional[int] = None,
    ) -> None:
        self.training = training
        self._rng = rng
        self._counter = 0
        self.mesh = mesh
        self.input_shardings = input_shardings
        self.op_sharding = op_sharding
        # per-call iteration config (reference FFIterationConfig.seq_length,
        # config.h:162-167): static — a new value retraces, like the
        # reference re-tracing per sequence length
        self.seq_length = seq_length

    def weight_axis(self, wname: str, dim: int) -> Optional[str]:
        """Mesh axis sharding dim ``dim`` of weight ``wname`` under the
        current strategy (None if replicated)."""
        if self.op_sharding is None or wname not in self.op_sharding.weights:
            return None
        axes = self.op_sharding.weights[wname].axes_of(dim)
        return axes[0] if axes else None

    def batch_axis(self, exclude: Optional[str] = None, input_idx: int = 0) -> Optional[str]:
        """Mesh axis sharding dim 0 of input ``input_idx`` (the batch/token
        dim), skipping ``exclude`` — shared by shard_map ops (EP dispatch,
        vocab-sharded embedding) that compose with DP."""
        if not self.input_shardings or input_idx >= len(self.input_shardings):
            return None
        sh = self.input_shardings[input_idx]
        if sh is None or not len(sh.spec):
            return None
        return next((a for a in sh.axes_of(0) if a != exclude), None)

    def seq_axis(self, input_idx: int = 0, dim: int = 1) -> Optional[str]:
        """Mesh axis sharding ``dim`` of input ``input_idx`` (None if
        replicated or no sharding context) — the signal sequence-parallel
        ops key off."""
        if self.mesh is None or not self.input_shardings:
            return None
        if input_idx >= len(self.input_shardings):
            return None
        sh = self.input_shardings[input_idx]
        if sh is None or dim >= len(sh.spec):
            return None
        axes = sh.axes_of(dim)
        return axes[0] if axes else None

    def next_rng(self) -> jax.Array:
        assert self._rng is not None, "op needs rng but none provided"
        key = jax.random.fold_in(self._rng, self._counter)
        self._counter += 1
        return key


class OpDef:
    op_type: OperatorType = OperatorType.NOOP
    # weights the executor hands over in float32 under mixed precision
    # (a router, a log-decay: what a bfloat16 rounding would change)
    fp32_weights: frozenset = frozenset()
    # names of the scalars ``forward`` returns after its outputs: the
    # executor sums ``step_counters`` over layers and steps and averages
    # ``step_gauges``; both reach the host with fit's metric flush
    step_counters: Tuple[str, ...] = ()
    step_gauges: Tuple[str, ...] = ()

    # --- graph side -------------------------------------------------------
    def infer(self, layer: Layer) -> List[ShapeDtype]:
        """Output shapes/dtypes from input tensors + attrs."""
        raise NotImplementedError

    def weights(self, layer: Layer) -> List[WeightSpec]:
        return []

    # --- compute side -----------------------------------------------------
    def forward(
        self,
        layer: Layer,
        params: Dict[str, jax.Array],
        inputs: Sequence[jax.Array],
        ctx: OpContext,
    ) -> List[jax.Array]:
        raise NotImplementedError

    # --- cost side (simulator S3 analog) ----------------------------------
    def flops(self, layer: Layer) -> float:
        """Forward FLOPs (single copy of the op, unsharded)."""
        return float(sum(math.prod(s) for s, _ in self.infer(layer)))

    def mem_bytes(self, layer: Layer) -> float:
        total = 0
        for t in layer.inputs:
            total += math.prod(t.shape) * _dtype_bytes(t.dtype)
        for s, dt in self.infer(layer):
            total += math.prod(s) * _dtype_bytes(dt)
        for w in self.weights(layer):
            total += math.prod(w.shape) * _dtype_bytes(w.dtype)
        return float(total)

    def shard_degree(self, layer: Layer, sharding, mesh) -> int:
        """How many ways this op's COMPUTE divides under ``sharding`` —
        the cost model's degree divisor (reference: per-MachineView local
        shapes in ``measure_operator_cost``).  Default: the output's shard
        degree incl. partial axes.  Ops whose compute splits along WEIGHT
        shards with a replicated output (the fused-Experts EP layout)
        override this, or the search could never see EP's win."""
        out0 = sharding.output[0] if sharding and sharding.output else None
        if out0 is None:
            return 1
        degree = out0.total_degree(mesh)
        for a in out0.partial_axes:
            degree *= mesh.axis_size(a)
        return max(1, degree)

    # --- parallelism metadata --------------------------------------------
    def partitionable_dims(self, layer: Layer) -> Dict[int, str]:
        """Output dims the search may shard, tagged with a semantic kind:
        ``sample`` (batch), ``channel`` (TP), ``seq`` (sequence/context
        parallel), ``expert``.  Analog of the reference's per-op
        ParallelDimMappingRecords restricted to legal degrees."""
        out_shape, _ = self.infer(layer)[0]
        return {0: "sample"} if out_shape else {}


_dtype_sizes = {
    DataType.BOOLEAN: 1,
    DataType.INT32: 4,
    DataType.INT64: 8,
    DataType.HALF: 2,
    DataType.BFLOAT16: 2,
    DataType.FLOAT: 4,
    DataType.DOUBLE: 8,
}


def _dtype_bytes(dt: DataType) -> int:
    return _dtype_sizes.get(dt, 4)


_REGISTRY: Dict[OperatorType, OpDef] = {}


def register_op(defn: OpDef) -> OpDef:
    """Analog of the reference task registry
    (``register_flexflow_internal_tasks``, ``src/runtime/model.cc:3732``) —
    but one entry per op, not three tasks (INIT/FWD/BWD collapse into one
    traced lowering + autodiff)."""
    _REGISTRY[defn.op_type] = defn
    return defn


def get_op_def(op_type: OperatorType) -> OpDef:
    if op_type not in _REGISTRY:
        raise KeyError(f"no OpDef registered for {op_type}")
    return _REGISTRY[op_type]


def all_ops() -> Dict[OperatorType, OpDef]:
    return dict(_REGISTRY)
