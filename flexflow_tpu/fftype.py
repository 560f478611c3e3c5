"""Core enums and type tables.

TPU-native re-design of the reference's ``include/flexflow/ffconst.h:69-161``
(OperatorType, DataType, ActiMode, ...) and ``src/runtime/fftype.cc``
(LayerID).  We keep the same *vocabulary* (so frontends / strategy files can
round-trip) but use Python enums and map data types onto jax dtypes.
"""

from __future__ import annotations

import enum
import itertools

import jax.numpy as jnp


class DataType(enum.Enum):
    """Mirror of ``DT_*`` in reference ``include/flexflow/ffconst.h:20-28``."""

    BOOLEAN = "bool"
    INT32 = "int32"
    INT64 = "int64"
    HALF = "float16"
    BFLOAT16 = "bfloat16"
    FLOAT = "float32"
    DOUBLE = "float64"
    NONE = "none"

    def to_jnp(self):
        if self is DataType.NONE:
            raise ValueError("DT_NONE has no jax dtype")
        return jnp.dtype(self.value)

    @staticmethod
    def from_jnp(dtype) -> "DataType":
        return DataType(jnp.dtype(dtype).name)


class ActiMode(enum.Enum):
    """``AC_MODE_*`` (reference ``include/flexflow/ffconst.h:30-36``)."""

    NONE = "none"
    RELU = "relu"
    SIGMOID = "sigmoid"
    TANH = "tanh"
    GELU = "gelu"


class AggrMode(enum.Enum):
    """Embedding aggregation (``ffconst.h:44-48``)."""

    NONE = "none"
    SUM = "sum"
    AVG = "avg"


class PoolType(enum.Enum):
    """``POOL_MAX / POOL_AVG`` (``ffconst.h:38-41``)."""

    MAX = "max"
    AVG = "avg"


class LossType(enum.Enum):
    """``LOSS_*`` (``ffconst.h:50-56``)."""

    CATEGORICAL_CROSSENTROPY = "categorical_crossentropy"
    SPARSE_CATEGORICAL_CROSSENTROPY = "sparse_categorical_crossentropy"
    MEAN_SQUARED_ERROR_AVG_REDUCE = "mean_squared_error_avg_reduce"
    MEAN_SQUARED_ERROR_SUM_REDUCE = "mean_squared_error_sum_reduce"
    IDENTITY = "identity"


class MetricsType(enum.Enum):
    """``METRICS_*`` bit-flags (``ffconst.h:58-66``) as an enum set."""

    ACCURACY = "accuracy"
    CATEGORICAL_CROSSENTROPY = "categorical_crossentropy"
    SPARSE_CATEGORICAL_CROSSENTROPY = "sparse_categorical_crossentropy"
    MEAN_SQUARED_ERROR = "mean_squared_error"
    ROOT_MEAN_SQUARED_ERROR = "root_mean_squared_error"
    MEAN_ABSOLUTE_ERROR = "mean_absolute_error"


class OperatorType(enum.Enum):
    """PCG node kinds — reference ``include/flexflow/ffconst.h:69-161``.

    The TPU build keeps the full vocabulary, including the four parallel ops
    that form the re-sharding language (``ffconst.h:152-158``).
    """

    NOOP = "noop"
    INPUT = "input"
    WEIGHT = "weight"
    CONV2D = "conv2d"
    DROPOUT = "dropout"
    LINEAR = "linear"
    BATCHMATMUL = "batch_matmul"
    POOL2D = "pool2d"
    SCALAR_MULTIPLY = "scalar_multiply"
    SCALAR_ADD = "scalar_add"
    SCALAR_SUB = "scalar_sub"
    SCALAR_TRUE_DIV = "scalar_true_div"
    RELU = "relu"
    IDENTITY = "identity"
    SIGMOID = "sigmoid"
    TANH = "tanh"
    ELU = "elu"
    GELU = "gelu"
    RSQRT = "rsqrt"
    POW = "pow"
    EXP = "exp"
    SIN = "sin"
    COS = "cos"
    FLAT = "flat"
    SOFTMAX = "softmax"
    BATCHNORM = "batch_norm"
    LAYERNORM = "layer_norm"
    RMS_NORM = "rms_norm"
    CONCAT = "concat"
    SPLIT = "split"
    EMBEDDING = "embedding"
    GATHER = "gather"
    CACHE = "cache"
    AGGREGATE = "aggregate"
    AGGREGATE_SPEC = "aggregate_spec"
    RESHAPE = "reshape"
    REVERSE = "reverse"
    TRANSPOSE = "transpose"
    EW_ADD = "ew_add"
    EW_MUL = "ew_mul"
    EW_SUB = "ew_sub"
    EW_DIV = "ew_div"
    EW_MAX = "ew_max"
    EW_MIN = "ew_min"
    REDUCE_SUM = "reduce_sum"
    REDUCE_MEAN = "reduce_mean"
    MULTIHEAD_ATTENTION = "multihead_attention"
    GATED_ATTENTION = "gated_attention"
    GATED_DELTA_NET = "gated_delta_net"
    MAMBA2_MIXER = "mamba2_mixer"
    MAMBA1_MIXER = "mamba1_mixer"
    DIFF_ATTENTION = "diff_attention"
    GATED_MEMORY = "gated_memory"
    LIGHTNING_ATTENTION = "lightning_attention"
    SPARSE_ATTENTION = "sparse_attention"
    TIED_HEAD = "tied_head"
    TOPK = "topk"
    GROUP_BY = "group_by"
    EXPERTS = "experts"
    ROUTED_EXPERTS = "routed_experts"
    GATED_FFN = "gated_ffn"
    CAST = "cast"
    FUSED = "fused"
    # --- parallel ops (the resharding vocabulary, ffconst.h:152-158) ---
    REPARTITION = "repartition"
    COMBINE = "combine"
    REPLICATE = "replicate"
    REDUCTION = "reduction"
    BATCH = "batch"
    PIPELINE = "pipeline"  # enum-only in the reference (no op impl)
    FUSED_PARALLEL = "fused_parallel"

    @property
    def is_parallel_op(self) -> bool:
        return self in _PARALLEL_OPS


_PARALLEL_OPS = frozenset(
    {
        OperatorType.REPARTITION,
        OperatorType.COMBINE,
        OperatorType.REPLICATE,
        OperatorType.REDUCTION,
        OperatorType.FUSED_PARALLEL,
    }
)


class ParameterSyncType(enum.Enum):
    """``CHOSEN_SYNC_TYPE`` analog (reference ``include/flexflow/config.h:55-59``).

    On TPU both lower to the same thing (psum emitted by GSPMD), but we keep
    the distinction for strategy-file parity:  ``NCCL`` -> fused all-reduce in
    the step program, ``PS`` -> parameter-server-style host reduction
    (implemented as the same collective; kept for API compat).
    """

    NONE = "none"
    PS = "ps"
    NCCL = "nccl"  # on TPU: XLA all-reduce over the mesh


class LayerID:
    """Monotonic layer guid — reference ``src/runtime/fftype.cc`` (LayerID)."""

    _counter = itertools.count(1000)

    def __init__(self) -> None:
        self.id = next(LayerID._counter)

    def __int__(self) -> int:
        return self.id

    def __repr__(self) -> str:
        return f"LayerID({self.id})"
