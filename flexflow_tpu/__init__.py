"""flexflow_tpu — a TPU-native auto-parallelizing DNN training framework.

A ground-up re-design of FlexFlow/Unity (C++/CUDA/Legion) for TPU:
jax/XLA/Pallas compute, GSPMD sharding over named meshes, and a
hardware-aware strategy search.  See SURVEY.md for the layer-by-layer
mapping to the reference.
"""

from flexflow_tpu.config import FFConfig
from flexflow_tpu.fftype import (
    ActiMode,
    AggrMode,
    DataType,
    LossType,
    MetricsType,
    OperatorType,
    PoolType,
)
from flexflow_tpu.initializer import (
    ConstantInitializer,
    GlorotUniform,
    NormInitializer,
    OnesInitializer,
    UniformInitializer,
    ZeroInitializer,
)
from flexflow_tpu.model import CheckpointError, FFModel
from flexflow_tpu.obs import Tracer, get_tracer
from flexflow_tpu.optimizer import AdamOptimizer, SGDOptimizer
from flexflow_tpu.parallel.machine import MachineMesh
from flexflow_tpu.runtime.faults import (
    FaultPlan,
    get_fault_plan,
    set_fault_plan,
)
from flexflow_tpu.runtime.recompile import RecompileState, RecoveryPolicy
from flexflow_tpu.parallel.spec import TensorSharding
from flexflow_tpu.parallel.strategy import (
    Strategy,
    data_parallel_strategy,
    tensor_parallel_strategy,
)
from flexflow_tpu.tensor import Tensor

__version__ = "0.1.0"

__all__ = [
    "FFModel",
    "FFConfig",
    "Tensor",
    "DataType",
    "ActiMode",
    "AggrMode",
    "PoolType",
    "LossType",
    "MetricsType",
    "OperatorType",
    "SGDOptimizer",
    "AdamOptimizer",
    "MachineMesh",
    "TensorSharding",
    "Strategy",
    "data_parallel_strategy",
    "tensor_parallel_strategy",
    "RecompileState",
    "RecoveryPolicy",
    "CheckpointError",
    "FaultPlan",
    "get_fault_plan",
    "set_fault_plan",
    "Tracer",
    "get_tracer",
    "GlorotUniform",
    "ZeroInitializer",
    "OnesInitializer",
    "ConstantInitializer",
    "UniformInitializer",
    "NormInitializer",
]
