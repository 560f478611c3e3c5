"""Operator library — importing registers every OpDef (SURVEY §2.3)."""

from flexflow_tpu.ops import (  # noqa: F401
    attention,
    conv,
    dense,
    elementwise,
    embedding,
    linear_attention,
    moe,
    norm,
    parallel_ops,
    sparse_attention,
    ssm,
    tensor_ops,
)
from flexflow_tpu.ops.base import OpContext, OpDef, WeightSpec, all_ops, get_op_def

__all__ = ["OpContext", "OpDef", "WeightSpec", "all_ops", "get_op_def"]
