"""Built-in model builders (reference ``examples/cpp/*`` apps as library
functions): Transformer/BERT, MLP, AlexNet, ResNet, ResNeXt-50,
InceptionV3, DLRM, XDL, CANDLE-Uno, MoE, the Qwen3-Next hybrid decoder
and the afmoe (Trinity) decoder."""

from flexflow_tpu.models.afmoe import afmoe_decoder
from flexflow_tpu.models.candle_uno import candle_uno
from flexflow_tpu.models.cnn import alexnet, inception_v3, resnet, resnext50
from flexflow_tpu.models.dlrm import dlrm, dlrm_strategy, xdl
from flexflow_tpu.models.mlp import mlp
from flexflow_tpu.models.moe import moe_classifier, moe_encoder
from flexflow_tpu.models.qwen3_next import qwen3_next_decoder
from flexflow_tpu.models.transformer import transformer_encoder

__all__ = [
    "afmoe_decoder",
    "alexnet",
    "candle_uno",
    "dlrm",
    "dlrm_strategy",
    "inception_v3",
    "mlp",
    "moe_classifier",
    "moe_encoder",
    "qwen3_next_decoder",
    "resnet",
    "resnext50",
    "transformer_encoder",
    "xdl",
]
