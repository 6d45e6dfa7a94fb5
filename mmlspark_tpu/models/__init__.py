"""Model layer: functional NN modules, flagship architectures, DNN inference stage."""

from .module import (
    BatchNorm,
    Conv2D,
    Dense,
    Fn,
    FunctionModel,
    GlobalAvgPool,
    MaxPool,
    Module,
    Residual,
    Sequential,
    flatten,
    matmul_dtype,
    matmul_precision,
    relu,
)
from .attention import (
    BiLSTM,
    Embed,
    LSTM,
    LayerNorm,
    MultiHeadAttention,
    bilstm_tagger,
    dense_attention,
    ring_attention,
    transformer_block,
    transformer_encoder,
)
from .moe import MoE, expert_shardings
from .ssm import GatedMemoryUnit, Mamba, ssm_scan
from .transformer import (
    CausalLM,
    DecoderLayer,
    DiffAttention,
    causal_lm,
    hybrid_causal_lm,
    latent_causal_lm,
)
from .resnet import build_resnet, param_shardings, resnet, resnet18, resnet50
from .dnn_model import DNNModel
from .graph_module import GraphModule, GraphNode
from .torch_import import from_torch_resnet

__all__ = [
    "BatchNorm", "BiLSTM", "CausalLM", "Conv2D", "DNNModel", "DecoderLayer", "Dense",
    "DiffAttention", "Embed", "Fn", "FunctionModel", "GatedMemoryUnit", "GlobalAvgPool",
    "GraphModule", "GraphNode", "LSTM", "LayerNorm", "Mamba", "MaxPool", "MoE", "Module",
    "MultiHeadAttention", "Residual", "Sequential", "bilstm_tagger", "build_resnet",
    "causal_lm", "dense_attention", "expert_shardings", "flatten", "from_torch_resnet",
    "hybrid_causal_lm", "latent_causal_lm", "param_shardings", "relu", "resnet",
    "resnet18", "resnet50", "ring_attention", "ssm_scan", "transformer_block",
    "transformer_encoder",
]
