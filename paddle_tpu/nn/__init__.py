"""Layer API — dygraph-equivalent modules (reference: fluid/dygraph/nn.py),
functional under the hood (functional_call over param/buffer pytrees)."""

from .layer import (Layer, LayerList, Parameter, Sequential,
                    remat_policy)
from .layers import (GELU, RNN, BatchNorm, BilinearTensorProduct, Conv2D,
                     Conv2DTranspose, Dropout, Embedding, Flatten, GroupNorm,
                     GRUCell, LayerNorm, Linear, LSTMCell, MultiHeadAttention,
                     Pool2D, PRelu, ReLU, RMSNorm, Sigmoid, Softmax,
                     SpectralNorm, Tanh)
from .gated_attention import GatedAttention
from .latent import HyperConnection, LatentAttention
from .lora import (LoRALinear, apply_lora, lora_parameters,
                   merge_lora)
from .moe import DroplessMoE, SwitchFFN
from .rnn_layers import GRU, LSTM
from .sampling_layers import NCE, HSigmoid
from .transformer import (FeedForward, LearnedPositionalEmbedding,
                          PositionalEncoding, TransformerDecoder,
                          TransformerDecoderLayer, TransformerEncoder,
                          TransformerEncoderLayer)

__all__ = [
    "Layer", "LayerList", "Parameter", "Sequential", "remat_policy",
    "GELU", "RNN", "BatchNorm", "BilinearTensorProduct", "Conv2D",
    "Conv2DTranspose", "Dropout", "Embedding", "Flatten", "GroupNorm",
    "GRUCell", "LayerNorm", "Linear", "LSTMCell", "MultiHeadAttention",
    "Pool2D", "PRelu", "ReLU", "RMSNorm", "Sigmoid", "Softmax",
    "SpectralNorm", "Tanh",
    "GRU", "LSTM", "NCE", "HSigmoid", "SwitchFFN", "DroplessMoE",
    "LatentAttention", "HyperConnection", "GatedAttention",
    "LoRALinear", "apply_lora", "lora_parameters", "merge_lora",
    "FeedForward", "LearnedPositionalEmbedding", "PositionalEncoding",
    "TransformerDecoder", "TransformerDecoderLayer", "TransformerEncoder",
    "TransformerEncoderLayer",
]
