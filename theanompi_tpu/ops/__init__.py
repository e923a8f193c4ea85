"""Ops layer: neural-net layers, initializers, losses, optimizers.

TPU-native replacement for the reference's layer library and update builders
(reference, unverified — SURVEY.md §2.1: ``theanompi/models/layers2.py``
[Conv/Pool/FC/Dropout/Softmax/BN/Weight on theano.gpuarray + cuDNN] and
``theanompi/lib/opt.py`` [SGD/momentum update-list builders]).  Here every
layer is a pure function pair (shape-inferred ``init``, ``apply``) lowered by
XLA — convs hit the MXU via ``lax.conv_general_dilated`` in NHWC, the
TPU-native layout (the reference's bc01/NCHW is a GPU-ism we do not copy).
"""

from theanompi_tpu.ops import initializers
from theanompi_tpu.ops.layers import (
    Activation,
    AvgPool,
    BatchNorm,
    Conv2D,
    ConvTranspose2D,
    Dense,
    Dropout,
    Embedding,
    Flatten,
    GlobalAvgPool,
    LayerNorm,
    LRN,
    LSTM,
    MaxPool,
    RMSNorm,
    Sequential,
)
from theanompi_tpu.ops.losses import (
    sigmoid_binary_cross_entropy,
    softmax_cross_entropy,
    top_k_error,
)
from theanompi_tpu.ops.opt import SGD, Adam, Optimizer, RMSProp

__all__ = [
    "Activation", "AvgPool", "BatchNorm", "Conv2D", "ConvTranspose2D",
    "Dense", "Dropout", "Embedding", "Flatten", "GlobalAvgPool", "LayerNorm",
    "LRN", "LSTM", "MaxPool", "RMSNorm", "Sequential", "initializers",
    "softmax_cross_entropy", "sigmoid_binary_cross_entropy", "top_k_error",
    "SGD", "Adam", "RMSProp", "Optimizer",
]
