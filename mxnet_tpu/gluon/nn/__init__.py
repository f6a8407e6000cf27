"""gluon.nn (reference: python/mxnet/gluon/nn/__init__.py)."""
from .basic_layers import (  # noqa: F401
    Sequential, HybridSequential, Dense, Dropout, BatchNorm, BatchNormReLU,
    SyncBatchNorm, LayerNorm, RMSNorm, GroupNorm, InstanceNorm, Embedding,
    Flatten, Identity, Lambda, HybridLambda, Concatenate, HybridConcatenate,
)
from .conv_layers import (  # noqa: F401
    Conv1D, Conv2D, Conv3D, Conv1DTranspose, Conv2DTranspose, Conv3DTranspose,
    MaxPool1D, MaxPool2D, MaxPool3D, AvgPool1D, AvgPool2D, AvgPool3D,
    GlobalMaxPool1D, GlobalMaxPool2D, GlobalMaxPool3D, GlobalAvgPool1D,
    GlobalAvgPool2D, GlobalAvgPool3D, ReflectionPad2D, DeformableConvolution,
    ModulatedDeformableConvolution, PixelShuffle1D, PixelShuffle2D,
    PixelShuffle3D,
)
from .activations import (  # noqa: F401
    Activation, LeakyReLU, PReLU, ELU, SELU, GELU, SiLU, Swish,
)
from .transformer import (  # noqa: F401
    MultiHeadAttention, GroupedQueryAttention, IndexedAttention,
    LatentAttention, SparseIndexer, PositionwiseFFN, GatedFFN,
    TransformerEncoder,
    TransformerEncoderCell, TransformerDecoderCell,
)
from .moe import MoEDense, RoutedExperts  # noqa: F401
from .ssm import Mamba2Mixer  # noqa: F401
from .fuse import FusableSequential  # noqa: F401
from ..block import Block, HybridBlock, SymbolBlock  # noqa: F401
