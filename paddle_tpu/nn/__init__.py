"""paddle_tpu.nn — layers + functional (reference: python/paddle/nn/__init__.py)."""
from . import functional  # noqa: F401
from . import initializer  # noqa: F401
from .layer import Layer, Parameter  # noqa: F401
from .initializer import ParamAttr  # noqa: F401
from .layers.activation import (  # noqa: F401
    CELU, ELU, GELU, GLU, Hardshrink, Hardsigmoid, Hardswish, Hardtanh,
    LeakyReLU, LogSigmoid, LogSoftmax, Maxout, Mish, PReLU, ReLU, ReLU6, RReLU,
    SELU, Sigmoid, Silu, Softmax, Softplus, Softshrink, Softsign, Swish, Tanh,
    Tanhshrink, ThresholdedReLU)
from .layers.common import (  # noqa: F401
    AlphaDropout, Bilinear, CosineSimilarity, Dropout, Dropout2D, Dropout3D,
    Embedding, Flatten, Fold, GatedSiluFFN, Identity, Linear, Pad1D, Pad2D,
    Pad3D, PairwiseDistance, Unfold, Upsample, UpsamplingBilinear2D,
    UpsamplingNearest2D, ZeroPad2D)
from .layers.container import LayerDict, LayerList, ParameterList, Sequential  # noqa: F401
from .layers.conv import (  # noqa: F401
    Conv1D, Conv1DTranspose, Conv2D, Conv2DTranspose, Conv3D, Conv3DTranspose)
from .layers.loss import (  # noqa: F401
    BCELoss, BCEWithLogitsLoss, CosineEmbeddingLoss, CrossEntropyLoss, CTCLoss,
    HingeEmbeddingLoss, HSigmoidLoss, KLDivLoss, L1Loss, MarginRankingLoss,
    MSELoss, NLLLoss, SmoothL1Loss, TripletMarginLoss)
from .decode import BeamSearchDecoder, Decoder, dynamic_decode  # noqa: F401
from .layers.linear_attention import GatedDeltaNet  # noqa: F401
from .layers.state_space import Mamba2Mixer  # noqa: F401
from .layers.norm import (  # noqa: F401
    BatchNorm, BatchNorm1D, BatchNorm2D, BatchNorm3D, GatedRMSNorm, GroupNorm,
    InstanceNorm1D, InstanceNorm2D, InstanceNorm3D, LayerNorm,
    LocalResponseNorm, RMSNorm, SpectralNorm, SyncBatchNorm)
from .layers.pooling import (  # noqa: F401
    AdaptiveAvgPool1D, AdaptiveAvgPool2D, AdaptiveAvgPool3D, AdaptiveMaxPool1D,
    AdaptiveMaxPool2D, AdaptiveMaxPool3D, AvgPool1D, AvgPool2D, AvgPool3D,
    MaxPool1D, MaxPool2D, MaxPool3D)
from .layers.rnn import (  # noqa: F401
    BiRNN, GRU, GRUCell, LSTM, LSTMCell, RNN, RNNCellBase, SimpleRNN,
    SimpleRNNCell)
from .layers.transformer import (  # noqa: F401
    MultiHeadAttention, Transformer, TransformerDecoder,
    TransformerDecoderLayer, TransformerEncoder, TransformerEncoderLayer)
from .layers.vision import ChannelShuffle, PixelShuffle, PixelUnshuffle  # noqa: F401
from ..optimizer.clip import (  # noqa: F401,E402  (reference: fluid/clip.py re-exported at paddle.nn)
    ClipGradByGlobalNorm, ClipGradByNorm, ClipGradByValue)
