from .layers import Layer
from .activation import *  # noqa: F401,F403
from .common import *      # noqa: F401,F403
from .container import Sequential, LayerList, LayerDict, ParameterList
from .conv import (Conv1D, Conv2D, Conv3D, Conv1DTranspose, Conv2DTranspose,
                   Conv3DTranspose)
from .loss import *        # noqa: F401,F403
from .norm import (BatchNorm, BatchNorm1D, BatchNorm2D, BatchNorm3D,
                   GroupNorm, InstanceNorm1D, InstanceNorm2D, InstanceNorm3D,
                   LayerNorm, LocalResponseNorm, RMSNorm, SpectralNorm,
                   SyncBatchNorm)
from .pooling import (AdaptiveAvgPool1D, AdaptiveAvgPool2D, AdaptiveAvgPool3D,
                      AdaptiveMaxPool1D, AdaptiveMaxPool2D, AdaptiveMaxPool3D,
                      AvgPool1D, AvgPool2D, AvgPool3D, MaxPool1D, MaxPool2D,
                      MaxPool3D)
from .transformer import (MultiHeadAttention, Transformer, TransformerDecoder,
                          TransformerDecoderLayer, TransformerEncoder,
                          TransformerEncoderLayer)
from .rnn import (RNN, BiRNN, GRU, GRUCell, LSTM, LSTMCell, RNNCellBase,
                  SimpleRNN, SimpleRNNCell)
from .tail import *        # noqa: F401,F403
from .latent_moe import LatentMoE
from .swiglu_moe import SwiGLUMoE
from .mla import MultiHeadLatentAttention
