"""paddle_tpu.nn — neural network layers (analogue of paddle.nn)."""

from . import functional  # noqa: F401
from . import initializer  # noqa: F401
from . import quant  # noqa: F401
from .lazy import LazyGuard, in_lazy_mode  # noqa: F401
from .clip import (ClipGradByGlobalNorm, ClipGradByNorm,  # noqa: F401
                   ClipGradByValue, clip_grad_norm_, clip_grad_value_)
from .layer.activation import *  # noqa: F401,F403
from .layer.common import *  # noqa: F401,F403
from .layer.container import *  # noqa: F401,F403
from .layer.experts import RoutedExperts  # noqa: F401
from .layer.conv import *  # noqa: F401,F403
from .layer.layers import Layer, ParamAttr, Parameter  # noqa: F401
from .layer.loss import *  # noqa: F401,F403
from .layer.norm import *  # noqa: F401,F403
from .layer.pooling import *  # noqa: F401,F403
from .layer.rnn import *  # noqa: F401,F403
from .layer.transformer import *  # noqa: F401,F403
from .decode import Decoder, BeamSearchDecoder, dynamic_decode  # noqa: F401
