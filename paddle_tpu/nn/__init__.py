"""paddle_tpu.nn — layers, functional ops, initializers, clipping.
(parity: python/paddle/nn/)"""

from paddle_tpu.nn import functional  # noqa: F401
from paddle_tpu.nn import initializer  # noqa: F401
from paddle_tpu.nn.clip import (  # noqa: F401
    ClipGradByGlobalNorm, ClipGradByNorm, ClipGradByValue,
)
from paddle_tpu.nn.common_layers import *  # noqa: F401,F403
from paddle_tpu.nn.conv_layers import *  # noqa: F401,F403
from paddle_tpu.nn.layer import Layer, LazyGuard  # noqa: F401
from paddle_tpu.nn.loss_layers import *  # noqa: F401,F403
from paddle_tpu.nn.norm_layers import *  # noqa: F401,F403
from paddle_tpu.nn.pooling_layers import *  # noqa: F401,F403
from paddle_tpu.nn.rnn import *  # noqa: F401,F403
from paddle_tpu.nn.transformer import *  # noqa: F401,F403
from paddle_tpu.core.functional import functional_call  # noqa: F401
