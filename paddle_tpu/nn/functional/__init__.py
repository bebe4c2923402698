"""paddle_tpu.nn.functional — functional neural net ops.

Reference surface: python/paddle/nn/functional/__init__.py.
"""
from .activation import *  # noqa: F401,F403
from .common import *      # noqa: F401,F403
from .conv import *        # noqa: F401,F403
from .pooling import *     # noqa: F401,F403
from .norm import *        # noqa: F401,F403
from .loss import *        # noqa: F401,F403
from .flash_attention import *  # noqa: F401,F403
from .vision import *      # noqa: F401,F403
from .paged_attention import *  # noqa: F401,F403
from .fused import *       # noqa: F401,F403
from .tail import *        # noqa: F401,F403
from .ssm import *         # noqa: F401,F403
from .delta_rule import *  # noqa: F401,F403
from .experts import *     # noqa: F401,F403
from ...ops.search import class_center_sample, gather_tree  # noqa: F401

from . import (activation, common, conv, delta_rule, experts,
               flash_attention, fused, loss, norm, paged_attention, pooling,
               ssm, tail, vision)
