"""Layers DSL (reference: python/paddle/fluid/layers/ — nn.py ~200 layer
functions, tensor.py, control_flow.py, ops.py autogenerated activations)."""

from . import math_op_patch  # patches Variable operators; must import first
from .tensor import *  # noqa: F401,F403
from .ops import *  # noqa: F401,F403
from .nn import *  # noqa: F401,F403
from .decoder import *  # noqa: F401,F403
from .io import *  # noqa: F401,F403
from .control_flow import *  # noqa: F401,F403
from .extended import *  # noqa: F401,F403
from .metric_op import *  # noqa: F401,F403
from .loss import *  # noqa: F401,F403
from .learning_rate_scheduler import *  # noqa: F401,F403
from .rnn import *  # noqa: F401,F403
from .detection import *  # noqa: F401,F403
from .compat import *  # noqa: F401,F403
from . import collective  # noqa: F401
from . import detection  # noqa: F401
from . import compat  # noqa: F401
from . import distributions  # noqa: F401
from .distributions import (  # noqa: F401
    Uniform,
    Normal,
    Categorical,
    MultivariateNormalDiag,
)
from . import utils  # noqa: F401

from . import nn  # noqa: F401
from . import rnn  # noqa: F401
from . import tensor  # noqa: F401
from . import control_flow  # noqa: F401
from . import ops  # noqa: F401
