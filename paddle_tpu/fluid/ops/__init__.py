"""Op registry + JAX lowering rules.

TPU-native replacement for the reference's operator library
(paddle/fluid/operators/, ~534 registered ops with CPU/CUDA kernels,
registry at paddle/fluid/framework/op_registry.h:199): each op is a
lowering rule from (attrs, input arrays) to output arrays in JAX, applied
while tracing a whole block into one XLA computation. Gradients are
desc-level grad ops (as in the reference's GradOpDescMaker protocol,
framework/grad_op_desc_maker.h:39) whose lowerings default to ``jax.vjp``
of the forward rule — XLA CSEs the recomputed forward away.
"""

from . import registry  # noqa: F401
from .registry import get_op_def, register_op, LowerCtx  # noqa: F401

# Importing these modules populates the registry.
from . import tensor_ops  # noqa: F401
from . import math_ops  # noqa: F401
from . import nn_ops  # noqa: F401
from . import decoder_ops  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import collective_ops  # noqa: F401
from . import controlflow_ops  # noqa: F401
from . import sequence_ops  # noqa: F401
from . import rnn_ops  # noqa: F401
from . import metric_ops  # noqa: F401
from . import io_ops  # noqa: F401
from . import distributed_ops  # noqa: F401
from . import manip_ops  # noqa: F401
from . import loss_ops  # noqa: F401
from . import rnn_fused_ops  # noqa: F401
from . import fused_ops  # noqa: F401
from . import text_ops  # noqa: F401
from . import detection_ops  # noqa: F401
from . import misc_ops  # noqa: F401
from . import quant_ops  # noqa: F401
