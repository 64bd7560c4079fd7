"""Kernel library: one traceable JAX kernel per op type.

Importing this package registers every kernel (parity with the reference's
static op registry in paddle/fluid/operators/*_op.cc).
"""
from . import common  # noqa
from . import math_ops  # noqa
from . import tensor_ops  # noqa
from . import nn_ops  # noqa
from . import optim_ops  # noqa
from . import sequence_ops  # noqa
from . import rnn_ops  # noqa
from . import control_flow_ops  # noqa
from . import crf_ops  # noqa
from . import ctc_ops  # noqa
from . import search_ops  # noqa
from . import detection_ops  # noqa
from . import collective_ops  # noqa
from . import zero_ops  # noqa
from . import misc_ops  # noqa
from . import hybrid_ops  # noqa

from ..core.registry import registered_ops  # noqa
