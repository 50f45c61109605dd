"""Operator library (FCompute<tpu> registry).

Importing this package registers all built-in ops; see SURVEY.md §2.4 for the
reference inventory being covered.
"""
from . import registry
from .registry import Op, get_op, invoke, invoke_raw, list_ops, register

# register built-in operator families
from . import math  # noqa: F401  (elemwise/broadcast/reduce/linalg)
from . import tensor  # noqa: F401  (shape/index/init/sequence)
from . import nn  # noqa: F401  (conv/pool/norm/dense/dropout)
from . import random_ops  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import rnn  # noqa: F401  (fused RNN via lax.scan)
from . import linalg  # noqa: F401  (la_op family)
from . import contrib  # noqa: F401  (detection/bounding-box ops)
from . import control_flow  # noqa: F401  (foreach/while_loop/cond)
from . import quantization  # noqa: F401  (int8 ops)
from . import contrib_tail  # noqa: F401  (warping/deformable/proposal/
#                                          transformer-matmul/fft tail)
from . import parity_tail  # noqa: F401  (remaining user-visible tail:
#                                         compare aliases, im2col, STE,
#                                         *_like samplers, multi-tensor
#                                         optimizer updates)
from . import npi  # noqa: F401  (numpy-internal _npi_*/_np_* ABI names)
from . import decoder  # noqa: F401  (RMS norm, rotary embedding)

__all__ = ["registry", "Op", "get_op", "invoke", "invoke_raw", "list_ops",
           "register"]
