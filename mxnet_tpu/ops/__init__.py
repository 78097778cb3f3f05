"""Operator corpus (reference: src/operator/ — see SURVEY.md §2.2).

Importing this package registers every operator; frontends
(`mx.nd.*`, `mx.sym.*`) are generated from the registry, mirroring how
the reference autogenerates Python wrappers from MXListAllOpNames
(python/mxnet/ndarray/register.py).
"""
from . import registry
from .registry import register, get, list_all_ops, OP_REGISTRY

from . import elementwise  # noqa: F401
from . import reduce  # noqa: F401
from . import matrix  # noqa: F401
from . import nn  # noqa: F401
from . import random_ops  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import sequence  # noqa: F401
from . import contrib_ops  # noqa: F401
from . import rnn_ops  # noqa: F401
from . import control_flow  # noqa: F401
from . import quantization_ops  # noqa: F401
from . import detection_ops  # noqa: F401
from . import rcnn_ops  # noqa: F401
from . import pallas_attention  # noqa: F401
from . import transformer_ops  # noqa: F401
from . import moe  # noqa: F401
from . import linear_attention  # noqa: F401

__all__ = ["registry", "register", "get", "list_all_ops", "OP_REGISTRY"]
