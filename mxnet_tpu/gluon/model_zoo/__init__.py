"""Model zoo (reference: python/mxnet/gluon/model_zoo/ — vision models +
pinned pretrained weights via model_store.py)."""
from . import vision
from . import deepseek_v3
from . import qwen3_next
from . import mellum
from .vision import get_model

__all__ = ["vision", "deepseek_v3", "qwen3_next", "mellum",
           "get_model"]
