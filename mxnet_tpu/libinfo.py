"""Runtime/library information (reference: python/mxnet/libinfo.py +
src/libinfo.cc feature flags).

There is no libmxnet.so in the TPU rebuild — the "library" is jaxlib's
PJRT runtime; `find_lib_path` points at it and `features` reports the
capability flags a reference user would probe (mx.runtime.Features
analogue), mapped to their TPU-world truth.
"""
from __future__ import annotations

__all__ = ["find_lib_path", "features", "__version__"]

__version__ = "2.0.0.tpu"


def find_lib_path():
    """Paths of the compute runtime actually backing this build
    (reference libinfo.py:26 returns libmxnet.so candidates)."""
    import jaxlib

    return list(getattr(jaxlib, "__path__", []))


def features():
    """Capability flags (reference runtime.Features / libinfo.cc):
    name -> enabled, interpreted for the TPU/XLA runtime."""
    import jax

    try:
        platform = jax.default_backend()
    except Exception:
        platform = "unknown"
    return {
        "TPU": platform == "tpu",
        "CUDA": False,
        "CUDNN": False,
        "NCCL": False,            # collectives ride XLA/ICI instead
        "XLA": True,
        "SPMD": True,
        "MKLDNN": False,
        "OPENCV": _has("cv2"),
        "DIST_KVSTORE": True,
        "INT8": True,             # preferred_element_type int8 path
        "BF16": True,
        "SIGNAL_HANDLER": False,
        "PROFILER": True,
    }


def _has(mod):
    import importlib.util

    return importlib.util.find_spec(mod) is not None
