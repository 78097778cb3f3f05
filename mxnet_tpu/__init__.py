"""mxnet_tpu — a TPU-native deep learning framework with the capabilities
of Apache MXNet (~1.3), built on JAX/XLA/PJRT.

Not a port: MXNet's semantics (mutable async NDArray, op registry,
autograd tape, Gluon + Module frontends, kvstore, RecordIO pipeline) are
kept, but execution is idiomatic XLA — per-op jitted FCompute with
per-shape executable caching, whole-graph compilation at the
hybridize()/bind() seam, SPMD collectives over a jax.sharding.Mesh for
data-parallel and distributed training. See SURVEY.md at the repo root
for the full capability map against the reference.

Usage mirrors the reference::

    import mxnet_tpu as mx
    a = mx.nd.ones((2, 3), ctx=mx.tpu(0))
    with mx.autograd.record():
        ...
"""
from __future__ import annotations

__version__ = "0.1.0"

from .base import MXNetError
from .attribute import AttrScope
from .context import Context, cpu, gpu, tpu, current_context, num_gpus, num_tpus
from . import engine
from . import random
from . import util
from . import ndarray
from . import ndarray as nd
from . import autograd
# Not lazy: importing it registers the compile log's listeners, so that
# every program built after `import mxnet_tpu` is in compile.build_log().
from . import compile
from .ndarray import NDArray

# Subsystems are imported lazily via __getattr__ to keep import fast and
# avoid circular imports during bring-up.
_LAZY = {
    "gluon": ".gluon",
    "optimizer": ".optimizer",
    "metric": ".metric",
    "initializer": ".initializer",
    "init": ".initializer",
    "lr_scheduler": ".lr_scheduler",
    "callback": ".callback",
    "checkpoint": ".checkpoint",
    "data": ".data",
    "kvstore": ".kvstore",
    "kv": ".kvstore",
    "io": ".io",
    "recordio": ".recordio",
    "image": ".image",
    "symbol": ".symbol",
    "sym": ".symbol",
    "module": ".module",
    "mod": ".module",
    "executor": ".executor",
    "parallel": ".parallel",
    "profiler": ".profiler",
    "serving": ".serving",
    "telemetry": ".telemetry",
    "test_utils": ".test_utils",
    "visualization": ".visualization",
    "viz": ".visualization",
    "monitor": ".monitor",
    "model": ".model",
    "rnn": ".rnn",
    "operator": ".operator_custom",
    "contrib": ".contrib",
    "rtc": ".rtc",
    "util": ".util",
    "env": ".env",
    "registry": ".registry_util",
    "attribute": ".attribute",
    "name": ".name",
    "log": ".log",
    "libinfo": ".libinfo",
    "subgraph": ".subgraph",
}


def __getattr__(attr):
    target = _LAZY.get(attr)
    if target is None:
        raise AttributeError("module 'mxnet_tpu' has no attribute %r" % attr)
    import importlib

    mod = importlib.import_module(target, __name__)
    globals()[attr] = mod
    return mod


def waitall():
    ndarray.waitall()


# A process launched with DMLC_ROLE=server/scheduler runs the blocking
# parameter-server loop here and never returns to the user script —
# mirroring the reference's python/mxnet/kvstore_server.py bootstrap.
import os as _os

if _os.environ.get("DMLC_ROLE", "").lower() in ("server", "scheduler"):
    from .kvstore_server import _init_kvstore_server_module

    _init_kvstore_server_module()
