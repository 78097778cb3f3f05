"""Gluon Parameter / ParameterDict.

Reference: python/mxnet/gluon/parameter.py (Parameter :43 with grad_req,
deferred init, ParameterDict; Constant).

TPU-specific: `override()` installs a thread-local map Parameter.data()
consults — during a CachedOp trace, parameters resolve to tracer-backed
NDArrays so they become *inputs* of the compiled executable rather than
baked constants, and aux-state writes (BatchNorm running stats) are
collected as extra executable outputs instead of mutations
(cached_op.py). This replaces the reference's arg/aux array binding in
CachedOp::Forward.
"""
from __future__ import annotations

import re
import threading

import numpy as np

from ..base import MXNetError
from ..context import Context, current_context, cpu
from .. import ndarray as nd
from ..ndarray.ndarray import NDArray

__all__ = ["Parameter", "Constant", "ParameterDict", "DeferredInitializationError",
           "override", "tracing_overrides"]

_tls = threading.local()


class DeferredInitializationError(MXNetError):
    """Parameter used before shapes were known (reference: parameter.py)."""


class _Override:
    def __init__(self, mapping, collect_writes=True):
        self.mapping = mapping
        self.writes = {} if collect_writes else None

    def __enter__(self):
        if not hasattr(_tls, "stack"):
            _tls.stack = []
        _tls.stack.append(self)
        return self

    def __exit__(self, *a):
        _tls.stack.pop()


def override(mapping):
    """Scope in which `Parameter.data()` returns `mapping[param]` and
    `set_data` is captured instead of applied (used during traces)."""
    return _Override(mapping)


def tracing_overrides():
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


class Parameter:
    """A trainable weight (reference: gluon/parameter.py:Parameter)."""

    def __init__(self, name, grad_req="write", shape=None, dtype=np.float32,
                 lr_mult=1.0, wd_mult=1.0, init=None, allow_deferred_init=False,
                 differentiable=True, stype="default", grad_stype="default"):
        self.name = name
        self._grad_req = grad_req if differentiable else "null"
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self._stype = stype
        # Gradient storage type (reference parameter.py: grad_stype
        # 'row_sparse' makes the kvstore pull only touched rows).
        self._grad_stype = grad_stype
        self._data = None  # dict ctx -> NDArray
        self._grad = None
        self._deferred_init = None
        self._live = None  # see _bind_live

    @property
    def grad_stype(self):
        return self._grad_stype

    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        self._grad_req = req
        if self._data is not None and req != "null":
            self._init_grad()
        if req == "null":
            self._grad = None

    def _check_initialized(self, ctx=None):
        if self._data is None:
            if self._deferred_init is not None:
                raise DeferredInitializationError(
                    "Parameter '%s' has not been initialized yet because "
                    "initialization was deferred. Call net(data) once to "
                    "trigger shape inference, or set shape explicitly." % self.name)
            raise RuntimeError(
                "Parameter '%s' has not been initialized. Call initialize() "
                "first." % self.name)

    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit=False):
        """Allocate and initialize on ctx(s) (reference: parameter.py
        Parameter.initialize; deferred when shape unknown)."""
        from .. import initializer as _initializer

        if self._data is not None and not force_reinit:
            return
        # A param-specific init (explicit arg or self.init, e.g. Dense's
        # bias_initializer) must bypass the global initializer's
        # name-suffix dispatch — reference marks this with the
        # InitDesc attrs['__init__'] convention.
        specific = init is not None or self.init is not None
        if init is None:
            init = self.init if self.init is not None else \
                (default_init if default_init is not None else
                 _initializer.Uniform())
        if isinstance(init, str):
            init = _initializer.registry.create(init)
        if ctx is None:
            ctx = [current_context()]
        if isinstance(ctx, Context):
            ctx = [ctx]
        if self.shape is None or any(s <= 0 for s in self.shape):
            if not self.allow_deferred_init:
                raise ValueError(
                    "Cannot initialize parameter %s with unknown shape %s"
                    % (self.name, self.shape))
            self._deferred_init = (init, list(ctx), specific)
            return
        self._finish_init(init, ctx, specific)

    def _finish_init(self, init, ctx_list, specific=False):
        from .. import initializer as _initializer

        data = np.zeros(self.shape, dtype=self.dtype)
        init_desc = _initializer.InitDesc(
            self.name, {"__init__": init} if specific else None)
        data = init(init_desc, data)
        self._data = {c: nd.array(data, ctx=c) for c in ctx_list}
        self._deferred_init = None
        if self._grad_req != "null":
            self._init_grad()

    def _init_grad(self):
        self._grad = {c: nd.zeros(self.shape, ctx=c, dtype=self.dtype)
                      for c in self._data}
        for c, d in self._data.items():
            from .. import autograd

            autograd.mark_variables([d], [self._grad[c]],
                                    grad_reqs=self._grad_req)

    def _finish_deferred_init(self, shape):
        if self._deferred_init is None:
            return
        if self.shape is None:
            self.shape = tuple(shape)
        else:
            self.shape = tuple(s if s > 0 else n
                               for s, n in zip(self.shape, shape))
        init, ctx, specific = self._deferred_init
        self._finish_init(init, ctx, specific)

    # -- access ---------------------------------------------------------------

    def data(self, ctx=None):
        ov = tracing_overrides()
        if ov is not None and self in ov.mapping:
            return ov.mapping[self]
        self._check_initialized(ctx)
        if ctx is None:
            return next(iter(self._data.values()))
        ctx = Context(ctx)
        if ctx not in self._data:
            raise RuntimeError(
                "Parameter '%s' was not initialized on context %s" % (self.name, ctx))
        return self._data[ctx]

    def _bind_live(self, source):
        """`source()` returns the value where it lives now, or None. A
        `parallel.TrainStep` owns the values of its net while it trains
        (this parameter's own array is stale until `sync_to_net`) and
        binds itself here, so that the one reader of non-gradient state
        outside a step (`nn.SparseMoE`'s telemetry) finds what the last
        step wrote."""
        self._live = source

    def _live_data(self):
        value = self._live() if self._live is not None else None
        return self.data() if value is None else NDArray(value)

    def list_data(self):
        self._check_initialized()
        return list(self._data.values())

    def list_ctx(self):
        self._check_initialized()
        return list(self._data)

    def grad(self, ctx=None):
        if self._grad is None:
            raise RuntimeError(
                "Cannot get gradient array for parameter '%s' because "
                "grad_req='null'" % self.name)
        if ctx is None:
            return next(iter(self._grad.values()))
        return self._grad[Context(ctx)]

    def list_grad(self):
        return list(self._grad.values()) if self._grad else []

    def set_data(self, data):
        """Set value on all contexts; during a trace this records an
        aux-state write (committed by CachedOp after execution)."""
        ov = tracing_overrides()
        if ov is not None and self in ov.mapping and ov.writes is not None:
            ov.writes[self] = data
            return
        if self._data is None:
            if self._deferred_init is not None:
                self.shape = tuple(data.shape)
                init, ctx, specific = self._deferred_init
                self._finish_init(init, ctx, specific)
            else:
                raise RuntimeError("Parameter '%s' not initialized" % self.name)
        for c, d in self._data.items():
            src = data.as_in_context(c) if isinstance(data, NDArray) else \
                nd.array(data, ctx=c)
            d._set_data(src._data)

    def zero_grad(self):
        if self._grad is None:
            return
        for g in self._grad.values():
            g._set_data(nd.zeros_like(g)._data)

    def reset_ctx(self, ctx):
        if isinstance(ctx, Context):
            ctx = [ctx]
        if self._data is not None:
            data = next(iter(self._data.values()))
            self._data = {c: data.as_in_context(c).copy() if c not in self._data
                          else self._data[c] for c in ctx}
            self._data = {c: v for c, v in self._data.items() if c in ctx}
            if self._grad_req != "null":
                self._init_grad()

    def cast(self, dtype):
        self.dtype = np.dtype(dtype)
        if self._data is not None:
            self._data = {c: d.astype(dtype) for c, d in self._data.items()}
            if self._grad_req != "null":
                self._init_grad()

    def var(self):
        from ..symbol import symbol as _sym

        return _sym.var(self.name, shape=self.shape, dtype=self.dtype)

    def __repr__(self):
        return "Parameter %s (shape=%s, dtype=%s)" % (self.name, self.shape,
                                                      np.dtype(self.dtype).name)


class Constant(Parameter):
    """Non-trainable parameter (reference: gluon/parameter.py:Constant)."""

    def __init__(self, name, value):
        value = np.asarray(value.asnumpy() if isinstance(value, NDArray) else value)
        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=value.dtype)
        self._value = value
        from .. import initializer as _initializer

        self.init = _initializer.Constant(value)


class ParameterDict:
    """Ordered name→Parameter mapping with prefix scoping
    (reference: gluon/parameter.py:ParameterDict)."""

    def __init__(self, prefix="", shared=None):
        self._prefix = prefix
        self._params = {}
        self._shared = shared

    @property
    def prefix(self):
        return self._prefix

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    def __iter__(self):
        return iter(self._params)

    def __getitem__(self, key):
        return self._params[key]

    def __contains__(self, key):
        return key in self._params

    def __len__(self):
        return len(self._params)

    def get(self, name, **kwargs):
        """Get or create a parameter named prefix+name."""
        full = self._prefix + name
        if self._shared is not None and full in self._shared:
            param = self._shared[full]
        elif full in self._params:
            param = self._params[full]
        else:
            param = Parameter(full, **kwargs)
        self._params[full] = param
        return param

    def get_constant(self, name, value=None):
        full = self._prefix + name
        if full not in self._params:
            self._params[full] = Constant(full, value)
        return self._params[full]

    def update(self, other):
        for k, v in other.items():
            self._params[k] = v

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        for p in self._params.values():
            p.initialize(init=None, ctx=ctx, default_init=init,
                         force_reinit=force_reinit)

    def zero_grad(self):
        for p in self._params.values():
            p.zero_grad()

    def reset_ctx(self, ctx):
        for p in self._params.values():
            p.reset_ctx(ctx)

    def setattr(self, name, value):
        for p in self._params.values():
            setattr(p, name, value)

    def save(self, fname, strip_prefix=""):
        arg = {}
        for name, p in self._params.items():
            if strip_prefix and name.startswith(strip_prefix):
                name = name[len(strip_prefix):]
            arg[name] = p.data().as_in_context(cpu())
        nd.save(fname, arg)

    def load(self, fname, ctx=None, allow_missing=False,
             ignore_extra=False, restore_prefix=""):
        loaded = nd.load(fname)
        if not isinstance(loaded, dict):
            raise ValueError("%s does not contain a parameter dict" % fname)
        if restore_prefix:
            loaded = {restore_prefix + k: v for k, v in loaded.items()}
        for name, p in self._params.items():
            if name not in loaded:
                if not allow_missing:
                    raise ValueError("Parameter %s missing in file %s"
                                     % (name, fname))
                continue
            if p.shape is None or p._data is None:
                p.shape = loaded[name].shape
                p.initialize(ctx=ctx)
            p.set_data(loaded[name])
        if not ignore_extra:
            extra = set(loaded) - set(self._params)
            if extra:
                raise ValueError("File %s has extra parameters %s" % (fname, extra))

    def __repr__(self):
        return "ParameterDict(%s)" % ", ".join(self._params)
