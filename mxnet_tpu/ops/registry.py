"""NNVM-style operator registry, TPU-native.

Reference: the NNVM op registry (`NNVM_REGISTER_OP` with FCompute<cpu/gpu>,
FGradient, FInferShape — include/mxnet/op_attr_types.h:115-283) plus the
per-shape cuDNN autotune registry (src/operator/nn/cudnn/cudnn_algoreg-inl.h).

TPU rebuild: an operator's FCompute is a pure JAX function
``fn(*arrays, **attrs) -> array | tuple``. Dispatch compiles it through a
per-(op, attrs) `jax.jit` wrapper; XLA then caches one executable per
input shape/dtype signature — the cudnn_algoreg pattern generalized to
whole-op compilation. FGradient comes for free from `jax.vjp` recorded on
the autograd tape, replacing hand-written backward kernels.

Inside a `hybridize()`/`bind()` trace the dispatcher detects JAX tracers
and inlines `fn` directly, so a whole Gluon block or Symbol graph fuses
into ONE XLA executable (the CachedOp seam, reference
src/imperative/cached_op.cc).
"""
from __future__ import annotations

import functools
from typing import Callable

import numpy as np

__all__ = ["Operator", "register", "get", "list_all_ops", "invoke", "OP_REGISTRY"]

OP_REGISTRY: dict[str, "Operator"] = {}

# Executable launches since import through two seams: invoke_raw's
# non-inlined path (an op called while nothing records) and the
# fused-update path's coalesced launches (fused_update._dispatch).
# NOT counted: a forward launched while recording
# (autograd._record_op), the vjp launches of backward(), raw jnp calls
# (backward's per-leaf astype among them), and traced-inline calls,
# which fuse into an enclosing executable instead of launching one.
# Read through test_utils.count_dispatches().
DISPATCHES = [0]


def named_fn(fn, name):
    """`fn` behind a function called `name`: what is handed to
    ``jax.jit`` at a framework seam, so that the executable is
    ``jit_<name>`` in the device trace, in JAX's compile events and in
    the compile log, and not the name of an inner closure. The name
    enters the module text and so the persistent-cache key: it must be
    the same in every process (no counter, no id)."""
    def call(*args, **kwargs):
        return fn(*args, **kwargs)

    call.__name__ = call.__qualname__ = name
    return call


def _freeze(value):
    """Make op attrs hashable so they can key the executable cache."""
    if isinstance(value, (list, tuple)):
        return tuple(_freeze(v) for v in value)
    if isinstance(value, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in value.items()))
    if isinstance(value, np.ndarray):
        return (value.shape, str(value.dtype), value.tobytes())
    return value


class Operator:
    """A registered operator.

    Parameters
    ----------
    name : canonical op name (`mx.nd.<name>` / `mx.sym.<name>`).
    fn : pure function of jax arrays + keyword attrs.
    differentiable : whether autograd may record a vjp for it.
    num_inputs : fixed arity or None for variadic.
    aliases : extra registry names (reference keeps legacy aliases).
    """

    def __init__(self, name: str, fn: Callable, *, differentiable=True,
                 num_inputs=None, aliases=(), needs_rng=False,
                 train_aware=False):
        self.name = name
        self.fn = fn
        self.differentiable = differentiable
        self.num_inputs = num_inputs
        self.aliases = tuple(aliases)
        self.needs_rng = needs_rng
        self.train_aware = train_aware
        # Names of this op's forward and vjp executables (named_fn); a
        # CachedOp, whose `name` holds a per-process counter, overrides
        # both.
        self.fwd_name = "mx_op_" + name
        self.vjp_name = "mx_vjp_" + name
        self._jit_cache: dict = {}
        # attrs_key -> True when the trace under those attrs consumed no
        # randomness (set by CachedOp.pure). Such calls reuse one cached
        # constant key instead of deriving + uploading a fresh one —
        # key construction otherwise dominates dispatch overhead
        # (tools/dispatch_bench.py).
        self.rng_static: dict = {}

    def bound_fn(self, attrs, named=()):
        """Return a positional-arrays closure: trailing `named` inputs are
        bound by keyword (array-valued op kwargs like softmax's `length`)."""
        fn = self.fn
        if not named and not attrs:
            return fn
        n_named = len(named)

        def call(*arrays):
            pos = arrays[:len(arrays) - n_named] if n_named else arrays
            kw = dict(zip(named, arrays[len(arrays) - n_named:])) if n_named else {}
            return fn(*pos, **kw, **attrs)

        return call

    def jitted(self, attrs_key, attrs, named=()):
        """Per-(op, attrs) compiled entry; XLA adds per-shape caching."""
        key = (attrs_key, named)
        hit = self._jit_cache.get(key)
        if hit is None:
            fn = named_fn(self.bound_fn(attrs, named), self.fwd_name)
            import jax

            hit = jax.jit(fn)
            self._jit_cache[key] = hit
        return hit

    def __repr__(self):
        return "Operator(%s)" % self.name


def register(name, *, differentiable=True, num_inputs=None, aliases=(),
             needs_rng=False, train_aware=False):
    """Decorator: register a JAX FCompute under `name`.

    RNG ops (`needs_rng=True`) take a PRNG key as their FIRST positional
    parameter; dispatch supplies a fresh counter-derived key per call so
    the compiled executable is reused while randomness varies
    (mxnet_tpu/random.py)."""

    def deco(fn):
        op = Operator(name, fn, differentiable=differentiable,
                      num_inputs=num_inputs, aliases=aliases,
                      needs_rng=needs_rng, train_aware=train_aware)
        OP_REGISTRY[name] = op
        for a in aliases:
            OP_REGISTRY[a] = op
        return fn

    return deco


def get(name: str) -> Operator:
    try:
        return OP_REGISTRY[name]
    except KeyError:
        raise AttributeError("operator %r is not registered" % name) from None


def list_all_ops():
    """Reference: MXListAllOpNames (src/c_api/c_api_symbolic.cc)."""
    return sorted(OP_REGISTRY)


def _is_traced(arrays) -> bool:
    import jax.core as jcore

    return any(isinstance(a, jcore.Tracer) for a in arrays)


def prep_inputs(op: Operator, arrays, attrs_key=None):
    """Prepend a fresh PRNG key for RNG ops (key is a runtime input, so
    one executable serves every call with fresh randomness). Ops whose
    trace provably consumed no randomness under these attrs get a cached
    constant key instead (the executable ignores it anyway)."""
    if op.needs_rng:
        from .. import random as _random

        if attrs_key is not None and op.rng_static.get(attrs_key):
            return [_random.static_key()] + list(arrays)
        return [_random.next_key()] + list(arrays)
    return arrays


_profiler_mod = None


def invoke_raw(op: Operator, arrays, attrs, named=()):
    """Run `op` on raw jax arrays, choosing traced-inline vs jitted path.
    Trailing `named` entries of `arrays` are bound by keyword."""
    global _profiler_mod
    attrs_key = _freeze(attrs)
    arrays = prep_inputs(op, arrays, attrs_key)
    if _is_traced(arrays):
        # Inside an enclosing jit/vjp/vmap trace: inline so the whole
        # surrounding graph compiles as one executable.
        return op.bound_fn(attrs, named)(*arrays)
    DISPATCHES[0] += 1
    if _profiler_mod is None:
        from .. import profiler as _profiler_mod_  # lazy, once

        _profiler_mod = _profiler_mod_
    if _profiler_mod.is_recording():
        # Profiling: record the dispatch span (reference ExecuteOprBlock
        # wraps each op in ProfileOperator, threaded_engine.h:338-347).
        import time as _time

        t0 = _time.perf_counter()
        out = op.jitted(attrs_key, attrs, named)(*arrays)
        _profiler_mod.record_op_span(op.name, _time.perf_counter() - t0)
        return out
    return op.jitted(attrs_key, attrs, named)(*arrays)
