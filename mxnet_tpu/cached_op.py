"""CachedOp — the hybrid JIT unit.

Reference: src/imperative/cached_op.cc/.h (Gluon `hybridize()` backend:
caches the traced NNVM graph, static_alloc pre-plans memory, bulking
fuses segments; SURVEY.md §3.3).

TPU rebuild — this is THE seam where the design diverges from the
reference on purpose: instead of replaying a cached graph op-by-op
through the engine, the entire traced computation compiles to ONE XLA
executable per input-shape signature (jax.jit). XLA buffer assignment
replaces NNVM PlanMemory; fusion replaces segment bulking; retracing on
a new shape replaces bucketed re-binds (per-signature executable cache =
the cudnn_algoreg pattern at whole-graph scope).

Under `autograd.record()`, a CachedOp call records a single tape node;
its backward is a cached jitted vjp of the whole graph, rematerializing
the forward inside the backward executable (`jax.checkpoint` semantics —
the TPU-friendly compute/memory trade, cf. MXNET_BACKWARD_DO_MIRROR).

Randomness inside the graph (Dropout) is threaded as a PRNG-key input,
so one executable serves every call with fresh masks.
"""
from __future__ import annotations

from . import autograd
from . import random as _random
from .ops.registry import Operator, _freeze
from .ndarray.ndarray import NDArray, _wrap_outputs
from .telemetry import metrics as _tm
from .telemetry import trace as _trace

__all__ = ["CachedOp"]

# Executable-cache fills per op — a climbing rate after warmup is a
# recompile storm (telemetry.StepMonitor.attach watches the same event
# through the on_trace hook).
_compiles_total = _tm.REGISTRY.counter(
    "mx_cachedop_compiles_total",
    "CachedOp trace/compile events (one per shape-signature "
    "executable-cache fill)", labels=("op",))


class CachedOp:
    """Compile a python function over NDArrays into a cached XLA executable.

    Parameters
    ----------
    fn : callable(*args) -> NDArray | list[NDArray]
        Pure function using `nd` ops / NDArray methods. Called with
        tracer-backed NDArrays during compilation.
    num_params : int
        How many leading arguments of `fn` are parameters (their
        gradients flow to `.grad` buffers on backward).
    static_alloc, static_shape, inline_limit, forward_bulk_size,
    backward_bulk_size : accepted for reference API parity
        (CachedOpConfig, cached_op.h:32-56). XLA owns memory planning and
        fusion, so they are advisory here.
    """

    _counter = [0]

    def __init__(self, fn, num_params=0, static_alloc=False, static_shape=False,
                 **flags):
        self._fn = fn
        self._num_params = num_params
        self._flags = flags
        # Trace-count hook: `pure` runs once per (shape-signature, attrs)
        # compilation, so num_traces counts executable-cache fills — the
        # serving warmup contract ("one compile per bucket") is asserted
        # against it (tests/test_serving.py).
        self.num_traces = 0
        self.on_trace = None
        CachedOp._counter[0] += 1
        name = "_cached_op_%d" % CachedOp._counter[0]

        cached = self

        def pure(rng_key, *arrays, training=False):
            cached.num_traces += 1
            _compiles_total.labels(op=name).inc()
            if cached.on_trace is not None:
                cached.on_trace(cached)
            params = arrays[:cached._num_params]
            inputs = arrays[cached._num_params:]
            with _trace.span("cached_op::trace", op=name,
                             trace=cached.num_traces), \
                    autograd.pause(train_mode=training):
                with _random.trace_key_scope(rng_key) as scope:
                    nd_params = [NDArray(p) for p in params]
                    nd_inputs = [NDArray(x) for x in inputs]
                    out = cached._fn(*(nd_params + nd_inputs))
            # Trace-time discovery: a graph that drew no keys is
            # deterministic under these attrs — later dispatches skip
            # the per-call key derivation (registry.prep_inputs).
            # Sticky-False: jit retraces per input-shape signature, and
            # a shape-dependent graph may consume randomness for one
            # shape but not another — once ANY trace consumed a key,
            # every dispatch keeps drawing fresh ones.
            skey = _freeze({"training": training})
            cached._op.rng_static[skey] = (
                scope.consumed == 0
                and cached._op.rng_static.get(skey) is not False)
            if isinstance(out, (list, tuple)):
                return tuple(o._data if isinstance(o, NDArray) else o for o in out)
            return out._data if isinstance(out, NDArray) else out

        self._op = Operator(name, pure, needs_rng=True, train_aware=True)
        self._op.fwd_name = "mx_cached_fwd"
        self._op.vjp_name = "mx_cached_vjp"
        # Off-ladder shape canonicalization (recompile elimination):
        # set via pad_to_buckets().
        self._pad_policy = None

    def __call__(self, *args, out=None):
        """Forward (reference: CachedOp::Forward via MXInvokeCachedOp).
        First call per shape signature compiles; later calls reuse the
        executable."""
        attrs = {"training": autograd.is_training()}
        arrays = [x._data if isinstance(x, NDArray) else x for x in args]
        ctx = next((x._ctx for x in args if isinstance(x, NDArray)), None)

        from .ops import registry as _reg

        with _trace.span("cached_op::execute", op=self._op.name):
            if autograd.is_recording():
                raw = autograd._record_op(self._op, list(args), arrays,
                                          attrs)
                result = _wrap_outputs(raw, ctx, out=out)
                autograd._attach_outputs(result)
            else:
                raw = _reg.invoke_raw(self._op, arrays, attrs)
                result = _wrap_outputs(raw, ctx, out=out)
        return result

    def pad_to_buckets(self, policy):
        """Canonicalize off-ladder batch shapes in :meth:`inference`
        onto a bucket ladder (recompile elimination): a request of 5
        rows pads to the 8-row bucket's executable and slices back,
        instead of minting a 5-row trace + compile.

        Contract — the serving contract: the graph must map each input
        row to an output row independently (eval mode already turns
        dropout off and pins BN to running stats, so per-row graphs
        qualify). Outputs that REDUCE over the batch (a mean loss, a
        batch sum) would silently include the padded zero rows, and an
        output whose leading dim is not the batch but happens to equal
        the bucket size would be wrongly sliced — don't enable padding
        on such graphs.

        ``policy``: a ``serving.BucketPolicy``, an explicit bucket list,
        or a max-batch int (powers-of-two ladder). Returns self."""
        from .serving.buckets import BucketPolicy

        if policy is None:
            self._pad_policy = None
        elif isinstance(policy, BucketPolicy):
            self._pad_policy = policy
        elif isinstance(policy, (list, tuple)):
            self._pad_policy = BucketPolicy(buckets=policy)
        else:
            self._pad_policy = BucketPolicy(max_batch=int(policy))
        return self

    def _canonical_rows(self, arrays):
        """(bucket, rows) when inference should pad the leading batch
        dim up the ladder, else None. Shapes above the ladder run
        unpadded (their own signature) — canonicalization must never
        reject work."""
        if self._pad_policy is None:
            return None
        inputs = arrays[self._num_params:]
        rows = next((int(a.shape[0]) for a in inputs
                     if getattr(a, "ndim", 0) >= 1), None)
        if rows is None or rows < 1 or rows > self._pad_policy.max_batch:
            return None
        bucket = self._pad_policy.bucket_for(rows)
        return None if bucket == rows else (bucket, rows)

    def _pad_inputs(self, arrays, bucket, rows):
        """Zero-pad every batch-carrying input (leading dim == rows) up
        to ``bucket``; params and batch-free inputs pass through."""
        import jax.numpy as jnp

        out = list(arrays)
        for i in range(self._num_params, len(arrays)):
            a = arrays[i]
            if getattr(a, "ndim", 0) >= 1 and int(a.shape[0]) == rows:
                pad = jnp.zeros((bucket - rows,) + tuple(a.shape[1:]),
                                a.dtype)
                out[i] = jnp.concatenate([a, pad])
        return out

    def inference(self, *args, out=None):
        """Eval-mode forward that never records on the autograd tape and
        never enables train-mode ops (dropout off, BatchNorm running
        stats) — regardless of any ambient `autograd.record()` scope.

        This is the serving hot path (mxnet_tpu/serving): the reference's
        ``bind(for_training=False)`` contract at CachedOp granularity.
        It shares the per-shape executable cache with eval-mode
        ``__call__`` dispatches. With :meth:`pad_to_buckets` set,
        off-ladder batch sizes canonicalize onto an existing bucket's
        executable (pad up, slice back) instead of tracing anew."""
        arrays = [x._data if isinstance(x, NDArray) else x for x in args]
        ctx = next((x._ctx for x in args if isinstance(x, NDArray)), None)

        from .ops import registry as _reg

        canon = self._canonical_rows(arrays)
        if canon is not None:
            bucket, rows = canon
            arrays = self._pad_inputs(arrays, bucket, rows)
        with _trace.span("cached_op::inference", op=self._op.name):
            raw = _reg.invoke_raw(self._op, arrays, {"training": False})
        if canon is not None:
            # Slice the padded rows back out (batch-dim outputs only —
            # a scalar/aggregate output is returned as computed).
            if isinstance(raw, (list, tuple)):
                raw = type(raw)(
                    o[:rows] if getattr(o, "ndim", 0) >= 1
                    and int(o.shape[0]) == bucket else o for o in raw)
            elif getattr(raw, "ndim", 0) >= 1 and \
                    int(raw.shape[0]) == bucket:
                raw = raw[:rows]
        return _wrap_outputs(raw, ctx, out=out)
