"""Model registry for the multi-model inference gateway.

A :class:`ModelSpec` is the serving-side description of ONE model:
how to build its backend (a pure ``fn(*params, data)`` or a
``model.save_checkpoint`` artifact), its bucket ladder, its fair-share
weight, its deadline classes, an optional latency SLO, and an optional
execution variant — ``quantize=`` (int8 weight-only or bf16 compute,
riding :mod:`..ops.quantization_ops`) or ``mesh_axes=`` (bucket
executables compiled over a ``jax.sharding.Mesh``, for models too large
for one chip).

The :class:`ModelRegistry` owns the name -> spec table and the
**generation counter** per model: every hot reload
(:func:`..serving.reload.hot_swap`) bumps the model's generation
atomically with the executable-cache swap, and every gateway response
is tagged with the generation that produced it — so "no in-flight
request ever mixes weights across versions" is checkable per response.

Registry format (``describe()``)::

    {"mnist": {"kind": "fn", "item_shape": [784], "dtype": "float32",
               "buckets": [1, 2, 4, 8], "weight": 2.0,
               "deadline_classes": [["interactive", 50.0],
                                    ["batch", null]],
               "quantize": "int8", "mesh_axes": null,
               "slo": [0.99, 0.25], "generation": 3}}
"""
from __future__ import annotations

import threading

import numpy as np

from .. import ndarray as nd
from ..ndarray.ndarray import NDArray
from .buckets import BucketPolicy
from .engine import _CheckpointModel, _FnModel

__all__ = ["ModelSpec", "ModelRegistry", "QuantizedFnModel",
           "MeshShardedModel"]

_QUANT_MODES = (None, "int8", "bf16")


class ModelSpec:
    """Declarative description of one served model.

    Parameters
    ----------
    name : str
        Registry key; also the ``model=`` label on every
        ``mx_serving_gateway_*`` series.
    fn : callable(*params, data), optional
        Pure eval-time forward. Mutually exclusive with ``checkpoint``.
    params : sequence of NDArray/ndarray
        Leading arguments bound to ``fn`` (version 1's weights; hot
        reloads supply later versions).
    checkpoint : str, optional
        ``model.save_checkpoint`` prefix; with ``epoch`` selects the
        served version. Mutually exclusive with ``fn``.
    epoch : int
        Checkpoint epoch (default 0).
    item_shape : tuple
        Per-example shape WITHOUT the batch dim.
    dtype : input dtype (default float32).
    max_batch, buckets : the bucket ladder (:class:`BucketPolicy`).
    weight : float
        Fair-share weight for the gateway's weighted round-robin —
        relative device-time share under contention (default 1).
    deadline_classes : sequence of (class_name, timeout_ms), optional
        Ordered HIGHEST priority first. A request names its class at
        ``submit()`` and inherits the class deadline unless it passes
        an explicit ``timeout_ms``; when the model's SLO burn rate
        exceeds budget the gateway sheds the LOWEST (last) class at
        admission. Default: one class ``("default", default_timeout_ms)``.
    default_timeout_ms : float, optional
        Deadline of the implicit single class (None = never expires).
    quantize : None | "int8" | "bf16"
        fn-model execution variant: ``int8`` = weight-only per-tensor
        symmetric quantization (matrices stored int8, dequantized
        in-graph via ``ops/quantization_ops``); ``bf16`` = params and
        compute in bfloat16, outputs cast back to fp32.
    mesh_axes : dict, optional
        fn-model execution variant: compile every bucket executable
        over ``parallel.make_mesh(mesh_axes)`` with params sharded by
        the Megatron-ish default rule (batch and outputs replicated) —
        the model-too-large-for-one-chip path. Incompatible with
        ``quantize`` and ``checkpoint``.
    slo : (objective, threshold_s), optional
        Latency SLO over this model's gateway latency series, e.g.
        ``(0.99, 0.250)``; drives SLO-coupled shedding.
    decode : DecodeConfig or dict, optional
        Marks a CONTINUOUS-BATCHING sequence model
        (:mod:`.continuous`): the decode step/prefill functions and the
        paged-state shape. Mutually exclusive with ``fn``/``checkpoint``
        (the step IS the model); ``max_batch``/``buckets`` become the
        decode slot ladder and ``item_shape`` is not required. Requests
        route through ``gateway.submit_sequence``.
    max_delay_ms : float, optional
        Per-model micro-batching window override: this model's queue
        flushes after at most this long even when the gateway-wide
        window (``ModelGateway(max_delay_ms=)``) is longer — the
        latency-class escape hatch. Default None = gateway window.
    queue_share : float in (0, 1], optional
        Cap on this model's share of the gateway admission pool: it may
        queue at most ``ceil(queue_share * max_queue)`` requests, so one
        hot model cannot fill the whole pool before fair-share kicks
        in. Default None = bounded only by the pool.
    data_name : checkpoint models' data input name (default "data").
    ctx : device context for backend calls (default device when None).
    """

    def __init__(self, name, *, fn=None, params=(), checkpoint=None,
                 epoch=0, item_shape=None, dtype="float32", max_batch=32,
                 buckets=None, weight=1.0, deadline_classes=None,
                 default_timeout_ms=None, quantize=None, mesh_axes=None,
                 slo=None, decode=None, max_delay_ms=None,
                 queue_share=None, data_name="data", ctx=None):
        if decode is not None:
            if fn is not None or checkpoint is not None:
                raise ValueError("a decode model's step function rides "
                                 "decode=; fn=/checkpoint= must be None")
            if quantize or mesh_axes is not None:
                raise ValueError("decode= is incompatible with "
                                 "quantize=/mesh_axes= (wrap the step "
                                 "function instead)")
            from .continuous import DecodeConfig

            if isinstance(decode, dict):
                decode = DecodeConfig(**decode)
            if not isinstance(decode, DecodeConfig):
                raise ValueError("decode= must be a DecodeConfig or its "
                                 "kwargs dict, got %r" % (decode,))
        else:
            if (fn is None) == (checkpoint is None):
                raise ValueError("pass exactly one of fn= or checkpoint=")
            if item_shape is None:
                raise ValueError("item_shape is required for batch "
                                 "(non-decode) models")
        if quantize not in _QUANT_MODES:
            raise ValueError("quantize must be one of %r, got %r"
                             % (_QUANT_MODES, quantize))
        if quantize and checkpoint is not None:
            raise ValueError("quantize= needs an fn model (checkpoint "
                             "symbols keep their trained dtypes)")
        if mesh_axes is not None and (checkpoint is not None or quantize):
            raise ValueError("mesh_axes= needs a plain fn model")
        self.name = str(name)
        self.fn = fn
        self.params = list(params)
        self.checkpoint = checkpoint
        self.epoch = int(epoch)
        self.decode = decode
        self.item_shape = tuple(item_shape) if item_shape is not None \
            else None
        self.dtype = np.dtype(dtype)
        self.policy = BucketPolicy(max_batch=max_batch, buckets=buckets)
        self.max_delay_ms = None if max_delay_ms is None \
            else float(max_delay_ms)
        if self.max_delay_ms is not None and self.max_delay_ms < 0:
            raise ValueError("max_delay_ms must be >= 0, got %r"
                             % (max_delay_ms,))
        self.queue_share = None if queue_share is None \
            else float(queue_share)
        if self.queue_share is not None \
                and not 0.0 < self.queue_share <= 1.0:
            raise ValueError("queue_share must be in (0, 1], got %r"
                             % (queue_share,))
        self.weight = float(weight)
        if self.weight <= 0:
            raise ValueError("weight must be > 0, got %r" % (weight,))
        if deadline_classes is None:
            deadline_classes = (("default", default_timeout_ms),)
        items = list(deadline_classes.items()
                     if isinstance(deadline_classes, dict)
                     else deadline_classes)
        if not items:
            raise ValueError("deadline_classes must not be empty")
        self.classes = tuple((str(c), None if t is None else float(t))
                             for c, t in items)
        self.class_timeouts = dict(self.classes)
        if len(self.class_timeouts) != len(self.classes):
            raise ValueError("duplicate deadline class names: %r"
                             % (self.classes,))
        self.default_class = self.classes[0][0]
        self.lowest_class = self.classes[-1][0]
        self.quantize = quantize
        self.mesh_axes = dict(mesh_axes) if mesh_axes is not None else None
        self.slo = (float(slo[0]), float(slo[1])) if slo is not None \
            else None
        self.data_name = data_name
        self.ctx = ctx

    # -- backend construction --------------------------------------------------

    def build_backend(self, params=None, checkpoint=None, epoch=None):
        """Build a fresh backend for this spec — version 1 at
        registration, or a NEW version for a hot reload (``params=`` for
        fn models, ``checkpoint=``/``epoch=`` for checkpoint models).
        The returned object is ``__call__(batch NDArray) -> NDArray``
        (or tuple) with a ``compile_count`` property, and owns its own
        executable cache — swapping backends swaps every executable.
        Decode specs build a :class:`.continuous._DecodeBackend` (the
        paged state buffers plus step/prefill executables) instead."""
        if self.decode is not None:
            if checkpoint is not None or epoch is not None:
                raise ValueError("model %r is a decode model: reload it "
                                 "with params=, not checkpoint="
                                 % self.name)
            from .continuous import _DecodeBackend

            pvals = self.params if params is None else list(params)
            return _DecodeBackend(self.decode, pvals, name=self.name,
                                  policy=self.policy, ctx=self.ctx)
        if self.fn is not None:
            if checkpoint is not None or epoch is not None:
                raise ValueError("model %r is an fn model: reload it "
                                 "with params=, not checkpoint="
                                 % self.name)
            pvals = self.params if params is None else list(params)
            if self.mesh_axes is not None:
                return MeshShardedModel(self.fn, pvals, self.mesh_axes)
            if self.quantize:
                return QuantizedFnModel(self.fn, pvals, self.quantize)
            return _FnModel(self.fn, pvals)
        if params is not None:
            raise ValueError("model %r is a checkpoint model: reload it "
                             "with checkpoint=/epoch=, not params="
                             % self.name)
        from .. import model as _model

        prefix = checkpoint if checkpoint is not None else self.checkpoint
        ep = self.epoch if epoch is None else int(epoch)
        symbol, arg_params, aux_params = _model.load_checkpoint(prefix, ep)
        return _CheckpointModel(symbol, arg_params, aux_params,
                                data_name=self.data_name, ctx=self.ctx)

    def describe(self):
        return {
            "kind": "decode" if self.decode is not None
            else "fn" if self.fn is not None else "checkpoint",
            "item_shape": list(self.item_shape)
            if self.item_shape is not None else None,
            "dtype": str(self.dtype),
            "buckets": list(self.policy.buckets),
            "weight": self.weight,
            "deadline_classes": [[c, t] for c, t in self.classes],
            "quantize": self.quantize,
            "mesh_axes": self.mesh_axes,
            "slo": list(self.slo) if self.slo else None,
            "decode": self.decode.describe()
            if self.decode is not None else None,
            "max_delay_ms": self.max_delay_ms,
            "queue_share": self.queue_share,
        }


class ModelRegistry:
    """Thread-safe name -> (spec, generation) table.

    The generation counter is the version authority for hot reloads:
    :meth:`bump` is called under the gateway's swap lock, so a response
    tagged generation N was produced by exactly the N-th committed
    version of that model's weights."""

    def __init__(self):
        self._lock = threading.Lock()
        self._specs = {}
        self._gens = {}

    def register(self, spec):
        with self._lock:
            if spec.name in self._specs:
                raise ValueError("model %r already registered" % spec.name)
            self._specs[spec.name] = spec
            self._gens[spec.name] = 1
        return spec

    def unregister(self, name):
        with self._lock:
            spec = self._specs.pop(name, None)
            self._gens.pop(name, None)
        if spec is None:
            raise KeyError("model %r is not registered" % (name,))
        return spec

    def spec(self, name):
        with self._lock:
            spec = self._specs.get(name)
        if spec is None:
            raise KeyError("model %r is not registered (have: %s)"
                           % (name, sorted(self._specs) or "none"))
        return spec

    def names(self):
        with self._lock:
            return sorted(self._specs)

    def generation(self, name):
        with self._lock:
            return self._gens[name]

    def bump(self, name):
        """Commit a new version: returns the NEW generation."""
        with self._lock:
            self._gens[name] += 1
            return self._gens[name]

    def describe(self):
        """JSON-able registry view (the documented registry format)."""
        with self._lock:
            items = [(n, s, self._gens[n])
                     for n, s in sorted(self._specs.items())]
        return {n: dict(s.describe(), generation=g) for n, s, g in items}


# -- execution variants --------------------------------------------------------

class QuantizedFnModel:
    """Weight-quantized fn backend on the same CachedOp bucket core.

    ``int8``: every floating matrix param (ndim >= 2) is quantized ONCE
    at build with a per-tensor symmetric range (the
    ``ops/quantization_ops`` int8 pipeline) and stored int8; the bucket
    executables hold int8 weights and dequantize in-graph, where XLA
    fuses the rescale into the consumer — the reference's
    quantized-inference memory shape. Vectors/scalars (biases, BN
    stats) stay fp32. ``bf16``: float params cast to bfloat16 once,
    inputs cast in-graph, outputs cast back to fp32."""

    def __init__(self, fn, params, mode):
        from ..cached_op import CachedOp

        if mode not in ("int8", "bf16"):
            raise ValueError("quantize mode must be int8|bf16, got %r"
                             % (mode,))
        import jax.numpy as jnp

        self.mode = mode
        params = [p if isinstance(p, NDArray) else nd.array(p)
                  for p in params]
        inner = fn

        def _floating(dtype):
            # jnp's lattice, not numpy's: bfloat16 (an ml_dtypes
            # extension type) is floating here and not under numpy.
            return jnp.issubdtype(dtype, jnp.floating)

        if mode == "bf16":
            flat = [p.astype("bfloat16") if _floating(p.dtype) else p
                    for p in params]
            n = len(flat)

            def wrapped(*args):
                ps, x = args[:n], args[n]
                out = inner(*ps, x.astype("bfloat16"))
                outs = out if isinstance(out, (list, tuple)) else (out,)
                outs = tuple(o.astype("float32")
                             if _floating(o.dtype) else o for o in outs)
                return outs if len(outs) > 1 else outs[0]
        else:
            entries, flat = [], []
            for p in params:
                if _floating(p.dtype) and p.ndim >= 2:
                    amax = float(np.abs(p.asnumpy()).max()) or 1.0
                    mn = nd.array(np.array([-amax], np.float32))
                    mx = nd.array(np.array([amax], np.float32))
                    q, _, _ = nd._contrib_quantize(p, mn, mx)
                    entries.append(("q", len(flat)))
                    flat.extend([q, mn, mx])
                else:
                    entries.append(("raw", len(flat)))
                    flat.append(p)
            n = len(flat)

            def wrapped(*args):
                stored, x = args[:n], args[n]
                ps = []
                for kind, i in entries:
                    if kind == "q":
                        ps.append(nd._contrib_dequantize(
                            stored[i], stored[i + 1], stored[i + 2]))
                    else:
                        ps.append(stored[i])
                return inner(*ps, x)

        self._params = flat
        self._cached = CachedOp(wrapped, num_params=len(flat))

    def __call__(self, batch):
        return self._cached.inference(*(self._params + [batch]))

    @property
    def compile_count(self):
        return self._cached.num_traces


class MeshShardedModel:
    """fn backend whose bucket executables are compiled over a
    ``jax.sharding.Mesh`` — params laid out sharded (the Megatron-ish
    ``parallel.mesh.shard_params`` rule), batch and outputs replicated,
    one ``jax.jit`` executable per bucket shape.

    Multi-process contract (a mesh spanning processes): every process
    must call the backend in LOCKSTEP with identical data — the device
    call is an SPMD collective, exactly the `TrainStep` discipline. The
    2-process acceptance test (tests/gateway_mesh_prog.py) drives it
    with a deterministic request schedule."""

    def __init__(self, fn, params, mesh_axes, param_rule=None):
        import jax

        from .. import autograd
        from .. import random as _random
        from ..parallel.mesh import make_mesh, replicate, shard_params

        params = [p if isinstance(p, NDArray) else nd.array(p)
                  for p in params]
        axes = dict(mesh_axes)
        devices = None
        sizes = [int(s) for s in axes.values()]
        if -1 not in sizes:
            # The mesh is the model's device footprint, not the
            # process's: {"tp": 2} serves over the first 2 devices and
            # leaves the rest for other models. A -1 axis means "all".
            need = int(np.prod(sizes)) if sizes else 1
            have = jax.devices()
            if need > len(have):
                raise ValueError(
                    "mesh_axes %r needs %d devices, have %d"
                    % (axes, need, len(have)))
            devices = have[:need]
        self.mesh = make_mesh(axes, devices=devices)
        self._multiproc = any(d.process_index != jax.process_index()
                              for d in self.mesh.devices.flat)
        named = {"p%d" % i: tuple(p.shape) for i, p in enumerate(params)}
        shardings = shard_params(self.mesh, named, rule=param_rule)
        self.param_shardings = [shardings["p%d" % i]
                                for i in range(len(params))]
        self._param_vals = [
            self._place(p.asnumpy(), s)
            for p, s in zip(params, self.param_shardings)]
        self._repl = replicate(self.mesh)
        self._key = self._place(np.zeros((2,), np.uint32), self._repl)
        n = len(params)

        def pure(key, *arrays):
            ps, x = arrays[:n], arrays[n]
            with autograd.pause(train_mode=False), \
                    _random.trace_key_scope(key):
                out = fn(*([NDArray(p) for p in ps] + [NDArray(x)]))
            if isinstance(out, (list, tuple)):
                return tuple(o._data for o in out)
            return out._data

        self._exec = jax.jit(
            pure, in_shardings=tuple([self._repl] + self.param_shardings
                                     + [self._repl]),
            out_shardings=self._repl)
        self._shapes = set()

    def _place(self, host, sharding):
        """Lay a host array out on the (possibly cross-process) mesh —
        the TrainStep._place discipline: multi-process ranks each hold
        the full host value and fill only their addressable shards."""
        import jax

        host = np.asarray(host)
        if self._multiproc:
            return jax.make_array_from_callback(host.shape, sharding,
                                                lambda idx: host[idx])
        return jax.device_put(host, sharding)

    def __call__(self, batch):
        arr = batch._data if isinstance(batch, NDArray) else batch
        xg = self._place(np.asarray(arr), self._repl)
        self._shapes.add(tuple(xg.shape))
        raw = self._exec(self._key, *(self._param_vals + [xg]))
        if isinstance(raw, tuple):
            return tuple(NDArray(o) for o in raw)
        return NDArray(raw)

    @property
    def compile_count(self):
        # one executable per observed batch shape (the bucket contract)
        return len(self._shapes)
