"""Executor — the static-graph runtime.

Reference: include/mxnet/executor.h + src/executor/graph_executor.cc
(GraphExecutor::Init builds fwd+grad graph, PlanMemory, InitCachedOps,
segment bulking; Forward/Backward push cached engine ops; monitor
callback per output :103,1313; Reshape for bucketing :785).

TPU rebuild: `bind` compiles the whole forward graph into ONE jitted
XLA executable, and backward into one vjp executable (built lazily on
first backward). XLA buffer assignment replaces NNVM PlanMemory; there
are no per-op engine pushes to bulk. A new input shape (bucketing)
simply retraces — the per-signature executable cache is jax.jit's.
`group2ctx` model-parallel placement (reference AssignContext,
src/executor/graph_executor.cc:907, with _CrossDeviceCopy inserted at
group boundaries, src/operator/cross_device_copy.cc:31-68) is honored
for real: when the bound symbol carries ``__ctx_group__`` attrs and a
``group2ctx`` map is given, the graph is evaluated eagerly with each
op's inputs transferred (``jax.device_put``) to its group's device —
the transfer *is* the cross-device copy. Unknown groups and absent
devices raise at bind time instead of being silently ignored. Under
SPMD the mesh sharding (mxnet_tpu.parallel) remains the idiomatic
high-performance equivalent; group placement is the parity path.
"""
from __future__ import annotations

import numpy as np

from .base import MXNetError
from . import autograd
from . import random as _random
from .ndarray.ndarray import NDArray, array as nd_array
from .ops import registry as _registry

__all__ = ["Executor"]


class Executor:
    """(reference executor.py:Executor)."""

    def __init__(self, symbol, ctx=None, args=None, args_grad=None,
                 grad_req="write", aux_states=None, group2ctx=None,
                 shared_exec=None):
        from . import env as _env

        backend = _env.get("MXNET_SUBGRAPH_BACKEND")
        if backend:
            # Auto-partition at bind like the reference's
            # MXNET_SUBGRAPH_BACKEND build_subgraph pass; unknown names
            # warn and continue (reference behavior).
            from . import subgraph as _subgraph

            if backend in _subgraph.list_backends():
                symbol = _subgraph.partition(symbol, backend)
            else:
                import logging

                logging.warning(
                    "MXNET_SUBGRAPH_BACKEND=%r is not a registered "
                    "subgraph backend (registered: %s); binding "
                    "without partitioning", backend,
                    _subgraph.list_backends())
        self._symbol = symbol
        self._ctx = ctx
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.output_names = symbol.list_outputs()

        # normalize args to ordered list
        if isinstance(args, dict):
            self.arg_arrays = [args[n] for n in self.arg_names]
        else:
            self.arg_arrays = list(args or [])
        if len(self.arg_arrays) != len(self.arg_names):
            raise MXNetError("bind: expected %d args (%s), got %d"
                             % (len(self.arg_names), self.arg_names,
                                len(self.arg_arrays)))
        self.arg_arrays = [a if isinstance(a, NDArray) else nd_array(a)
                           for a in self.arg_arrays]

        if isinstance(args_grad, dict):
            self.grad_arrays = [args_grad.get(n) for n in self.arg_names]
        elif args_grad is None:
            self.grad_arrays = [None] * len(self.arg_names)
        else:
            self.grad_arrays = list(args_grad)

        if isinstance(grad_req, str):
            self.grad_req = {n: grad_req for n in self.arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self.grad_req = dict(zip(self.arg_names, grad_req))
        else:
            self.grad_req = dict(grad_req or {})

        if isinstance(aux_states, dict):
            self.aux_arrays = [aux_states[n] for n in self.aux_names]
        else:
            self.aux_arrays = list(aux_states or [])
        if len(self.aux_arrays) != len(self.aux_names):
            # allocate aux lazily from inferred shapes when not provided
            if not self.aux_arrays and self.aux_names:
                from . import ndarray as nd

                shapes = {n: tuple(a.shape) for n, a in
                          zip(self.arg_names, self.arg_arrays)}
                _, _, aux_shapes = symbol.infer_shape(**shapes)
                self.aux_arrays = [nd.zeros(s, ctx=ctx) for s in aux_shapes]
            else:
                raise MXNetError("bind: expected %d aux states, got %d"
                                 % (len(self.aux_names), len(self.aux_arrays)))
        self.aux_arrays = [a if isinstance(a, NDArray) else nd_array(a)
                           for a in self.aux_arrays]

        self.outputs = []
        self._monitor_callback = None
        self._fwd_cache = {}  # is_train -> jitted fn
        self._vjp = None
        self._last_fwd = None

        # -- group2ctx model-parallel placement --------------------------
        self._group2ctx = dict(group2ctx or {})
        used_groups = {n._attrs.get("__ctx_group__")
                       for n in symbol._topo()
                       if n._attrs.get("__ctx_group__") is not None}
        self._node_device = {}
        if used_groups and self._group2ctx:
            from .context import Context as _Ctx

            unknown = used_groups - set(self._group2ctx)
            if unknown:
                raise MXNetError(
                    "bind: symbol uses ctx_group(s) %s with no entry in "
                    "group2ctx %s" % (sorted(unknown),
                                      sorted(self._group2ctx)))
            group_dev = {}
            for g, c in self._group2ctx.items():
                c = c if isinstance(c, _Ctx) else _Ctx(c)
                group_dev[g] = c.jax_device  # raises if device absent
            default_dev = (_Ctx(ctx).jax_device if ctx is not None
                           else _Ctx.default_ctx().jax_device)
            for n in symbol._topo():
                if n._op is None:
                    continue
                g = n._attrs.get("__ctx_group__")
                self._node_device[n._uid] = (group_dev[g] if g is not None
                                            else default_dev)

    # -- graph evaluation -----------------------------------------------------

    def _eval_graph(self, arg_map, aux_map, out_syms):
        """Evaluate the symbol DAG on jax values (traced or concrete).
        Aux writes (BatchNorm moving stats in train mode) are collected
        into `aux_writes`."""
        results = {}
        aux_writes = {}

        def value_of(node, out_index):
            key = (node._uid, out_index)
            if key in results:
                return results[key]
            if node._op is None:
                val = arg_map[node._name] if node._name in arg_map \
                    else aux_map[node._name]
                results[key] = val
                return val
            if node._op == "_subgraph":
                # Partitioned fragment (mxnet_tpu/subgraph.py): custom
                # backend fn if provided (e.g. a Pallas kernel), else
                # evaluate the embedded sub-DAG — always semantics-
                # preserving. Fragments may expose several outputs.
                in_vals = [value_of(i, i._out_index or 0)
                           for i in node._inputs]
                fn = getattr(node, "_sub_fn", None)
                if fn is not None:
                    vals = fn(*in_vals)
                else:
                    sub_map = dict(zip(node._sub_arg_names, in_vals))
                    vals, _ = self._eval_graph(sub_map, {},
                                               node._sub_sym.outputs)
                if not isinstance(vals, (list, tuple)):
                    vals = [vals]
                if len(vals) < node._num_outputs:
                    raise ValueError(
                        "_subgraph %r: backend fn returned %d value(s) "
                        "for a %d-output fragment — a consumer of the "
                        "missing output would silently read the wrong "
                        "value" % (node._name, len(vals),
                                   node._num_outputs))
                for oi, v in enumerate(vals):
                    results[(node._uid, oi)] = v
                results[(node._uid, None)] = vals[0]
                return results[key] if key in results else vals[0]
            op_name = node._attrs.get("_op_name", node._op)
            op = _registry.get(op_name)
            in_vals = [value_of(i, i._out_index or 0) for i in node._inputs]
            in_vals = _registry.prep_inputs(op, in_vals)
            dev = self._node_device.get(node._uid)
            if dev is not None:
                # cross-device copy at group boundaries (reference
                # _CrossDeviceCopy): inputs move to this op's device.
                import jax as _jax

                in_vals = [_jax.device_put(v, dev) for v in in_vals]
            attrs = node._clean_attrs()
            if op.train_aware:
                attrs = dict(attrs, training=autograd.is_training())
            raw = op.bound_fn(attrs)(*in_vals)
            outs = raw if isinstance(raw, (tuple, list)) else (raw,)
            # BatchNorm returns (out, new_mean, new_var) in train mode:
            # route updates to aux (reference: aux states mutated by op).
            aux_inputs = [i for i in node._inputs
                          if i._op is None and i._is_aux]
            if aux_inputs and len(outs) == 1 + len(aux_inputs):
                for a, v in zip(aux_inputs, outs[1:]):
                    aux_writes[a._name] = v
                outs = outs[:1]
            for i, o in enumerate(outs):
                results[(node._uid, i)] = o
            results[(node._uid, None)] = outs[0]
            return results[(node._uid, out_index)]

        out_vals = [value_of(s, s._out_index or 0) for s in out_syms]
        return out_vals, aux_writes

    def _forward_fn(self, is_train):
        symbol = self._symbol
        arg_names = self.arg_names
        aux_names = self.aux_names

        def fn(arg_vals, aux_vals, key):
            arg_map = dict(zip(arg_names, arg_vals))
            aux_map = dict(zip(aux_names, aux_vals))
            with autograd.pause(train_mode=is_train), \
                    _random.trace_key_scope(key):
                outs, aux_writes = self._eval_graph(arg_map, aux_map,
                                                    symbol.outputs)
            new_aux = [aux_writes.get(n, aux_map[n]) for n in aux_names]
            return outs, new_aux

        return fn

    def forward(self, is_train=False, **kwargs):
        """(reference executor.py:forward → GraphExecutor::Forward)."""
        import jax

        if kwargs:
            for name, val in kwargs.items():
                if name not in self.arg_names:
                    raise MXNetError("unknown argument %r" % name)
                idx = self.arg_names.index(name)
                self.arg_arrays[idx][:] = val if isinstance(val, NDArray) \
                    else nd_array(val)

        fn = self._fwd_cache.get(is_train)
        if fn is None:
            fn = self._forward_fn(is_train)
            if not self._node_device:
                # One XLA executable for the whole graph. With group
                # placement active the graph instead runs eagerly so
                # each op executes on its group's device (a single
                # executable cannot span explicitly placed devices
                # without a mesh).
                fn = jax.jit(fn)
            self._fwd_cache[is_train] = fn
        arg_vals = [a._data for a in self.arg_arrays]
        aux_vals = [a._data for a in self.aux_arrays]
        key = _random.next_key()
        outs, new_aux = fn(arg_vals, aux_vals, key)
        for arr, val in zip(self.aux_arrays, new_aux):
            arr._data = val
        self.outputs = [NDArray(o, ctx=self._ctx) for o in outs]
        self._last_fwd = (arg_vals, aux_vals, key, is_train)
        if self._monitor_callback is not None:
            for name, out in zip(self.output_names, self.outputs):
                self._monitor_callback(name, out)
        return self.outputs

    def backward(self, out_grads=None, is_train=True):
        """(reference executor.py:backward → GraphExecutor::Backward).
        Gradient graph = jax.vjp of the jitted forward; loss-layer ops
        carry custom vjps that define their own gradient (SoftmaxOutput
        et al.), so calling with no out_grads matches the reference."""
        import jax

        if self._last_fwd is None:
            raise MXNetError("backward called before forward")
        arg_vals, aux_vals, key, fwd_train = self._last_fwd

        grad_names = [n for n in self.arg_names
                      if self.grad_req.get(n, "null") != "null"]
        if not grad_names:
            return
        if self._vjp is None:
            arg_names = self.arg_names

            def loss_like(grad_vals, const_vals, aux_vals_, key_):
                merged = dict(const_vals)
                merged.update(dict(zip(grad_names, grad_vals)))
                full = [merged[n] for n in arg_names]
                outs, _ = self._forward_fn(True)(full, aux_vals_, key_)
                return outs

            def vjp_fn(grad_vals, const_vals, aux_vals_, key_, head_grads):
                _, pullback = jax.vjp(
                    lambda gv: loss_like(gv, const_vals, aux_vals_, key_),
                    grad_vals)
                return pullback(head_grads)[0]

            self._vjp = vjp_fn if self._node_device else jax.jit(vjp_fn)

        import jax.numpy as jnp

        grad_vals = []
        const_vals = {}
        for n, v in zip(self.arg_names, arg_vals):
            if n in grad_names:
                grad_vals.append(v)
            else:
                const_vals[n] = v
        if out_grads is None:
            head = [jnp.ones_like(o._data) for o in self.outputs]
        else:
            if isinstance(out_grads, (NDArray,)):
                out_grads = [out_grads]
            head = [g._data if isinstance(g, NDArray) else jnp.asarray(g)
                    for g in out_grads]
        grads = self._vjp(grad_vals, const_vals, aux_vals, key, head)
        gi = 0
        for i, n in enumerate(self.arg_names):
            req = self.grad_req.get(n, "null")
            if req == "null":
                continue
            g = grads[gi]
            gi += 1
            target = self.grad_arrays[i]
            if target is None:
                self.grad_arrays[i] = NDArray(g, ctx=self._ctx)
            elif req == "add":
                target._data = target._data + g
            else:  # write
                target._data = g

    # -- utilities ------------------------------------------------------------

    @property
    def arg_dict(self):
        return dict(zip(self.arg_names, self.arg_arrays))

    @property
    def grad_dict(self):
        return dict(zip(self.arg_names, self.grad_arrays))

    @property
    def aux_dict(self):
        return dict(zip(self.aux_names, self.aux_arrays))

    @property
    def output_dict(self):
        return dict(zip(self.output_names, self.outputs))

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        """(reference executor.py:copy_params_from)."""
        for name, array in arg_params.items():
            if name in self.arg_dict:
                self.arg_dict[name][:] = array
            elif not allow_extra_params:
                raise ValueError("Find name \"%s\" that is not in the "
                                 "arguments" % name)
        if aux_params:
            for name, array in aux_params.items():
                if name in self.aux_dict:
                    self.aux_dict[name][:] = array
                elif not allow_extra_params:
                    raise ValueError("Find name \"%s\" that is not in the "
                                     "auxiliary states" % name)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """New executor for new input shapes, sharing parameter arrays
        (reference GraphExecutor::Reshape :785 — the bucketing mechanism;
        here XLA compiles one executable per shape signature and weights
        are shared by reference)."""
        from . import ndarray as nd

        shapes = {n: tuple(a.shape) for n, a in
                  zip(self.arg_names, self.arg_arrays)}
        shapes.update({k: tuple(v) for k, v in kwargs.items()})
        arg_shapes, _, _ = self._symbol.infer_shape(**shapes)
        new_args = []
        for n, a, s in zip(self.arg_names, self.arg_arrays, arg_shapes):
            if tuple(a.shape) == tuple(s):
                new_args.append(a)  # shared (weights)
            else:
                new_args.append(nd.zeros(s, ctx=self._ctx))
        new_grads = None
        if any(g is not None for g in self.grad_arrays):
            new_grads = []
            for g, s in zip(self.grad_arrays, arg_shapes):
                if g is not None and tuple(g.shape) == tuple(s):
                    new_grads.append(g)
                elif g is not None:
                    new_grads.append(nd.zeros(s, ctx=self._ctx))
                else:
                    new_grads.append(None)
        return Executor(self._symbol, self._ctx, new_args, new_grads,
                        self.grad_req, self.aux_arrays)

    def set_monitor_callback(self, callback, monitor_all=False):
        """(reference MXExecutorSetMonitorCallback)."""
        self._monitor_callback = callback

    def debug_str(self):
        lines = ["Symbol outputs: %s" % self.output_names]
        for n in self._symbol._topo():
            if n._op:
                lines.append("%s(%s)" % (n._op, n._name))
        return "\n".join(lines)
