"""Gluon Block / HybridBlock.

Reference: python/mxnet/gluon/block.py (Block :126, HybridBlock :672
with _build_cache/_call_cached_op :749-796, SymbolBlock :953,
save/load_parameters :314-356).

TPU rebuild: `hybridize()` does not build an NNVM graph — the block's
unmodified Python forward is traced by jax.jit through CachedOp
(mxnet_tpu/cached_op.py), with parameters lifted to executable inputs
via parameter.override() and aux-state writes (BatchNorm running stats)
returned as extra outputs. One XLA executable per (input-signature,
train-mode); shape changes retrace automatically — MXNet's bucketing
rebinds, subsumed.

Deferred initialization: layers implement `infer_shape(*args)`; on first
forward with unknown param shapes the hook fills them from the inputs
(replacing the reference's symbolic shape-inference pass).
"""
from __future__ import annotations

import contextlib
import re
import threading

import jax
import numpy as np

from .. import ndarray as nd
from ..base import atomic_write
from ..ndarray.ndarray import NDArray
from .. import autograd
from ..cached_op import CachedOp
from .parameter import (Parameter, ParameterDict, DeferredInitializationError,
                        override, tracing_overrides)

__all__ = ["Block", "HybridBlock", "SymbolBlock"]

_naming = threading.local()


class _BlockScope:
    """Name scoping for parameter prefixes (reference: block.py:_BlockScope)."""

    _counters = {}

    @staticmethod
    def create(prefix, params, hint):
        if prefix is None:
            cnt = _BlockScope._counters.get(hint, 0)
            _BlockScope._counters[hint] = cnt + 1
            prefix = "%s%d_" % (hint, cnt)
        if params is None:
            params = ParameterDict(prefix)
        else:
            # Donor-prefix semantics: names resolve under the donor
            # dict's prefix so its parameters are reused by name
            # (reference block.py:_BlockScope.create —
            # Dense(4, params=other.params) shares other's weight).
            params = ParameterDict(params.prefix, shared=params)
        return prefix, params


class _HookHandle:
    def __init__(self, hooks, hook):
        self._hooks, self._hook = hooks, hook

    def detach(self):
        if self._hook in self._hooks:
            self._hooks.remove(self._hook)


class Block:
    """Base building block (reference: gluon/block.py:Block)."""

    def __init__(self, prefix=None, params=None):
        hint = self._alias()
        self._prefix, self._params = _BlockScope.create(prefix, params, hint)
        self._name = self._prefix[:-1] if self._prefix.endswith("_") else self._prefix
        self._children = {}
        self._reg_params = {}
        self._forward_hooks = []
        self._forward_pre_hooks = []

    def _alias(self):
        return self.__class__.__name__.lower()

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    @property
    def params(self):
        return self._params

    def name_scope(self):
        return contextlib.nullcontext()

    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
        elif isinstance(value, Parameter):
            reg = self.__dict__.get("_reg_params")
            if reg is not None:
                reg[name] = value
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        self._children[name or str(len(self._children))] = block

    def register_forward_hook(self, hook):
        """`hook(block, args, out)` after every forward; returns a handle
        whose `detach()` takes it off (reference: utils.py:HookHandle)."""
        self._forward_hooks.append(hook)
        return _HookHandle(self._forward_hooks, hook)

    def register_forward_pre_hook(self, hook):
        """`hook(block, args)` before every forward; see
        `register_forward_hook`."""
        self._forward_pre_hooks.append(hook)
        return _HookHandle(self._forward_pre_hooks, hook)

    def collect_params(self, select=None):
        """All parameters of self + descendants (reference: block.py:
        collect_params)."""
        out = ParameterDict(self._params.prefix)
        pattern = re.compile(select) if select else None
        seen = set()

        def visit(block):
            if id(block) in seen:
                return
            seen.add(id(block))
            for name, p in block._params.items():
                if pattern is None or pattern.match(name):
                    out._params[name] = p
            for child in block._children.values():
                visit(child)

        visit(self)
        return out

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def cast(self, dtype):
        for p in self.collect_params().values():
            p.cast(dtype)

    def _collect_params_with_prefix(self, prefix=""):
        ret = {}
        for name, p in self._reg_params.items():
            ret[prefix + name] = p
        for cname, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + cname + "."))
        return ret

    def save_parameters(self, filename):
        """Structured param file (reference: block.py:314 — flat
        attribute-path names, portable across prefixes)."""
        params = self._collect_params_with_prefix()
        arg = {}
        for name, p in params.items():
            if p._data is None:
                continue
            arg[name] = p.data()
        nd.save(filename, arg)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False):
        loaded = nd.load(filename)
        params = self._collect_params_with_prefix()
        if not isinstance(loaded, dict):
            raise ValueError("%s is not a parameter file" % filename)
        for name, p in params.items():
            if name in loaded:
                if p.shape is None or p._data is None:
                    p.shape = loaded[name].shape
                    p.initialize(ctx=ctx)
                p.set_data(loaded[name])
            elif not allow_missing:
                raise ValueError("Parameter %s missing in %s" % (name, filename))
        if not ignore_extra:
            extra = set(loaded) - set(params)
            if extra:
                raise ValueError("Extra parameters in %s: %s" % (filename, extra))

    # legacy aliases (reference keeps both save_params/save_parameters)
    def save_params(self, filename):
        self.save_parameters(filename)

    def load_params(self, filename, ctx=None, **kwargs):
        self.load_parameters(filename, ctx=ctx, **kwargs)

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def summary(self, *inputs):
        out = self(*inputs)
        n_params = sum(int(np.prod(p.shape)) for p in
                       self.collect_params().values() if p.shape)
        print("Total params: %d" % n_params)
        return out

    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        if self._name and _being_traced(args):
            # The block's name goes into the metadata of every device op
            # traced under it (telemetry/device_table.py sums by it).
            with jax.named_scope(self._name):
                out = self.forward(*args, **kwargs)
        else:
            out = self.forward(*args, **kwargs)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def forward(self, *args):
        raise NotImplementedError

    def __repr__(self):
        lines = [self.__class__.__name__ + "("]
        for name, child in self._children.items():
            mod = repr(child).replace("\n", "\n  ")
            lines.append("  (%s): %s" % (name, mod))
        lines.append(")")
        return "\n".join(lines)


class HybridBlock(Block):
    """Block compilable to a single XLA executable (reference:
    gluon/block.py:HybridBlock — hybrid_forward(F, x, **params))."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_op = None
        self._cached_op_params = None
        self._cached_aux = {}
        self._cached_n_out = {}
        self._cached_in_tree = None
        self._cached_out_tree = {}
        self._flags = {}

    def hybridize(self, active=True, **kwargs):
        self._active = active
        self._flags = kwargs
        self._cached_op = None
        super().hybridize(active, **kwargs)

    def infer_shape(self, *args):
        """Fill deferred parameter shapes from input shapes. Layers with
        deferred params override this; composite blocks infer via their
        children during forward."""

    def _ensure_init(self, *args):
        # Use the replica living on the input's device (data-parallel
        # forward on context i must read params[i], reference
        # parameter.py:data(ctx)).
        ctx = next((a.context for a in args if isinstance(a, NDArray)), None)
        try:
            return {k: p.data(ctx) for k, p in self._reg_params.items()}
        except DeferredInitializationError:
            self.infer_shape(*args)
            for p in self._reg_params.values():
                if p._deferred_init is not None:
                    p._finish_deferred_init(p.shape)
            return {k: p.data(ctx) for k, p in self._reg_params.items()}

    def forward(self, x, *args):
        from .. import symbol as _sym

        if isinstance(x, _sym.Symbol):
            # Symbolic re-trace (export path): parameters become named
            # variables so the graph serializes with stable arg names
            # (reference block.py:_get_graph traces with F=symbol).
            # Aux-ness (BatchNorm moving stats) is assigned by the op
            # composition from the op signature — NOT from grad_req,
            # which would misfile frozen weights as aux.
            params = {k: _sym.Symbol(None, name=p.name)
                      for k, p in self._reg_params.items()}
            return self.hybrid_forward(_sym, x, *args, **params)
        self._num_forward_inputs = 1 + len(args)
        params = self._ensure_init(x, *args)
        return self.hybrid_forward(nd, x, *args, **params)

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    def _build_cache(self, *args):
        # Trigger any deferred init with a real (non-traced) pass context:
        # shapes are known from args.
        params = list(self.collect_params().values())
        deferred = [p for p in params if p._data is None and
                    p._deferred_init is not None]
        if deferred:
            # Empty override scope: children see an active trace and take
            # their plain forward path, so this shape-discovery pass does
            # not compile throwaway per-child executables (and aux writes
            # are captured, not applied).
            with autograd.pause(), override({}):
                self.forward(*args)
        params = [p for p in self.collect_params().values()
                  if p._data is not None]
        self._cached_op_params = params
        n = len(params)
        block = self

        def fn(*xs):
            from jax import tree_util as jtu

            ps, flat_ins = xs[:n], xs[n:]
            ins = jtu.tree_unflatten(block._cached_in_tree, list(flat_ins))
            ov = override(dict(zip(params, ps)))
            scope = jax.named_scope(block._name) if block._name \
                else contextlib.nullcontext()
            with ov, scope:
                out = block.forward(*ins)
            # Outputs may be nested (e.g. RNN cells return
            # (output, [states])); flatten to the executable's flat tuple
            # and remember the structure for _call_cached_op.
            outs, out_tree = jtu.tree_flatten(
                out, is_leaf=lambda x: isinstance(x, NDArray))
            # Aux bookkeeping is per train-mode: the train and eval traces
            # are distinct executables with different aux writes (BatchNorm
            # updates running stats only in train mode).
            aux = list(ov.writes.keys())
            mode = autograd.is_training()
            block._cached_aux[mode] = aux
            block._cached_n_out[mode] = len(outs)
            block._cached_out_tree[mode] = out_tree
            return tuple(outs) + tuple(ov.writes[p] for p in aux)

        self._cached_op = CachedOp(fn, num_params=n, **self._flags)

    def _call_cached_op(self, *args):
        """Reference: block.py:_call_cached_op → CachedOp::Forward."""
        from jax import tree_util as jtu

        # export() needs the call arity; a hybridized block may never run
        # the plain forward path that records it.
        self._num_forward_inputs = len(args)
        flat_args, in_tree = jtu.tree_flatten(
            list(args), is_leaf=lambda x: isinstance(x, NDArray))
        if self._cached_op is None or in_tree != self._cached_in_tree:
            self._cached_in_tree = in_tree
            self._build_cache(*args)
        ctx = next((a.context for a in flat_args
                    if isinstance(a, NDArray)), None)
        param_data = [p.data(ctx) for p in self._cached_op_params]
        result = self._cached_op(*(param_data + flat_args))
        if not isinstance(result, tuple):
            result = (result,)
        mode = autograd.is_training()
        n_out = self._cached_n_out[mode]
        outs = result[:n_out]
        aux_vals = result[n_out:]
        for p, v in zip(self._cached_aux[mode], aux_vals):
            p.set_data(v)
        out = jtu.tree_unflatten(self._cached_out_tree[mode], list(outs))
        return out

    def __call__(self, *args, **kwargs):
        from ..symbol import Symbol as _Symbol

        if self._active and tracing_overrides() is None and \
                not any(isinstance(a, _Symbol) for a in args) and \
                not any(isinstance(a, NDArray) and _is_traced_nd(a) for a in args):
            for hook in self._forward_pre_hooks:
                hook(self, args)
            out = self._call_cached_op(*args)
            for hook in self._forward_hooks:
                hook(self, args, out)
            return out
        return super().__call__(*args, **kwargs)

    def export(self, path, epoch=0):
        """Write ``path-symbol.json`` + ``path-%04d.params`` (reference
        block.py:export :1008): the block is re-traced through the
        Symbol frontend in inference mode and the graph serialized; the
        params file uses the reference's ``arg:``/``aux:``-prefixed
        checkpoint format so ``SymbolBlock.imports`` (and the reference
        itself) can reload it. Parameters must be initialized (call the
        block once first). The exported graph is an inference graph.

        Returns (symbol_filename, params_filename)."""
        from .. import symbol as _sym

        n_in = getattr(self, "_num_forward_inputs", 1)
        names = ["data"] if n_in == 1 else \
            ["data%d" % i for i in range(n_in)]
        ins = [_sym.var(n) for n in names]
        with autograd.pause(train_mode=False):
            out = self(*ins)
        if isinstance(out, (list, tuple)):
            out = _sym.Group(list(out))
        sym_file = "%s-symbol.json" % path
        out.save(sym_file)

        arg_names = set(out.list_arguments())
        aux_names = set(out.list_auxiliary_states())
        save_dict = {}
        for p in self.collect_params().values():
            if p._data is None:
                continue
            kind = "aux" if p.name in aux_names else "arg"
            if p.name in arg_names or p.name in aux_names:
                save_dict["%s:%s" % (kind, p.name)] = p.data()
        params_file = "%s-%04d.params" % (path, epoch)
        nd.save(params_file, save_dict)
        return sym_file, params_file

    def export_stablehlo(self, path, *example_inputs):
        """Serialize the jitted inference computation as a portable
        StableHLO artifact via ``jax.export`` — loadable and runnable
        with plain jax, no mxnet_tpu required (the TPU analogue of the
        reference's deployment exports through the C predict API).

        Writes ``path.stablehlo`` and returns its filename."""
        import jax
        from jax import export as jexport
        import jax.numpy as jnp

        param_objs = list(self.collect_params().values())
        pvals = {p.name: p.data()._data for p in param_objs}

        def fn(*xs):
            # params are closure constants: the artifact is
            # self-contained (weights embedded in the StableHLO module).
            mapping = {p: NDArray(pvals[p.name]) for p in param_objs}
            with autograd.pause(train_mode=False), override(mapping):
                out = self(*[NDArray(x) for x in xs])
            if isinstance(out, (list, tuple)):
                return tuple(o._data for o in out)
            return out._data

        xs = [x._data if isinstance(x, NDArray) else jnp.asarray(x)
              for x in example_inputs]
        exported = jexport.export(jax.jit(fn))(*xs)
        blob = exported.serialize()
        fname = "%s.stablehlo" % path
        # Deployment artifact: a crash mid-serialize must leave the old
        # export, never a torn .stablehlo a server would then load.
        with atomic_write(fname, "wb") as f:
            f.write(blob)
        return fname


def _is_traced_nd(x):
    return isinstance(x._data, jax.core.Tracer)


def _being_traced(args):
    """Whether a trace is being made: a step or a CachedOp holds the
    parameters' overrides, or an argument is a tracer."""
    return tracing_overrides() is not None or any(
        isinstance(a, NDArray) and _is_traced_nd(a) for a in args)


class SymbolBlock(HybridBlock):
    """Construct a block from a symbol graph (reference: block.py:953).
    Implemented with the Symbol layer (mxnet_tpu/symbol): forward binds
    a graph executor (cached per input signature) with the block's
    parameters as args/aux."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="", params=None)
        if isinstance(outputs, (list, tuple)):
            from .. import symbol as _sym

            outputs = _sym.Group(list(outputs))
        self._outputs = outputs
        self._inputs = inputs if isinstance(inputs, (list, tuple)) \
            else [inputs]
        self._executors = {}
        input_names = {i.name for i in self._inputs}
        if params is None:
            params = {}
        aux_set = set(outputs.list_auxiliary_states())
        for name in (list(outputs.list_arguments()) + sorted(aux_set)):
            if name in input_names:
                continue
            p = params.get(name)
            if isinstance(p, Parameter):
                self._params._params[name] = p
            else:
                newp = self._params.get(
                    name, allow_deferred_init=True,
                    grad_req="null" if name in aux_set else "write")
                if p is not None:                    # NDArray / ndarray
                    newp.shape = tuple(p.shape)
                    newp.initialize()
                    newp.set_data(p)

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        """Reload an exported model (reference block.py:SymbolBlock.imports
        :1032). Accepts the ``arg:``/``aux:``-prefixed checkpoint format
        written by `HybridBlock.export` (and plain-name files)."""
        from .. import symbol as _sym

        sym = _sym.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [_sym.var(n) for n in input_names]
        params = {}
        if param_file:
            loaded = nd.load(param_file)
            for k, v in loaded.items():
                name = k.split(":", 1)[1] if k.startswith(("arg:", "aux:")) \
                    else k
                params[name] = v.as_in_context(ctx) if ctx is not None else v
            # allow_missing=False semantics: a truncated checkpoint must
            # fail HERE with the missing names, not as a deferred-init
            # error on first forward.
            input_names = set(input_names)
            missing = [n for n in (list(sym.list_arguments())
                                   + list(sym.list_auxiliary_states()))
                       if n not in input_names and n not in params]
            if missing:
                raise ValueError(
                    "Parameter file %s is missing graph parameters %s"
                    % (param_file, sorted(missing)))
        block = SymbolBlock(sym, inputs, params=params)
        if ctx is not None:
            block.collect_params().reset_ctx(ctx)
        return block

    def _forward_imperative(self, data):
        """Tape-recording DAG walk: every node dispatches through the
        imperative nd path so autograd records vjps — imported models
        are trainable (reference SymbolBlock trains like any Block)."""
        from .. import autograd as _ag
        from ..ndarray.ndarray import _invoke
        from ..ops import registry as _reg

        cache = {}

        def value_of(node, out_index):
            key = (node._uid, out_index or 0)
            if key in cache:
                return cache[key]
            if node._op is None:
                v = data.get(node._name)
                if v is None:
                    v = self._params[node._name].data()
                cache[key] = v
                return v
            op_name = node._attrs.get("_op_name", node._op)
            in_vals = [value_of(i, i._out_index or 0)
                       for i in node._inputs]
            attrs = node._clean_attrs()
            if _reg.get(op_name).train_aware:
                # drop any baked-in mode so _invoke injects the CURRENT
                # autograd train state (Executor._eval_graph does the
                # same override for train-aware ops)
                attrs.pop("training", None)
            res = _invoke(op_name, in_vals, **attrs)
            outs = res if isinstance(res, (tuple, list)) else (res,)
            # aux writes (BatchNorm moving stats) route back into the
            # aux parameters, mirroring Executor._eval_graph.
            aux_inputs = [i for i in node._inputs
                          if i._op is None and i._is_aux]
            if aux_inputs and len(outs) == 1 + len(aux_inputs) and \
                    _ag.is_training():
                for a, v in zip(aux_inputs, outs[1:]):
                    if a._name in self._params:
                        self._params[a._name].set_data(v)
                outs = outs[:1]
            elif aux_inputs and len(outs) == 1 + len(aux_inputs):
                outs = outs[:1]
            for i, o in enumerate(outs):
                cache[(node._uid, i)] = o
            return cache[(node._uid, out_index or 0)]

        outs = [value_of(s, s._out_index or 0)
                for s in self._outputs.outputs]
        return outs[0] if len(outs) == 1 else outs

    def forward(self, *args):
        from .. import autograd as _ag

        data = {}
        for inp, val in zip(self._inputs, args):
            data[inp.name] = val if isinstance(val, NDArray) \
                else nd.array(val)
        if _ag.is_recording():
            return self._forward_imperative(data)
        sig = tuple(sorted((k, tuple(v.shape), str(v.dtype))
                           for k, v in data.items()))
        ex = self._executors.get(sig)
        if ex is None:
            # Data inputs bind as COPIES (Executor.forward writes
            # fed values into the bound arrays in place — binding the
            # caller's NDArray would corrupt it on later calls).
            # Parameters bind by reference: set_data mutates the same
            # buffers, so updates between calls are visible with no
            # per-call re-feed.
            args_map = {k: v.copy() for k, v in data.items()}
            for n in self._outputs.list_arguments():
                if n not in args_map:
                    args_map[n] = self._params[n].data()
            aux_map = {n: self._params[n].data()
                       for n in self._outputs.list_auxiliary_states()}
            ex = self._outputs.bind(args=args_map, aux_states=aux_map,
                                    grad_req="null")
            self._executors[sig] = ex
        outs = ex.forward(is_train=_ag.is_training(), **data)
        return outs[0] if len(outs) == 1 else list(outs)
