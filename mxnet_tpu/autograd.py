"""Imperative autograd.

Reference: src/imperative/imperative.cc (Imperative::RecordOp attaching
AGInfo tape nodes, Imperative::Backward building and executing the
gradient graph via each op's FGradient) and python/mxnet/autograd.py
(record/pause/train_mode scopes, mark_variables, backward, grad, Function).

TPU rebuild: the tape records (op, attrs, input snapshots) per invocation.
Backward computes each node's input cotangents with a cached, jitted
``jax.vjp`` runner — the forward is *rematerialized inside the backward
executable* (XLA fuses fwd+bwd per node), replacing hand-written FGradient
kernels. Input snapshots are immutable jax.Arrays, so later mutation of an
NDArray (engine-var version bump) can never corrupt the tape — the
versioned-variable guarantee of the reference's engine, for free.

For whole-graph training the blessed path is CachedOp/hybridize (one XLA
executable for fwd+bwd+update); this tape is the eager path.
"""
from __future__ import annotations

import threading

import numpy as np

from .ops import registry as _reg
from .telemetry import trace as _trace

__all__ = [
    "record", "pause", "train_mode", "predict_mode", "is_recording",
    "is_training", "set_recording", "set_training", "mark_variables",
    "backward", "grad", "get_symbol", "Function",
]

_state = threading.local()


def _st():
    if not hasattr(_state, "recording"):
        _state.recording = False
        _state.training = False
    return _state


def is_recording():
    return _st().recording


def is_training():
    return _st().training


def set_recording(flag):
    prev = _st().recording
    _state.recording = flag
    return prev


def set_training(flag):
    prev = _st().training
    _state.training = flag
    return prev


class _RecordingScope:
    def __init__(self, recording, training):
        self._recording = recording
        self._training = training

    def __enter__(self):
        st = _st()
        self._prev = (st.recording, st.training)
        if self._recording is not None:
            st.recording = self._recording
        if self._training is not None:
            st.training = self._training
        return self

    def __exit__(self, *a):
        st = _st()
        st.recording, st.training = self._prev

    def __call__(self, fn):
        def wrapped(*args, **kwargs):
            with self.__class__(self._recording, self._training):
                return fn(*args, **kwargs)

        return wrapped


def record(train_mode=True):
    """Scope in which executed ops are recorded on the tape
    (reference: python/mxnet/autograd.py:122)."""
    return _RecordingScope(True, train_mode)


def pause(train_mode=False):
    return _RecordingScope(False, train_mode)


def train_mode():
    return _RecordingScope(None, True)


def predict_mode():
    return _RecordingScope(None, False)


# ---------------------------------------------------------------------------
# tape
# ---------------------------------------------------------------------------

class _Node:
    """One recorded op invocation (reference AGInfo, imperative.h:42-76)."""

    __slots__ = ("op", "attrs", "attrs_key", "inputs", "parents",
                 "out_avals", "n_out", "custom_backward", "named")

    def __init__(self, op, attrs, attrs_key, inputs, parents, outputs_raw,
                 custom_backward=None):
        self.op = op
        self.attrs = attrs
        self.attrs_key = attrs_key
        self.inputs = inputs  # raw jax arrays (snapshots)
        self.parents = parents  # per input: (_Node, out_idx) | ('leaf', nd) | None
        multi = isinstance(outputs_raw, (tuple, list))
        outs = list(outputs_raw) if multi else [outputs_raw]
        self.n_out = len(outs)
        self.out_avals = [(tuple(o.shape), o.dtype) for o in outs]
        self.custom_backward = custom_backward
        self.named = ()


def _parent_of(x):
    from .ndarray.ndarray import NDArray

    if isinstance(x, NDArray):
        if x._ag_node is not None:
            return (x._ag_node, x._ag_out_index)
        # Any other NDArray input is a potential leaf: gradients are
        # accumulated for it and committed to .grad only per grad_req,
        # but autograd.grad() can query them without pre-marking.
        return ("leaf", x)
    return None


def _record_op(op, nd_inputs, arrays, attrs, named=()):
    """Called from the dispatch path while recording: run forward (jitted)
    and push a tape node. RNG keys prepended by prep_inputs are captured
    as constants of the node."""
    attrs_key = _reg._freeze(attrs)
    arrays = _reg.prep_inputs(op, arrays, attrs_key)
    raw = op.jitted(attrs_key, attrs, named)(*arrays)
    pad = len(arrays) - len(nd_inputs)
    parents = [None] * pad + [_parent_of(x) for x in nd_inputs]
    node = _Node(op, attrs, attrs_key, arrays, parents, raw)
    node.named = named
    _st().pending_node = node
    return raw


def _attach_outputs(result):
    node = getattr(_st(), "pending_node", None)
    if node is None:
        return
    _state.pending_node = None
    outs = result if isinstance(result, (tuple, list)) else [result]
    for i, o in enumerate(outs):
        o._ag_node = node
        o._ag_out_index = i


_VJP_CACHE: dict = {}


def _vjp_runner(op, attrs_key, attrs, named=()):
    """Cached jitted fwd-rematerializing vjp for one (op, attrs)."""
    key = (op.name, attrs_key, named)
    fn = _VJP_CACHE.get(key)
    if fn is None:
        import jax

        bound = op.bound_fn(attrs, named)

        def run(inputs, cotangents):
            def f(*xs):
                out = bound(*xs)
                return tuple(out) if isinstance(out, (tuple, list)) else (out,)

            _, pullback = jax.vjp(f, *inputs)
            return pullback(tuple(cotangents))

        run.__name__ = op.vjp_name           # the executable's name
        fn = jax.jit(run)
        _VJP_CACHE[key] = fn
    return fn


def mark_variables(variables, gradients, grad_reqs="write", grad_req=None):
    """Reference: MXAutogradMarkVariables."""
    if grad_req is not None:
        grad_reqs = grad_req
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for v, g, req in zip(variables, gradients, grad_reqs):
        v._grad = g
        v._grad_req = req
        v._ag_node = None


def _toposort(root_nodes):
    order = []
    seen = set()
    stack = [(n, False) for n in root_nodes]
    while stack:
        node, processed = stack.pop()
        if processed:
            order.append(node)
            continue
        if id(node) in seen:
            continue
        seen.add(id(node))
        stack.append((node, True))
        for p in node.parents:
            if p is not None and p[0] != "leaf" and id(p[0]) not in seen:
                stack.append((p[0], False))
    return order  # parents before children


def _zeros_aval(aval):
    import jax.numpy as jnp

    shape, dtype = aval
    return jnp.zeros(shape, dtype)


def backward(heads, head_grads=None, retain_graph=False, train_mode=True,
             variables=None):
    """Run backward from `heads`, writing into each marked variable's
    `.grad` per its grad_req — or, with `variables`, returning their
    gradients (reference: Imperative::Backward imperative.cc:270)."""
    with _trace.span("autograd::backward"):
        return _backward(heads, head_grads, variables)


def _backward(heads, head_grads, variables):
    from .ndarray.ndarray import NDArray

    if head_grads is None:
        head_grads = [None] * len(heads)

    node_cts: dict[int, list] = {}
    nodes_by_id: dict[int, _Node] = {}
    leaf_grads: dict[int, tuple] = {}
    roots = []

    import jax.numpy as jnp

    def _accum_node(node, idx, g):
        lst = node_cts.setdefault(id(node), [None] * node.n_out)
        nodes_by_id[id(node)] = node
        lst[idx] = g if lst[idx] is None else lst[idx] + g

    def _accum_leaf(nd, g):
        ent = leaf_grads.get(id(nd))
        leaf_grads[id(nd)] = (nd, g if ent is None else ent[1] + g)

    for h, hg in zip(heads, head_grads):
        g = hg._data if isinstance(hg, NDArray) else (
            hg if hg is not None else jnp.ones(h.shape, h.dtype))
        if h._ag_node is not None:
            _accum_node(h._ag_node, h._ag_out_index, g)
            roots.append(h._ag_node)
        elif h._grad is not None:
            _accum_leaf(h, g)
        else:
            raise ValueError(
                "cannot differentiate a head that was not computed under "
                "autograd.record() nor marked with attach_grad()")

    order = _toposort(roots)
    for node in reversed(order):
        cts = node_cts.get(id(node))
        if cts is None:
            continue
        cts = [c if c is not None else _zeros_aval(a)
               for c, a in zip(cts, node.out_avals)]
        if getattr(node, "custom_backward", None) is not None:
            ct_nds = [NDArray(c) for c in cts]
            with _trace.span("autograd::vjp", op=node.op.name):
                res = node.custom_backward.backward(*ct_nds)
            if not isinstance(res, (tuple, list)):
                res = (res,)
            in_grads = [r._data if isinstance(r, NDArray) else r for r in res]
        else:
            runner = _vjp_runner(node.op, node.attrs_key, node.attrs,
                                 node.named)
            with _trace.span("autograd::vjp", op=node.op.name):
                in_grads = runner(tuple(node.inputs), tuple(cts))
        for parent, g in zip(node.parents, in_grads):
            if parent is None or g is None:
                continue
            if getattr(g.dtype, "name", str(g.dtype)) == "float0":
                continue
            if parent[0] == "leaf":
                _accum_leaf(parent[1], g)
            else:
                _accum_node(parent[0], parent[1], g)

    if variables is not None:
        out = []
        for v in variables:
            ent = leaf_grads.get(id(v))
            if ent is None and v._ag_node is not None:
                cts = node_cts.get(id(v._ag_node))
                g = cts[v._ag_out_index] if cts else None
            else:
                g = ent[1] if ent else None
            if g is None:
                g = jnp.zeros(v.shape, v.dtype)
            out.append(NDArray(g, ctx=v.context))
        return out

    with _trace.span("autograd::commit", leaves=len(leaf_grads)):
        for nd, g in leaf_grads.values():
            if nd._grad_req == "null" or nd._grad is None:
                continue
            if nd._grad_req == "add":
                nd._grad._set_data(nd._grad._data + g)
            else:
                nd._grad._set_data(g.astype(nd._grad.dtype))
    return None


def grad(heads, variables, head_grads=None, retain_graph=None,
         create_graph=False, train_mode=True):
    """Reference: mx.autograd.grad — return gradients of heads w.r.t.
    variables without touching `.grad` buffers."""
    if create_graph:
        raise NotImplementedError(
            "create_graph=True (higher-order autograd through the tape) is "
            "not supported; use hybridized blocks + jax.grad composition")
    if not isinstance(heads, (list, tuple)):
        heads = [heads]
    if head_grads is not None and not isinstance(head_grads, (list, tuple)):
        head_grads = [head_grads]
    return backward(list(heads), head_grads, retain_graph=bool(retain_graph),
                    train_mode=train_mode, variables=list(variables))


def get_symbol(x):
    raise NotImplementedError(
        "autograd.get_symbol: the tape does not build a Symbol; export "
        "hybridized blocks instead")


class Function:
    """User-defined differentiable function
    (reference: mx.autograd.Function, python/mxnet/autograd.py:Function;
    C++ side src/c_api/c_api_function.cc)."""

    class _Ctx:
        def __init__(self):
            self.saved = ()

        def save_for_backward(self, *arrays):
            self.saved = arrays

    def forward(self, *inputs):
        raise NotImplementedError

    def backward(self, *output_grads):
        raise NotImplementedError

    def __call__(self, *inputs):
        from .ndarray.ndarray import NDArray

        out = self.forward(*inputs)
        if not is_recording():
            return out
        outs = out if isinstance(out, (tuple, list)) else [out]
        arrays = [x._data if isinstance(x, NDArray) else x for x in inputs]
        parents = [_parent_of(x) for x in inputs]

        func = self

        class _CustomOp:
            name = "_custom_function"

        node = _Node.__new__(_Node)
        node.op = _CustomOp()
        node.attrs = {}
        node.attrs_key = ()
        node.inputs = arrays
        node.parents = parents
        node.n_out = len(outs)
        node.out_avals = [(tuple(o.shape), o.dtype) for o in outs]
        node.custom_backward = func
        node.named = ()
        for i, o in enumerate(outs):
            o._ag_node = node
            o._ag_out_index = i
        return out
