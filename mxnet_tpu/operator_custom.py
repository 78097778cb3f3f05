"""mx.operator — user-defined operators with Python callbacks.

Reference: python/mxnet/operator.py (CustomOp/CustomOpProp + register)
over src/operator/custom/custom-inl.h:50-170 (CustomOperator registry;
Python callbacks run on a dedicated thread pool off the engine threads,
results re-pushed with correct dependencies, custom-inl.h:116).

TPU rebuild: a registered custom op becomes `mx.nd.Custom(...)` /
`mx.sym.Custom(...)`. Imperatively the callbacks run inline (the tape
records a custom-vjp op, so `backward()` reaches the user's backward).
Inside a traced/compiled graph the callbacks ride `jax.pure_callback` —
XLA's host-callback mechanism, the direct analogue of the reference's
callback thread pool: the device computation yields to the host at the
op's position, with shapes fixed by `CustomOpProp.infer_shape`.

Stateless contract: under compilation the operator instance is created
fresh per callback invocation (the reference's stateful
`FStatefulCompute` custom path is not carried — state must live in the
op's inputs/outputs).
"""
from __future__ import annotations

import numpy as np

from .ndarray.ndarray import NDArray, array as nd_array, zeros as nd_zeros
from .ops.registry import register as _register_op

__all__ = ["CustomOp", "CustomOpProp", "register",
           "get_all_registered_operators"]

_CUSTOM_REGISTRY = {}


class CustomOp:
    """Base class for the runtime op (reference operator.py:CustomOp)."""

    def forward(self, is_train, req, in_data, out_data, aux):
        raise NotImplementedError

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        raise NotImplementedError

    @staticmethod
    def assign(dst, req, src):
        """Write `src` into `dst` honoring the gradient request
        (reference operator.py:CustomOp.assign)."""
        if req == "null":
            return
        if req in ("write", "inplace"):
            dst[:] = src
        elif req == "add":
            dst[:] = dst + src
        else:
            raise ValueError("unknown req %r" % req)


class CustomOpProp:
    """Describes a custom op (reference operator.py:CustomOpProp)."""

    def __init__(self, need_top_grad=True):
        self.need_top_grad_ = need_top_grad

    def list_arguments(self):
        return ["data"]

    def list_outputs(self):
        return ["output"]

    def list_auxiliary_states(self):
        return []

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]] * len(self.list_outputs()), []

    def infer_type(self, in_type):
        return (in_type, [in_type[0]] * len(self.list_outputs()),
                [in_type[0]] * len(self.list_auxiliary_states()))

    def need_top_grad(self):
        return self.need_top_grad_

    def create_operator(self, ctx, in_shapes, in_dtypes):
        return CustomOp()


def register(reg_name):
    """Decorator registering a CustomOpProp subclass under `reg_name`
    (reference operator.py:register)."""

    def deco(prop_cls):
        _CUSTOM_REGISTRY[reg_name] = prop_cls
        return prop_cls

    return deco


def get_all_registered_operators():
    return sorted(_CUSTOM_REGISTRY)


def _make_prop(op_type, attrs):
    if op_type not in _CUSTOM_REGISTRY:
        raise ValueError(
            "custom op %r is not registered (known: %s)"
            % (op_type, get_all_registered_operators()))
    # The reference passes ctor kwargs as strings; we pass them through.
    return _CUSTOM_REGISTRY[op_type](**attrs)


@_register_op("Custom", num_inputs=None)
def _custom(*arrays, op_type=None, **attrs):
    """FCompute for `Custom`: wraps the user's forward/backward in a
    jax.custom_vjp whose host side is pure_callback."""
    import jax

    prop = _make_prop(op_type, attrs)
    n_out = len(prop.list_outputs())
    n_in = len(arrays)
    in_shapes = [list(a.shape) for a in arrays]
    _, out_shapes, _ = prop.infer_shape(in_shapes)
    in_np = [np.dtype(str(a.dtype)) for a in arrays]
    _, out_types, _ = prop.infer_type(in_np)
    out_struct = tuple(jax.ShapeDtypeStruct(tuple(s), t)
                       for s, t in zip(out_shapes, out_types))
    in_struct = tuple(jax.ShapeDtypeStruct(tuple(s), t)
                      for s, t in zip(in_shapes, in_np))

    def host_forward(*xs):
        op = prop.create_operator(None, in_shapes, in_np)
        in_data = [nd_array(np.asarray(x)) for x in xs]
        out_data = [nd_zeros(tuple(s), dtype=t)
                    for s, t in zip(out_shapes, out_types)]
        op.forward(is_train=True, req=["write"] * n_out,
                   in_data=in_data, out_data=out_data, aux=[])
        return tuple(o.asnumpy().astype(t, copy=False)
                     for o, t in zip(out_data, out_types))

    def host_backward(*flat):
        xs = flat[:n_in]
        outs = flat[n_in:n_in + n_out]
        cts = flat[n_in + n_out:]
        op = prop.create_operator(None, in_shapes, in_np)
        in_data = [nd_array(np.asarray(x)) for x in xs]
        out_data = [nd_array(np.asarray(o)) for o in outs]
        out_grad = [nd_array(np.asarray(c)) for c in cts]
        in_grad = [nd_zeros(tuple(s), dtype=t)
                   for s, t in zip(in_shapes, in_np)]
        op.backward(req=["write"] * n_in, out_grad=out_grad,
                    in_data=in_data, out_data=out_data, in_grad=in_grad,
                    aux=[])
        return tuple(g.asnumpy().astype(t, copy=False)
                     for g, t in zip(in_grad, in_np))

    @jax.custom_vjp
    def run(*xs):
        return jax.pure_callback(host_forward, out_struct, *xs)

    def fwd(*xs):
        outs = run(*xs)
        return outs, (xs, outs)

    def bwd(res, cts):
        xs, outs = res
        grads = jax.pure_callback(host_backward, in_struct,
                                  *(tuple(xs) + tuple(outs) + tuple(cts)))
        return tuple(grads)

    run.defvjp(fwd, bwd)
    out = run(*arrays)
    return out if n_out > 1 else out[0]


def _eager_custom(*inputs, op_type=None, **attrs):
    """Imperative Custom: callbacks run inline (no host-callback XLA
    machinery, so it works on every backend), with the user's backward
    wired into the autograd tape via autograd.Function (reference: the
    engine pushes the python callback work directly,
    custom-inl.h:116)."""
    from . import autograd

    prop = _make_prop(op_type, attrs)
    n_out = len(prop.list_outputs())
    in_shapes = [list(x.shape) for x in inputs]
    _, out_shapes, _ = prop.infer_shape(in_shapes)
    in_types = [np.dtype(str(x.dtype)) for x in inputs]
    _, out_types, _ = prop.infer_type(in_types)
    op = prop.create_operator(None, in_shapes, in_types)
    n_in = len(inputs)

    class _CustomFunction(autograd.Function):
        def forward(self, *ins):
            out_data = [nd_zeros(tuple(s), dtype=t)
                        for s, t in zip(out_shapes, out_types)]
            op.forward(is_train=autograd.is_recording(),
                       req=["write"] * n_out, in_data=list(ins),
                       out_data=out_data, aux=[])
            self._in_data = list(ins)
            self._out_data = out_data
            return out_data[0] if n_out == 1 else tuple(out_data)

        def backward(self, *ograds):
            in_grad = [nd_zeros(tuple(s), dtype=t)
                       for s, t in zip(in_shapes, in_types)]
            op.backward(req=["write"] * n_in, out_grad=list(ograds),
                        in_data=self._in_data, out_data=self._out_data,
                        in_grad=in_grad, aux=[])
            return in_grad[0] if n_in == 1 else tuple(in_grad)

    return _CustomFunction()(*inputs)


def Custom(*inputs, op_type=None, out=None, **attrs):
    """`mx.nd.Custom` entry: imperative calls run the callbacks inline;
    traced calls (hybridize/bind) lower to pure_callback inside the
    compiled graph (requires a callback-capable PJRT backend)."""
    from .ndarray.ndarray import _invoke
    from .ops.registry import _is_traced

    arrays = [x._data for x in inputs if isinstance(x, NDArray)]
    if _is_traced(arrays):
        return _invoke("Custom", list(inputs), out=out, op_type=op_type,
                       **attrs)
    res = _eager_custom(*inputs, op_type=op_type, **attrs)
    if out is not None:
        targets = out if isinstance(out, (tuple, list)) else [out]
        results = res if isinstance(res, (tuple, list)) else [res]
        for t, r in zip(targets, results):
            t._set_data(r._data)
        return out
    return res


def _custom_num_outputs(attrs):
    clean = {k: v for k, v in attrs.items()
             if k not in ("_op_name", "op_type")
             and not (k.startswith("__") and k.endswith("__"))}
    return len(_make_prop(attrs["op_type"], clean).list_outputs())


# Symbol composition needs the output count before execution
# (reference: CustomOpProp.list_outputs feeds NNVM's num_outputs).
from . import symbol as _symbol  # noqa: E402

_symbol._NUM_OUTPUT_RULES["Custom"] = _custom_num_outputs

# Route mx.nd.Custom through the eager-aware dispatcher instead of the
# generic jitted op path.
from .ndarray import _FUNC_CACHE as _ND_FUNC_CACHE  # noqa: E402

_ND_FUNC_CACHE["Custom"] = Custom
