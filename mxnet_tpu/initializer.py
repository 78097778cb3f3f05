"""Weight initializers.

Reference: python/mxnet/initializer.py (registry; Uniform/Normal/
Xavier/MSRAPrelu/Orthogonal/Bilinear/LSTMBias/One/Zero/Constant/Mixed).

Initializers run host-side on numpy (they execute once at startup; the
arrays are then placed in HBM), seeded from the framework RNG state.
"""
from __future__ import annotations

import re

import numpy as np

from . import random as _random
from .registry_util import Registry

__all__ = ["InitDesc", "Initializer", "Uniform", "Normal", "Xavier",
           "LogUniform", "DeviceNormal", "MSRAPrelu", "Orthogonal",
           "Bilinear", "One", "Zero", "Constant", "LSTMBias", "Mixed",
           "registry", "register"]

registry = Registry("initializer")


def _from_spec(spec):
    """Recreate an initializer from a registry name or a dumps() JSON
    string (reference: mx.init.create / legacy_json handling)."""
    import json

    if not isinstance(spec, str):
        return spec
    s = spec.strip()
    if s.startswith("["):
        name, kwargs = json.loads(s)
        return registry.create(name, **kwargs)
    return registry.create(s)
register = registry.register


class InitDesc(str):
    """Name + attrs describing what is being initialized
    (reference: initializer.py:InitDesc)."""

    def __new__(cls, name, attrs=None, global_init=None):
        ret = super().__new__(cls, name)
        ret.attrs = attrs or {}
        ret.global_init = global_init
        return ret


def _rng():
    """Fresh host-side RandomState per call: the global counter advances
    so two same-shaped parameters never draw identical weights."""
    seed, counter = _random.get_state()
    _random.advance()
    return np.random.RandomState((seed * 1000003 + counter * 7919) % (2 ** 31))


class Initializer:
    """Base class (reference: initializer.py:Initializer). Dispatches on
    name suffix like the reference's InitDesc pattern matching."""

    def __init__(self, **kwargs):
        self._kwargs = kwargs

    def dumps(self):
        """JSON string ["name", {kwargs}] (reference
        initializer.py:Initializer.dumps — the form stored in symbol
        __init__ attrs and kvstore set_optimizer payloads)."""
        import json

        name = getattr(self.__class__, "_register_name",
                       self.__class__.__name__.lower())
        return json.dumps([name, {k: v for k, v in self._kwargs.items()}])

    def __call__(self, desc, arr):
        if not isinstance(desc, InitDesc):
            desc = InitDesc(str(desc))
        init = desc.attrs.get("__init__", "")
        if init:
            if isinstance(init, Initializer):
                return init._init_weight(desc, arr)
            return _from_spec(init)._init_weight(desc, arr)
        name = desc.lower()
        if name.endswith("weight"):
            return self._init_weight(desc, arr)
        if name.endswith("bias"):
            return self._init_bias(desc, arr)
        if name.endswith("gamma"):
            return self._init_one(desc, arr)
        if name.endswith("beta"):
            return self._init_zero(desc, arr)
        if name.endswith("running_mean") or name.endswith("moving_mean"):
            return self._init_zero(desc, arr)
        if name.endswith("running_var") or name.endswith("moving_var"):
            return self._init_one(desc, arr)
        return self._init_weight(desc, arr)

    def _init_weight(self, desc, arr):
        raise NotImplementedError

    def _init_bias(self, desc, arr):
        arr[...] = 0.0
        return arr

    def _init_one(self, desc, arr):
        arr[...] = 1.0
        return arr

    def _init_zero(self, desc, arr):
        arr[...] = 0.0
        return arr


@register("uniform")
class Uniform(Initializer):
    def __init__(self, scale=0.07):
        super().__init__(scale=scale)
        self.scale = scale

    def _init_weight(self, desc, arr):
        arr[...] = _rng().uniform(-self.scale, self.scale, arr.shape)
        return arr


@register("normal")
class Normal(Initializer):
    def __init__(self, sigma=0.01):
        super().__init__(sigma=sigma)
        self.sigma = sigma

    def _init_weight(self, desc, arr):
        arr[...] = _rng().normal(0, self.sigma, arr.shape)
        return arr


@register("loguniform")
class LogUniform(Initializer):
    """``log(U(low, high))``: the log of a positive rate drawn evenly
    (Gated DeltaNet's `A_log`)."""

    def __init__(self, low=0.0, high=16.0):
        super().__init__(low=low, high=high)
        self.low, self.high = low, high

    def _init_weight(self, desc, arr):
        arr[...] = np.log(np.maximum(
            _rng().uniform(self.low, self.high, arr.shape),
            np.finfo(np.float32).tiny))
        return arr


@register("devicenormal")
class DeviceNormal(Initializer):
    """`Normal`, drawn on the device by `mx.nd.random.normal` under
    `mx.random.seed`: no host array of the weight's size is drawn or
    copied (a 500 M-parameter model draws for 20 s with numpy). Other
    values than `Normal`'s for the same seed."""

    def __init__(self, sigma=0.01):
        super().__init__(sigma=sigma)
        self.sigma = sigma

    def _init_weight(self, desc, arr):
        from . import ndarray as nd

        return nd.random.normal(0, self.sigma, shape=arr.shape,
                                dtype=arr.dtype)


@register("xavier")
class Xavier(Initializer):
    """Reference: initializer.py:Xavier (rnd_type uniform/gaussian,
    factor_type avg/in/out)."""

    def __init__(self, rnd_type="uniform", factor_type="avg", magnitude=3):
        super().__init__(rnd_type=rnd_type, factor_type=factor_type,
                         magnitude=magnitude)
        self.rnd_type = rnd_type
        self.factor_type = factor_type
        self.magnitude = float(magnitude)

    def _init_weight(self, desc, arr):
        shape = arr.shape
        hw_scale = 1.0
        if len(shape) < 2:
            raise ValueError("Xavier requires ndim >= 2, got %s for %s"
                             % (shape, desc))
        if len(shape) > 2:
            hw_scale = np.prod(shape[2:])
        fan_in, fan_out = shape[1] * hw_scale, shape[0] * hw_scale
        factor = {"avg": (fan_in + fan_out) / 2.0, "in": fan_in,
                  "out": fan_out}[self.factor_type]
        scale = np.sqrt(self.magnitude / factor)
        if self.rnd_type == "uniform":
            arr[...] = _rng().uniform(-scale, scale, shape)
        else:
            arr[...] = _rng().normal(0, scale, shape)
        return arr


@register("msra_prelu")
class MSRAPrelu(Xavier):
    def __init__(self, factor_type="avg", slope=0.25):
        magnitude = 2.0 / (1 + slope ** 2)
        super().__init__("gaussian", factor_type, magnitude)
        self._kwargs = {"factor_type": factor_type, "slope": slope}


@register("orthogonal")
class Orthogonal(Initializer):
    def __init__(self, scale=1.414, rand_type="uniform"):
        super().__init__(scale=scale, rand_type=rand_type)
        self.scale = scale
        self.rand_type = rand_type

    def _init_weight(self, desc, arr):
        nout = arr.shape[0]
        nin = int(np.prod(arr.shape[1:]))
        rng = _rng()
        if self.rand_type == "uniform":
            tmp = rng.uniform(-1.0, 1.0, (nout, nin))
        else:
            tmp = rng.normal(0.0, 1.0, (nout, nin))
        u, _, v = np.linalg.svd(tmp, full_matrices=False)
        q = u if u.shape == tmp.shape else v
        arr[...] = (self.scale * q).reshape(arr.shape)
        return arr


@register("bilinear")
class Bilinear(Initializer):
    """Bilinear upsampling kernels (reference: initializer.py:Bilinear)."""

    def _init_weight(self, desc, arr):
        weight = np.zeros(arr.size, dtype=np.float64)
        shape = arr.shape
        f = np.ceil(shape[3] / 2.0)
        c = (2 * f - 1 - f % 2) / (2.0 * f)
        for i in range(arr.size):
            x = i % shape[3]
            y = (i // shape[3]) % shape[2]
            weight[i] = (1 - abs(x / f - c)) * (1 - abs(y / f - c))
        arr[...] = weight.reshape(shape)
        return arr


@register("one")
@register("ones")
class One(Initializer):
    def _init_weight(self, desc, arr):
        arr[...] = 1.0
        return arr


@register("zero")
@register("zeros")
class Zero(Initializer):
    def _init_weight(self, desc, arr):
        arr[...] = 0.0
        return arr


@register("constant")
class Constant(Initializer):
    def __init__(self, value=0.0):
        super().__init__(value=value)
        self.value = value

    def _init_weight(self, desc, arr):
        arr[...] = np.asarray(self.value.asnumpy() if hasattr(self.value, "asnumpy")
                              else self.value)
        return arr


@register("lstmbias")
class LSTMBias(Initializer):
    """Forget-gate bias init (reference: initializer.py:LSTMBias)."""

    def __init__(self, forget_bias=1.0):
        super().__init__(forget_bias=forget_bias)
        self.forget_bias = forget_bias

    def _init_weight(self, desc, arr):
        arr[...] = 0.0
        num_hidden = arr.shape[0] // 4
        arr[num_hidden:2 * num_hidden] = self.forget_bias
        return arr


class Mixed:
    """Pattern → initializer dispatch (reference: initializer.py:Mixed)."""

    def __init__(self, patterns, initializers):
        self.map = list(zip([re.compile(p) for p in patterns], initializers))

    def __call__(self, desc, arr):
        for prog, init in self.map:
            if prog.match(str(desc)):
                return init(desc, arr)
        raise ValueError("no initializer pattern matches %s" % desc)


@register("fused_rnn")
class FusedRNN(Initializer):
    """Initialize a fused RNN op's flat parameter vector slice by slice
    (reference: initializer.py:FusedRNN — unpacks, applies the wrapped
    initializer per gate block, repacks). Weights get `init` (default
    Uniform(0.07) like reference DEFAULT), biases zero with the LSTM
    forget-gate slice set to `forget_bias`."""

    def __init__(self, init=None, num_hidden=0, num_layers=1, mode="lstm",
                 bidirectional=False, forget_bias=1.0):
        if isinstance(init, str):
            init = _from_spec(init)
        super().__init__(init=init.dumps() if init is not None else None,
                         num_hidden=num_hidden,
                         num_layers=num_layers, mode=mode,
                         bidirectional=bidirectional,
                         forget_bias=forget_bias)
        self._init = init or Uniform(0.07)
        self._num_hidden = num_hidden
        self._num_layers = num_layers
        self._mode = mode
        self._bidirectional = bidirectional
        self._forget_bias = forget_bias

    def _init_weight(self, desc, arr):
        from .ops.rnn_ops import (rnn_infer_input_size, rnn_param_layout,
                                  _NGATES)

        flat = arr.reshape(-1)
        h = self._num_hidden
        in_sz = rnn_infer_input_size(flat.shape[0], self._num_layers, h,
                                     self._mode, self._bidirectional)
        for name, shape, off in rnn_param_layout(
                self._num_layers, h, in_sz, self._mode, self._bidirectional):
            n = int(np.prod(shape))
            block = np.zeros(shape, dtype=arr.dtype)
            if name.endswith("weight"):
                self._init._init_weight(InitDesc(name), block)
            elif self._mode == "lstm" and name.endswith("i2h_bias"):
                # gate order [i, f, g, o]: forget slice is [h:2h]
                block[h:2 * h] = self._forget_bias
            flat[off:off + n] = block.reshape(-1)
        return arr
