"""Neural-network operators.

Reference: src/operator/nn/ (convolution-inl.h, fully_connected-inl.h,
pooling-inl.h, batch_norm-inl.h, layer_norm-inl.h, activation-inl.h,
softmax-inl.h, dropout-inl.h, upsampling-inl.h, deconvolution-inl.h,
lrn-inl.h) and src/operator/ (softmax_output-inl.h, regression ops,
l2_normalization, instance_norm, embedding in indexing_op.h).

TPU rebuild notes:
- Convolution lowers to `lax.conv_general_dilated`; XLA:TPU's layout
  assignment maps it onto the MXU with its preferred (NHWC-ish blocked)
  layout, so the public API stays NCHW like the reference while the
  compiler owns the internal layout — replacing the cuDNN algo-selection
  + autotune machinery (cudnn_algoreg-inl.h) entirely.
- FullyConnected is a plain dot_general → MXU.
- BatchNorm returns updated running stats as extra outputs instead of
  mutating aux states in-place (functional form; the Gluon layer commits
  them, which under a jitted train step becomes a donated buffer).
- Dropout/RNG use counter-based stateless keys (mxnet_tpu/random.py) —
  the TPU answer to the reference's per-device RNG resources
  (include/mxnet/resource.h kRandom).
"""
from __future__ import annotations

import functools

import numpy as np

from .registry import register
from .. import random as _random
from ..telemetry import metrics as _tm


def _jnp():
    import jax.numpy as jnp

    return jnp


def _lax():
    import jax.lax as lax

    return lax


def _nn():
    import jax.nn

    return jax.nn


def _pair(x, n=2):
    if isinstance(x, (tuple, list)):
        return tuple(x)
    return (x,) * n


# ---------------------------------------------------------------------------
# dense / conv
# ---------------------------------------------------------------------------

@register("FullyConnected", aliases=("fully_connected",))
def _fully_connected(data, weight, bias=None, num_hidden=0, no_bias=False,
                     flatten=True):
    jnp = _jnp()
    x = data
    if flatten and x.ndim > 2:
        x = x.reshape(x.shape[0], -1)
    out = jnp.dot(x, weight.T) if x.ndim == 2 else jnp.einsum("...i,oi->...o", x, weight)
    if bias is not None and not no_bias:
        out = out + bias
    return out


@register("Convolution", aliases=("convolution",))
def _convolution(data, weight, bias=None, kernel=(), stride=(), dilate=(),
                 pad=(), num_filter=0, num_group=1, no_bias=False,
                 layout="NCHW", preferred_element_type=None):
    """`preferred_element_type` widens the accumulator (int8 inputs with
    an int32 accumulator engage the MXU's narrow-input path — the
    quantized conv shares this body)."""
    lax = _lax()
    ndim = len(kernel) if kernel else weight.ndim - 2
    stride = stride or (1,) * ndim
    dilate = dilate or (1,) * ndim
    pad = pad or (0,) * ndim
    spec = {1: ("NCH", "OIH", "NCH"), 2: ("NCHW", "OIHW", "NCHW"),
            3: ("NCDHW", "OIDHW", "NCDHW")}[ndim]
    dn = lax.conv_dimension_numbers(data.shape, weight.shape, spec)
    out = lax.conv_general_dilated(
        data, weight,
        window_strides=tuple(stride),
        padding=[(p, p) for p in pad],
        rhs_dilation=tuple(dilate),
        dimension_numbers=dn,
        feature_group_count=num_group,
        preferred_element_type=preferred_element_type,
    )
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1) + (1,) * ndim)
    return out


@register("Deconvolution", aliases=("deconvolution",))
def _deconvolution(data, weight, bias=None, kernel=(), stride=(), dilate=(),
                   pad=(), adj=(), num_filter=0, num_group=1, no_bias=False,
                   target_shape=(), layout=None):
    # `layout` accepted for parity with Convolution (gluon's
    # Conv*DTranspose layers pass it); channel-first is the only
    # supported public layout, same as the conv path — anything else
    # must fail loudly, not silently compute NCHW results.
    if layout not in (None, "NCW", "NCHW", "NCDHW"):
        raise ValueError("Deconvolution supports channel-first layouts "
                         "only (got %r)" % (layout,))
    lax = _lax()
    jnp = _jnp()
    ndim = len(kernel) if kernel else weight.ndim - 2
    stride = tuple(stride) if stride else (1,) * ndim
    dilate = tuple(dilate) if dilate else (1,) * ndim
    pad = tuple(pad) if pad else (0,) * ndim
    adj = tuple(adj) if adj else (0,) * ndim
    k = tuple(weight.shape[2:])
    # Transposed conv as the gradient of conv: dilate the input by
    # `stride` (lhs_dilation) and convolve with the spatially-flipped,
    # in/out-swapped kernel. Weight is stored (C_in, C_out/g, *k) like
    # the reference (deconvolution-inl.h); regroup to (C_out, C_in/g, *k).
    g = num_group
    cin = weight.shape[0]
    cout_pg = weight.shape[1]
    w = weight.reshape((g, cin // g, cout_pg) + k)
    w = jnp.swapaxes(w, 1, 2).reshape((g * cout_pg, cin // g) + k)
    w = jnp.flip(w, axis=tuple(range(2, 2 + ndim)))
    k_eff = tuple((kk - 1) * d + 1 for kk, d in zip(k, dilate))
    padding = [(ke - 1 - p, ke - 1 - p + a) for ke, p, a in zip(k_eff, pad, adj)]
    spec = {1: ("NCH", "OIH", "NCH"), 2: ("NCHW", "OIHW", "NCHW"),
            3: ("NCDHW", "OIDHW", "NCDHW")}[ndim]
    dn = lax.conv_dimension_numbers(data.shape, w.shape, spec)
    out = lax.conv_general_dilated(
        data, w, window_strides=(1,) * ndim, padding=padding,
        lhs_dilation=stride, rhs_dilation=dilate,
        dimension_numbers=dn, feature_group_count=g)
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1) + (1,) * ndim)
    return out


# ---------------------------------------------------------------------------
# pooling
# ---------------------------------------------------------------------------

@register("Pooling", aliases=("pooling",))
def _pooling(data, kernel=(), pool_type="max", global_pool=False, stride=(),
             pad=(), pooling_convention="valid", count_include_pad=True,
             cudnn_off=False):
    jnp = _jnp()
    lax = _lax()
    ndim = data.ndim - 2
    if global_pool:
        axes = tuple(range(2, data.ndim))
        if pool_type == "max":
            return jnp.max(data, axis=axes, keepdims=True)
        return jnp.mean(data, axis=axes, keepdims=True)
    kernel = _pair(kernel, ndim)
    stride = _pair(stride, ndim) if stride else (1,) * ndim
    pad = _pair(pad, ndim) if pad else (0,) * ndim
    window = (1, 1) + tuple(kernel)
    strides = (1, 1) + tuple(stride)
    pads = ((0, 0), (0, 0)) + tuple((p, p) for p in pad)
    if pooling_convention == "full":
        # ceil-mode: pad extra on the right so ceil division is honored
        extra = []
        for i in range(ndim):
            in_sz = data.shape[2 + i] + 2 * pad[i]
            rem = (in_sz - kernel[i]) % stride[i]
            e = (stride[i] - rem) % stride[i] if rem != 0 else 0
            extra.append(e)
        pads = ((0, 0), (0, 0)) + tuple((p, p + e) for p, e in zip(pad, extra))
    if pool_type == "max":
        init = -np.inf
        out = lax.reduce_window(data, np.array(init, data.dtype), lax.max,
                                window, strides, pads)
        return out
    if pool_type in ("avg", "sum"):
        out = lax.reduce_window(data, np.array(0, data.dtype), lax.add,
                                window, strides, pads)
        if pool_type == "sum":
            return out
        if count_include_pad:
            denom = np.prod(kernel).astype(np.float32)
            return out / np.asarray(denom, data.dtype)
        ones = jnp.ones_like(data)
        counts = lax.reduce_window(ones, np.array(0, data.dtype), lax.add,
                                   window, strides, pads)
        return out / counts
    if pool_type == "lp":
        sq = lax.reduce_window(data * data, np.array(0, data.dtype), lax.add,
                               window, strides, pads)
        return jnp.sqrt(sq)
    raise ValueError("unknown pool_type %s" % pool_type)


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

_batchnorm_train_traced = _tm.REGISTRY.counter(
    "mx_batchnorm_train_traced_total",
    "BatchNorm training branches traced (one per call of the operator "
    "in train mode with batch statistics: 53 for one build of a "
    "ResNet-50 step program)")


@functools.lru_cache(maxsize=None)
def _batch_norm_train_impl(axis, eps):
    """The training branch's core, `(x, g, beta) -> (out, mean, var)`
    for the channel axis `axis` (not negative), `g` and `beta` already
    in the accumulator type `promote_types(x.dtype, float32)`, in which
    `mean` and the biased `var` come back.

    Two passes over the activation each way. Forward: `sum(x)` and
    `sum(x*x)` over one read of `x` (plain `jnp` reductions: under a
    mesh that shards the batch they become collectives, which is all
    SyncBatchNorm is), `var = max(E[x^2] - E[x]^2, 0)`, then one pass
    that writes `x * scale + shift` with the per-channel constants
    folded. Backward: `sum(dy)` and `sum(dy * xhat)` over one read of
    `dy` and `x`, then one pass that writes `dx`. The residuals are `x`
    as it came in and three per-channel vectors: no centred, normalised
    or widened copy of the activation is kept. The statistics are
    outputs for the moving averages only; their cotangents are ignored,
    as the operator stops their gradient.
    """
    import jax

    jnp = _jnp()

    def layout(x):
        red = tuple(i for i in range(x.ndim) if i != axis)
        shape = tuple(x.shape[i] if i == axis else 1 for i in range(x.ndim))
        n = float(np.prod([x.shape[i] for i in red]))
        return red, shape, n

    def fwd(x, g, beta):
        with jax.named_scope("batchnorm_train_fwd"):
            red, shape, n = layout(x)
            xf = x.astype(g.dtype)
            mean = jnp.sum(xf, axis=red) / n
            var = jnp.maximum(jnp.sum(xf * xf, axis=red) / n - mean * mean,
                              0.0)
            inv = jax.lax.rsqrt(var + eps)
            scale = g * inv
            shift = beta - mean * scale
            out = xf * scale.reshape(shape) + shift.reshape(shape)
            return (out.astype(x.dtype), mean, var), (x, mean, inv, g)

    @jax.custom_vjp
    def f(x, g, beta):
        return fwd(x, g, beta)[0]

    def bwd(res, cts):
        with jax.named_scope("batchnorm_train_bwd"):
            x, mean, inv, g = res
            red, shape, n = layout(x)
            dy = cts[0].astype(g.dtype)
            xhat = (x.astype(g.dtype) - mean.reshape(shape)) \
                * inv.reshape(shape)
            dbeta = jnp.sum(dy, axis=red)
            dgamma = jnp.sum(dy * xhat, axis=red)
            dx = (g * inv).reshape(shape) * (
                dy - (dbeta / n).reshape(shape)
                - xhat * (dgamma / n).reshape(shape))
            return dx.astype(x.dtype), dgamma, dbeta

    f.defvjp(fwd, bwd)
    return f


@register("BatchNorm", aliases=("batch_norm",), train_aware=True)
def _batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
                momentum=0.9, fix_gamma=True, use_global_stats=False,
                axis=1, training=False):
    """Returns (out, new_moving_mean, new_moving_var).

    Reference semantics (batch_norm-inl.h): train mode normalizes with
    batch stats and updates moving stats; eval mode uses moving stats.
    Functional form — caller commits the updated stats.

    Train mode takes the batch mean and the biased variance in
    `promote_types(data.dtype, float32)` (the reference's AccReal) and
    keeps them there: the normalisation and the moving averages both
    read the fp32 statistics, `out` has `data`'s type, and the backward
    is written by hand (`_batch_norm_train_impl`).
    """
    import jax

    jnp = _jnp()
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    if training and not use_global_stats:
        _batchnorm_train_traced.inc()
        acc = jnp.promote_types(data.dtype, jnp.float32)
        fn = _batch_norm_train_impl(axis % data.ndim, float(eps))
        out, mean, var = fn(data, g.astype(acc), beta.astype(acc))
        # The moving averages keep the type they had: that of the stored
        # statistics, or the activation's where that is wider.
        stat = jnp.promote_types(moving_mean.dtype, data.dtype)
        new_mm = (moving_mean * momentum
                  + jax.lax.stop_gradient(mean) * (1 - momentum)).astype(stat)
        new_mv = (moving_var * momentum
                  + jax.lax.stop_gradient(var) * (1 - momentum)).astype(stat)
        return out, new_mm, new_mv
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    shape = tuple(shape)
    inv = jax.lax.rsqrt(moving_var.reshape(shape)
                        + np.asarray(eps, data.dtype))
    out = (data - moving_mean.reshape(shape)) * inv * g.reshape(shape) \
        + beta.reshape(shape)
    return out, moving_mean, moving_var


@register("LayerNorm", aliases=("layer_norm",))
def _layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    import jax

    jnp = _jnp()
    mean = jnp.mean(data, axis=axis, keepdims=True)
    var = jnp.var(data, axis=axis, keepdims=True)
    inv = jax.lax.rsqrt(var + np.asarray(eps, data.dtype))
    shape = [1] * data.ndim
    ax = axis % data.ndim
    shape[ax] = data.shape[ax]
    out = (data - mean) * inv * gamma.reshape(shape) + beta.reshape(shape)
    if output_mean_var:
        return out, jnp.squeeze(mean, axis), jnp.squeeze(var, axis)
    return out


@register("InstanceNorm", aliases=("instance_norm",))
def _instance_norm(data, gamma, beta, eps=1e-3):
    import jax

    jnp = _jnp()
    axes = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=axes, keepdims=True)
    var = jnp.var(data, axis=axes, keepdims=True)
    inv = jax.lax.rsqrt(var + np.asarray(eps, data.dtype))
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return (data - mean) * inv * gamma.reshape(shape) + beta.reshape(shape)


@register("L2Normalization", aliases=("l2_normalization",))
def _l2_normalization(data, eps=1e-10, mode="instance"):
    jnp = _jnp()
    if mode == "instance":
        axes = tuple(range(1, data.ndim))
    elif mode == "channel":
        axes = (1,)
    elif mode == "spatial":
        axes = tuple(range(2, data.ndim))
    else:
        raise ValueError(mode)
    norm = jnp.sqrt(jnp.sum(data * data, axis=axes, keepdims=True) + eps)
    return data / norm


@register("LRN", aliases=("lrn",))
def _lrn(data, nsize=5, alpha=1e-4, beta=0.75, knorm=2.0):
    lax = _lax()
    sq = data * data
    half = nsize // 2
    window = (1, nsize, 1, 1)
    pads = ((0, 0), (half, half), (0, 0), (0, 0))
    ssum = lax.reduce_window(sq, np.array(0, data.dtype), lax.add, window,
                             (1, 1, 1, 1), pads)
    return data / ((knorm + alpha / nsize * ssum) ** beta)


# ---------------------------------------------------------------------------
# activations / softmax
# ---------------------------------------------------------------------------

@register("Activation", aliases=("activation",))
def _activation(data, act_type="relu"):
    jnp = _jnp()
    nn = _nn()
    if act_type == "relu":
        return jnp.maximum(data, 0)
    if act_type == "sigmoid":
        return nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return nn.softplus(data)
    if act_type == "softsign":
        return data / (1 + jnp.abs(data))
    if act_type == "relu6":
        # MobileNet family (reference: clip(relu(x), 0, 6) via mshadow_op).
        return jnp.clip(data, 0, 6)
    raise ValueError("unknown act_type %s" % act_type)


@register("LeakyReLU", aliases=("leaky_relu",))
def _leaky_relu(data, gamma=None, act_type="leaky", slope=0.25,
                lower_bound=0.125, upper_bound=0.334):
    jnp = _jnp()
    nn = _nn()
    if act_type == "leaky":
        return jnp.where(data > 0, data, slope * data)
    if act_type == "elu":
        return jnp.where(data > 0, data, slope * (jnp.exp(data) - 1))
    if act_type == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2)) \
            if gamma is not None and gamma.ndim == 1 and data.ndim > 2 else gamma
        return jnp.where(data > 0, data, g * data)
    if act_type == "selu":
        return 1.0507009873554805 * nn.elu(data, 1.6732632423543772)
    if act_type == "gelu":
        return nn.gelu(data)
    if act_type == "rrelu":
        mid = (lower_bound + upper_bound) / 2.0
        return jnp.where(data > 0, data, mid * data)
    raise ValueError("unknown act_type %s" % act_type)


@register("softmax")
def _softmax(data, axis=-1, temperature=None, length=None):
    nn = _nn()
    x = data / temperature if temperature else data
    if length is not None:
        jnp = _jnp()
        mask = jnp.arange(data.shape[axis]) < length[..., None]
        x = jnp.where(mask, x, -np.inf)
    return nn.softmax(x, axis=axis)


@register("log_softmax")
def _log_softmax(data, axis=-1, temperature=None):
    nn = _nn()
    x = data / temperature if temperature else data
    return nn.log_softmax(x, axis=axis)


@register("softmin")
def _softmin(data, axis=-1):
    return _nn().softmax(-data, axis=axis)


@register("SoftmaxActivation", aliases=("softmax_activation",))
def _softmax_activation(data, mode="instance"):
    nn = _nn()
    if mode == "channel":
        return nn.softmax(data, axis=1)
    return nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


# ---------------------------------------------------------------------------
# loss-layer ops (forward value + custom backward like the reference)
# ---------------------------------------------------------------------------

_softmax_output_cache = {}


def _softmax_output_impl(grad_scale, ignore_label, multi_output, use_ignore,
                         normalization, smooth_alpha):
    import jax

    jnp = _jnp()
    nn = _nn()
    axis = 1 if multi_output else -1

    @jax.custom_vjp
    def f(data, label):
        return nn.softmax(data, axis=axis)

    def fwd(data, label):
        out = f(data, label)
        return out, (out, label)

    def bwd(res, g):
        out, label = res
        depth = out.shape[axis]
        lab = label.astype(jnp.int32)
        onehot = jax.nn.one_hot(lab, depth, axis=axis, dtype=out.dtype)
        if smooth_alpha:
            onehot = onehot * (1 - smooth_alpha) + smooth_alpha / (depth - 1) * (1 - onehot)
        grad = out - onehot
        keep = None
        if use_ignore:
            keep = (lab != int(ignore_label)).astype(out.dtype)
            grad = grad * jnp.expand_dims(keep, axis)
        # Normalization (reference softmax_output-inl.h): 'valid' divides
        # by the count of non-ignored samples, 'batch' by batch size.
        if normalization == "valid":
            count = jnp.sum(keep) if keep is not None else np.asarray(
                float(np.prod(lab.shape)), out.dtype)
            grad = grad / jnp.maximum(count, 1.0).astype(out.dtype)
        elif normalization == "batch":
            grad = grad / np.asarray(float(lab.shape[0]), out.dtype)
        grad = grad * np.asarray(grad_scale, out.dtype)
        # SoftmaxOutput ignores the incoming head gradient (reference:
        # softmax_output-inl.h — backward is defined by the loss itself).
        return (grad, jnp.zeros_like(label))

    f.defvjp(fwd, bwd)
    return f


@register("SoftmaxOutput", aliases=("softmax_output", "Softmax"))
def _softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                    multi_output=False, use_ignore=False, preserve_shape=False,
                    normalization="null", out_grad=False, smooth_alpha=0.0):
    key = (float(grad_scale), float(ignore_label), bool(multi_output),
           bool(use_ignore), str(normalization), float(smooth_alpha))
    fn = _softmax_output_cache.get(key)
    if fn is None:
        fn = _softmax_output_impl(*key)
        _softmax_output_cache[key] = fn
    return fn(data, label)


_regression_cache = {}


def _regression(kind, grad_scale):
    """Regression output ops: identity/sigmoid forward, (out - label)
    backward (reference: src/operator/regression_output-inl.h)."""
    import jax

    jnp = _jnp()
    fwd_act = {"linear": lambda d: d,
               "logistic": lambda d: _nn().sigmoid(d),
               "mae": lambda d: d}[kind]
    grad_fn = {"linear": lambda o, l: o - l.reshape(o.shape),
               "logistic": lambda o, l: o - l.reshape(o.shape),
               "mae": lambda o, l: jnp.sign(o - l.reshape(o.shape))}[kind]

    @jax.custom_vjp
    def f(data, label):
        return fwd_act(data)

    def fwd(data, label):
        out = f(data, label)
        return out, (out, label)

    def bwd(res, g):
        out, label = res
        grad = grad_fn(out, label) * np.asarray(grad_scale, out.dtype)
        return (grad, jnp.zeros_like(label))

    f.defvjp(fwd, bwd)
    return f


def _regression_get(kind, grad_scale):
    key = (kind, float(grad_scale))
    fn = _regression_cache.get(key)
    if fn is None:
        fn = _regression(kind, float(grad_scale))
        _regression_cache[key] = fn
    return fn


@register("LinearRegressionOutput", aliases=("linear_regression_output",))
def _linear_regression_output(data, label, grad_scale=1.0):
    return _regression_get("linear", grad_scale)(data, label)


@register("LogisticRegressionOutput", aliases=("logistic_regression_output",))
def _logistic_regression_output(data, label, grad_scale=1.0):
    return _regression_get("logistic", grad_scale)(data, label)


@register("MAERegressionOutput", aliases=("mae_regression_output",))
def _mae_regression_output(data, label, grad_scale=1.0):
    return _regression_get("mae", grad_scale)(data, label)


@register("make_loss", aliases=("MakeLoss",))
def _make_loss(data, grad_scale=1.0, valid_thresh=0.0, normalization="null"):
    return data * np.asarray(1.0, data.dtype)


@register("softmax_cross_entropy")
def _softmax_cross_entropy(data, label):
    jnp = _jnp()
    nn = _nn()
    logp = nn.log_softmax(data, axis=-1)
    lab = label.astype(jnp.int32)
    picked = jnp.take_along_axis(logp, lab[:, None], axis=-1)
    return -jnp.sum(picked)


# ---------------------------------------------------------------------------
# dropout / embedding / upsampling
# ---------------------------------------------------------------------------

@register("Dropout", aliases=("dropout",), needs_rng=True, train_aware=True)
def _dropout(rng_key, data, p=0.5, mode="training", axes=(), training=False):
    import jax

    if not training and mode != "always":
        return data
    if axes:
        shape = tuple(1 if i in axes else s for i, s in enumerate(data.shape))
    else:
        shape = data.shape
    keep = 1.0 - p
    mask = jax.random.bernoulli(rng_key, keep, shape).astype(data.dtype) / \
        np.asarray(keep, data.dtype)
    return data * mask


@register("Embedding", aliases=("embedding",))
def _embedding(data, weight, input_dim=0, output_dim=0, dtype="float32",
               sparse_grad=False):
    jnp = _jnp()
    idx = jnp.clip(data.astype(jnp.int32), 0, weight.shape[0] - 1)
    return jnp.take(weight, idx, axis=0)


@register("UpSampling", aliases=("upsampling",))
def _upsampling(*args, scale=1, sample_type="nearest", num_args=1,
                num_filter=0, multi_input_mode="concat"):
    jnp = _jnp()
    data = args[0]
    if sample_type == "nearest":
        out = jnp.repeat(jnp.repeat(data, scale, axis=2), scale, axis=3)
        return out
    # bilinear: resize via jax.image
    import jax

    n, c, h, w = data.shape
    return jax.image.resize(data, (n, c, h * scale, w * scale), method="bilinear")


@register("GridGenerator", aliases=("grid_generator",))
def _grid_generator(data, transform_type="affine", target_shape=(0, 0)):
    jnp = _jnp()
    if transform_type == "affine":
        h, w = target_shape
        ys = jnp.linspace(-1, 1, h)
        xs = jnp.linspace(-1, 1, w)
        gx, gy = jnp.meshgrid(xs, ys)
        ones = jnp.ones_like(gx)
        base = jnp.stack([gx.ravel(), gy.ravel(), ones.ravel()], axis=0)
        theta = data.reshape(-1, 2, 3)
        grid = jnp.einsum("nij,jk->nik", theta, base)
        return grid.reshape(-1, 2, h, w)
    return data


@register("BilinearSampler", aliases=("bilinear_sampler",))
def _bilinear_sampler(data, grid, cudnn_off=False):
    import jax

    jnp = _jnp()
    n, c, h, w = data.shape
    gx = (grid[:, 0] + 1) * (w - 1) / 2
    gy = (grid[:, 1] + 1) * (h - 1) / 2
    coords = jnp.stack([gy, gx], axis=1)  # (n, 2, oh, ow)

    def sample_one(img, coord):
        # img (c,h,w), coord (2,oh,ow)
        return jax.vmap(
            lambda ch: jax.scipy.ndimage.map_coordinates(ch, [coord[0], coord[1]],
                                                         order=1, mode="constant")
        )(img)

    return jax.vmap(sample_one)(data, coords)


@register("SpatialTransformer", aliases=("spatial_transformer",))
def _spatial_transformer(data, loc, target_shape=(0, 0),
                         transform_type="affine", sampler_type="bilinear",
                         cudnn_off=False):
    grid = _grid_generator(loc, transform_type="affine", target_shape=tuple(target_shape))
    return _bilinear_sampler(data, grid)
