"""ResNet v1 with bottleneck blocks (He et al. 2015, arXiv:1512.03385),
as `gluon.model_zoo.vision` builds it: the stride of a stage's first
block sits on its 3x3 convolution, not on the first 1x1 as in the paper's
table 1, so a 224x224 forward of the 50-layer net is 4.09e9
multiply-accumulates, not the paper's 3.8e9.

The configuration file gives `layers`, `channels`, `classes`, `image`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def build(cfg, seed):
    """(net, loss_fn): the zoo's classes at the file's sizes, weights by
    the program's initializers from the seed."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(seed)
    net = vision.ResNetV1(vision.BottleneckV1, list(cfg["layers"]),
                          list(cfg["channels"]), classes=cfg["classes"])
    net.initialize()
    return net, gluon.loss.SoftmaxCrossEntropyLoss()


def make_batch(cfg, rng, batch):
    """Uniform noise plus a coarse pattern of the image's class, so that
    there is something to learn and the loss check means something.
    fp32 images (a step casts to its compute type inside the program, as
    it does for a user's batch) and float32 class indices, as the
    program's losses take them."""
    c, h, w = cfg["image"]
    k_noise, k_label = jax.random.split(rng)
    y = jax.random.randint(k_label, (batch,), 0, cfg["classes"])
    # The pattern depends on the class alone, never on the batch's key.
    pat = jax.random.uniform(jax.random.PRNGKey(cfg["classes"]),
                             (cfg["classes"], c, 8, 8), jnp.float32)
    pat = jnp.repeat(jnp.repeat(pat[y], h // 8, axis=2), w // 8, axis=3)
    x = 0.5 * jax.random.uniform(k_noise, (batch, c, h, w), jnp.float32) \
        + 0.5 * pat
    return x, y.astype(jnp.float32)


def _walk(cfg):
    """(kind, out_channels, kernel, stride, pad, in_hw -> out_hw) of every
    convolution and the dense layer, in the order the net holds them.
    Yields ("conv", cin, cout, k, stride, out_hw) and ("dense", cin, cout).
    """
    chans = list(cfg["channels"])
    hw = cfg["image"][1]
    hw = (hw + 2 * 3 - 7) // 2 + 1
    yield ("conv", cfg["image"][0], chans[0], 7, 2, hw)
    hw = (hw + 2 - 3) // 2 + 1                       # max pool 3/2/1
    cin = chans[0]
    for i, n in enumerate(cfg["layers"]):
        cout = chans[i + 1]
        for b in range(n):
            stride = 2 if (i > 0 and b == 0) else 1
            mid = cout // 4
            yield ("conv", cin, mid, 1, 1, hw)
            hw = (hw + 2 - 3) // stride + 1
            yield ("conv", mid, mid, 3, stride, hw)
            yield ("conv", mid, cout, 1, 1, hw)
            if b == 0 and cout != cin:
                yield ("conv", cin, cout, 1, stride, hw)
            cin = cout
    yield ("dense", cin, cfg["classes"])


def forward_macs(cfg):
    """Multiply-accumulates of one image's forward: convolutions and the
    dense layer, from the shapes (BatchNorm, pooling, adds not counted)."""
    macs = 0
    for layer in _walk(cfg):
        if layer[0] == "conv":
            _, cin, cout, k, _, hw = layer
            macs += cin * cout * k * k * hw * hw
        else:
            macs += layer[1] * layer[2]
    return macs


def flops_per_item(cfg):
    """Operations one image's training step requires: two per
    multiply-accumulate, forward once and backward twice (gradients with
    respect to inputs and to weights). Recomputation is not counted."""
    return 3 * 2 * forward_macs(cfg)


def _plain_conv(x, w, stride, pad):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


class _Params:
    """The net's parameters in collection order, handed out one by one
    with the name's suffix checked."""

    def __init__(self, params):
        self._items = iter(params.items())

    def take(self, suffix):
        name, value = next(self._items)
        if not name.endswith(suffix):
            raise ValueError("expected a *%s parameter, got %s"
                             % (suffix, name))
        return jnp.asarray(value, jnp.float32)

    def bn(self, x, train, eps=1e-5):
        gamma, beta = self.take("gamma"), self.take("beta")
        mean, var = self.take("running_mean"), self.take("running_var")
        if train:        # batch statistics, biased variance
            mean, var = x.mean((0, 2, 3)), x.var((0, 2, 3))
        scale = gamma / jnp.sqrt(var + eps)
        return x * scale[None, :, None, None] \
            + (beta - mean * scale)[None, :, None, None]


def reference_forward(cfg, params, x, train=False, operand=None):
    """Forward in plain fp32 jax.numpy at the highest matmul precision:
    logits (N, classes). `params` is the ordered name -> array mapping of
    the net (running statistics included). `train` takes BatchNorm's
    statistics from the batch, as a training step does. `operand`, where
    given, is applied to both operands of every product (a control
    rounds them to a lower precision there)."""
    q = operand or (lambda a: a)

    def _conv(x, w, stride, pad):
        return _plain_conv(q(x), q(w), stride, pad)

    with jax.default_matmul_precision("highest"):
        p = _Params(params)
        x = jnp.asarray(x, jnp.float32)
        x = jax.nn.relu(p.bn(_conv(x, p.take("weight"), 2, 3), train))
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
            [(0, 0), (0, 0), (1, 1), (1, 1)])
        cin = cfg["channels"][0]
        for i, n in enumerate(cfg["layers"]):
            cout = cfg["channels"][i + 1]
            for b in range(n):
                stride = 2 if (i > 0 and b == 0) else 1
                r = x
                x = jax.nn.relu(p.bn(_conv(x, p.take("weight"), 1, 0), train))
                x = jax.nn.relu(p.bn(_conv(x, p.take("weight"), stride, 1), train))
                x = p.bn(_conv(x, p.take("weight"), 1, 0), train)
                if b == 0 and cout != cin:
                    r = p.bn(_conv(r, p.take("weight"), stride, 0), train)
                x = jax.nn.relu(x + r)
                cin = cout
        x = jnp.mean(x, axis=(2, 3))
        return q(x) @ q(p.take("weight")).T + p.take("bias")


def reference_loss(logits, y):
    """Mean softmax cross-entropy of float class indices."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    idx = y.astype(jnp.int32)[..., None]
    return -jnp.mean(jnp.take_along_axis(logp, idx, axis=-1))
