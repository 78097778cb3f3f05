"""BatchNorm's training branch (ops/nn.py:_batch_norm_train_impl): one-pass
fp32 statistics and a hand-written backward, against a plain two-pass fp32
reference written here; the evaluation branch against the expression it
has always been; and the structure that makes it worth having: how many
reductions over the activation the compiled gradient holds, and what the
backward keeps.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.ops.nn import _batch_norm
from mxnet_tpu.parallel import TrainStep, make_mesh
from mxnet_tpu.telemetry import metrics

EPS, MOMENTUM = 1e-5, 0.9
_SHAPES = {2: (24, 6), 4: (6, 5, 4, 3), 5: (4, 3, 4, 3, 5)}


def _layout(x, axis):
    axis %= x.ndim
    red = tuple(i for i in range(x.ndim) if i != axis)
    shape = tuple(x.shape[i] if i == axis else 1 for i in range(x.ndim))
    return red, shape


def _reference(x, gamma, beta, mm, mv, axis, fix_gamma, momentum=MOMENTUM):
    """Two passes, everything in fp32: the mean, then the mean of the
    squared distance from it; autodiff gives the gradients."""
    red, shape = _layout(x, axis)
    x = x.astype(jnp.float32)
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    mean = jnp.mean(x, axis=red)
    var = jnp.mean((x - mean.reshape(shape)) ** 2, axis=red)
    out = (x - mean.reshape(shape)) / jnp.sqrt(var.reshape(shape) + EPS) \
        * g.astype(jnp.float32).reshape(shape) \
        + beta.astype(jnp.float32).reshape(shape)
    stop = jax.lax.stop_gradient
    return (out, mm * momentum + stop(mean) * (1 - momentum),
            mv * momentum + stop(var) * (1 - momentum))


def _inputs(shape, axis, dtype, seed=0, mean=0.5, std=2.0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    c = shape[axis]
    x = (jax.random.normal(k[0], shape) * std + mean).astype(dtype)
    gamma = (jax.random.normal(k[1], (c,)) * 0.5 + 1).astype(dtype)
    beta = jax.random.normal(k[2], (c,)).astype(dtype)
    mm = jax.random.normal(k[3], (c,))
    mv = jax.random.uniform(k[4], (c,)) + 0.5
    w = jax.random.normal(k[5], shape)     # the loss's weights: dy
    return x, gamma, beta, mm, mv, w


def _value_and_grads(fn, x, gamma, beta, w):
    def loss(x, gamma, beta):
        out, new_mm, new_mv = fn(x, gamma, beta)
        return jnp.sum(out.astype(jnp.float32) * w), (out, new_mm, new_mv)
    (_, outs), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True)(x, gamma, beta)
    return outs, grads


def _close(got, want, tol):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert np.abs(got - want).max() <= tol * max(1.0, np.abs(want).max()), \
        (np.abs(got - want).max(), np.abs(want).max())


@pytest.mark.parametrize("fix_gamma", [False, True])
@pytest.mark.parametrize("rank", [2, 4, 5])
@pytest.mark.parametrize("axis", [1, -1])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_training_branch_against_two_pass_fp32_reference(dtype, axis, rank,
                                                         fix_gamma):
    shape = _SHAPES[rank]
    x, gamma, beta, mm, mv, w = _inputs(shape, axis, dtype)

    def new(x, gamma, beta):
        return _batch_norm(x, gamma, beta, mm, mv, eps=EPS,
                           momentum=MOMENTUM, fix_gamma=fix_gamma,
                           axis=axis, training=True)

    def ref(x, gamma, beta):
        return _reference(x, gamma, beta, mm, mv, axis, fix_gamma)

    (out, new_mm, new_mv), (dx, dgamma, dbeta) = _value_and_grads(
        new, x, gamma, beta, w)
    (r_out, r_mm, r_mv), (r_dx, r_dgamma, r_dbeta) = _value_and_grads(
        ref, x, gamma, beta, w)
    assert out.dtype == x.dtype and dx.dtype == x.dtype
    assert dgamma.dtype == gamma.dtype and dbeta.dtype == beta.dtype
    # The statistics are fp32 whatever the activation's type: the moving
    # averages read them unrounded.
    assert new_mm.dtype == jnp.float32 and new_mv.dtype == jnp.float32
    _close(new_mm, r_mm, 1e-6)
    _close(new_mv, r_mv, 1e-5)
    # bf16 rounds the output, dy's consumer and the gradients once each
    # (8 bits of mantissa: 2**-8 of the largest value, and the sums of
    # dgamma and dbeta are rounded on the way out).
    tol = 1e-5 if dtype == "float32" else 2 ** -7
    _close(out, r_out, tol)
    _close(dx, r_dx, tol)
    _close(dbeta, r_dbeta, tol)
    if fix_gamma:
        assert not np.asarray(dgamma, np.float32).any()
    else:
        _close(dgamma, r_dgamma, tol)


def _eval_expression(data, gamma, beta, moving_mean, moving_var, eps,
                     fix_gamma, axis):
    """The evaluation branch as it stood before the training branch was
    rewritten, word for word: the serving stacks and the benchmark's
    reference comparison run it, so its bits may not move."""
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    shape = tuple(shape)
    mean, var = moving_mean, moving_var
    inv = jax.lax.rsqrt(var.reshape(shape) + np.asarray(eps, data.dtype))
    return (data - mean.reshape(shape)) * inv * g.reshape(shape) \
        + beta.reshape(shape)


@pytest.mark.parametrize("fix_gamma", [False, True])
@pytest.mark.parametrize("mode", ["eval", "use_global_stats"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_evaluation_branches_keep_their_bits(dtype, mode, fix_gamma):
    x, gamma, beta, mm, mv, _ = _inputs((6, 5, 4, 3), 1, dtype, seed=3)
    before = metrics.REGISTRY.get("mx_batchnorm_train_traced_total").value
    out, new_mm, new_mv = _batch_norm(
        x, gamma, beta, mm, mv, eps=EPS, fix_gamma=fix_gamma,
        use_global_stats=(mode == "use_global_stats"),
        training=(mode == "use_global_stats"))
    want = _eval_expression(x, gamma, beta, mm, mv, EPS, fix_gamma, 1)
    assert out.dtype == want.dtype
    assert np.array_equal(np.asarray(out, np.float32),
                          np.asarray(want, np.float32))
    assert new_mm is mm and new_mv is mv
    assert metrics.REGISTRY.get(
        "mx_batchnorm_train_traced_total").value == before


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_constant_input_clamps_the_variance(dtype):
    """Every element equal: E[x^2] - E[x]^2 may round below zero, the
    clamp holds it at zero, and nothing is NaN."""
    x = jnp.full((8, 4, 3, 3), 3.3, dtype)
    gamma = jnp.full((4,), 1.5, dtype)
    beta = jnp.asarray([0.0, 1.0, -2.0, 0.5], dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), x.shape)

    def new(x, gamma, beta):
        return _batch_norm(x, gamma, beta, jnp.zeros(4), jnp.zeros(4),
                           eps=EPS, momentum=0.0, fix_gamma=False,
                           training=True)

    (out, new_mm, new_mv), grads = _value_and_grads(new, x, gamma, beta, w)
    assert np.array_equal(np.asarray(new_mv), np.zeros(4, np.float32))
    _close(new_mm, np.full(4, np.float32(x[0, 0, 0, 0])), 1e-6)
    # (x - mean) * rsqrt(0 + eps) is at most a rounding of the mean
    # times 316: beta within 2e-3 in fp32.
    _close(out, np.broadcast_to(
        np.asarray(beta, np.float32).reshape(1, 4, 1, 1), x.shape), 2e-3)
    for g in grads:
        assert np.isfinite(np.asarray(g, np.float32)).all()


@pytest.mark.parametrize("mean", [30.0, 1e3])
def test_large_mean_input_stays_within_the_one_pass_error(mean):
    """std 1 around a large mean, in fp32: E[x^2] - E[x]^2 cancels, and
    the n rounded additions under E[x^2] leave about sqrt(n) half-steps
    of fp32 at mean^2. That is the price of one read (Flax's default
    pays it too) and this is the error it is allowed: twice that. At a
    mean of 30 standard deviations it is 4e-3 of the variance and the
    output follows the two-pass form to a percent; at 1e3 it is larger
    than the variance itself (XLA's CPU reduction read 0.66 of 1.0),
    so all that holds there is an exact mean, a variance that is not
    negative and finite values. The two-pass form is off by 1e-6."""
    x, gamma, beta, _, _, w = _inputs((64, 8, 8, 8), 1, "float32",
                                      mean=mean, std=1.0)
    n = x.size // 8
    allowed = 2 * np.sqrt(n) * 2.0 ** -24 * mean ** 2
    zeros = jnp.zeros(8)

    def new(x, gamma, beta):
        return _batch_norm(x, gamma, beta, zeros, zeros, eps=EPS,
                           momentum=0.0, fix_gamma=False, training=True)

    def ref(x, gamma, beta):
        return _reference(x, gamma, beta, zeros, zeros, 1, False,
                          momentum=0.0)

    (out, got_mean, var), (dx, _, _) = _value_and_grads(
        new, x, gamma, beta, w)
    (r_out, r_mean, r_var), (r_dx, _, _) = _value_and_grads(
        ref, x, gamma, beta, w)
    _close(got_mean, r_mean, 1e-6)
    assert (np.asarray(var) >= 0).all()
    assert np.abs(np.asarray(var) - np.asarray(r_var)).max() <= allowed
    assert np.isfinite(np.asarray(out)).all()
    assert np.isfinite(np.asarray(dx)).all()
    if allowed < 0.01:
        _close(out, r_out, 0.01)
        _close(dx, r_dx, 0.01)


def _count_full_reductions(text, elements):
    """`reduce` instructions of a compiled module whose first operand
    has `elements` elements."""
    shapes = {}
    for m in re.finditer(r"%?([\w.\-]+) = \(?\w+\[([\d,]*)\]", text):
        dims = [int(d) for d in m.group(2).split(",") if d]
        shapes[m.group(1)] = int(np.prod(dims)) if dims else 1
    n = 0
    for m in re.finditer(r"= \(?[^=]*? reduce\(%?([\w.\-]+)", text):
        if shapes.get(m.group(1)) == elements:
            n += 1
    return n


def test_gradient_holds_at_most_four_reductions_over_the_activation():
    """Forward sum(x), sum(x*x); backward sum(dy), sum(dy*xhat): each
    pair reads the activation once. The autodiff of mean-then-var, which
    the two-pass reference here still is, holds seven, two of them
    waiting for another."""
    shape = (16, 8, 6, 6)
    x, gamma, beta, mm, mv, _ = _inputs(shape, 1, "bfloat16")

    def count(fn):
        def loss(x, gamma, beta):
            return jnp.sum(jnp.maximum(fn(x, gamma, beta)[0], 0)
                           .astype(jnp.float32))
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            x, gamma, beta).compile().as_text()
        return _count_full_reductions(text, int(np.prod(shape))), text

    n, text = count(lambda x, gamma, beta: _batch_norm(
        x, gamma, beta, mm, mv, eps=EPS, fix_gamma=False, training=True))
    assert 1 <= n <= 4, text
    n_ref, _ = count(lambda x, gamma, beta: _reference(
        x, gamma, beta, mm, mv, 1, False))
    assert n_ref > 4        # the count sees what it is there to see


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_backward_keeps_one_activation_in_its_type(dtype):
    shape = (16, 8, 6, 6)
    x, gamma, beta, mm, mv, _ = _inputs(shape, 1, dtype)
    _, vjp = jax.vjp(
        lambda x, gamma, beta: _batch_norm(
            x, gamma, beta, mm, mv, eps=EPS, fix_gamma=False,
            training=True)[0], x, gamma, beta)
    big = [leaf for leaf in jax.tree_util.tree_leaves(vjp)
           if getattr(leaf, "size", 0) >= x.size]
    assert [(leaf.shape, leaf.dtype) for leaf in big] == [(shape, x.dtype)]
    small = [leaf for leaf in jax.tree_util.tree_leaves(vjp)
             if 1 < getattr(leaf, "size", 0) < x.size]
    assert all(leaf.shape == (8,) for leaf in small)


def test_scopes_name_both_halves():
    x, gamma, beta, mm, mv, _ = _inputs((8, 4, 3, 3), 1, "float32")

    def loss(x):
        return jnp.sum(_batch_norm(x, gamma, beta, mm, mv, eps=EPS,
                                   fix_gamma=False, training=True)[0] ** 2)

    text = jax.jit(jax.grad(loss)).lower(x).as_text(debug_info=True)
    assert "batchnorm_train_fwd" in text and "batchnorm_train_bwd" in text


@pytest.mark.parametrize("hybridize", [False, True])
def test_through_record_and_backward(hybridize):
    """autograd.backward() takes jax.vjp of each recorded node: the
    hand-written backward has to come through it, eager and cached."""
    x, gamma, beta, mm, mv, w = _inputs((6, 5, 4, 3), 1, "float32", seed=7)
    net = gluon.nn.BatchNorm(in_channels=5, epsilon=EPS, momentum=MOMENTUM)
    net.initialize()
    net.gamma.set_data(nd.array(np.asarray(gamma)))
    net.beta.set_data(nd.array(np.asarray(beta)))
    net.running_mean.set_data(nd.array(np.asarray(mm)))
    net.running_var.set_data(nd.array(np.asarray(mv)))
    if hybridize:
        net.hybridize()
    data = nd.array(np.asarray(x))
    data.attach_grad()
    with autograd.record():
        out = net(data)
        loss = (out * nd.array(np.asarray(w))).sum()
    loss.backward()

    def ref(x, gamma, beta):
        return _reference(x, gamma, beta, mm, mv, 1, False)

    (r_out, r_mm, r_mv), (r_dx, r_dgamma, r_dbeta) = _value_and_grads(
        ref, x, gamma, beta, w)
    _close(out.asnumpy(), r_out, 1e-5)
    _close(data.grad.asnumpy(), r_dx, 1e-5)
    _close(net.gamma.grad().asnumpy(), r_dgamma, 1e-5)
    _close(net.beta.grad().asnumpy(), r_dbeta, 1e-5)
    _close(net.running_mean.data().asnumpy(), r_mm, 1e-6)
    _close(net.running_var.data().asnumpy(), r_mv, 1e-5)


def _conv_bn_net():
    mx.random.seed(11)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(8, 3, padding=1, in_channels=4),
            gluon.nn.BatchNorm(),
            gluon.nn.Activation("relu"),
            gluon.nn.Conv2D(8, 3, padding=1, in_channels=8),
            gluon.contrib.nn.SyncBatchNorm(in_channels=8),
            gluon.nn.Flatten(),
            gluon.nn.Dense(4))
    net.initialize(force_reinit=True)
    return net


@pytest.mark.parametrize("dtype", [None, "bfloat16"])
def test_train_step_sharded_statistics_equal_unsharded(dtype):
    """Under a dp mesh the batch axis is sharded and the two sums become
    collectives: the statistics are the global batch's, so one device
    and eight give the same running averages, parameters and loss; and
    one build of the step traces each BatchNorm once."""
    rng = np.random.RandomState(5)
    X = (rng.rand(16, 4, 8, 8) * 3 + 1).astype(np.float32)
    Y = (np.arange(16) % 4).astype(np.float32)
    traced = metrics.REGISTRY.get("mx_batchnorm_train_traced_total")
    states = {}
    for dp in (1, 8):
        net = _conv_bn_net()
        step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                         optimizer="sgd",
                         optimizer_params={"learning_rate": 0.1,
                                           "momentum": 0.9},
                         mesh=make_mesh({"dp": dp},
                                        devices=jax.devices()[:dp]),
                         dtype=dtype)
        before = None
        for i in range(3):
            if i == 1:
                before = traced.value
            loss = step(X, Y)
        # Steps two and three run the built program: nothing is traced.
        assert traced.value == before
        states[dp] = (jax.device_get(step._aux_vals),
                      jax.device_get(step._param_vals),
                      float(jax.device_get(loss)))
    # bf16: the sharded convolutions round in another order, and three
    # steps at lr 0.1 carry that into every value.
    tol = 2e-5 if dtype is None else 5e-2
    aux1, par1, loss1 = states[1]
    aux8, par8, loss8 = states[8]
    assert abs(loss1 - loss8) <= tol * max(1.0, abs(loss1))
    # Parameter names carry a per-build counter; sorted order lines the
    # two builds up.
    for a, b in zip(sorted(aux1), sorted(aux8)):
        assert aux1[a].dtype == np.float32
        _close(aux8[b], aux1[a], tol)
        assert np.abs(aux1[a] - (0.0 if "mean" in a else 1.0)).max() > 1e-4
    for a, b in zip(sorted(par1), sorted(par8)):
        _close(par8[b], par1[a], tol)


def test_counter_counts_one_per_traced_training_branch():
    """One build of a step program bumps the counter once for each
    BatchNorm in the net (53 for ResNet-50), an evaluation forward not
    at all."""
    traced = metrics.REGISTRY.get("mx_batchnorm_train_traced_total")
    net = _conv_bn_net()
    X = np.random.rand(8, 4, 8, 8).astype(np.float32)
    net(nd.array(X))           # deferred shapes settle in predict mode
    before = traced.value
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                     mesh=make_mesh({"dp": 1}, devices=jax.devices()[:1]))
    step(X, np.zeros(8, np.float32))
    assert traced.value - before == 2
    step(X, np.zeros(8, np.float32))
    assert traced.value - before == 2
