#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the
chip: the main path once, through the entry points users call, at the
full width of ResNet-50 v1 (1000 classes, 3x224x224), random weights
from a seed.

    python chip_smoke.py            # one chip: train, gluon, serve, kernel
    python chip_smoke.py --chips 4  # four chips: dp=4 TrainStep only

Phases (each a function of its sizes, so tests/test_chip_smoke.py drives
them on the CPU at tiny sizes; the script itself has no CPU mode):

* train  — examples/train_imagenet.py:build_train_step -> TrainStep, a
  few steps on a fixed synthetic batch (b32 fp32, b128 bf16).
* gluon  — hybridized HybridBlock + autograd.record() + Trainer.step.
* serve  — serving.InferenceServer over the forward, bucket ladder
  (1, 8, 32), answers against a direct forward.
* kernel — flash_attention forward and backward, compiled, against a
  plain fp32 jax.numpy attention.
* dp     — (--chips 4 only) TrainStep on make_mesh({"dp": 4}) against
  the one-device TrainStep at the same global batch and seed.

One process, no child. Exits non-zero, and prints no result, when JAX
finds no TPU or any phase raises or any comparison fails. Earlier lines
are one JSON object per phase (set-up facts, not metrics); the LAST line
is {"ok": true, "device": {"platform", "kind", "count"}} as JAX reports
the device.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "examples"))

# The example's default lr (0.1, no warm-up) overshoots on one fixed
# batch within the first steps; the smoke wants a loss that falls.
_LR = 0.01


def _batch(seed, batch, image, classes):
    rng = np.random.RandomState(seed)
    return (rng.rand(batch, *image).astype(np.float32),
            rng.randint(0, classes, batch).astype(np.float32))


def _check(cond, what):
    if not cond:
        raise AssertionError(what)


def _run_steps(step, x, y, steps):
    """`steps` calls, each closed by block_until_ready. Returns (losses,
    seconds to the first result, median seconds of a later step). The
    first call takes the host batch, as a user's would, and lays the
    parameters out; after it the batch stays on the device, so a later
    step is not timed with its upload."""
    import jax

    losses, times = [], []
    for i in range(steps):
        t0 = time.perf_counter()
        loss = jax.block_until_ready(step(x, y))
        times.append(time.perf_counter() - t0)
        losses.append(float(loss))
        if i == 0:
            x, y = jax.device_put((x, y), step._data_sharding)
    return losses, times[0], float(np.median(times[1:]))


def _check_losses(losses, classes):
    _check(all(math.isfinite(v) for v in losses),
           "non-finite loss: %r" % (losses,))
    # An untrained classifier sits at ln(classes) plus the spread of
    # its logits (default init: about 1.3x).
    ratio = losses[0] / math.log(classes)
    _check(0.7 < ratio < 1.5,
           "first loss %.4f is not near ln(%d)" % (losses[0], classes))
    _check(losses[-1] < losses[0],
           "loss did not fall: %r" % (losses,))


def phase_train(network, batch, dtype, image, classes, steps, seed,
                devices, **net_kwargs):
    """TrainStep through the example's builder: one executable for
    forward + loss + backward + SGD-momentum."""
    import mxnet_tpu as mx
    from train_imagenet import build_train_step

    mx.random.seed(seed)
    step = build_train_step(network, classes, dtype, devices=devices,
                            lr=_LR, **net_kwargs)
    x, y = _batch(seed, batch, image, classes)
    losses, first_s, step_s = _run_steps(step, x, y, steps)
    _check_losses(losses, classes)
    return {"phase": "train", "network": network, "batch": batch,
            "dtype": dtype or "float32", "first_s": round(first_s, 3),
            "step_s": round(step_s, 4),
            "losses": [round(v, 4) for v in losses]}


def phase_gluon(network, batch, image, classes, steps, seed,
                **net_kwargs):
    """The imperative path: hybridized block (cached_op.py) under
    autograd.record(), Trainer.step through the fused update
    (fused_update.py), then the optimizer state read back through its
    lazy flat views."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, fused_update, gluon
    from train_imagenet import build_net

    mx.random.seed(seed)
    net = build_net(network, classes, **net_kwargs)
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": _LR, "momentum": 0.9,
                             "wd": 1e-4})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    xs, ys = _batch(seed, batch, image, classes)
    x, y = mx.nd.array(xs), mx.nd.array(ys)
    losses, times = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(batch)
        losses.append(float(loss.mean().asnumpy()))
        times.append(time.perf_counter() - t0)
    _check(all(math.isfinite(v) for v in losses),
           "non-finite loss: %r" % (losses,))
    _check(trainer._applier.num_compiles >= 1,
           "the fused update never compiled (per-parameter fallback)")
    # Momentum is read AFTER the last donating apply: a view that still
    # pointed into a donated buffer would raise here.
    states = [s for s in trainer._updater.states.values() if s is not None]
    _check(states, "no optimizer state to read back")
    moms = [np.abs(s.asnumpy()).max() for s in states]
    _check(all(np.isfinite(m) for m in moms) and max(moms) > 0,
           "momentum read back as zero or non-finite")
    _check(all(np.isfinite(p.data().asnumpy()).all()
               for p in net.collect_params().values()),
           "non-finite parameter after the steps")
    return {"phase": "gluon", "network": network, "batch": batch,
            "first_s": round(times[0], 3),
            "step_s": round(float(np.median(times[1:])), 4),
            "losses": [round(v, 4) for v in losses],
            "fused_compiles": trainer._applier.num_compiles,
            "donate": fused_update.donate_enabled()}


def phase_serve(network, buckets, request_rows, image, classes, seed,
                **net_kwargs):
    """InferenceServer over the eval forward: warm-up compiles one
    executable per bucket and none after it; answers equal a direct
    forward of the same net."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, serving
    from mxnet_tpu.gluon.parameter import override
    from train_imagenet import build_net

    mx.random.seed(seed)
    net = build_net(network, classes, **net_kwargs)
    xs, _ = _batch(seed, sum(request_rows), image, classes)
    with autograd.pause(train_mode=False):
        want = net(mx.nd.array(xs)).asnumpy()     # also finishes init
    params = list(net.collect_params().values())

    def forward(*args):
        with override(dict(zip(params, args[:-1]))):
            return net(args[-1])

    t0 = time.perf_counter()
    srv = serving.InferenceServer(
        forward, [p.data() for p in params], item_shape=image,
        buckets=buckets, max_delay_ms=1.0)
    warm_s = time.perf_counter() - t0
    try:
        _check(srv.compile_count == len(buckets),
               "warm-up compiled %d executables for buckets %r"
               % (srv.compile_count, buckets))
        got, times, off = [], [], 0
        for rows in request_rows:
            t0 = time.perf_counter()
            got.append(srv.predict(xs[off:off + rows]).asnumpy())
            times.append(time.perf_counter() - t0)
            off += rows
        _check(srv.compile_count == len(buckets),
               "a request compiled: %d executables" % srv.compile_count)
    finally:
        srv.shutdown()
    got = np.concatenate(got)
    _check(got.shape == want.shape == (sum(request_rows), classes),
           "answer shape %r" % (got.shape,))
    _check(np.isfinite(got).all(), "non-finite answer")
    # Bucket padding changes the executable, not the math: fp32 convs
    # on the MXU round their inputs, so allow that much.
    err = float(np.abs(got - want).max() / np.abs(want).max())
    _check(err < 2e-2, "served answers differ from the direct forward: "
           "rel err %.3g" % err)
    return {"phase": "serve", "network": network, "buckets": list(buckets),
            "requests": list(request_rows), "warmup_s": round(warm_s, 3),
            "request_s": [round(t, 4) for t in times],
            "compiles": srv.compile_count, "rel_err": err}


def _dense_attention(q, k, v, causal):
    """Plain fp32 jax.numpy attention — the reference. HIGHEST, because
    the MXU's default rounds fp32 matmul operands to bf16."""
    import jax
    import jax.numpy as jnp

    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=hi) \
        * q.shape[-1] ** -0.5
    if causal:
        t = q.shape[2]
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v,
                      precision=hi)


def phase_kernel(shape, dtype, seed, interpret):
    """flash_attention forward and backward (one fused kernel at this
    size) against the dense reference. With interpret=False the lowered
    program must hold the Mosaic kernels (tpu_custom_call), not an
    interpreter's HLO."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_attention import flash_attention

    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v, g = (jax.random.normal(kk, shape, jnp.float32).astype(dtype)
                  for kk in keys)

    def run(attn):
        def f(q_, k_, v_, g_):
            out, vjp = jax.vjp(attn, q_, k_, v_)
            return (out,) + vjp(g_.astype(out.dtype))
        return jax.jit(f)

    flash = run(lambda a, b, c: flash_attention(a, b, c, causal=True,
                                                interpret=interpret))
    dense = run(lambda a, b, c: _dense_attention(a, b, c, True))
    if not interpret:
        _check("tpu_custom_call" in flash.lower(q, k, v, g).as_text(),
               "no tpu_custom_call in the lowered flash attention")
    t0 = time.perf_counter()
    got = jax.block_until_ready(flash(q, k, v, g))
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(flash(q, k, v, g))
    again_s = time.perf_counter() - t0
    want = dense(q, k, v, g)
    # bf16 keeps 8 bits: outputs and gradients are rounded once on the
    # way out and the probability tiles once on the way into the MXU
    # (which rounds fp32 operands the same way by default).
    tol = 3e-2
    errs = {}
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        _check(np.isfinite(a).all(), "non-finite %s" % name)
        errs[name] = float(np.abs(a - b).max() / np.abs(b).max())
        _check(errs[name] < tol, "flash %s differs from the fp32 "
               "reference: rel err %.3g" % (name, errs[name]))
    return {"phase": "kernel", "shape": list(shape),
            "dtype": jnp.dtype(dtype).name, "compiled": not interpret,
            "first_s": round(first_s, 3), "call_s": round(again_s, 4),
            "rel_err": errs}


def phase_dp(network, batch, dtype, image, classes, steps, seed, devices,
             tol, **net_kwargs):
    """The path across chips: TrainStep on a dp mesh over `devices`
    against the one-device TrainStep at the same global batch and seed.
    Both run the same math (GSPMD keeps BatchNorm's statistics global);
    `tol` bounds how far reduction order and rounding may carry the two
    trajectories apart, relative to the loss."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from train_imagenet import build_train_step

    n = len(devices)
    x, y = _batch(seed, batch, image, classes)

    def run(devs):
        mx.random.seed(seed)
        step = build_train_step(network, classes, dtype, devices=devs,
                                lr=_LR, **net_kwargs)
        return (step,) + _run_steps(step, x, y, steps)

    _, one, _, one_step_s = run(devices[:1])
    step, losses, first_s, step_s = run(devices)
    _check_losses(losses, classes)
    for i, (a, b) in enumerate(zip(losses, one)):
        _check(abs(a - b) <= tol * abs(b),
               "step %d: dp=%d loss %.5f, one-device loss %.5f"
               % (i, n, a, b))

    def spread(arr):
        return {s.device for s in arr.addressable_shards}

    xd = jax.device_put(jnp.asarray(x), step._data_sharding)
    _check(len(spread(xd)) == n and
           all(s.data.shape[0] == batch // n
               for s in xd.addressable_shards),
           "the batch is not split over %d devices" % n)
    for leaf in jax.tree_util.tree_leaves(
            (step._param_vals, step._opt_state, step._aux_vals)):
        _check(spread(leaf) == set(devices),
               "a step output does not live on all %d devices" % n)
    # The program the step ran, compiled again for its text and sizes
    # (a hit in the persistent cache).
    compiled = step._jitted.lower(
        step._param_vals, step._opt_state, step._aux_vals, xd,
        jax.device_put(jnp.asarray(y), step._data_sharding),
        jnp.float32(step.lr), jnp.float32(1), mx.random.next_key()
    ).compile()
    _check("all-reduce" in compiled.as_text(),
           "no all-reduce in the dp=%d program" % n)
    mem = compiled.memory_analysis()
    return {"phase": "dp", "network": network, "batch": batch,
            "dtype": dtype or "float32", "devices": n,
            "first_s": round(first_s, 3), "step_s": round(step_s, 4),
            "losses": [round(v, 4) for v in losses],
            "one_device_losses": [round(v, 4) for v in one],
            "one_device_step_s": round(one_step_s, 4),
            "all_reduce": True,
            "bytes_per_device": {
                "arguments": mem.argument_size_in_bytes,
                "outputs": mem.output_size_in_bytes,
                "temporaries": mem.temp_size_in_bytes,
                "aliased": mem.alias_size_in_bytes}}


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 runs the dp=4 phase and its one-device "
                             "comparison, and no other phase")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from mxnet_tpu.compile import enable_jax_cache

    cache_dir = enable_jax_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("chip_smoke.py needs a TPU; JAX found platform %r"
              % devices[0].platform, file=sys.stderr)
        return 2
    if len(devices) != args.chips:
        print("chip_smoke.py --chips %d: JAX reports %d device(s)"
              % (args.chips, len(devices)), file=sys.stderr)
        return 2

    full = dict(network="resnet50", image=(3, 224, 224), classes=1000,
                seed=args.seed)
    if args.chips == 4:
        phases = [lambda: phase_dp(batch=128, dtype="bfloat16", steps=3,
                                   devices=devices, tol=2e-2, **full)]
    else:
        phases = [
            lambda: phase_train(batch=32, dtype=None, steps=5,
                                devices=devices, **full),
            lambda: phase_train(batch=128, dtype="bfloat16", steps=5,
                                devices=devices, **full),
            lambda: phase_gluon(batch=32, steps=2, **full),
            lambda: phase_serve(buckets=(1, 8, 32),
                                request_rows=(1, 5, 32, 1, 5, 32), **full),
            lambda: phase_kernel((4, 16, 2048, 128), jnp.bfloat16,
                                 args.seed, interpret=False),
        ]
    print(json.dumps({"jax_cache_dir": cache_dir}), flush=True)
    for phase in phases:
        print(json.dumps(phase()), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
