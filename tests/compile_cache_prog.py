"""One process start against JAX's persistent cache, the child of
tests/test_compile_cache.py: `python compile_cache_prog.py OUT.json`.

Builds the programs of every compile seam from fixed seeds, with the
cache where `JAX_COMPILATION_CACHE_DIR` says (placed by
`compile.enable_jax_cache()`, as the entry points place it), and writes
for each group of seams the `build` records of `compile.build_log()`
made while it ran, as `[fun_name, outcome]`, and the values it computed,
as hex. The test starts it twice on one directory: the second start has
to load every program and return the first one's values to the bit."""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=2")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import autograd, compile as cc, gluon, nd  # noqa: E402
from mxnet_tpu.cached_op import CachedOp  # noqa: E402
from mxnet_tpu.gluon import loss as gloss, nn  # noqa: E402


def _rand(seed, *shape):
    return np.random.RandomState(seed).rand(*shape).astype(np.float32)


def cached_op():
    w, x = nd.array(_rand(1, 6, 3)), nd.array(_rand(2, 2, 6))
    op = CachedOp(lambda w_, x_: nd.dot(x_, w_), num_params=1)
    with autograd.record():
        w.attach_grad()
        out = op(w, x)
    out.backward()
    return [out.asnumpy(), w.grad.asnumpy()]


def executor():
    data = mx.sym.var("data")
    net = mx.sym.FullyConnected(data, num_hidden=5, name="ccx_fc")
    args = {"ccx_fc_weight": nd.array(_rand(3, 5, 7)),
            "ccx_fc_bias": nd.zeros((5,)),
            "data": nd.array(_rand(4, 3, 7))}
    return [net.bind(mx.cpu(), args).forward(is_train=False)[0].asnumpy()]


def fused():
    mx.random.seed(5)
    net = nn.Dense(8, in_units=16, prefix="cc_fused_")
    net.initialize(force_reinit=True)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    for seed in (6, 7):
        with autograd.record():
            loss = net(nd.array(_rand(seed, 4, 16))).sum()
        loss.backward()
        trainer.step(4)
    return [p.data().asnumpy() for p in net.collect_params().values()]


def train_step():
    from mxnet_tpu.parallel import TrainStep

    mx.random.seed(11)
    net = nn.Dense(4, in_units=8, prefix="cc_step_")
    net.initialize(force_reinit=True)
    step = TrainStep(net, gloss.L2Loss(), optimizer="sgd",
                     optimizer_params={"learning_rate": 0.1})
    x, y = _rand(8, 8, 8), _rand(9, 8, 4)
    return [np.asarray(step(x, y)) for _ in range(3)]


def decode():
    from mxnet_tpu.serving import DecodeConfig, ModelSpec

    def step(w, state, tokens, pos):
        return state + w, tokens + 1

    def init(w, prompt, length):
        return nd.reshape(w, (1, 4)) * 2, length + 3

    cfg = DecodeConfig(step, state_shape=(4,), init=init, page_slots=2,
                       max_tokens=4, max_prompt_len=4)
    spec = ModelSpec("cc_decode", params=[nd.array(_rand(12, 4))],
                     max_batch=4, decode=cfg)
    backend = spec.build_backend()
    backend.warm()
    first = backend.admit(1, [5, 6, 7])
    n = backend.config.page_slots
    tok = backend.step(1, np.full(n, first, np.int32),
                       np.zeros(n, np.int32), np.array([False, True]))
    return [np.asarray(first), tok, np.asarray(backend.pages[0][0])]


def serving_mesh():
    from mxnet_tpu.serving.registry import MeshShardedModel

    model = MeshShardedModel(lambda w, x: nd.dot(x, w),
                             [nd.array(_rand(13, 4, 6))], {"tp": 2})
    return [model(nd.array(_rand(14, b, 4))).asnumpy() for b in (1, 2)]


def inference_server():
    from mxnet_tpu.serving import InferenceServer

    srv = InferenceServer(lambda w, x: nd.dot(x, w),
                          [nd.array(_rand(15, 4, 3))], item_shape=(4,),
                          max_batch=4, max_delay_ms=5)
    with srv:
        srv.warmup()
        return [srv.predict(_rand(16, 1, 4)).asnumpy(),
                np.asarray(srv.compile_count)]


GROUPS = (cached_op, executor, fused, train_step, decode, serving_mesh,
          inference_server)


def main(out_path):
    cache_dir = cc.enable_jax_cache()
    result = {"cache_dir": cache_dir, "groups": {}}
    for group in GROUPS:
        seen = len(cc.build_log())
        try:
            values = [np.ascontiguousarray(v).tobytes().hex()
                      for v in group()]
            error = None
        except Exception as exc:      # one seam's fault is that case's
            values, error = [], "%s: %s" % (type(exc).__name__, exc)
        result["groups"][group.__name__] = {
            "values": values, "error": error,
            "builds": [[r.fun_name, r.outcome]
                       for r in cc.build_log()[seen:] if r.kind == "build"]}
    with open(out_path, "w") as f:
        json.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
