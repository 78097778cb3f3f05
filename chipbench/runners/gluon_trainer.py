"""The imperative path, as `chip_smoke.py:phase_gluon` drives it: a
hybridized block under `autograd.record()`, `loss.backward()`,
`Trainer.step` through the fused update, on the first of the cell's
devices. Workload fields: `batch`, `pool_batches`. fp32 throughout."""
from __future__ import annotations

import collections

import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import SingleDeviceSharding

from chipbench.pool import make_pool


class Runner:
    def __init__(self, cfg, wl, seed, devices, model):
        from mxnet_tpu import gluon
        from mxnet_tpu.ndarray import NDArray

        if len(devices) != 1:
            raise ValueError("the gluon_trainer runner drives one device")
        self.cfg, self.model = cfg, model
        self.net, self.loss_fn = model.build(cfg, seed)
        self.net.hybridize()
        self.trainer = gluon.Trainer(
            self.net.collect_params(), cfg["optimizer"]["name"],
            dict(cfg["optimizer"]["params"]))
        self.batch = wl["batch"]
        pool = make_pool(model, cfg, seed, self.batch, wl["pool_batches"],
                         SingleDeviceSharding(devices[0]))
        self.pool = [(NDArray(x), NDArray(y)) for x, y in pool]
        self.items_per_step = self.batch * cfg.get("bptt", 1)
        self.calls = 0

    def step(self):
        from mxnet_tpu import autograd

        x, y = self.pool[self.calls % len(self.pool)]
        self.calls += 1
        with TraceAnnotation("chipbench::forward"):
            with autograd.record():
                loss = self.loss_fn(self.net(x), y)
        with TraceAnnotation("chipbench::backward"):
            loss.backward()
        with TraceAnnotation("chipbench::update"):
            self.trainer.step(self.batch)
        return loss

    def read_loss(self, loss):
        return float(loss.mean().asnumpy())

    def params(self):
        return collections.OrderedDict(
            (name, p.data()._data)
            for name, p in self.net.collect_params().items())

    def masters(self):
        """name -> the trainable masters as the Trainer updates them."""
        return {name: p.data()._data
                for name, p in self.net.collect_params().items()
                if p.grad_req != "null"}

    def eval_forward(self, x, y, train=False):
        """(fp32 logits, mean loss) of the net's evaluation forward;
        `train`: in training mode (BatchNorm by the batch's statistics,
        the running statistics it would write dropped)."""
        from mxnet_tpu import autograd
        from mxnet_tpu.gluon.parameter import override
        from mxnet_tpu.ndarray import NDArray

        own = {p: p.data() for p in self.net.collect_params().values()}
        with autograd.pause(train_mode=train), override(own):
            out = self.net(NDArray(x))
            loss = self.loss_fn(out, NDArray(y))
        return np.asarray(out._data, np.float32), \
            float(loss.mean().asnumpy())


setup = Runner
