"""The whole-step path: `parallel.TrainStep` over the model's net, built
as `examples/train_imagenet.py:build_train_step` builds it (mesh
``{"dp": -1}`` over the cell's devices), one executable for forward, loss,
backward and the optimizer. Workload fields: `batch`, `pool_batches`,
`dtype` (null for fp32, else the compute type; masters stay fp32)."""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.pool import make_pool


class Runner:
    def __init__(self, cfg, wl, seed, devices, model):
        from mxnet_tpu.parallel import TrainStep, make_mesh
        from mxnet_tpu.parallel.mesh import data_sharding

        if wl["batch"] % len(devices):
            raise ValueError("batch %d does not divide over %d devices"
                             % (wl["batch"], len(devices)))
        self.cfg, self.model = cfg, model
        self.net, self.loss_fn = model.build(cfg, seed)
        mesh = make_mesh({"dp": -1}, devices=devices)
        self.step_fn = TrainStep(
            self.net, self.loss_fn, optimizer=cfg["optimizer"]["name"],
            optimizer_params=dict(cfg["optimizer"]["params"]),
            mesh=mesh, dtype=wl.get("dtype"))
        self.dtype = jnp.dtype(wl.get("dtype") or "float32")
        self.sharding = data_sharding(mesh)
        self.pool = make_pool(model, cfg, seed, wl["batch"],
                              wl["pool_batches"], self.sharding)
        self.items_per_step = wl["batch"] * cfg.get("bptt", 1)
        self.calls = 0
        self._eval = {}

    def step(self):
        x, y = self.pool[self.calls % len(self.pool)]
        self.calls += 1
        return self.step_fn(x, y)

    def read_loss(self, loss):
        return float(loss)

    def params(self):
        """name -> trained fp32 value, in the net's collection order."""
        st = self.step_fn
        vals = dict(st._param_vals, **st._aux_vals)
        return collections.OrderedDict(
            (name, vals[name]) for name in self.net.collect_params())

    def masters(self):
        """name -> the trainable masters as the optimizer holds them."""
        return dict(self.step_fn._param_vals)

    def eval_forward(self, x, y, train=False):
        """The system's evaluation forward with the trained values, in
        the cell's compute type (every value cast inside the program, as
        `net.cast` would: BatchNorm's evaluation branch promotes to the
        type of its running statistics), and its loss in fp32:
        (fp32 logits, mean loss). `train`: in training mode, as the step
        runs it (BatchNorm normalises by the batch's own statistics; the
        running statistics it would write are dropped)."""
        from mxnet_tpu import autograd
        from mxnet_tpu.gluon.parameter import override
        from mxnet_tpu.ndarray import NDArray

        st = self.step_fn
        if train not in self._eval:
            cdt = self.dtype

            def fwd(pvals, aux_vals, data, labels):
                def cast(a):
                    return a.astype(cdt) \
                        if jnp.issubdtype(a.dtype, jnp.floating) else a
                mapping = {p: NDArray(cast(pvals[p.name]))
                           for p in st._train_params}
                mapping.update({p: NDArray(cast(aux_vals[p.name]))
                                for p in st._aux_params})
                with autograd.pause(train_mode=train), override(mapping):
                    out = self.net(NDArray(cast(data)))
                    out = NDArray(out._data.astype(jnp.float32))
                    loss = self.loss_fn(out, NDArray(labels))
                return out._data, jnp.mean(loss._data)

            self._eval[train] = jax.jit(fwd)
        logits, loss = self._eval[train](st._param_vals, st._aux_vals,
                                         x, y)
        return np.asarray(logits), float(loss)


setup = Runner
