"""`train_step` for a net with sparse layers (`gluon.nn.SparseMoE`): the
same whole-step path, and an evaluation forward that also records, for
every sparse layer, what its router multiplied (input and weight in
fp32, pinned as arrays) and which experts it chose on the sample.
`params()` hands them to the model's reference beside the trained
values (`<layer>router_input`, `<layer>router_weight`,
`<layer>selected`), so that the reference can judge the selection on
the operands the router really had (`models/deepseek_v3.py:_selection`)
and does not mistake a near-tie that rounding upstream swapped for a
fault, nor a router that scores in too low a precision for rounding.
Non-gradient state keeps its own type in the evaluation, as it does in
`TrainStep`."""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_base = harness.load_module(_ROOT, "runners", "train_step")


class Runner(_base.Runner):
    def __init__(self, cfg, wl, seed, devices, model):
        super().__init__(cfg, wl, seed, devices, model)
        self._told = {}

    def params(self):
        """The trained values, and what the last evaluation forward's
        routers saw and chose."""
        values = super().params()
        values.update(self._told)
        return values

    def _evaluation(self):
        """The jitted evaluation forward: (values, non-gradient state,
        tokens, labels) -> (fp32 logits, mean loss, what the routers
        multiplied and chose)."""
        from mxnet_tpu import autograd
        from mxnet_tpu.gluon.nn import SparseMoE
        from mxnet_tpu.gluon.parameter import override
        from mxnet_tpu.ndarray import NDArray

        st, cdt = self.step_fn, self.dtype
        sparse = []
        self.net.apply(lambda b: sparse.append(b)
                       if isinstance(b, SparseMoE) else None)

        def fwd(pvals, aux_vals, data, labels):
            def cast(a):
                return a.astype(cdt) \
                    if jnp.issubdtype(a.dtype, jnp.floating) else a
            mapping = {p: NDArray(cast(pvals[p.name]))
                       for p in st._train_params}
            mapping.update({p: NDArray(aux_vals[p.name])
                            for p in st._aux_params})
            told = {}

            def pinned(block):
                """`block.route` on fp32 operands that exist as
                arrays, which are also what is handed over. The TPU
                compiler may skip a rounding to bf16 inside a fused
                computation (excess precision), so neither a bf16
                output of this program nor a second conversion of
                it is what the router multiplied; behind the
                barrier the two are one array."""
                route = block.route

                def spy(F, tokens, weight, steps):
                    tokens, weight = jax.lax.optimization_barrier(
                        (tokens._data.astype(jnp.float32),
                         weight._data.astype(jnp.float32)))
                    out = route(F, NDArray(tokens), NDArray(weight),
                                steps)
                    told[block.prefix + "router_input"] = \
                        tokens.reshape(data.shape + (-1,))
                    told[block.prefix + "router_weight"] = weight
                    told[block.prefix + "selected"] = \
                        out[1]._data.reshape(data.shape + (-1,))
                    return out

                return spy

            for block in sparse:
                block.route = pinned(block)
            try:
                with autograd.pause(train_mode=False), override(mapping):
                    out = self.net(NDArray(cast(data)))
                    out = NDArray(out._data.astype(jnp.float32))
                    loss = self.loss_fn(out, NDArray(labels))
            finally:
                for block in sparse:
                    del block.route
            return out._data, jnp.mean(loss._data), told

        return jax.jit(fwd)

    def eval_forward(self, x, y):
        st = self.step_fn
        if False not in self._eval:     # evaluation mode only
            self._eval[False] = self._evaluation()
        logits, loss, self._told = self._eval[False](
            st._param_vals, st._aux_vals, x, y)
        return np.asarray(logits), float(loss)


setup = Runner
