"""Whole-step SPMD compilation: forward + loss + backward + optimizer
update as ONE XLA executable over a device mesh.

This is the TPU-blessed training path (SURVEY.md §7 "hard parts":
per-op dispatch is µs-scale in the reference's engine but ms-scale for
XLA launches, so the imperative Trainer loop can never reach reference
throughput — compiling the whole step can and does). Equivalent
reference machinery: GraphExecutor's fwd+bwd graph with bulked segments
(graph_executor.cc:1186) + kvstore push/pull, here fused so the
gradient all-reduce (psum XLA inserts for the sharded-batch mean loss)
overlaps backward compute on the ICI.

Buffer donation of params/optimizer state gives in-place updates (the
engine-var mutation semantics of the reference, expressed as XLA
aliasing).
"""
from __future__ import annotations

import logging
import resource
import threading
import time
import weakref

import numpy as np

import jax
import jax.numpy as jnp

from .. import autograd
from .. import compile as _cc
from .. import random as _random
from ..telemetry import attribution as _attr
from ..telemetry import healthplane as _hp
from ..telemetry import metrics as _tm
from ..telemetry import trace as _trace
from ..telemetry import watchdog as _watchdog
from ..ndarray.ndarray import NDArray
from ..gluon.parameter import override
from .mesh import make_mesh, data_sharding, replicate, shard_params, \
    NamedSharding, P

__all__ = ["TrainStep"]

# Step-path telemetry: dispatch-side wall time per __call__ (the device
# truth for the fused step lives in the XPlane trace — under async
# dispatch this histogram measures what the HOST pays per step, which
# is exactly what the <=2% bench overhead contract bounds).
_step_seconds = _tm.REGISTRY.histogram(
    "mx_train_step_seconds",
    "TrainStep.__call__ wall time (host dispatch path)")
_steps_total = _tm.REGISTRY.counter(
    "mx_train_steps_total", "Completed TrainStep calls")
_switches_total = _tm.REGISTRY.counter(
    "mx_train_step_involuntary_switches_total",
    "Involuntary context switches of the stepping thread, summed over "
    "its TrainStep calls and the time between them (a host that was "
    "taken away)")
# What the step executable itself says it needs (memory_analysis() of the
# program the last step ran), folded when the registry is collected.
_PROGRAM_GAUGES = {
    "total": _tm.REGISTRY.gauge(
        "mx_step_program_bytes", "Device bytes the step executable needs "
        "to run: arguments + outputs - aliased + temporaries + code (the "
        "largest live TrainStep)"),
    "temp": _tm.REGISTRY.gauge(
        "mx_step_program_temp_bytes", "Temporaries of that executable"),
}
_program_recompiled = _tm.REGISTRY.counter(
    "mx_step_program_recompiled_total", "Demands of a step executable's "
    "statistics that compiled a program instead of finding the step's "
    "own (should stay 0)")
_live_steps = weakref.WeakSet()
_log = logging.getLogger(__name__)


def _host_usage():
    """(CPU seconds, involuntary context switches) of this thread."""
    return (time.thread_time(),
            resource.getrusage(resource.RUSAGE_THREAD).ru_nivcsw)


def _fold_step_programs():
    """Registry.on_collect hook: the gauges of the largest live step
    program. The first collect after a step pays one lookup of the
    cached executable; nothing inside a step does."""
    best = None
    for ts in list(_live_steps):
        try:
            stats = ts.program_stats()
        except RuntimeError:          # arguments given away to a running step
            continue
        if stats and (best is None or stats["total_bytes"]
                      > best["total_bytes"]):
            best = stats
    if best is not None:
        _PROGRAM_GAUGES["total"].set(best["total_bytes"])
        _PROGRAM_GAUGES["temp"].set(best["temp_bytes"])


_tm.REGISTRY.on_collect(_fold_step_programs)


def _as_pair(res):
    """(new_weight, single_state) -> (new_weight, (single_state,))."""
    w, s = res
    return w, (s,)


class TrainStep:
    """Compile `net` + `loss_fn` + optimizer into one sharded step.

    Parameters
    ----------
    net : initialized gluon Block (params live on one context; TrainStep
        takes ownership of the values and shards them over the mesh).
    loss_fn : callable (pred NDArray, label NDArray) -> per-sample loss.
    optimizer : sgd | nag | signum | signsgd | adam | rmsprop |
        adagrad | adadelta | ftrl | ftml | nadam | dcasgd | sgld |
        lbsgd — the SAME update bodies as the Trainer path
        (ops/optimizer_ops.py), fused into the step. One documented
        deviation: NADAM's momentum-schedule product is per-parameter
        here (the paper's definition), while the imperative Trainer
        reproduces the reference's optimizer-instance-shared schedule
        (optimizer.py:466 — it advances once per parameter per step);
        the two agree exactly for single-parameter groups.
    optimizer_params : dict — learning_rate, momentum, wd, beta1/2, ...
        learning_rate is a *runtime input* to the executable, so LR
        schedules don't retrace.
    mesh : jax Mesh (default: all devices on one 'dp' axis).
    param_rule : callable(name, shape, mesh) -> PartitionSpec for tensor
        parallelism (default Megatron-ish rule in mesh.shard_params).
    dtype : compute dtype for mixed precision (e.g. 'bfloat16'). Master
        weights and optimizer state stay fp32 — params/activations are
        cast inside the compiled step (XLA fuses the casts into the
        matmuls/convs, which then run bf16 on the MXU) and gradients flow
        back to the fp32 masters. This is the reference's multi_precision
        / mp_sgd_update contract (python/mxnet/optimizer.py:201-266,
        src/operator/optimizer_op.cc mp_sgd) in XLA form.
    deterministic_reduction : bool — aggregate gradients in explicit
        shard order (see `_make_deterministic_grad`) so training state
        is bit-for-bit identical across process topologies (1 host vs
        N hosts of the same mesh). dp-only meshes; slightly more
        bandwidth (all_gather instead of fused psum).
    """

    def __init__(self, net, loss_fn, optimizer="sgd", optimizer_params=None,
                 mesh=None, param_rule=None, dtype=None,
                 deterministic_reduction=False):
        self.deterministic_reduction = bool(deterministic_reduction)
        self.net = net
        self.loss_fn = loss_fn
        self.mesh = mesh if mesh is not None else make_mesh()
        opt_params = dict(optimizer_params or {})
        self._explicit = frozenset(opt_params)
        self.lr = float(opt_params.pop("learning_rate", 0.01))
        self.optimizer = optimizer
        self.momentum = float(opt_params.pop("momentum", 0.0))
        # Defaults match mxnet_tpu.optimizer.Optimizer so Trainer and
        # TrainStep train identically on the same optimizer_params.
        self.wd = float(opt_params.pop("wd", 0.0))
        self.beta1 = float(opt_params.pop("beta1", 0.9))
        self.beta2 = float(opt_params.pop("beta2", 0.999))
        self.epsilon = float(opt_params.pop("epsilon", 1e-8)) \
            if "epsilon" in opt_params else None
        self.rescale_grad = float(opt_params.pop("rescale_grad", 1.0))
        clip = opt_params.pop("clip_gradient", None)
        self.clip_gradient = None if clip is None else float(clip)
        # remaining knobs are optimizer-family specific (gamma1, rho,
        # lamda1, ...), resolved by _make_opt_rule with the same
        # defaults as mxnet_tpu.optimizer's classes
        self._opt_extra = opt_params
        self._opt_init = None          # custom state init (e.g. DCASGD)
        self._opt_needs_key = False    # stochastic update (e.g. SGLD)
        self._opt_n_states, self._opt_update = self._make_opt_rule()
        self.num_update = 0

        self._dtype = dtype
        self._param_rule = param_rule
        self._jitted = None
        self._materialized = False
        self._multiproc = False
        # Readiness slot for /readyz: claimed lazily on the FIRST
        # __call__ (a TrainStep built but never stepped — eval-only, a
        # discarded retune — must not leave a permanently not-ready
        # ghost; there is no close() to release one), flipped ready
        # once the warmup compile lands, so an orchestrator's readiness
        # gate holds traffic/elastic peers off a rank still paying
        # whole-step XLA compile.
        self._hp_component = None
        self._hp_ready = False
        # Shapes, dtypes and shardings of the last call's (x, y, lr, t, key)
        # (never the arrays: a batch must not outlive its step) and, once
        # asked for, the executable it ran: program_stats() / program_text().
        self._last_args = None
        self._program = None
        # (thread, CPU seconds, involuntary switches) at the last step's end
        self._host_mark = None
        _live_steps.add(self)

    def _make_opt_rule(self):
        """(n_states, update_fn) for the configured optimizer.

        update_fn(param, grad, states_tuple, lr, t) ->
        (new_param, new_states_tuple). The bodies are the SAME pure
        FCompute functions the imperative Trainer path dispatches
        (ops/optimizer_ops.py), so TrainStep and Trainer produce
        bit-identical updates for every supported family."""
        from ..ops import optimizer_ops as oo

        name = self.optimizer.lower()
        mom, wd, rs = self.momentum, self.wd, self.rescale_grad
        clip = -1.0 if self.clip_gradient is None else self.clip_gradient
        b1, b2 = self.beta1, self.beta2
        ex = self._opt_extra

        def eps(default):
            return self.epsilon if self.epsilon is not None else default

        def check_extra(*allowed):
            unknown = set(ex) - set(allowed)
            if unknown:
                raise ValueError(
                    "TrainStep(%s) got unsupported optimizer_params %s"
                    % (name, sorted(unknown)))

        if name == "sgd":
            check_extra()
            if mom > 0:
                return 1, lambda p, g, s, lr, t: _as_pair(
                    oo._sgd_mom_update(p, g, s[0], lr=lr, momentum=mom,
                                       wd=wd, rescale_grad=rs,
                                       clip_gradient=clip))
            return 0, lambda p, g, s, lr, t: (
                oo._sgd_update(p, g, lr=lr, wd=wd, rescale_grad=rs,
                               clip_gradient=clip), ())
        if name == "nag":
            check_extra()
            if mom > 0:
                return 1, lambda p, g, s, lr, t: _as_pair(
                    oo._nag_mom_update(p, g, s[0], lr=lr, momentum=mom,
                                       wd=wd, rescale_grad=rs,
                                       clip_gradient=clip))
            return 0, lambda p, g, s, lr, t: (
                oo._sgd_update(p, g, lr=lr, wd=wd, rescale_grad=rs,
                               clip_gradient=clip), ())
        if name in ("signum", "signsgd"):
            check_extra("wd_lh")
            # Trainer defaults: Signum momentum=0.9, SignSGD 0.0 — but
            # an explicitly passed momentum wins for BOTH (SignSGD only
            # setdefault's it, optimizer.py:261).
            if "momentum" in self._explicit:
                sig_mom = mom
            else:
                sig_mom = 0.9 if name == "signum" else 0.0
            wd_lh = float(ex.get("wd_lh", 0.0))
            if sig_mom > 0:
                return 1, lambda p, g, s, lr, t: _as_pair(
                    oo._signum_update(p, g, s[0], lr=lr, momentum=sig_mom,
                                      wd=wd, rescale_grad=rs,
                                      clip_gradient=clip, wd_lh=wd_lh))
            return 0, lambda p, g, s, lr, t: (
                oo._signsgd_update(p, g, lr=lr, wd=wd, rescale_grad=rs,
                                   clip_gradient=clip), ())
        if name == "adam":
            check_extra()
            e = eps(1e-8)

            def adam(p, g, s, lr, t):
                lr_t = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
                w, m, v = oo._adam_update(
                    p, g, s[0], s[1], lr=lr_t, beta1=b1, beta2=b2,
                    epsilon=e, wd=wd, rescale_grad=rs, clip_gradient=clip)
                return w, (m, v)

            return 2, adam
        if name == "rmsprop":
            check_extra("gamma1", "gamma2", "centered", "clip_weights")
            g1 = float(ex.get("gamma1", 0.9))
            g2 = float(ex.get("gamma2", 0.9))
            cw = float(ex.get("clip_weights", -1.0))
            e = eps(1e-8)
            if ex.get("centered", False):
                def rmsc(p, g, s, lr, t):
                    w, n, gb, d = oo._rmspropalex_update(
                        p, g, s[0], s[1], s[2], lr=lr, gamma1=g1,
                        gamma2=g2, epsilon=e, wd=wd, rescale_grad=rs,
                        clip_gradient=clip, clip_weights=cw)
                    return w, (n, gb, d)

                return 3, rmsc
            return 1, lambda p, g, s, lr, t: _as_pair(
                oo._rmsprop_update(p, g, s[0], lr=lr, gamma1=g1,
                                   epsilon=e, wd=wd, rescale_grad=rs,
                                   clip_gradient=clip, clip_weights=cw))
        if name == "adagrad":
            check_extra("eps")
            # AdaGrad spells its knob "eps" (optimizer.py:322) but an
            # "epsilon" kwarg must not be silently discarded either
            e = float(ex.get("eps", eps(1e-7)))
            return 1, lambda p, g, s, lr, t: _as_pair(
                oo._adagrad_update(p, g, s[0], lr=lr, epsilon=e, wd=wd,
                                   rescale_grad=rs, clip_gradient=clip))
        if name == "adadelta":
            check_extra("rho")
            rho = float(ex.get("rho", 0.90))
            e = eps(1e-5)

            def adad(p, g, s, lr, t):
                w, ag, ad = oo._adadelta_update(
                    p, g, s[0], s[1], rho=rho, epsilon=e, wd=wd,
                    rescale_grad=rs, clip_gradient=clip)
                return w, (ag, ad)

            return 2, adad
        if name == "ftrl":
            check_extra("lamda1", "beta")
            lam = float(ex.get("lamda1", 0.01))
            beta = float(ex.get("beta", 1.0))

            def ftrl(p, g, s, lr, t):
                w, z, n = oo._ftrl_update(
                    p, g, s[0], s[1], lr=lr, lamda1=lam, beta=beta,
                    wd=wd, rescale_grad=rs, clip_gradient=clip)
                return w, (z, n)

            return 2, ftrl
        if name == "ftml":
            check_extra()
            e = eps(1e-8)
            fb1 = self.beta1 if "beta1" in self._explicit else 0.6

            def ftml(p, g, s, lr, t):
                w, d, v, z = oo._ftml_update(
                    p, g, s[0], s[1], s[2], lr=lr, beta1=fb1, beta2=b2,
                    epsilon=e, wd=wd, rescale_grad=rs, clip_grad=clip,
                    t=t)
                return w, (d, v, z)

            return 3, ftml
        if name == "nadam":
            check_extra("schedule_decay")
            e = eps(1e-8)
            decay = float(ex.get("schedule_decay", 0.004))
            # The running schedule product is state starting at 1.0 —
            # a 0.0 "fresh" sentinel would collide with genuine float32
            # underflow of the product (~step 130 at default betas) and
            # reset the bias correction mid-training.
            self._opt_init = lambda v: (
                jnp.zeros_like(v, dtype=jnp.float32),
                jnp.zeros_like(v, dtype=jnp.float32),
                jnp.ones_like(v, dtype=jnp.float32))

            def nadam(p, g, s, lr, t):
                mean, var, sched = s
                g = g * rs + wd * p
                if clip > 0:
                    g = jnp.clip(g, -clip, clip)
                mom_t = b1 * (1.0 - 0.5 * 0.96 ** (t * decay))
                mom_t1 = b1 * (1.0 - 0.5 * 0.96 ** ((t + 1) * decay))
                m_sched = sched * mom_t
                m_sched_next = m_sched * mom_t1
                mean = b1 * mean + (1.0 - b1) * g
                var = b2 * var + (1.0 - b2) * g * g
                g_prime = g / (1.0 - m_sched)
                m_prime = mean / (1.0 - m_sched_next)
                v_prime = var / (1.0 - b2 ** t)
                m_bar = (1.0 - mom_t) * g_prime + mom_t1 * m_prime
                w = p - lr * m_bar / (jnp.sqrt(v_prime) + e)
                return w, (mean, var, m_sched)
            return 3, nadam
        if name == "dcasgd":
            check_extra("lamda")
            lam = float(ex.get("lamda", 0.04))
            # previous_weight must start AT the weight, not zero — and
            # as its OWN buffer (asarray would alias the param, and a
            # donated buffer cannot be donated twice).
            self._opt_init = lambda v: (
                jnp.zeros_like(v, dtype=jnp.float32),
                jnp.array(v, dtype=jnp.float32, copy=True))

            def dcasgd(p, g, s, lr, t):
                mom_s, prev = s
                g = g * rs
                if clip > 0:
                    g = jnp.clip(g, -clip, clip)
                delta = -lr * (g + wd * p + lam * g * g * (p - prev))
                if mom > 0:
                    mom_s = mom * mom_s + delta
                    delta = mom_s
                return p + delta, (mom_s, p.astype(jnp.float32))

            return 2, dcasgd
        if name == "sgld":
            check_extra()
            self._opt_needs_key = True

            def sgld(p, g, s, lr, t, key):
                g = g * rs
                if clip > 0:
                    g = jnp.clip(g, -clip, clip)
                noise = jax.random.normal(key, p.shape, p.dtype) * \
                    jnp.sqrt(lr)
                return p - lr / 2.0 * (g + wd * p) + noise, ()

            return 0, sgld
        if name == "lbsgd":
            # LARS-style trust-ratio scaling over SGD (optimizer.py:LBSGD);
            # warmup knobs are accepted and advisory there too.
            check_extra("warmup_strategy", "warmup_epochs", "batch_scale",
                        "updates_per_epoch", "begin_epoch", "num_epochs")

            def lars_lr(p, g, lr):
                wnorm = jnp.linalg.norm(p)
                gnorm = jnp.linalg.norm(g) * rs
                ratio = jnp.minimum(
                    wnorm / (gnorm + wd * wnorm + 1e-9), 10.0)
                return jnp.where((wnorm > 0) & (gnorm > 0),
                                 lr * ratio, lr)

            if mom > 0:
                return 1, lambda p, g, s, lr, t: _as_pair(
                    oo._sgd_mom_update(p, g, s[0], lr=lars_lr(p, g, lr),
                                       momentum=mom, wd=wd,
                                       rescale_grad=rs,
                                       clip_gradient=clip))
            return 0, lambda p, g, s, lr, t: (
                oo._sgd_update(p, g, lr=lars_lr(p, g, lr), wd=wd,
                               rescale_grad=rs, clip_gradient=clip), ())
        raise ValueError(
            "TrainStep supports sgd/nag/signum/signsgd/adam/rmsprop/"
            "adagrad/adadelta/ftrl/ftml/nadam/dcasgd/sgld/lbsgd (got %r);"
            " for other optimizers use gluon.Trainer" % self.optimizer)

    def _place(self, value, sharding):
        """Lay a host/default-device array out on the (possibly
        cross-process) mesh. Single-process: plain device_put. Multi-
        process: every process holds the full value (identical seeds →
        identical init, the dist_sync contract), and each fills only its
        addressable shards."""
        if not self._multiproc:
            return jax.device_put(value, sharding)
        host = np.asarray(value)
        return jax.make_array_from_callback(host.shape, sharding,
                                            lambda idx: host[idx])

    def _materialize(self, x_example):
        """Collect param values (triggering deferred init with a real
        forward if needed) and lay them out on the mesh."""
        self._multiproc = any(d.process_index != jax.process_index()
                              for d in self.mesh.devices.flat)
        net = self.net
        params = list(net.collect_params().values())
        if any(p._data is None and p._deferred_init is not None
               for p in params):
            if x_example is None:
                raise RuntimeError(
                    "net has deferred-init parameters; run one step (or "
                    "a forward) before load_state_dict so shapes exist")
            with autograd.pause():
                net(NDArray(jnp.asarray(x_example)))
            params = list(net.collect_params().values())
        self._train_params = [p for p in params if p.grad_req != "null"]
        self._aux_params = [p for p in params if p.grad_req == "null"]
        # Masters stay in the param's own (fp32) dtype even under mixed
        # precision; the cast to the compute dtype happens inside the
        # compiled step.
        self._param_vals = {p.name: p.data()._data
                            for p in self._train_params}
        self._aux_vals = {p.name: p.data()._data for p in self._aux_params}
        # Non-gradient state lives here until sync_to_net.
        me = weakref.ref(self)
        for p in self._aux_params:
            p._bind_live(lambda name=p.name: me() and me()._aux_vals[name])

        # Optimizer state mirrors param sharding (ZeRO-0; the state is
        # sharded exactly like its weight so updates are local). Always
        # a k-tuple per param (k from the optimizer rule; empty for
        # stateless rules).
        k = self._opt_n_states
        init = self._opt_init or (lambda v: tuple(
            jnp.zeros_like(v, dtype=jnp.float32) for _ in range(k)))
        self._opt_state = {n: init(v)
                           for n, v in self._param_vals.items()}

        self._shardings = shard_params(
            self.mesh, {n: v.shape for n, v in self._param_vals.items()},
            rule=self._param_rule)
        self._data_sharding = data_sharding(self.mesh)
        self._repl = replicate(self.mesh)

        # Place params/aux/state according to the sharding plan.
        self._param_vals = {n: self._place(v, self._shardings[n])
                            for n, v in self._param_vals.items()}
        self._aux_vals = {n: self._place(v, self._repl)
                          for n, v in self._aux_vals.items()}
        self._opt_state = {
            n: tuple(self._place(s, self._shardings[n]) for s in st)
            for n, st in self._opt_state.items()}
        self._ckpt_view = (self._param_vals, self._opt_state,
                           self._aux_vals, self.num_update,
                           _random.get_state())
        self._materialized = True

    # -- the pure step --------------------------------------------------------

    def _make_deterministic_grad(self, loss_of):
        """Topology-invariant gradient aggregation (beyond reference).

        The GSPMD path lets XLA insert a `psum` for the sharded-batch
        mean gradient; its reduction order depends on the collective
        implementation (single-host shared-memory vs cross-host ring),
        so a 2-host run differs from a 1-host run in the last float bit.
        This mode computes per-shard gradients under `shard_map`, then
        `all_gather`s them and sums the shards in explicit ascending
        mesh order — an unrolled chain of adds whose order is part of
        the program, not the transport. Training state then matches
        bit-for-bit across any process topology of the same mesh.

        Restrictions: dp-only meshes (params replicated) — the point is
        multi-host data parallelism; and BatchNorm aux stats become the
        ordered mean of per-shard stats (same mean, variance of shard
        means differs from global-batch variance at O(1/B²)).
        """
        mesh = self.mesh
        for ax in mesh.axis_names:
            if ax != "dp" and mesh.shape[ax] != 1:
                raise ValueError(
                    "deterministic_reduction supports dp-only meshes; "
                    "got axis %r of size %d" % (ax, mesh.shape[ax]))
        ndp = mesh.shape["dp"]

        def ordered_mean(gathered):
            # gathered: (ndp, ...) from all_gather — reduce in explicit
            # shard order so the float rounding is identical everywhere.
            acc = gathered[0]
            for i in range(1, ndp):
                acc = acc + gathered[i]
            return acc / ndp

        def per_shard(pvals, aux_vals, xs, ys, key):
            (loss, new_aux), g = jax.value_and_grad(
                loss_of, has_aux=True)(pvals, aux_vals, xs, ys, key)
            gather = lambda t: jax.tree_util.tree_map(
                lambda a: ordered_mean(jax.lax.all_gather(a, "dp")), t)
            # the gradients' aggregation is the backward's in the
            # profiler's table of phases
            with jax.named_scope("backward"):
                return gather(loss), gather(new_aux), gather(g)

        data_spec = P(tuple(a for a in ("dp",) if a in mesh.axis_names))
        rep = P()

        def grad_of(pvals, aux_vals, x, y, key):
            # check_vma=False: outputs ARE replicated (all_gather +
            # identical per-device arithmetic) but the static checker
            # cannot infer it through the gathered-and-resummed chain.
            loss, new_aux, grads = jax.shard_map(
                per_shard, mesh=mesh,
                in_specs=(rep, rep, data_spec, data_spec, rep),
                out_specs=(rep, rep, rep),
                check_vma=False)(pvals, aux_vals, x, y, key)
            return (loss, new_aux), grads

        return grad_of

    def _build(self):
        net, loss_fn = self.net, self.loss_fn
        train_params = self._train_params
        aux_params = self._aux_params

        cdt = None if self._dtype is None else jnp.dtype(self._dtype)

        def loss_of(pvals, aux_vals, x, y, key):
            # Mixed precision: cast fp32 masters (and inputs/aux) to the
            # compute dtype here, inside the traced step — XLA fuses the
            # casts, the MXU runs bf16, and autodiff carries gradients
            # back through the casts to the fp32 masters.
            cast = (lambda a: a) if cdt is None else \
                (lambda a: a.astype(cdt) if jnp.issubdtype(a.dtype,
                                                           jnp.floating)
                 else a)
            # The phases' names go into every device op's metadata
            # (`forward`, `loss`; autodiff names the backward
            # `transpose(jvp(forward))`): telemetry/device_table.py sums
            # a capture by them. The casts are the forward's.
            with jax.named_scope("forward"):
                mapping = {p: NDArray(cast(pvals[p.name]))
                           for p in train_params}
                # Aux (BN running stats) stay fp32: in train mode they
                # sit only on the EMA-update path, and BatchNorm hands
                # that path the batch moments as it summed them, in fp32
                # (the reference's AccReal contract), while activations
                # stay in the compute dtype.
                mapping.update({p: NDArray(aux_vals[p.name])
                                for p in aux_params})
                x = NDArray(cast(x))
            ov = override(mapping)
            with autograd.pause(train_mode=True), \
                    _random.trace_key_scope(key), ov:
                with jax.named_scope("forward"):
                    out = net(x)
                with jax.named_scope("loss"):
                    if cdt is not None:
                        # Loss math in fp32 regardless of compute dtype.
                        out = NDArray(out._data.astype(jnp.float32))
                    loss = loss_fn(out, NDArray(y))
            new_aux = dict(aux_vals)
            with jax.named_scope("forward"):
                for p, v in ov.writes.items():
                    nv = v._data if isinstance(v, NDArray) else v
                    # Running stats keep their stored (fp32) dtype.
                    new_aux[p.name] = nv.astype(aux_vals[p.name].dtype)
            with jax.named_scope("loss"):
                return jnp.mean(loss._data), new_aux

        opt_update = self._opt_update

        if self.deterministic_reduction:
            grad_of = self._make_deterministic_grad(loss_of)
        else:
            def grad_of(pvals, aux_vals, x, y, key):
                return jax.value_and_grad(loss_of, has_aux=True)(
                    pvals, aux_vals, x, y, key)

        needs_key = self._opt_needs_key

        def step(pvals, opt_state, aux_vals, x, y, lr, t, key):
            (loss, new_aux), grads = grad_of(pvals, aux_vals, x, y, key)
            # Stochastic optimizers (SGLD) draw per-param noise from a
            # stream disjoint from the net's dropout keys.
            opt_key = jax.random.fold_in(key, 0x7FFFFFFF) if needs_key \
                else None
            new_p, new_s = {}, {}
            with jax.named_scope("optimizer_update"):
                for idx, (name, p) in enumerate(pvals.items()):
                    g = grads[name].astype(jnp.float32)
                    if needs_key:
                        new_p[name], new_s[name] = opt_update(
                            p, g, opt_state[name], lr, t,
                            jax.random.fold_in(opt_key, idx))
                    else:
                        new_p[name], new_s[name] = opt_update(
                            p, g, opt_state[name], lr, t)
            return new_p, new_s, new_aux, loss

        step.__name__ = "mx_train_step"      # the executable's name

        shardings = self._shardings
        k = self._opt_n_states
        state_shardings = {n: tuple(shardings[n] for _ in range(k))
                           for n in shardings}
        aux_shardings = {p.name: self._repl for p in aux_params}
        in_shardings = (shardings, state_shardings, aux_shardings,
                        self._data_sharding, self._data_sharding,
                        self._repl, self._repl, self._repl)
        out_shardings = (shardings, state_shardings, aux_shardings,
                         self._repl)
        # The whole-step executable is the single largest compile in
        # the system; a warm restart loads it from JAX's persistent
        # cache (compile.enable_jax_cache).
        self._jitted = jax.jit(
            step, in_shardings=in_shardings, out_shardings=out_shardings,
            donate_argnums=(0, 1, 2))

    # -- public API -----------------------------------------------------------

    def __call__(self, x, y):
        """Run one training step; returns the (host) scalar loss.

        Multi-process meshes (after `parallel.dist.initialize`): `x`/`y`
        are this process's *local* slice of the global batch
        (`dist.local_slice` gives the rows) — the global array is
        assembled across processes, exactly how each reference worker
        feeds its own `num_parts`/`part_index` shard of the epoch.
        """
        t_start = time.perf_counter()
        me = threading.get_ident()
        if self._host_mark is None or self._host_mark[0] != me:
            self._host_mark = (me,) + _host_usage()
        if self._hp_component is None:
            self._hp_component = _hp.unique_component("train_step")
        # Heartbeat lane for the hang watchdog: in-flight work between
        # begin/end past its deadline fires a `step_hang` anomaly with
        # this thread's stack in the bundle.
        _watchdog.begin("step")
        try:
            if isinstance(x, NDArray):
                x = x._data
            if isinstance(y, NDArray):
                y = y._data
            if not self._materialized:
                self._materialize(np.asarray(x)[:1])
            if self._jitted is None:
                self._build()
            with _trace.span("train_step::data_put"):
                if self._multiproc:
                    x = jax.make_array_from_process_local_data(
                        self._data_sharding, np.asarray(x))
                    y = jax.make_array_from_process_local_data(
                        self._data_sharding, np.asarray(y))
                else:
                    x = jax.device_put(jnp.asarray(x),
                                       self._data_sharding)
                    y = jax.device_put(jnp.asarray(y),
                                       self._data_sharding)
            t = self.num_update + 1
            key = _random.next_key()
            # The dispatch span covers fwd+bwd+grad-sync+update as one
            # fused executable; grad-sync is the psum XLA inserted
            # inside it, so its device-side cost is only separable in
            # the XPlane trace.
            with _trace.span("train_step::dispatch", step=t):
                last = (x, y, jnp.float32(self.lr), jnp.float32(t), key)
                new_p, new_s, new_a, loss = self._jitted(
                    self._param_vals, self._opt_state, self._aux_vals,
                    *last)
            self._last_args = tuple(
                (a.shape, a.dtype, a.sharding) for a in last)
            if _attr.device_spans_enabled():
                # Step attribution's device bracket: how long the
                # device still chews after dispatch returned. Gated —
                # the block_until_ready makes every step host-
                # synchronous, which only an attributor should buy.
                with _trace.span("train_step::device", step=t):
                    jax.block_until_ready(loss)
            # Single-bytecode commit of everything a checkpoint reads: a
            # signal handler (checkpoint.PreemptionHook) can interrupt
            # between any two statements here, and snapshotting params
            # from step N with the counter/RNG of step N+1 would
            # silently lose an update on resume. state_dict() reads
            # THIS tuple.
            self._ckpt_view = (new_p, new_s, new_a, t,
                               _random.get_state())
            self._param_vals, self._opt_state, self._aux_vals = \
                new_p, new_s, new_a
            self.num_update = t
            t_end = time.perf_counter()
            # Wall time without CPU time and with switches is a host that
            # was taken away; with CPU time, a pause of the program's own.
            cpu, switches = _host_usage()
            _, cpu0, switches0 = self._host_mark
            self._host_mark = (me, cpu, switches)
            _trace.complete("train_step::step", t_start, t_end, step=t,
                            cpu_ms=(cpu - cpu0) * 1e3,
                            switches=switches - switches0)
            _switches_total.inc(switches - switches0)
            _step_seconds.observe(t_end - t_start)
            _steps_total.inc()
            _cc.step_done()
            if not self._hp_ready:  # warmup compile done: ready
                self._hp_ready = True
                _hp.set_ready(self._hp_component)
            if self._multiproc:
                # The replicated loss is not fully addressable from one
                # controller; hand back this process's local replica so
                # the return type (a scalar jax array) matches
                # single-process and dispatch stays async.
                return loss.addressable_data(0)
            return loss
        finally:
            _watchdog.end("step")

    def set_learning_rate(self, lr):
        self.lr = float(lr)

    # -- the step executable's own account ----------------------------------

    def _last_structs(self):
        """The last call's (x, y, lr, t, key) as ShapeDtypeStructs."""
        return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
                for shape, dtype, sharding in self._last_args]

    def _last_program(self):
        """The executable the last step ran, found again in the jit's
        caches by lowering the live state and the last batch's shapes,
        dtypes and shardings: no trace of the Python, no lowering, no
        compile. None before the first step."""
        last = self._last_args
        if last is None:
            return None
        sig = tuple(a[:2] for a in last[:2])
        if self._program is None or self._program["sig"] != sig:
            t0 = time.perf_counter()
            compiled = self._jitted.lower(
                self._param_vals, self._opt_state, self._aux_vals,
                *self._last_structs()).compile()
            if any(r.kind == "build" and r.start >= t0
                   for r in _cc.build_log()):
                _program_recompiled.inc()
                if _program_recompiled.value == 1:
                    _log.warning(
                        "TrainStep.program_stats() compiled a program: the "
                        "step's own executable was not found in the cache")
            self._program = {"sig": sig, "compiled": compiled, "text": None}
        return self._program

    def program_stats(self):
        """``memory_analysis()`` of the executable the last step ran:
        ``argument_bytes``, ``output_bytes``, ``alias_bytes`` (outputs
        that reuse donated arguments), ``temp_bytes``, ``code_bytes`` and
        their sum ``total_bytes`` (arguments + outputs - aliased +
        temporaries + code: what the device must hold to run a step,
        which the allocator's peak counter misses the temporaries of).
        Found on first demand and kept; None before the first step."""
        program = self._last_program()
        if program is None:
            return None
        m = program["compiled"].memory_analysis()
        stats = {
            "argument_bytes": int(m.argument_size_in_bytes),
            "output_bytes": int(m.output_size_in_bytes),
            "alias_bytes": int(m.alias_size_in_bytes),
            "temp_bytes": int(m.temp_size_in_bytes),
            "code_bytes": int(m.generated_code_size_in_bytes),
        }
        stats["total_bytes"] = (
            stats["argument_bytes"] + stats["output_bytes"]
            - stats["alias_bytes"] + stats["temp_bytes"]
            + stats["code_bytes"])
        return stats

    def program_text(self):
        """Optimized HLO text of the executable the last step ran (every
        instruction with its ``op_name``; fused computations with their
        inner instructions'). None before the first step."""
        program = self._last_program()
        if program is None:
            return None
        if program["text"] is None:
            program["text"] = program["compiled"].as_text()
        return program["text"]

    def _gather_host(self, tree):
        """Pytree of global arrays -> pytree of host numpy, valid on
        every process. Shards are re-replicated through a jitted
        identity (an all-gather over the mesh), then read locally."""
        if not self._multiproc:
            return jax.device_get(tree)
        if not hasattr(self, "_rep_identity"):
            # One stable jitted identity so repeated gathers hit the
            # executable cache instead of retracing per call.
            self._rep_identity = jax.jit(lambda t: t,
                                         out_shardings=self._repl)
        rep = self._rep_identity(tree)
        return jax.tree_util.tree_map(
            lambda a: np.asarray(a.addressable_data(0)), rep)

    def state_to_host(self):
        """(params, opt_state, aux) as host numpy dicts on every
        process — the checkpoint/inspection surface for multi-host runs
        (each reference worker could pull full weights from the servers;
        kvstore_dist.h:217)."""
        return (self._gather_host(self._param_vals),
                self._gather_host(self._opt_state),
                self._gather_host(self._aux_vals))

    # -- checkpoint-subsystem state (mxnet_tpu.checkpoint) --------------------

    def _host_or_shard(self, arr):
        """One array for state_dict: full host numpy when this process
        can (and should) hold the whole value, else a checkpoint.Shard
        of the locally-addressable primary-replica pieces."""
        from ..checkpoint.manager import Shard

        shards = [s for s in arr.addressable_shards if s.replica_id == 0]
        if len(shards) == 1 and not self._multiproc and \
                shards[0].data.shape == arr.shape:
            return np.asarray(shards[0].data)
        chunks = []
        for s in shards:
            index = tuple(
                (sl.start if sl.start is not None else 0,
                 sl.stop if sl.stop is not None else dim)
                for sl, dim in zip(s.index, arr.shape))
            chunks.append((index, np.asarray(s.data)))
        return Shard(arr.shape, arr.dtype, chunks)

    def state_dict(self, sharded=None):
        """Checkpointable state as a nested host dict: params, fused
        optimizer state, aux (BN stats), step counter and RNG position.

        ``sharded`` (default: multi-process meshes only) snapshots each
        array as the checkpoint.Shard of this process's addressable
        primary-replica pieces — the per-host write contract of
        `checkpoint.CheckpointManager`'s sharded SPMD saves. The
        single-process path is one batched device_get (params are
        donated buffers, so the snapshot must copy before the next
        step). Restore with :meth:`load_state_dict`."""
        if not self._materialized:
            raise RuntimeError(
                "run one step before state_dict so there is state to "
                "snapshot")
        if sharded is None:
            sharded = self._multiproc
        # _ckpt_view is committed by __call__ / load_state_dict /
        # _materialize in ONE attribute store, so reading it here is
        # signal-safe: a preemption handler interrupting mid-step sees
        # either the pre-step or the post-step state, never a mix of
        # step-N params with a step-N+1 counter.
        pvals, opt_state, aux_vals, num_update, (seed, counter) = \
            self._ckpt_view
        opt_tree = {n: {str(i): s for i, s in enumerate(st)}
                    for n, st in opt_state.items()}
        if sharded:
            conv = self._host_or_shard
            params = {n: conv(v) for n, v in pvals.items()}
            opt = {n: {k: conv(s) for k, s in d.items()}
                   for n, d in opt_tree.items()}
            aux = {n: conv(v) for n, v in aux_vals.items()}
        else:
            # One batched transfer for the whole snapshot — this is the
            # entire synchronous cost of an async checkpoint.
            params, opt, aux = jax.device_get(
                (pvals, opt_tree, aux_vals))
        return {
            "params": params,
            "opt": opt,
            "aux": aux,
            "num_update": int(num_update),
            "rng": {"seed": int(seed), "counter": int(counter)},
        }

    def load_state_dict(self, state):
        """Restore a :meth:`state_dict` snapshot (full host arrays — the
        manager stitches sharded saves back together on restore) onto
        this step's mesh. Resume is bit-exact: params, optimizer state,
        step counter and the RNG stream position all continue as the
        uninterrupted run would."""
        if not self._materialized:
            # Materialize from the net's initialized params so resume
            # does not need a throwaway step (deferred-init nets must
            # have run a forward once before this).
            self._materialize(None)
        # Empty sections (stateless optimizer, no BN aux) drop out of a
        # flattened checkpoint entirely — absent means empty here.
        params = state.get("params", {})
        opt = state.get("opt", {})
        aux = state.get("aux", {})

        def place_as(value, like, sharding):
            return self._place(np.asarray(value).astype(like.dtype),
                               sharding)

        # Build everything before mutating self: a mismatched snapshot
        # must raise cleanly, not leave a half-loaded step.
        new_p, new_s, new_a = {}, {}, {}
        for n in self._param_vals:
            new_p[n] = place_as(params[n], self._param_vals[n],
                                self._shardings[n])
            new_s[n] = tuple(
                place_as(opt.get(n, {})[str(i)], s, self._shardings[n])
                for i, s in enumerate(self._opt_state[n]))
        for n in self._aux_vals:
            new_a[n] = place_as(aux[n], self._aux_vals[n], self._repl)
        num_update = int(state["num_update"])
        rng = state.get("rng")

        self._param_vals, self._opt_state, self._aux_vals = \
            new_p, new_s, new_a
        self.num_update = num_update
        if rng is not None:
            _random.set_state(int(rng["seed"]), int(rng["counter"]))
        self._ckpt_view = (new_p, new_s, new_a, num_update,
                           _random.get_state())

    def save_checkpoint(self, path):
        """Write params + optimizer state + aux + step counter in the
        framework's binary .params wire format (reference
        save_checkpoint/save_optimizer_states, model.py:383-413). In a
        multi-process group every rank gathers but only rank 0 writes;
        the path works unchanged from 1 host to N.

        Returns the filename written (on every rank)."""
        from ..ndarray import utils as _nd_utils

        if not self._materialized:
            raise RuntimeError(
                "run one step before save_checkpoint so there is state "
                "to save")
        pvals, opt, aux = self.state_to_host()
        seed, counter = _random.get_state()
        flat = {"step:num_update": np.asarray(self.num_update,
                                              np.int64),
                # RNG stream position: resume draws the same keys the
                # uninterrupted run would (dropout/SGLD bitwise resume).
                "step:rng": np.asarray([seed, counter], np.int64)}
        for n, v in pvals.items():
            flat["arg:" + n] = np.asarray(v)
        for n, st in opt.items():
            for i, sv in enumerate(st):
                flat["opt:%d:%s" % (i, n)] = np.asarray(sv)
        for n, v in aux.items():
            flat["aux:" + n] = np.asarray(v)
        from .dist import rank, barrier

        if rank() == 0:
            _nd_utils.save(path, {k: NDArray(v)
                                  for k, v in flat.items()})
        barrier("train_step_ckpt")
        return path

    def load_checkpoint(self, path):
        """Restore a `save_checkpoint` file onto this step's mesh (every
        rank reads the file — shared filesystems are the pod norm — and
        places only its addressable shards)."""
        from ..ndarray import utils as _nd_utils

        if not self._materialized:
            raise RuntimeError(
                "run one step (or call after materialization) before "
                "load_checkpoint so shardings exist")
        blob = {k: v.asnumpy() if isinstance(v, NDArray) else v
                for k, v in _nd_utils.load(path).items()}

        def place_as(name, value, like, sharding):
            # The wire format promotes bf16 to f32 — restore the LIVE
            # dtype or jit would silently retrace in the wrong one.
            return self._place(np.asarray(value).astype(like.dtype),
                               sharding)

        # Build everything BEFORE mutating self: a mismatched file
        # (wrong net / optimizer family) must raise cleanly, not leave
        # a half-loaded step.
        new_p, new_s, new_a = {}, {}, {}
        for n in self._param_vals:
            new_p[n] = place_as(n, blob["arg:" + n],
                                self._param_vals[n], self._shardings[n])
            new_s[n] = tuple(
                place_as(n, blob["opt:%d:%s" % (i, n)], s,
                         self._shardings[n])
                for i, s in enumerate(self._opt_state[n]))
        for n in self._aux_vals:
            new_a[n] = place_as(n, blob["aux:" + n],
                                self._aux_vals[n], self._repl)
        num_update = int(np.asarray(blob["step:num_update"]).ravel()[0])
        rng = blob.get("step:rng")

        self._param_vals, self._opt_state, self._aux_vals = \
            new_p, new_s, new_a
        self.num_update = num_update
        if rng is not None:
            seed, counter = np.asarray(rng).ravel()
            _random.set_state(int(seed), int(counter))
        self._ckpt_view = (new_p, new_s, new_a, num_update,
                           _random.get_state())

    def sync_to_net(self):
        """Copy the (possibly sharded) param values back into the net's
        Parameters (gather happens lazily on host read)."""
        if self._multiproc:
            # Gather only params + aux — optimizer state stays put.
            pvals = self._gather_host(self._param_vals)
            avals = self._gather_host(self._aux_vals)
        else:
            pvals, avals = self._param_vals, self._aux_vals
        for p in self._train_params:
            p.set_data(NDArray(pvals[p.name]))
        for p in self._aux_params:
            p.set_data(NDArray(avals[p.name]))
