"""Fused imperative update path — multi-tensor optimizer apply and
bucketed gradient aggregation.

The imperative training contract (`gluon.Trainer`, `Module.update`)
historically pays O(num_params) executable launches per step: one
optimizer-op dispatch per parameter plus one kvstore push/pull per key.
That is the PyTorch-DDP gradient-bucketing observation (Li et al.,
VLDB 2020) and the apex/ZeRO multi-tensor-apply observation rolled into
one: coalesce many small tensors into few large dispatches and the
per-step host cost scales with *bucket count*, not parameter count.

Two pieces, both riding the executable-cache discipline CachedOp
established (one compile per signature, then pure cache hits):

:class:`FusedApplier`
    Compiles ONE jitted executable per ~25MB chunk of the parameter
    set for a supported optimizer family (SGD/momentum, NAG, Adam,
    RMSProp, AdaGrad, AdaDelta, Signum/SignSGD), grouped by (context,
    dtype). Inside the executable the chunk's gradients concatenate
    into ONE flat vector, the optimizer body — the SAME pure FCompute
    functions the per-parameter loop dispatches
    (ops/optimizer_ops.py) — runs elementwise over it, and new
    per-parameter weights slice back out. Every supported body is
    purely elementwise, so math on the concatenation is positionwise
    identical to math per parameter: fused and loop paths produce
    bit-identical updates for vector-aligned parameter sizes
    (multiples of 8 floats — the common NN case; the flat vector is
    padded so no real lane hits the remainder epilogue, whose FMA
    contraction XLA:CPU compiles differently) and stay within an ulp
    for odd sizes and for divide-by-sqrt-heavy bodies (centered
    RMSProp) — the same documented contract as PyTorch's
    fused/foreach optimizers. Per-parameter learning rates / weight
    decays ride as *runtime vector inputs* expanded in-graph (LR
    schedules never retrace); ``rescale_grad`` is baked per value,
    mirroring the loop path's op-attrs cache.

    Optimizer state is kept FLAT between steps (the ZeRO observation:
    nothing reads momentum per-parameter on the hot path), and the
    flat weights are cached too — validated against NDArray versions,
    so an external ``set_data``/checkpoint restore re-flattens. The
    ``updater.states`` entries become lazy flat-backed views
    (:class:`_FlatView`) that materialize on first read and detach on
    write: checkpointing, ``fused=False`` toggling and introspection
    all see exactly the state the loop path would have written, while
    the steady-state step moves O(params) fewer buffers through the
    runtime.

    Multi-precision weights (fp16/bf16 under ``multi_precision=True``)
    ride the same table through per-family ``mp_*`` variants: the fp32
    master lives as the LAST flat state slot, the gradient upcasts
    in-graph, and the low-precision weight slices back out as a cast
    of the master — elementwise-identical to the loop path's
    ``update_multi_precision``. Anything the table does not cover
    (row-sparse gradients, exotic optimizers, odd state layouts) falls
    back to the per-parameter updater, entry by entry.

:class:`GradBucketer`
    Flattens many same-dtype gradients into ~25MB coalesced buckets
    (``MXNET_FUSED_BUCKET_MB``) so the kvstore allreduce moves
    ``ceil(params/bucket)`` tensors per step instead of ``params``.
    Merging a summed flat bucket is element-for-element the same
    arithmetic as merging each key separately (the kvstore `_merge`
    add-chain runs in the same device order), so bucketed and per-key
    aggregation agree bitwise. Bucket keys are stable across steps,
    which keeps per-key state in the transport (e.g. 2-bit
    gradient-compression error feedback on the dist path) coherent.

Telemetry: ``mx_fused_apply_compiles_total{optimizer=...}`` counts
executable-cache fills (a climbing rate after warmup is a recompile
storm — `telemetry.StepMonitor.attach_fused` watches it through the
``on_compile`` hook, the CachedOp ``on_trace`` pattern), and
``mx_trainer_fused_dispatches`` counts coalesced launches.
"""
from __future__ import annotations

import numpy as np

from . import env as _env
from .ndarray.ndarray import NDArray
from .ndarray import sparse as _sp
from .ops import registry as _reg
from .ops import optimizer_ops as _oo
from .telemetry import metrics as _tm
from .telemetry import trace as _trace

__all__ = ["FusedApplier", "GradBucketer", "bucket_bytes"]

_apply_compiles = _tm.REGISTRY.counter(
    "mx_fused_apply_compiles_total",
    "Fused multi-tensor optimizer-apply compiles (one per param-set "
    "signature — a climbing rate after warmup is a recompile storm)",
    labels=("optimizer",))
_fused_dispatches = _tm.REGISTRY.counter(
    "mx_trainer_fused_dispatches",
    "Coalesced executable launches on the fused imperative update path "
    "(multi-tensor applies + bucket flatten/unflatten)")


def bucket_bytes():
    """Coalescing bucket size in bytes (``MXNET_FUSED_BUCKET_MB``,
    default 25MB — the DDP bucket default, large enough to amortize
    launch overhead, small enough to overlap)."""
    return int(_env.get("MXNET_FUSED_BUCKET_MB")) * (1 << 20)


def _pack_by_bytes(items, max_bytes, nbytes):
    """Greedy contiguous packing into runs of <= max_bytes (oversize
    singletons get their own run). The ONE packing policy shared by the
    gradient bucketer and the apply chunker, so allreduce buckets and
    apply chunks stay boundary-compatible (the ROADMAP's
    overlap-allreduce-with-apply follow-up depends on that)."""
    out, cur, cur_bytes = [], [], 0
    for item in items:
        nb = nbytes(item)
        if cur and cur_bytes + nb > max_bytes:
            out.append(cur)
            cur, cur_bytes = [], 0
        cur.append(item)
        cur_bytes += nb
    if cur:
        out.append(cur)
    return out


def _dispatch(label, exec_fn, *args, **span_attrs):
    """Launch one coalesced executable, counted as a single dispatch."""
    _reg.DISPATCHES[0] += 1
    _fused_dispatches.inc()
    with _trace.span(label, **span_attrs):
        return exec_fn(*args)


# -- optimizer family table ----------------------------------------------------
#
# Each entry maps an optimizer CLASS (exact type — subclasses like LBSGD
# override `update` and must fall back) to a spec:
#   n_states  : per-param state arity the fused body expects
#   statics   : hashable tuple of baked hyperparameters (part of the
#               executable-cache key; mutating them mid-run recompiles)
#   body      : (w, g, states_tuple, lr, wd, rescale) ->
#               (new_w, new_states_tuple) — built from the SAME
#               ops/optimizer_ops bodies the per-param loop dispatches
#   host_lr   : python-float per-index learning rate, computed exactly
#               the way the loop path computes it (e.g. Adam's bias-
#               corrected lr_t) so the runtime scalar carries identical
#               bits to the loop path's baked attr.
#
# Excluded on purpose: FTML (bakes `t` as an attr — the loop path
# already recompiles per step), Nadam (optimizer-instance-shared
# m_schedule mutation), DCASGD/SGLD/LBSGD/Test (stateful host logic or
# eager randomness), and Ftrl (its body DIVIDES by lr; with lr baked,
# XLA folds the division into a multiply-by-reciprocal, so a runtime-lr
# executable differs from the loop path by an ulp — bit-identity wins
# over fusing a niche family). They take the per-param fallback.

def _spec_for(opt):
    from . import optimizer as om

    t = type(opt)
    clip = opt._clip()

    if t is om.SGD or t is om.NAG:
        mom = float(opt.momentum)
        mom_op = _oo._sgd_mom_update if t is om.SGD else _oo._nag_mom_update
        if mom != 0.0:
            def body(w, g, s, lr, wd, rs):
                nw, nm = mom_op(w, g, s[0], lr=lr, momentum=mom, wd=wd,
                                rescale_grad=rs, clip_gradient=clip)
                return nw, (nm,)
            return _Spec(t.__name__.lower(), 1, (mom, clip), body)

        def body(w, g, s, lr, wd, rs):
            return _oo._sgd_update(w, g, lr=lr, wd=wd, rescale_grad=rs,
                                   clip_gradient=clip), ()
        return _Spec(t.__name__.lower(), 0, (0.0, clip), body)

    if t is om.Adam:
        b1, b2, e = float(opt.beta1), float(opt.beta2), float(opt.epsilon)

        def body(w, g, s, lr, wd, rs):
            nw, nm, nv = _oo._adam_update(w, g, s[0], s[1], lr=lr, beta1=b1,
                                          beta2=b2, epsilon=e, wd=wd,
                                          rescale_grad=rs,
                                          clip_gradient=clip)
            return nw, (nm, nv)

        def host_lr(o, index, lr):
            # Bias-corrected step size, python-float math identical to
            # Adam.update (optimizer.py) so the runtime input carries
            # the same f32 bits the loop path bakes.
            ti = o._index_update_count[index]
            coef1 = 1.0 - b1 ** ti
            coef2 = 1.0 - b2 ** ti
            return lr * (coef2 ** 0.5) / coef1

        return _Spec("adam", 2, (b1, b2, e, clip), body, host_lr)

    if t is om.RMSProp:
        g1, g2 = float(opt.gamma1), float(opt.gamma2)
        e = float(opt.epsilon)
        cw = float(opt.clip_weights) if opt.clip_weights is not None else -1.0
        if opt.centered:
            def body(w, g, s, lr, wd, rs):
                nw, nn, ng, nd_ = _oo._rmspropalex_update(
                    w, g, s[0], s[1], s[2], lr=lr, gamma1=g1, gamma2=g2,
                    epsilon=e, wd=wd, rescale_grad=rs, clip_gradient=clip,
                    clip_weights=cw)
                return nw, (nn, ng, nd_)
            return _Spec("rmsprop_centered", 3, (g1, g2, e, clip, cw), body)

        def body(w, g, s, lr, wd, rs):
            nw, nn = _oo._rmsprop_update(w, g, s[0], lr=lr, gamma1=g1,
                                         epsilon=e, wd=wd, rescale_grad=rs,
                                         clip_gradient=clip, clip_weights=cw)
            return nw, (nn,)
        return _Spec("rmsprop", 1, (g1, e, clip, cw), body)

    if t is om.AdaGrad:
        e = float(opt.float_stable_eps)

        def body(w, g, s, lr, wd, rs):
            nw, nh = _oo._adagrad_update(w, g, s[0], lr=lr, epsilon=e, wd=wd,
                                         rescale_grad=rs, clip_gradient=clip)
            return nw, (nh,)
        return _Spec("adagrad", 1, (e, clip), body)

    if t is om.AdaDelta:
        rho, e = float(opt.rho), float(opt.epsilon)

        def body(w, g, s, lr, wd, rs):
            nw, nag, nad = _oo._adadelta_update(w, g, s[0], s[1], rho=rho,
                                                epsilon=e, wd=wd,
                                                rescale_grad=rs,
                                                clip_gradient=clip)
            return nw, (nag, nad)
        return _Spec("adadelta", 2, (rho, e, clip), body)

    if t is om.Signum or t is om.SignSGD:
        mom = float(opt.momentum)
        wd_lh = float(opt.wd_lh)
        if mom != 0.0:
            def body(w, g, s, lr, wd, rs):
                nw, nm = _oo._signum_update(w, g, s[0], lr=lr, momentum=mom,
                                            wd=wd, rescale_grad=rs,
                                            clip_gradient=clip, wd_lh=wd_lh)
                return nw, (nm,)
            return _Spec("signum", 1, (mom, clip, wd_lh), body)

        def body(w, g, s, lr, wd, rs):
            return _oo._signsgd_update(w, g, lr=lr, wd=wd, rescale_grad=rs,
                                       clip_gradient=clip), ()
        return _Spec("signsgd", 0, (clip,), body)

    return None


class _Spec:
    __slots__ = ("name", "n_states", "statics", "body", "host_lr",
                 "hyp_dtype", "mp", "base_k")

    def __init__(self, name, n_states, statics, body, host_lr=None,
                 hyp_dtype=None, mp=False, base_k=None):
        self.name = name
        self.n_states = n_states
        self.statics = statics
        self.body = body
        self.host_lr = host_lr or (lambda opt, index, lr: lr)
        # lr/wd runtime vectors are built in this dtype (None = the
        # weight dtype). Master-weight variants compute in fp32.
        self.hyp_dtype = hyp_dtype
        self.mp = mp
        self.base_k = n_states if base_k is None else base_k


def _mp_spec(spec):
    """Master-weight variant of a supported family: the fp32 master
    lives as the LAST flat state slot, the low-precision weight is a
    per-step cast of it (the mp_sgd/mp_adam contract generalized to
    every fused family). Elementwise math matches the loop path's
    ``update_multi_precision`` exactly: grad casts to the master dtype,
    the base body runs in fp32, the weight slices back as
    ``master.astype(weight.dtype)``."""
    base_body, base_k = spec.body, spec.n_states

    def body(w, g, s, lr, wd, rs):
        inner, w32 = tuple(s[:base_k]), s[base_k]
        new_w32, new_inner = base_body(w32, g.astype(w32.dtype), inner,
                                       lr, wd, rs)
        return new_w32.astype(w.dtype), tuple(new_inner) + (new_w32,)

    return _Spec("mp_" + spec.name, base_k + 1, ("mp",) + spec.statics,
                 body, spec.host_lr, hyp_dtype=np.float32, mp=True,
                 base_k=base_k)


class _FlatView(NDArray):
    """Optimizer-state NDArray backed by a slice of its chunk's flat
    state buffer.

    Reads materialize the slice lazily — one eager op, only when
    something actually looks (checkpointing, the ``fused=False``
    fallback, introspection); the per-step fused apply never touches
    per-parameter state at all. A direct write (loop-path ``out=``
    commit, ``load_states``) detaches the view onto the concrete
    buffer and marks the owning chunk stale, so the next fused apply
    re-flattens from the updater's states: staleness is impossible by
    construction, not by convention.
    """

    __slots__ = ("_chunk", "_kind", "_off", "_size", "_vshape",
                 "_concrete")

    def __init__(self, chunk, kind, off, size, shape, ctx):
        # Parent __init__ skipped on purpose: it assigns _data, which
        # for a view means "detach".
        self._chunk = chunk
        self._kind = kind
        self._off = off
        self._size = size
        self._vshape = shape
        self._concrete = None
        self._ctx = ctx
        self._grad = None
        self._grad_req = "null"
        self._ag_node = None
        self._ag_out_index = 0
        self.version = 0

    @property
    def _data(self):
        if self._concrete is None:
            flat = self._chunk.flat_s[self._kind]
            self._concrete = flat[self._off:self._off + self._size] \
                .reshape(self._vshape)
        return self._concrete

    @_data.setter
    def _data(self, value):
        self._concrete = value
        self._chunk.stale = True


def donate_enabled():
    """Whether chunk executables donate their flat weight/state input
    buffers (``MXNET_FUSED_DONATE``: auto = on for accelerator
    backends, off on CPU where PJRT ignores donation and warns). With
    donation the steady-state fused cache holds ONE copy of the flat
    weights/state instead of two — XLA aliases the input buffer to the
    same-shaped output, halving the cache's HBM footprint."""
    raw = str(_env.get("MXNET_FUSED_DONATE", "auto") or "auto").lower()
    if raw in ("1", "true", "yes", "on"):
        return True
    if raw in ("0", "false", "no", "off"):
        return False
    import jax

    return jax.default_backend() != "cpu"


class _ApplyChunk:
    """One compiled flat-apply executable plus its cached flat weight
    and state buffers."""

    __slots__ = ("exec_fn", "flatten_fn", "shapes", "sizes", "offsets",
                 "n", "k", "flat_w", "flat_s", "weights", "wver",
                 "views", "state_objs", "stale",
                 "mp", "base_k", "with_scale")

    def __init__(self, exec_fn, flatten_fn, shapes, sizes, offsets, k):
        self.exec_fn = exec_fn
        self.flatten_fn = flatten_fn
        self.shapes = shapes
        self.sizes = sizes
        self.offsets = offsets
        self.n = len(shapes)
        self.k = k
        self.mp = False
        self.base_k = k
        self.with_scale = False
        self.flat_w = None
        self.flat_s = [None] * k
        self.weights = None
        self.wver = None
        self.views = []
        self.state_objs = []
        self.stale = True


class FusedApplier:
    """Multi-tensor optimizer apply over an :class:`optimizer.Updater`.

    One instance per Trainer/Module; it shares the updater's state dict
    (momentum/variance buffers — exposed as :class:`_FlatView` slices
    of the flat state), so `save_states`/`load_states` and the
    ``fused=False`` escape hatch see exactly the state the loop path
    would have written. The flat weight cache costs one extra copy of
    the parameters; optimizer state lives flat-only.

    ``apply(entries)`` with ``entries = [(index, weight, grad)]`` runs
    the fused executable(s) and returns the entries it could NOT handle
    (unsupported optimizer family, sparse gradient, unrecognized state
    layout, ...) for the caller's per-param fallback loop.
    """

    def __init__(self, updater):
        self.updater = updater
        self._chunks = {}       # signature -> _ApplyChunk
        # Steady-state plan cache: the (index, weight, grad) entry
        # objects are identity-stable across steps (autograd writes
        # gradients into the same buffers), so the per-step grouping /
        # chunking / signature hashing collapses to one O(n) identity
        # sweep. Keyed per entry-index run so the overlapped Trainer's
        # per-bucket applies each keep their own hot plan.
        self._plans = {}
        # Compile-count hook, the CachedOp num_traces/on_trace pattern:
        # StepMonitor.attach_fused chains here to flag signature churn.
        self.num_compiles = 0
        self.on_compile = None
        # Warmup accounting for StepMonitor.attach_fused: compiles are
        # a storm signal only when `_replanning` — i.e. an existing
        # plan is being rebuilt (signature churn), or ANY new plan is
        # built after the first apply window completed (`_warmed`).
        # During the very first window (the overlapped path plans one
        # bucket at a time) every build is warmup.
        self._replanning = False
        self._warmed = False
        # Numeric-health hook (telemetry.NumericGuard.install): when
        # set and armed for this apply, every chunk's post-apply flat
        # vector gets one device-side isfinite reduction — O(buckets),
        # not O(params).
        self.grad_guard = None
        self._guard_armed = False

    # -- eligibility ----------------------------------------------------------

    def _state_tuple(self, state, n_states):
        """Normalize an updater state entry to the n-tuple of dense
        NDArrays the fused body expects, or None if the layout doesn't
        match (multi-precision masters, sparse state, ...)."""
        if n_states == 0:
            return () if state is None or state == () else None
        if n_states == 1:
            if isinstance(state, NDArray) and \
                    not isinstance(state, _sp.BaseSparseNDArray):
                return (state,)
            return None
        if isinstance(state, (list, tuple)) and len(state) == n_states and \
                all(isinstance(s, NDArray) and
                    not isinstance(s, _sp.BaseSparseNDArray) for s in state):
            return tuple(state)
        return None

    def _state_tuple_mp(self, state, base_k):
        """Normalize a multi-precision state entry ``(inner_state,
        master_weight)`` to the flat ``inner... + (master,)`` tuple the
        mp chunk body expects, or None when the layout doesn't match."""
        if not (isinstance(state, (list, tuple)) and len(state) == 2):
            return None
        inner, master = state
        if not isinstance(master, NDArray) or \
                isinstance(master, _sp.BaseSparseNDArray):
            return None
        inner_t = self._state_tuple(inner, base_k)
        if inner_t is None:
            return None
        return inner_t + (master,)

    def _state_for(self, state, ch_or_spec):
        """Chunk/spec-aware normalization (mp layouts nest)."""
        if ch_or_spec.mp:
            return self._state_tuple_mp(state, ch_or_spec.base_k)
        return self._state_tuple(state, ch_or_spec.k
                                 if isinstance(ch_or_spec, _ApplyChunk)
                                 else ch_or_spec.n_states)

    # -- one compile per (family, statics, shapes) signature ------------------

    def _build_chunk(self, spec, sig, shapes, rescale, with_scale=False):
        import jax
        import jax.numpy as jnp

        n, k = len(shapes), spec.n_states
        sizes = [int(np.prod(s)) if s else 1 for s in shapes]
        offsets = np.cumsum([0] + sizes).tolist()
        total = offsets[-1]
        # Pad the flat vector to a SIMD-register multiple so no REAL
        # lane lands in the kernel's vector-remainder epilogue: XLA:CPU
        # compiles the epilogue without FMA contraction while the
        # standalone per-param kernels contract, an ulp-level divergence
        # (found by end-to-end cross-check). With the pad, parameters
        # whose sizes are vector-aligned (multiples of 8 floats — the
        # common NN case) update bit-identically to the loop path; odd
        # sizes stay within an ulp (same contract PyTorch's fused
        # optimizers document). Pad lanes are zeros and every supported
        # body maps zeros to zeros, so they never drift or NaN.
        pad = (-total) % 64
        body = spec.body

        def per_element(hyp):
            # One hyperparameter per parameter -> one per element, as a
            # concatenation of broadcasts. (jnp.repeat builds its gather
            # indices from a cumsum over the whole flat length; the
            # sizes are constants, so XLA folds that at compile time,
            # through its slow evaluator: minutes for a ResNet.)
            parts = [jnp.broadcast_to(hyp[i], (sizes[i],))
                     for i in range(n)]
            if pad:
                parts.append(jnp.zeros((pad,), hyp.dtype))
            return parts[0] if len(parts) == 1 else \
                jnp.concatenate(parts)

        # rescale_grad is BAKED, exactly like the loop path bakes it in
        # the op's attrs key (a changed batch size recompiles once per
        # distinct value there too): as a runtime scalar, XLA can't
        # constant-fold the rescale=1.0 multiply away, and the extra
        # in-kernel op perturbs FMA contraction by an ulp vs the loop.
        def chunk_fn(grads, flat_w, flat_s, lrs, wds, *scale):
            # Concat + elementwise + slice: positionwise identical to
            # running the body once per parameter, in one executable
            # whose compute is a single vectorized pass.
            parts = [x.ravel() for x in grads]
            if pad:
                parts.append(jnp.zeros((pad,), grads[0].dtype))
            g = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            if with_scale:
                # Fused global-norm clip: one runtime scalar scales the
                # whole flat gradient (the per-param `a *= scale` of
                # gluon.utils.clip_global_norm, inside the executable).
                # Pad lanes stay zero. Compiled only when the Trainer
                # clips — unclipped executables are byte-identical to
                # the pre-clip ones.
                g = g * scale[0].astype(g.dtype)
            # The barrier materializes the expanded hyperparameters as
            # plain buffers: an expansion fused INTO the update loop
            # perturbs XLA:CPU codegen the same ulp-level way the
            # epilogue does. Found by end-to-end cross-check.
            lr_el, wd_el = jax.lax.optimization_barrier(
                (per_element(lrs), per_element(wds)))
            new_w, new_s = body(flat_w, g, tuple(flat_s), lr_el, wd_el,
                                rescale)
            outs = tuple(
                new_w[offsets[i]:offsets[i + 1]].reshape(shapes[i])
                for i in range(n))
            return outs, new_w, tuple(new_s)

        def mx_flatten_chunk(*xs):
            parts = [x.ravel() for x in xs]
            if pad:
                parts.append(jnp.zeros((pad,), xs[0].dtype))
            return parts[0] if len(parts) == 1 else \
                jnp.concatenate(parts)

        # The executables' names (no counter: they enter the cache key).
        # The flatten is not `mx_fused_*`: the compile log files that
        # prefix under site fused_apply, where it never counted.
        chunk_fn.__name__ = "mx_fused_" + spec.name

        # Donation (TPU/GPU): the flat weight and state inputs alias
        # their same-shaped outputs, so the steady-state fused cache
        # holds one flat copy, not two. The mp variant's low-precision
        # flat_w is dtype-only (the master drives), so only the state
        # tuple (which carries the master) donates there.
        jit_kwargs = {}
        if donate_enabled():
            jit_kwargs["donate_argnums"] = (2,) if spec.mp else (1, 2)
        ch = _ApplyChunk(
            jax.jit(chunk_fn, **jit_kwargs), jax.jit(mx_flatten_chunk),
            tuple(shapes), sizes, offsets, k)
        ch.mp = spec.mp
        ch.base_k = spec.base_k
        ch.with_scale = with_scale
        self._chunks[sig] = ch
        self.num_compiles += 1
        _apply_compiles.labels(optimizer=spec.name).inc()
        if self.on_compile is not None:
            self.on_compile(self)
        return ch

    def _sync_chunk(self, ch, group, states):
        """Reuse the cached flat weight/state buffers when nothing wrote
        around the fused path since the last step (validated by NDArray
        versions + state-entry identity); otherwise re-flatten from the
        LIVE updater states (not the grouping-time snapshot — a
        load_states in between must win) and install fresh views.
        Returns False when the live state layout no longer fits the
        family (caller falls back per-param)."""
        ws = [e[1] for e in group]
        fresh = (not ch.stale and ch.flat_w is not None
                 and ch.weights is not None
                 and all(a is b for a, b in zip(ch.weights, ws))
                 and all(w.version == v for w, v in zip(ws, ch.wver)))
        if fresh and ch.k:
            fresh = all(states[e[0]] is so
                        for e, so in zip(group, ch.state_objs))
        if fresh:
            return True
        sts = [self._state_for(states[e[0]], ch) for e in group]
        if any(s is None for s in sts):
            return False
        ch.flat_w = _dispatch("trainer::fused_flatten", ch.flatten_fn,
                              *[w._data for w in ws], kind="weights",
                              params=ch.n)
        for j in range(ch.k):
            ch.flat_s[j] = _dispatch(
                "trainer::fused_flatten", ch.flatten_fn,
                *[st[j]._data for st in sts], kind="state%d" % j,
                params=ch.n)
        ch.weights = ws
        ch.wver = [w.version for w in ws]
        ch.views, ch.state_objs = [], []
        if ch.k:
            ctx = ws[0].context
            for i, e in enumerate(group):
                views = tuple(
                    _FlatView(ch, j, ch.offsets[i], ch.sizes[i],
                              ch.shapes[i], ctx) for j in range(ch.k))
                if ch.mp:
                    # Preserve the (inner_state, master) nesting the
                    # loop path / checkpoints expect — the master is
                    # the LAST flat slot.
                    inner = views[:ch.base_k]
                    inner_obj = None if ch.base_k == 0 else \
                        inner[0] if ch.base_k == 1 else inner
                    obj = (inner_obj, views[ch.base_k])
                else:
                    obj = views[0] if ch.k == 1 else views
                states[e[0]] = obj
                ch.views.append(views)
                ch.state_objs.append(obj)
        ch.stale = False
        return True

    def _run_chunk(self, spec, gk, ch, group, opt, jnp, grad_scale=None):
        """Sync + dispatch + commit one chunk. Returns [] or the group's
        (index, weight, grad) triples when it must fall back."""
        from . import engine as _engine

        if not self._sync_chunk(ch, group, self.updater.states):
            return [(e[0], e[1], e[2]) for e in group]
        lrs, wds = [], []
        for e in group:
            index = e[0]
            # Host-side bookkeeping in loop-path order: count first,
            # then resolve per-index lr/wd multipliers (Adam's bias-
            # corrected lr_t etc. in python floats, like the loop).
            opt._update_count(index)
            lrs.append(spec.host_lr(opt, index, opt._get_lr(index)))
            wds.append(opt._get_wd(index))
        wdt = spec.hyp_dtype or gk[1]
        # lr/wd are RUNTIME vector inputs in the weight dtype (fp32 for
        # master-weight variants — one host->device rounding, the same
        # bits the loop path's baked attr gets after _c's cast), so LR
        # schedules never retrace; rescale is baked into the executable
        # (see _build_chunk).
        lrs = jnp.asarray(np.asarray(lrs, wdt))
        wds = jnp.asarray(np.asarray(wds, wdt))
        scale_args = ()
        if ch.with_scale:
            scale_args = (jnp.asarray(
                np.float32(1.0 if grad_scale is None else grad_scale)),)
        outs, new_w, new_s = _dispatch(
            "trainer::fused_apply", ch.exec_fn,
            tuple(e[2]._data for e in group), ch.flat_w,
            tuple(ch.flat_s), lrs, wds, *scale_args,
            optimizer=spec.name, params=len(group))
        # Inlined _set_data: this commit loop runs once per parameter
        # per step and the engine-mode check hoists out of it.
        naive = _engine.is_naive()
        wver = []
        for e, nw in zip(group, outs):
            w = e[1]
            w._data = nw
            w.version += 1
            wver.append(w.version)
            if naive:
                nw.block_until_ready()
        ch.flat_w = new_w
        ch.flat_s = list(new_s)
        ch.wver = wver
        for views in ch.views:
            for v in views:
                v._concrete = None           # value moved under the view
        if self._guard_armed and self.grad_guard is not None:
            # One isfinite reduction over the post-apply flat vector: a
            # NaN/Inf gradient anywhere in the bucket propagates into
            # the updated weights for every supported (elementwise)
            # body, so checking the flat weight catches poisoned grads
            # AND poisoned optimizer math in one O(buckets) pass. The
            # result stays on device (guard.flush() in apply() is the
            # single sync point), so the check never serializes the
            # bucket pipeline.
            self.grad_guard.check_flat(new_w, optimizer=spec.name,
                                       params=len(group))
        return []

    # -- public ----------------------------------------------------------------

    def open_guard_window(self):
        """Arm (or not, per its cadence) the numeric guard for a window
        of ``apply(..., manage_guard=False)`` calls — the Trainer's
        overlapped path applies bucket-by-bucket but the guard must
        still decide once per STEP, checking all of a step's buckets or
        none."""
        self._guard_armed = (self.grad_guard is not None
                            and self.grad_guard.arm_apply())

    def close_guard_window(self):
        """Single guard sync point after every bucket of the window
        dispatched. Also closes the warmup window: any plan built
        after this counts toward the recompile-storm budget."""
        if self._guard_armed and self.grad_guard is not None:
            self.grad_guard.flush()
        self._guard_armed = False
        self._warmed = True

    def apply(self, entries, grad_scale=None, manage_guard=True):
        """Fused-apply ``[(index, weight, grad)]``; returns the subset
        of entries that must take the per-param fallback loop.

        ``grad_scale``: optional runtime scalar multiplying every
        gradient inside the executable (the Trainer's fused global-norm
        clip). Presence (not value) is part of the executable
        signature, so unclipped trainers compile exactly the same
        chunks as before.

        ``manage_guard=False``: the caller brackets several applies in
        one :meth:`open_guard_window`/:meth:`close_guard_window` pair
        (one guard decision + one flush per step, however many buckets
        the step applies)."""
        opt = self.updater.optimizer
        base_spec = _spec_for(opt)
        if base_spec is None or not entries:
            return list(entries)

        import jax.numpy as jnp

        if manage_guard:
            # Cadence decision once per apply (not per chunk), so a
            # guard with every=N checks all of step N's buckets or none.
            self.open_guard_window()
        rescale = float(opt.rescale_grad)
        with_scale = grad_scale is not None
        # Plan cache keyed per entry-index run: the overlapped Trainer
        # applies one bucket at a time, so each bucket's entry list
        # gets its own steady-state plan instead of thrashing one slot.
        pk = (len(entries), entries[0][0], entries[-1][0])
        plan = self._plans.get(pk)
        if plan is not None and plan[0] == base_spec.name \
                and plan[1] == (base_spec.statics, rescale, with_scale) \
                and len(entries) == plan[2] \
                and all(e[0] == p[0] and e[1] is p[1] and e[2] is p[2]
                        for e, p in zip(entries, plan[3])):
            pending = list(plan[5])
            for spec, gk, ch, group in plan[4]:
                pending.extend(self._run_chunk(spec, gk, ch, group, opt,
                                               jnp, grad_scale))
            if manage_guard:
                self.close_guard_window()
            return pending

        self._replanning = plan is not None or self._warmed
        states = self.updater.states
        mp_spec = None
        pending, groups = [], {}
        for index, weight, grad in entries:
            if index not in states:
                # Same creation seam as Updater.__call__, so the loop
                # path / checkpoints see identical state layouts.
                states[index] = opt.create_state_multi_precision(
                    index, weight)
                self.updater.states_synced[index] = True
            if isinstance(grad, _sp.BaseSparseNDArray) \
                    or isinstance(weight, _sp.BaseSparseNDArray) \
                    or weight._data.dtype.kind not in "fV":
                # kind "V" admits bfloat16 (numpy reports ml_dtypes
                # extension floats as void-kind); integers and bools
                # still fall back.
                pending.append((index, weight, grad))
                continue
            spec = None
            if self._state_tuple(states[index], base_spec.n_states) \
                    is not None:
                spec = base_spec
            elif getattr(opt, "multi_precision", False):
                if mp_spec is None:
                    mp_spec = _mp_spec(base_spec)
                if self._state_tuple_mp(states[index],
                                        mp_spec.base_k) is not None:
                    spec = mp_spec
            if spec is None:
                pending.append((index, weight, grad))
                continue
            gk = (weight._ctx, weight._data.dtype, grad._data.dtype)
            groups.setdefault((spec, gk), []).append(
                (index, weight, grad))

        max_bytes = bucket_bytes()
        chunks = []
        for (spec, gk), group in groups.items():
            itemsize = gk[1].itemsize
            # ~bucket-sized chunks bound compile time and keep the
            # per-step dispatch count at ceil(params/bucket).
            for part in _pack_by_bytes(
                    group, max_bytes,
                    lambda e: (e[1]._data.size or 1) * itemsize):
                shapes = tuple(e[1]._data.shape for e in part)
                sig = (spec.name, spec.statics, gk, shapes, rescale,
                       with_scale)
                ch = self._chunks.get(sig)
                if ch is None:
                    ch = self._build_chunk(spec, sig, shapes, rescale,
                                           with_scale)
                chunks.append((spec, gk, ch, part))
        while len(self._plans) > 64:   # bounded: ~bucket count in play
            # Oldest-inserted first: retired generations' plans (which
            # pin their entries' NDArrays) go before the current
            # generation's hot per-bucket plans.
            self._plans.pop(next(iter(self._plans)))
        self._plans[pk] = (base_spec.name,
                           (base_spec.statics, rescale, with_scale),
                           len(entries), list(entries), chunks,
                           list(pending))
        pending = list(pending)
        for spec, gk, ch, part in chunks:
            pending.extend(self._run_chunk(spec, gk, ch, part, opt, jnp,
                                           grad_scale))
        if manage_guard:
            self.close_guard_window()
        return pending


class GradBucketer:
    """Coalesce many same-dtype gradients into few flat buckets.

    Built once per (param-set, bucket-size) signature; `flatten` and
    `unflatten` are each ONE cached jitted executable per bucket, so the
    per-step aggregation cost scales with ``ceil(params/bucket)``.
    """

    def __init__(self, shapes_dtypes, max_bytes=None):
        """``shapes_dtypes``: list of (key, shape, dtype) in push order."""
        max_bytes = bucket_bytes() if max_bytes is None else max_bytes
        by_dtype = {}
        for key, shape, dtype in shapes_dtypes:
            by_dtype.setdefault(np.dtype(dtype).str, []).append(
                (key, tuple(shape), np.dtype(dtype)))
        self.buckets = []
        for _, items in sorted(by_dtype.items()):
            for part in _pack_by_bytes(
                    items, max_bytes,
                    lambda it: int(np.prod(it[1] or (1,))) * it[2].itemsize):
                self.buckets.append(_Bucket(len(self.buckets), part))

    def __len__(self):
        return len(self.buckets)


class _Bucket:
    def __init__(self, bucket_id, items):
        self.id = bucket_id
        self.keys = [k for k, _, _ in items]
        self.shapes = [s for _, s, _ in items]
        self.sizes = [int(np.prod(s or (1,))) for _, s, _ in items]
        self.dtype = items[0][2]
        self.store_key = "__fused_grad_bucket_%d" % bucket_id
        self._flatten = None
        self._unflatten = None
        self._sumsq = None

    def sumsq(self, flat):
        """One executable: fp32 sum of squares of this bucket's flat
        gradient (XLA lowers the reduction as a tree-reduce). The
        Trainer's fused global-norm clip sums these per-bucket scalars
        on host instead of issuing one norm per parameter."""
        if self._sumsq is None:
            import jax
            import jax.numpy as jnp

            def mx_bucket_sumsq(f):      # the executable's name
                return jnp.sum(jnp.square(f.astype(jnp.float32)))

            self._sumsq = jax.jit(mx_bucket_sumsq)
        return _dispatch("trainer::bucket_sumsq", self._sumsq,
                         flat._data, bucket=self.id)

    def flatten(self, arrays, ctx):
        """One executable: ravel+concat this bucket's gradients."""
        if self._flatten is None:
            import jax
            import jax.numpy as jnp

            def mx_bucket_flatten(*gs):  # the executable's name
                return jnp.concatenate([g.ravel() for g in gs])

            self._flatten = jax.jit(mx_bucket_flatten)
        flat = _dispatch("trainer::bucket_flatten", self._flatten,
                         *[a._data for a in arrays],
                         bucket=self.id, params=len(self.keys))
        return NDArray(flat, ctx=ctx)

    def unflatten(self, flat):
        """One executable: slice+reshape back to per-param gradients
        (raw jax arrays — the caller commits them via `_set_data`)."""
        if self._unflatten is None:
            import jax

            offs = np.cumsum([0] + self.sizes)
            shapes = self.shapes

            def mx_bucket_unflatten(f):  # the executable's name
                return tuple(
                    f[offs[i]:offs[i + 1]].reshape(shapes[i])
                    for i in range(len(shapes)))

            self._unflatten = jax.jit(mx_bucket_unflatten)
        return _dispatch("trainer::bucket_unflatten", self._unflatten,
                         flat._data, bucket=self.id,
                         params=len(self.keys))
