"""Gluon Trainer.

Reference: python/mxnet/gluon/trainer.py (Trainer :27, _init_kvstore
:158, step/allreduce_grads/update, update_on_kvstore logic,
save_states/load_states).

TPU rebuild: single-context training updates in place via fused ops;
multi-context data-parallel reduces gradients through the kvstore
(XLA collectives / host reduction — kvstore package). The blessed
high-throughput path compiles fwd+bwd+update into one executable
(parallel.TrainStep); this Trainer keeps the imperative contract.

The imperative contract no longer means O(num_params) dispatches:
with ``fused=True`` (the default) the optimizer apply for supported
families is ONE jitted multi-tensor executable over the whole
parameter set (mxnet_tpu.fused_update.FusedApplier, bit-identical to
the per-param loop), gradient aggregation across devices moves
~25MB coalesced buckets instead of per-key tensors, and the
row-sparse gradient conversion runs on device instead of round-
tripping through `asnumpy()`. ``fused=False`` (or
``MXNET_FUSED_UPDATE=0``) restores the reference-shaped per-param
loop unchanged.
"""
from __future__ import annotations

import math
import queue
import threading
import time

from .. import compile as _cc
from .. import env as _env
from .. import optimizer as opt
from .. import ndarray as nd
from ..ndarray import sparse as _sp
from ..telemetry import metrics as _tm
from ..telemetry import trace as _trace
from ..telemetry import xtrace as _xtrace
from .parameter import ParameterDict

__all__ = ["Trainer"]

_update_seconds = _tm.REGISTRY.histogram(
    "mx_trainer_update_seconds",
    "Trainer._update wall time (host dispatch path, fused or loop; on "
    "the overlapped path this covers the whole reduce+apply pipeline)")
_reduce_seconds = _tm.REGISTRY.counter(
    "mx_trainer_reduce_seconds_total",
    "Gradient-reduce (kvstore push+pull) busy seconds on the fused "
    "bucketed path")
_reduce_hidden_seconds = _tm.REGISTRY.counter(
    "mx_trainer_reduce_hidden_seconds_total",
    "Reduce seconds hidden behind compute by the overlapped "
    "reduce->apply pipeline (busy - exposed main-thread wait)")
_overlap_efficiency = _tm.REGISTRY.gauge(
    "mx_trainer_overlap_efficiency",
    "Per-step overlap efficiency of the fused bucketed step: reduce "
    "time hidden / total reduce time (0 = fully serial)")


def _gn_sumsq(grad):
    """fp32 sum of squares of one gradient (the per-param half of the
    global-norm clip; low-precision grads upcast first — the bucketed
    tree-reduce does the same, fused_update._Bucket.sumsq)."""
    import numpy as np

    g32 = grad if grad.dtype == np.float32 else grad.astype(np.float32)
    return (g32 * g32).sum()


def overlap_depth():
    """Comm/compute overlap window (``MXNET_FUSED_OVERLAP_DEPTH``,
    default 2): how many gradient buckets may be reducing ahead of
    their fused applies. 0 restores the serial reduce-then-apply step.
    Read per step, so mid-run toggles take effect immediately."""
    return int(_env.get("MXNET_FUSED_OVERLAP_DEPTH"))


class _ReduceTask:
    """One bucket's reduce in flight: push + async pull issued on the
    Trainer's comm thread (or inline when serial), drained by the main
    thread in submission order."""

    __slots__ = ("key", "flats", "register", "event", "error", "handle",
                 "seconds", "inline_pull", "kv", "ctx")

    def __init__(self, key, flats, register=None, kv=None):
        self.key = key
        self.flats = flats
        self.register = register
        self.event = threading.Event()
        self.error = None
        self.handle = None
        self.seconds = 0.0
        self.inline_pull = False
        self.kv = kv
        # The step's trace context, captured where the task is BUILT
        # (the stepping thread) and re-activated on the comm thread so
        # the bucket's push/pull spans — and the wire context the dist
        # store injects — belong to the step's trace, not the thread's.
        self.ctx = _xtrace.current()

    def run(self, kv):
        t0 = time.perf_counter()
        try:
            with _xtrace.activate(self.ctx), \
                    _trace.span("trainer::allreduce", key=self.key,
                                overlapped=True):
                if self.register is not None:
                    self.register()
                kv.push(self.key, self.flats)
                self.handle = kv.pull_async(self.key, self.flats)
                # Local stores complete the pull inside pull_async
                # (handle.inline, a capability, not a timing race);
                # counting handle.seconds again would double-bill.
                self.inline_pull = self.handle.inline
        except BaseException as exc:      # noqa: BLE001 — relayed
            self.error = exc
        self.seconds = time.perf_counter() - t0
        self.event.set()

    def wait(self):
        """Block until push+pull landed; re-raise any transport error."""
        self.event.wait()
        if self.error is not None:
            raise self.error
        self.handle.wait()

    @property
    def comm_seconds(self):
        """Busy seconds this bucket spent in the store (push + pull)."""
        extra = 0.0 if (self.handle is None or self.inline_pull) \
            else self.handle.seconds
        return self.seconds + extra


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None, kvstore="device",
                 compression_params=None, update_on_kvstore=None, fused=None,
                 global_norm_clip=None):
        if isinstance(params, (dict, ParameterDict)):
            params = list(params.values())
        if not isinstance(params, (list, tuple)):
            raise ValueError("params must be a ParameterDict, dict or list")
        self._params = []
        self._param2idx = {}
        for i, p in enumerate(params):
            self._params.append(p)
            self._param2idx[p.name] = i
        self._compression_params = compression_params
        self._contexts = self._check_contexts()
        optimizer_params = optimizer_params or {}
        self._init_optimizer(optimizer, optimizer_params)
        self._scale = self._optimizer.rescale_grad
        self._kvstore_type = kvstore
        self._kvstore = None
        self._kv_initialized = False
        self._update_on_kvstore = update_on_kvstore
        self._states = {}
        self._fused = bool(_env.get("MXNET_FUSED_UPDATE")) \
            if fused is None else bool(fused)
        # Created unconditionally (it is a tiny object) and eagerly, so
        # telemetry.StepMonitor.attach_fused(trainer._applier) can wire
        # up before the first step and survives fused=False -> True
        # toggles with its hooks intact.
        from .. import fused_update as _fu

        self._applier = _fu.FusedApplier(self._updater)
        # Stable merge buffers for the local (kvstore=None) multi-device
        # path: reusing one NDArray per param keeps the applier's
        # identity-based plan cache hot (a fresh merged NDArray per step
        # would force the slow regroup path every step).
        self._merge_bufs = {}
        self._bucketer = None
        self._bucket_plan = None
        self._bucket_keys_inited = set()
        # Fused global-norm clip: ONE tree-reduce per flat bucket
        # replaces per-param norms; the resulting scale rides the chunk
        # executables as a runtime scalar (gluon.utils.clip_global_norm
        # semantics — norm of the summed, pre-rescale gradient).
        self._global_norm_clip = (None if global_norm_clip is None
                                  else float(global_norm_clip))
        if self._global_norm_clip is not None and \
                self._global_norm_clip <= 0:
            raise ValueError("global_norm_clip must be positive")
        # Overlapped reduce->apply pipeline (comm thread + bounded
        # async-pull window, MXNET_FUSED_OVERLAP_DEPTH).
        self._comm_q = None
        self._comm_thread = None
        self._uokv_bucketed = None     # update_on_kvstore bucket plan
        self._uokv_wbufs = {}          # bucket.id -> per-device flats

    def _check_contexts(self):
        contexts = None
        for p in self._params:
            if p._data is None:
                continue
            ctx = p.list_ctx()
            if contexts is None:
                contexts = ctx
        return contexts or []

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: p for i, p in enumerate(self._params)}
        if isinstance(optimizer, opt.Optimizer):
            if optimizer_params:
                raise ValueError(
                    "optimizer_params must be empty when optimizer is an instance")
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt.create(optimizer, param_dict=param_dict,
                                         **optimizer_params)
        self._updater = opt.get_updater(self._optimizer)

    def _init_kvstore(self):
        """Create the kvstore lazily on first step (reference:
        trainer.py:_init_kvstore). Needed for multi-context and for all
        ``dist_*`` stores (even single-context: the sync happens across
        worker processes, not local devices)."""
        contexts = self._check_contexts()
        name = (self._kvstore_type.type
                if hasattr(self._kvstore_type, "type")
                else str(self._kvstore_type or ""))
        dist = "dist" in name
        if (len(contexts) > 1 or dist) and self._kvstore_type:
            from .. import kvstore as kvs

            self._kvstore = (self._kvstore_type
                             if isinstance(self._kvstore_type, kvs.KVStore)
                             else kvs.create(name))
            if self._compression_params:
                self._kvstore.set_gradient_compression(
                    self._compression_params)
            # dist defaults to optimizer-on-server (reference trainer.py:
            # update_on_kvstore defaults True for dist); local stores
            # keep the local updater, which matches the reference's
            # multi-device default here because our local updater already
            # applies once-then-broadcast.
            if self._update_on_kvstore is None:
                self._update_on_kvstore = dist
            if self._update_on_kvstore and \
                    self._global_norm_clip is not None:
                # The server applies per key as pushes arrive; no point
                # exists where a worker holds the whole summed gradient
                # to take its norm.
                raise ValueError("global_norm_clip is not supported "
                                 "with update_on_kvstore")
            if dist and "async" in name and not self._update_on_kvstore:
                # Async pushes apply server-side immediately; without the
                # optimizer there the server would assign raw gradients
                # over the weights (reference raises the same way).
                raise ValueError(
                    "Please set update_on_kvstore=True for dist_async")
            if self._update_on_kvstore:
                self._kvstore.set_optimizer(self._optimizer)
            self._uokv_bucketed = (self._update_on_kvstore
                                   and self._uokv_eligible())
            skip = set()
            if self._uokv_bucketed:
                # Optimizer-on-server over coalesced flat buckets: the
                # server stores (and updates) one flat WEIGHT vector per
                # bucket, so per-step traffic and server applies scale
                # with ceil(params/bucket). Per-param keys exist only
                # for the odd (sparse/mixed-layout) leftovers.
                bucketer, bucket_params, _odd = self._ensure_bucketer()
                for b in bucketer.buckets:
                    skip.update(b.keys)     # bucket carries the indices
                self._init_uokv_buckets(bucketer, bucket_params)
            for i, p in enumerate(self._params):
                if p.grad_req != "null" and i not in skip:
                    self._kvstore.init(i, p.data())
        else:
            if self._update_on_kvstore:
                raise ValueError(
                    "update_on_kvstore=True requires a kvstore (multi-"
                    "context or dist_*); this trainer has %d context(s) "
                    "and kvstore=%r" % (len(contexts), self._kvstore_type))
            self._update_on_kvstore = False
        self._kv_initialized = True

    @property
    def learning_rate(self):
        return self._optimizer.lr_scheduler(self._optimizer.num_update) \
            if self._optimizer.lr_scheduler else self._optimizer.lr

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    def step(self, batch_size, ignore_stale_grad=False):
        """allreduce_grads + update (reference: trainer.py:step)."""
        # The step is a trace head: under an existing context (a caller
        # already rooted the step) keep it, else mint one — every span
        # and kvstore wire message below then carries the step's trace.
        ctx = _xtrace.current()
        with _xtrace.activate(ctx if ctx is not None
                              else _xtrace.new_root()), \
                _trace.span("trainer::step"):
            self._step_traced(batch_size, ignore_stale_grad)
        _cc.step_done()

    def _step_traced(self, batch_size, ignore_stale_grad=False):
        self._optimizer.rescale_grad = self._scale / batch_size
        if not self._kv_initialized:
            # Init after rescale_grad is final: dist stores pickle the
            # optimizer to the servers once (reference sends optstr at
            # kvstore init with the current rescale baked in).
            self._init_kvstore()
        if self._update_on_kvstore:
            if self._uokv_bucketed:
                self._step_on_kvstore_bucketed()
                return
            # Optimizer-on-server: push ALL gradients first, then pull all
            # weights (reference _update_params_on_kvstore ordering) — an
            # interleaved per-key push/pull would turn every key into a
            # cluster-wide sync point, since sync servers park the pull
            # until all workers pushed that key.
            for i, p in enumerate(self._params):
                if p.grad_req != "null":
                    self._kvstore.push(i, p.list_grad())
            for i, p in enumerate(self._params):
                if p.grad_req != "null":
                    self._kvstore.pull(i, out=p.list_data())
            return
        depth = overlap_depth() if self._fused else 0
        if self._kvstore is not None and self._fused and \
                (depth > 0 or self._global_norm_clip is not None):
            # Pipelined reduce->apply: bucket i's fused apply
            # dispatches while bucket i+1 is still reducing (depth 0 =
            # same per-bucket math run serially — the bit-identical
            # escape hatch; a global-norm clip also routes here so the
            # norm always comes from the same per-bucket tree-reduce).
            self._step_pipelined(depth, ignore_stale_grad)
            return
        self._allreduce_grads()
        self._update(ignore_stale_grad)

    def allreduce_grads(self):
        if not self._kv_initialized:
            self._init_kvstore()
        assert not self._update_on_kvstore, \
            "allreduce_grads is not supported with update_on_kvstore"
        self._allreduce_grads()

    def _allreduce_grads(self):
        if self._kvstore is None:
            return
        if not self._fused:
            for i, p in enumerate(self._params):
                if p.grad_req != "null":
                    grads = p.list_grad()
                    self._kvstore.push(i, grads)
                    self._kvstore.pull(i, grads)
            return
        # Bucketed aggregation: kvstore traffic and executable launches
        # scale with ceil(params/bucket), not parameter count. The flat
        # bucket sum is element-for-element the same add chain the
        # per-key merge runs, so the merged gradients are bit-identical;
        # bucket keys are stable across steps so per-key transport state
        # (gradient-compression error feedback on dist stores) stays
        # coherent.
        bucketer, bucket_params, odd = self._ensure_bucketer()
        with _trace.span("trainer::allreduce", buckets=len(bucketer),
                         unbucketed=len(odd)):
            for bucket in bucketer.buckets:
                params_b = bucket_params[bucket.id]
                # One grad-list build per param per step (list_grad
                # allocates a fresh list per call — measurable at
                # 1000s of params x devices).
                dev_grads = [list(p._grad.values()) for p in params_b]
                n_dev = len(dev_grads[0])
                flats = []
                for d in range(n_dev):
                    arrays = [g[d] for g in dev_grads]
                    flats.append(bucket.flatten(arrays,
                                                arrays[0].context))
                key = bucket.store_key
                self._register_bucket_key(bucket, flats)
                self._kvstore.push(key, flats)
                self._kvstore.pull(key, flats)
                for d, flat in enumerate(flats):
                    for grads, piece in zip(dev_grads,
                                            bucket.unflatten(flat)):
                        grads[d]._set_data(piece)
            for i in odd:
                grads = self._params[i].list_grad()
                self._kvstore.push(i, grads)
                self._kvstore.pull(i, grads)

    def _ensure_bucketer(self):
        """Build (or reuse) the coalescing plan for the current gradient
        set. Steady state is one O(n) identity sweep (param + grad-dict
        objects are stable across steps — the FusedApplier plan-cache
        trick); the full signature rebuild runs only on drift (e.g.
        late-initialized params), and each generation gets fresh store
        keys — the retired generation's entries are discarded — so
        stale kvstore state of the old layout is never summed into."""
        from .. import fused_update as _fu

        plan = self._bucket_plan
        if plan is not None:
            p_snap, g_snap, result = plan
            if len(p_snap) == len(self._params) and \
                    all(a is b for a, b in zip(p_snap, self._params)) and \
                    all(p._grad is g for p, g in zip(p_snap, g_snap)):
                return result

        entries, odd, sig = [], [], []
        first_ctx = None
        for i, p in enumerate(self._params):
            if p.grad_req == "null" or p._data is None:
                continue
            grad = p.list_grad()[0]
            ctxs = tuple(str(c) for c in p.list_ctx())
            if first_ctx is None:
                first_ctx = ctxs
            if isinstance(grad, _sp.BaseSparseNDArray) or ctxs != first_ctx:
                # Sparse gradients / odd device layouts keep the per-key
                # path; everything dense and uniform coalesces.
                odd.append(i)
                continue
            entries.append((i, grad.shape, grad.dtype))
            sig.append((i, grad.shape, str(grad.dtype)))
        sig = tuple(sig)
        if self._bucketer is None or self._bucketer_sig != sig:
            gen = getattr(self, "_bucket_gen", -1) + 1
            self._bucket_gen = gen
            # Free the retired generation's flat buffers — without this
            # every signature drift leaks bucket-sized store entries
            # for process lifetime.
            if self._kvstore is not None:
                for key in self._bucket_keys_inited:
                    self._kvstore.discard(key)
            self._bucketer = _fu.GradBucketer(entries)
            self._bucketer_sig = sig
            self._bucket_keys_inited = set()
            for b in self._bucketer.buckets:
                b.store_key = "__fused_grad_bucket_%d_%d" % (gen, b.id)
        bucket_params = {b.id: [self._params[i] for i in b.keys]
                         for b in self._bucketer.buckets}
        result = (self._bucketer, bucket_params, odd)
        self._bucket_plan = (tuple(self._params),
                             tuple(p._grad for p in self._params), result)
        return result

    # -- optimizer-on-server over flat buckets --------------------------------

    def _uokv_eligible(self):
        """Bucketed update_on_kvstore is safe when the optimizer family
        is elementwise (the fused-apply table is exactly that list —
        updating a concatenation then slicing equals updating each
        param) and no per-key lr/wd multipliers exist (a flat bucket
        has ONE server key; reference param_dict multipliers never
        cross the wire either way)."""
        if not self._fused:
            return False
        from .. import fused_update as _fu

        if _fu._spec_for(self._optimizer) is None:
            return False
        if getattr(self._optimizer, "multi_precision", False):
            return False
        if self._optimizer.lr_mult or self._optimizer.wd_mult:
            return False
        return all(getattr(p, "lr_mult", 1.0) == 1.0 and
                   getattr(p, "wd_mult", 1.0) == 1.0
                   for p in self._params)

    def _init_uokv_buckets(self, bucketer, bucket_params):
        """Seed the servers with one flat WEIGHT vector per bucket."""
        for b in bucketer.buckets:
            params_b = bucket_params[b.id]
            weights = [list(p._data.values())[0] for p in params_b]
            wflat = b.flatten(weights, weights[0].context)
            if not self._kvstore.contains(b.store_key):
                self._kvstore.init(b.store_key, wflat)
            self._bucket_keys_inited.add(b.store_key)

    def _step_on_kvstore_bucketed(self):
        """Optimizer-on-server step over coalesced buckets: push flat
        gradient buckets (push-all), pull flat weight buckets back
        (pull-all — the reference _update_params_on_kvstore ordering),
        slice weights out per parameter. Odd (sparse / mixed-layout)
        parameters keep the per-key path."""
        t0 = time.perf_counter()
        bucketer, bucket_params, odd = self._ensure_bucketer()
        kv = self._kvstore
        if not all(b.store_key in self._bucket_keys_inited
                   for b in bucketer.buckets):
            # Signature drift retired the old generation; seed the new
            # bucket keys from the current weights.
            self._uokv_wbufs = {}
            self._init_uokv_buckets(bucketer, bucket_params)
        with _trace.span("trainer::allreduce", buckets=len(bucketer),
                         unbucketed=len(odd), on_kvstore=True):
            for bucket in bucketer.buckets:
                params_b = bucket_params[bucket.id]
                dev_grads = [list(p._grad.values()) for p in params_b]
                flats = [bucket.flatten([g[d] for g in dev_grads],
                                        dev_grads[0][d].context)
                         for d in range(len(dev_grads[0]))]
                kv.push(bucket.store_key, flats)
            for i in odd:
                kv.push(i, self._params[i].list_grad())
            for bucket in bucketer.buckets:
                params_b = bucket_params[bucket.id]
                dev_datas = [list(p._data.values()) for p in params_b]
                wbufs = self._uokv_wbufs.get(bucket.id)
                if wbufs is None:
                    # Per-device flat weight buffers, shaped by one
                    # flatten and reused every step thereafter.
                    wbufs = [bucket.flatten([d[dd] for d in dev_datas],
                                            dev_datas[0][dd].context)
                             for dd in range(len(dev_datas[0]))]
                    self._uokv_wbufs[bucket.id] = wbufs
                kv.pull(bucket.store_key, out=wbufs)
                for dd, wflat in enumerate(wbufs):
                    for datas, piece in zip(dev_datas,
                                            bucket.unflatten(wflat)):
                        datas[dd]._set_data(piece)
            for i in odd:
                kv.pull(i, out=self._params[i].list_data())
        _update_seconds.observe(time.perf_counter() - t0)

    # -- overlapped reduce->apply pipeline ------------------------------------

    def _ensure_comm_thread(self):
        if self._comm_thread is None:
            import weakref

            q = self._comm_q = queue.Queue()

            def loop():
                # References only the queue (tasks carry their store):
                # the thread must not pin the Trainer. The finalizer
                # below posts the None sentinel when the Trainer is
                # collected, so the thread exits instead of leaking
                # one per retired Trainer in long-lived processes.
                while True:
                    task = q.get()
                    if task is None:
                        return
                    task.run(task.kv)
                    # Drop the binding before parking in get(): the
                    # last task's register closure holds the Trainer,
                    # and an idle thread must not pin it past GC.
                    task = None

            self._comm_thread = threading.Thread(
                target=loop, name="mx-trainer-comm", daemon=True)
            self._comm_thread.start()
            fin = weakref.finalize(self, q.put, None)
            # GC-time cleanup only: waking the daemon thread DURING
            # interpreter shutdown makes CPython pthread_exit it inside
            # C++ frames ("terminate called without an active
            # exception"); at process exit daemon threads just die.
            fin.atexit = False

    def _register_bucket_key(self, bucket, flats):
        """Lazy kvstore registration for one bucket key (on the
        overlapped path this runs on the comm thread, serialized with
        the pushes that follow it). contains() covers a store shared by
        two trainers (same generation keys); the per-trainer set covers
        stores that can't track membership."""
        key = bucket.store_key
        if key not in self._bucket_keys_inited:
            if not self._kvstore.contains(key):
                self._kvstore.init(key, flats[0])
            self._bucket_keys_inited.add(key)

    def _classify_entries(self, items):
        """The ONE fused-path entry classification (shared by the
        per-bucket and odd-key reduces): ``items`` yields
        ``(index, param, merged_grad)``; row-sparse-stype params get
        the device-side conversion and fall back per param, everything
        else is fused-apply work."""
        work, fallback = [], []
        for i, p, grad in items:
            datas = list(p._data.values())
            if p._grad_stype == "row_sparse":
                fallback.append((i, datas, _sp.dense_to_rsp_device(grad)))
            else:
                work.append((i, datas, grad))
        return work, fallback

    def _bucket_entries(self, bucket, params_b, dev_grads):
        """Split one landed bucket into fused-apply entries and
        per-param fallback entries. ``bucket.keys`` carries the
        parameter indices in pack order."""
        return self._classify_entries(
            (i, p, grads[0])
            for i, p, grads in zip(bucket.keys, params_b, dev_grads))

    def _step_pipelined(self, depth, ignore_stale_grad=False):
        """The overlapped fused step: buckets reduce in REVERSE
        parameter order (reverse-topological — the gradients backward
        produced last reduce first, the DDP discipline) through a comm
        thread + async pull handles, and each bucket's fused apply
        dispatches as soon as THAT bucket's pull lands, while up to
        ``depth`` later buckets are still reducing. ``depth == 0`` runs
        the same per-bucket math serially (bit-identical toggle). With
        a global-norm clip the applies gate on the last bucket's norm
        contribution, but the per-bucket sum-of-squares tree-reduces
        still ride the overlap window."""
        t0 = time.perf_counter()
        bucketer, bucket_params, odd = self._ensure_bucketer()
        clip = self._global_norm_clip
        if clip is not None and any(
                p._grad_stype != "default" or
                (p._grad and isinstance(next(iter(p._grad.values())),
                                        _sp.BaseSparseNDArray))
                for p in self._params
                if p._grad_req != "null" and p._data is not None):
            raise ValueError("global_norm_clip requires dense gradients")
        serial = depth <= 0
        if not serial:
            self._ensure_comm_thread()
        buckets = list(reversed(bucketer.buckets))
        stats = {"wait": 0.0, "comm": 0.0}
        in_flight = []                   # (bucket, task, dev_grads)
        next_i = [0]

        def submit_one():
            if next_i[0] >= len(buckets):
                return False
            bucket = buckets[next_i[0]]
            next_i[0] += 1
            params_b = bucket_params[bucket.id]
            dev_grads = [list(p._grad.values()) for p in params_b]
            flats = [bucket.flatten([g[d] for g in dev_grads],
                                    dev_grads[0][d].context)
                     for d in range(len(dev_grads[0]))]
            task = _ReduceTask(
                bucket.store_key, flats,
                lambda b=bucket, f=flats: self._register_bucket_key(b, f),
                kv=self._kvstore)
            in_flight.append((bucket, task, dev_grads))
            if serial:
                # Inline reduce: the main thread is blocked for the
                # whole round-trip, so it all counts as EXPOSED wait
                # (hidden stays 0 — the honest serial baseline).
                w0 = time.perf_counter()
                task.run(self._kvstore)
                stats["wait"] += time.perf_counter() - w0
            else:
                self._comm_q.put(task)
            return True

        def drain_one():
            """Wait for the oldest in-flight bucket, commit its merged
            gradients, return (bucket, task, dev_grads)."""
            bucket, task, dev_grads = in_flight.pop(0)
            w0 = time.perf_counter()
            task.wait()
            waited = time.perf_counter() - w0
            stats["wait"] += waited
            stats["comm"] += task.comm_seconds
            for d, flat in enumerate(task.flats):
                for grads, piece in zip(dev_grads, bucket.unflatten(flat)):
                    grads[d]._set_data(piece)
            _trace.complete("trainer::bucket_overlap", w0,
                            time.perf_counter(),
                            bucket=bucket.id, wait_s=round(waited, 6),
                            comm_s=round(task.comm_seconds, 6),
                            serial=serial)
            return bucket, task, dev_grads

        window = 1 if serial else max(1, depth)
        for _ in range(window):
            if not submit_one():
                break

        applier = self._applier
        applier.open_guard_window()
        processed = []                   # (work, fallback) per bucket
        pending_applies = []             # deferred under global clip
        sumsq = []
        scale = None
        try:
            with _trace.span("trainer::update", fused=True,
                             overlapped=not serial,
                             buckets=len(buckets), unbucketed=len(odd)):
                while in_flight:
                    bucket, task, dev_grads = drain_one()
                    submit_one()
                    params_b = bucket_params[bucket.id]
                    work, fallback = self._bucket_entries(
                        bucket, params_b, dev_grads)
                    processed.append((work, fallback))
                    if clip is not None:
                        # One fp32 tree-reduce per flat bucket; the
                        # scalar syncs lazily when the norm is taken.
                        sumsq.append(bucket.sumsq(task.flats[0]))
                        pending_applies.append((work, fallback))
                        continue
                    self._apply_bucket(work, fallback, None)
                # Odd (per-key) leftovers reduce after the buckets.
                odd_entries = self._reduce_odd(odd)
                if clip is not None:
                    for i, datas, grad in odd_entries[0]:
                        sumsq.append(_gn_sumsq(grad))
                    total = math.fsum(float(s.asnumpy())
                                      if hasattr(s, "asnumpy")
                                      else float(s) for s in sumsq)
                    # Exactly 1.0 below the limit: stable executable
                    # signature, exact multiply.
                    scale = min(1.0, clip / (math.sqrt(total) + 1e-8))
                    for work, fallback in pending_applies:
                        self._apply_bucket(work, fallback, scale)
                self._apply_bucket(*odd_entries, scale)
                processed.append(odd_entries)
        except BaseException:
            # Quiesce before surfacing: buckets already handed to the
            # comm thread keep running there — wait out their pushes
            # AND (bounded) their async pulls, ignoring errors, so in
            # the common transient-failure case nothing is still
            # touching the store or the gradient buffers after step()
            # raises. Bounded, not absolute: a sync-mode pull parked on
            # a dead peer cannot be cancelled (same property as the
            # serial path, which would block the main thread on it).
            for _, task, _ in in_flight:
                if task.event.wait(timeout=60.0) and task.error is None \
                        and task.handle is not None:
                    try:
                        task.handle.wait(timeout=60.0)
                    except Exception:   # noqa: BLE001 — quiescing
                        pass
            raise
        finally:
            applier.close_guard_window()
        # Broadcast the updated first replica to the other devices
        # (same tail the serial `_update` runs).
        for work, fallback in processed:
            for i, d, g in work + fallback:
                for dd in d[1:]:
                    dd[:] = d[0].as_in_context(dd.context)
        total_comm = stats["comm"]
        hidden = max(0.0, total_comm - stats["wait"])
        _reduce_seconds.inc(total_comm)
        _reduce_hidden_seconds.inc(hidden)
        _overlap_efficiency.set(hidden / total_comm if total_comm > 0
                                else 0.0)
        _update_seconds.observe(time.perf_counter() - t0)

    def _reduce_odd(self, odd):
        """Per-key reduce + entry classification for the parameters the
        bucketer left out (sparse grads, mixed device layouts)."""
        def reduced():
            for i in odd:
                p = self._params[i]
                grads = p.list_grad()
                self._kvstore.push(i, grads)
                self._kvstore.pull(i, grads)
                yield i, p, grads[0]

        return self._classify_entries(reduced())

    def _apply_bucket(self, work, fallback, scale):
        """Fused-apply one bucket's entries (falling back per param
        where the applier declines), then the explicit fallbacks."""
        if work:
            pend = self._applier.apply([(i, d[0], g) for i, d, g in work],
                                       grad_scale=scale,
                                       manage_guard=False)
            for i, w, g in pend:
                if scale is not None and scale != 1.0:
                    g = g * scale
                self._updater(i, g, w)
        for i, d, g in fallback:
            if scale is not None and scale != 1.0:
                g = g * scale
            self._updater(i, g, d[0])

    def update(self, batch_size, ignore_stale_grad=False):
        if not self._kv_initialized:
            self._init_kvstore()
        assert not self._update_on_kvstore, \
            "update() is not supported with update_on_kvstore"
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False):
        """Apply the optimizer ONCE per parameter on the first replica,
        then broadcast the result (reference update_on_kvstore=True path,
        module.py:_update_params_on_kvstore) — running one updater per
        context would advance Adam's t / the LR schedule num_ctx times
        per batch.

        Fused path (default): dense parameters of a supported optimizer
        family go through ONE multi-tensor executable per (ctx, dtype)
        group instead of one dispatch each, and row-sparse gradients
        convert on device. Parameter values match the ``fused=False``
        loop bitwise for vector-aligned sizes, within an ulp otherwise
        (fused_update._build_chunk)."""
        t0 = time.perf_counter()
        work, fallback = [], []
        for i, p in enumerate(self._params):
            # Direct attribute reads: this loop runs once per parameter
            # per step, so property indirection is measurable at 1000s
            # of params.
            if p._grad_req == "null" or p._data is None:
                continue
            datas = list(p._data.values())
            grads = list(p._grad.values()) if p._grad else []
            # After _allreduce_grads all replicas hold the merged
            # gradient; without a kvstore (kvstore=None) merge locally so
            # replicas 1..N are not silently dropped.
            grad = grads[0]
            if len(grads) > 1 and self._kvstore is None:
                for g in grads[1:]:
                    grad = grad + g.as_in_context(grad.context)
                buf = self._merge_bufs.get(i)
                if buf is None:
                    buf = self._merge_bufs[i] = grad
                else:
                    buf._set_data(grad._data)
                grad = buf
            if p._grad_stype == "row_sparse":
                # Embedding-style gradients touch few rows: convert the
                # (dense, mostly-zero) autograd gradient to row_sparse so
                # the optimizer's lazy sparse update path runs (reference
                # grad_stype='row_sparse' Parameter contract).
                if self._fused:
                    # Nonzero-row extraction on device — only the row
                    # COUNT crosses to host, never the gradient payload.
                    grad = _sp.dense_to_rsp_device(grad)
                else:
                    grad = _sp.row_sparse_array(grad.asnumpy(),
                                                ctx=grad.context)
                fallback.append((i, datas, grad))
                continue
            work.append((i, datas, grad))
        scale = None
        if self._global_norm_clip is not None:
            if fallback:
                raise ValueError(
                    "global_norm_clip requires dense gradients")
            # Per-param norms (the reference clip_global_norm shape) —
            # the bucketed pipeline replaces these with one tree-reduce
            # per flat bucket. fp32 accumulation: squaring fp16 grads
            # in their own dtype overflows to inf past |g|~256 and the
            # f16 accumulator saturates long before that.
            total = math.fsum(float(_gn_sumsq(g).asnumpy())
                              for _, _, g in work)
            # Below the limit the scale pins to exactly 1.0 (an exact
            # multiply) so the clipped executable signature is stable
            # step to step instead of flapping with the norm.
            scale = min(1.0,
                        self._global_norm_clip / (math.sqrt(total) + 1e-8))
        with _trace.span("trainer::update", fused=self._fused,
                         params=len(work) + len(fallback)):
            if self._fused and work:
                # Entries the applier cannot fuse (unsupported family,
                # sparse state layouts, ...) come back for the
                # reference-shaped per-param loop.
                for i, w, g in self._applier.apply(
                        [(i, d[0], g) for i, d, g in work],
                        grad_scale=scale):
                    if scale is not None and scale != 1.0:
                        g = g * scale
                    self._updater(i, g, w)
            else:
                for i, d, g in work:
                    if scale is not None and scale != 1.0:
                        g = g * scale
                    self._updater(i, g, d[0])
            for i, d, g in fallback:
                self._updater(i, g, d[0])
            for i, d, g in work + fallback:
                for dd in d[1:]:
                    dd[:] = d[0].as_in_context(dd.context)
        _update_seconds.observe(time.perf_counter() - t0)

    def save_states(self, fname):
        """Reference: trainer.py:save_states — updater state pickles.
        Atomic (tmp + rename) so a mid-save crash never leaves a
        truncated pickle."""
        from ..base import atomic_write

        with atomic_write(fname) as f:
            f.write(self._updater.get_states(dump_optimizer=False))

    def load_states(self, fname):
        with open(fname, "rb") as f:
            payload = f.read()
        self._updater.set_states(payload)
        self._updater.optimizer = self._optimizer
