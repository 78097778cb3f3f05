"""mxnet_tpu.compile — persistent compilation cache with pod-wide
distribution and recompile elimination (ROADMAP direction 2).

Every process of this framework historically re-paid full XLA compile
cost at warmup: the serving bucket ladder, the fused-update flat
chunks and the whole-step TrainStep executable each traced and
compiled from scratch on every start, and a recompile storm was only
*detected* (telemetry.StepMonitor), never prevented. This package
makes executables durable:

* :func:`cached_compile` / :func:`maybe_cached_jit` wrap a pure
  function the way ``jax.jit`` does, but back the per-shape-signature
  executable cache with a disk store (:mod:`.store`): a miss lowers the
  function, fingerprints the StableHLO, compiles, serializes the
  executable (``jax.experimental.serialize_executable``) and commits it
  atomically; a hit deserializes and loads — no XLA compile at all. The
  key is (caller key-parts, HLO fingerprint, device kind + topology,
  backend platform, jax/jaxlib versions): anything that could change
  generated code changes the key, so version skew is a miss, never a
  wrong executable.

* Distribution (:mod:`.distribute`): with a kvstore attached
  (:func:`attach_kvstore`), rank 0 publishes every entry it compiles
  over new ``cc_push``/``cc_pull``/``cc_probe`` commands, and any rank
  that misses locally pulls the peer-compiled entry instead of
  compiling — an elastic worker joining the pod warm-starts from the
  fleet's cache (rank-0-compiles-peers-pull, the telemetry/diag
  command-channel precedent).

* Fallback discipline: backends that cannot serialize executables, IO
  failures and damaged entries all degrade to a plain compile, counted
  on ``mx_compile_cache_{hits,misses,errors}_total`` — the cache is
  never load-bearing; the worst failure costs one recompile.

Enable with ``MXNET_COMPILE_CACHE=<dir>`` (optionally
``MXNET_COMPILE_CACHE_MB`` for LRU retention) or programmatically via
:func:`configure`. Disabled (the default) every seam compiles exactly
as before.

This executable store is NOT JAX's own persistent compilation cache.
That one is keyed by XLA on the compile request, serves every
``jax.jit`` in the process and is what the entry points turn on with
:func:`enable_jax_cache`; the store above serves three seams, ships
entries across a pod and stays opt-in.
"""
from __future__ import annotations

import os
import pickle
import threading
import time

from .store import CompileCacheStore, make_key, entry_name, ENTRY_FORMAT
from .buildlog import build_log, step_done, install as _install_log
from ..telemetry import metrics as _tm
from ..telemetry import trace as _trace
from .. import log as _log

__all__ = ["CachedFunction", "CompileCacheStore", "cached_compile",
           "maybe_cached_jit", "configure", "reset", "enabled",
           "active_store", "attach_kvstore", "set_distributor",
           "shared_filesystem", "backend_fingerprint", "make_key",
           "entry_name", "ENTRY_FORMAT", "enable_jax_cache", "build_log",
           "step_done"]

# The compile log (buildlog.py) listens to JAX's compile events from
# here on: every program traced, lowered or built after this import is
# in compile.build_log().
_install_log()

_hits_total = _tm.REGISTRY.counter(
    "mx_compile_cache_hits_total",
    "Persistent-compile-cache hits (an executable loaded instead of "
    "compiled); source=local is this process's disk, source=remote a "
    "peer's entry pulled over the kvstore", labels=("site", "source"))
_misses_total = _tm.REGISTRY.counter(
    "mx_compile_cache_misses_total",
    "Persistent-compile-cache misses (a real XLA compile was paid)",
    labels=("site",))
_errors_total = _tm.REGISTRY.counter(
    "mx_compile_cache_errors_total",
    "Cache failures, all degraded to a plain compile: kind=corrupt "
    "(entry failed validation), serialize_unsupported (backend cannot "
    "serialize), deserialize (stored entry failed to load), io (commit "
    "failed), distribute (peer fetch/publish failed)",
    labels=("site", "kind"))
_load_seconds = _tm.REGISTRY.histogram(
    "mx_compile_cache_load_seconds",
    "Wall time to deserialize+load a cached executable (the cost a hit "
    "pays instead of mx_compile_seconds)", labels=("site",))

_logger = _log.get_logger("mxnet_tpu.compile")

# -- JAX's own persistent compilation cache ------------------------------------

_JAX_CACHE_DEFAULT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_jax_cache():
    """Place JAX's persistent compilation cache for this process; entry
    points call it before their first compile. ``JAX_COMPILATION_CACHE_DIR``
    wins when set (JAX reads it itself, nothing is set here); otherwise
    the cache lives at ``<checkout>/.jax_cache``. The directory is part
    of the cache key, so it is a fixed path, never one named after a
    temporary directory, a pid or the time. Returns the directory.

    Every program is stored, however quickly it compiled, unless the
    operator set ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS``. JAX's
    default keeps what took a second or more, and the framework's
    set-up is mostly programs under it (``compile.build_log()``:
    parameter initialisers, eager ops, the input pool), each compiled
    again by every process and stored only by the one run in which it
    happened to take longer, so that a warm start depended on how many
    runs the directory had seen."""
    import jax

    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not directory:
        directory = _JAX_CACHE_DEFAULT
        jax.config.update("jax_compilation_cache_dir", directory)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return directory


# -- process-wide configuration ------------------------------------------------

_lock = threading.Lock()
_store = None
_distributor = None
_configured = False        # configure()/env decision made


def _default_max_bytes():
    from .. import env as _env

    return int(_env.get("MXNET_COMPILE_CACHE_MB")) * (1 << 20)


def configure(directory, max_bytes=None):
    """Enable the cache at ``directory`` for this process (overrides the
    ``MXNET_COMPILE_CACHE`` env decision). ``max_bytes=None`` uses the
    ``MXNET_COMPILE_CACHE_MB`` budget. Returns the active store."""
    global _store, _configured
    with _lock:
        _store = CompileCacheStore(
            directory,
            _default_max_bytes() if max_bytes is None else max_bytes)
        _configured = True
        return _store


def reset():
    """Disable the cache and forget the env decision + distributor
    (tests; a later call re-reads the environment)."""
    global _store, _distributor, _configured
    with _lock:
        _store = None
        _distributor = None
        _configured = False


def active_store():
    """The live :class:`CompileCacheStore`, or None when disabled.
    First call reads ``MXNET_COMPILE_CACHE`` unless :func:`configure`
    already decided."""
    global _store, _configured
    with _lock:
        if not _configured:
            _configured = True
            from .. import env as _env

            directory = _env.get("MXNET_COMPILE_CACHE")
            if directory:
                _store = CompileCacheStore(directory, _default_max_bytes())
        return _store


def enabled():
    return active_store() is not None


def set_distributor(distributor):
    """Install (or clear, with None) the pod-distribution transport
    consulted on local misses and fed on local compiles."""
    global _distributor
    with _lock:
        _distributor = distributor
    return distributor


def shared_filesystem():
    """``MXNET_COMPILE_CACHE_SHARED=1``: every rank's
    ``MXNET_COMPILE_CACHE`` points at ONE shared directory (NFS,
    GCS-fuse). Safe by construction — entries commit through the
    checkpoint tmp+fsync+rename seam, so concurrent ranks see either a
    whole entry or none, and a racing double-compile just commits the
    same bytes twice. The kvstore ``cc_*`` channel is redundant then:
    :func:`attach_kvstore` becomes a no-op (no pushes, no probe
    round-trips)."""
    from .. import env as _env

    return bool(_env.get("MXNET_COMPILE_CACHE_SHARED"))


def attach_kvstore(kv, prefetch=True):
    """Convenience: wire a :class:`.distribute.CacheDistributor` over a
    kvstore-shaped transport (``KVStoreDist`` or a LocalBus endpoint
    with the ``cc_*`` commands). No-op returning None when the cache is
    disabled — or in shared-filesystem mode
    (``MXNET_COMPILE_CACHE_SHARED=1``), where the common cache
    directory already distributes entries and the kvstore channel would
    only duplicate bytes.

    By default the attach also PREFETCHES: one ``cc_probe(None)``
    round enumerates every entry the rendezvous holds, and the ones
    missing from this rank's disk store are pulled and committed
    immediately — an elastic joiner warms its store before the first
    trace instead of discovering entries miss-by-miss. Pass
    ``prefetch=False`` to attach lazily."""
    if not enabled() or shared_filesystem():
        return None
    from .distribute import CacheDistributor

    dist = set_distributor(CacheDistributor(kv))
    if prefetch:
        dist.prefetch(active_store())
    return dist


def _active_distributor():
    with _lock:
        return _distributor


# -- key ingredients -----------------------------------------------------------

_backend_fp = None


def backend_fingerprint():
    """Everything about THIS process's backend that could change
    generated code: platform, device kind, device count, process count,
    jax/jaxlib versions, XLA flags. Part of every cache key, so an
    upgraded jaxlib or a different chip is a clean miss."""
    global _backend_fp
    if _backend_fp is None:
        import jax
        import jaxlib

        devices = jax.devices()
        _backend_fp = {
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "num_devices": len(devices),
            "process_count": jax.process_count(),
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "xla_flags": os.environ.get("XLA_FLAGS", ""),
        }
    return _backend_fp


def _signature(args):
    """Hashable per-call shape/dtype signature — the same distinctions
    ``jax.jit`` retraces on (shape, dtype, weak_type, tree structure)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    sig = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        if shape is not None:
            sig.append((tuple(shape), str(getattr(leaf, "dtype", "?")),
                        bool(getattr(leaf, "weak_type", False))))
        elif isinstance(leaf, (bool, int, float, complex)):
            # Python scalars are DYNAMIC weak-typed inputs under jit:
            # key by type, not value, or every new value would mint a
            # fresh executable slot.
            sig.append(("py", type(leaf).__name__))
        else:
            sig.append(("py", repr(leaf)))
    return treedef, tuple(sig)


# -- the cached jit wrapper ----------------------------------------------------

class CachedFunction:
    """``jax.jit``-shaped callable whose per-shape executables load from
    the persistent cache.

    Dispatch: a per-signature dict lookup then the executable call —
    the steady state adds one tree-flatten over the arguments versus a
    plain jitted call. A signature's first call fills the slot:
    local disk hit → deserialize; else peer fetch (when a distributor
    is attached) → commit locally + deserialize; else compile,
    serialize, commit, publish. Every fallback lands on the plain
    compiled executable, so behavior is identical to ``jax.jit`` minus
    the compile time saved.
    """

    def __init__(self, fn, site, key_parts=(), store=None,
                 publish=None, **jit_kwargs):
        import jax

        self._fn = fn
        self.site = site
        self.key_parts = tuple(key_parts)
        self._store = store
        # publish: None = ask the distributor (rank 0 publishes);
        # True/False force.
        self._publish = publish
        self._jit = jax.jit(fn, **jit_kwargs)
        self._execs = {}
        self._fill_lock = threading.Lock()
        self.num_compiles = 0       # real XLA compiles this instance paid
        self.num_hits = 0           # executables loaded without compiling

    # -- dispatch -------------------------------------------------------------

    def __call__(self, *args):
        sig = _signature(args)
        entry = self._execs.get(sig)
        if entry is None:
            entry = self._fill(sig, args)
        return entry(*args)

    def lower(self, *args):
        return self._jit.lower(*args)

    # -- fill (one compile-or-load per signature) ------------------------------

    def _fill(self, sig, args):
        with self._fill_lock:
            entry = self._execs.get(sig)
            if entry is not None:
                return entry
            try:
                entry = self._load_or_compile(args)
            except Exception as exc:
                # The cache must never take down a dispatch: any
                # unforeseen AOT-path failure degrades to the plain
                # jitted callable (which compiles internally).
                _errors_total.labels(site=self.site, kind="aot").inc()
                _log.warn_rate_limited(
                    _logger, "cc_aot:%d" % id(self), 60.0,
                    "compile cache AOT path failed at site %s "
                    "(falling back to plain jit): %s", self.site, exc)
                entry = self._jit
            self._execs[sig] = entry
            return entry

    def _load_or_compile(self, args):
        store = self._store if self._store is not None else active_store()
        with _trace.span("compile_cache::lower", site=self.site):
            lowered = self._jit.lower(*args)
            fingerprint = _fingerprint_text(lowered)
        key = make_key([list(self.key_parts), fingerprint,
                        backend_fingerprint()])
        if store is not None:
            compiled = self._try_load(store, key, source="local")
            if compiled is not None:
                return compiled
            compiled = self._try_remote(store, key)
            if compiled is not None:
                return compiled
        # Miss: pay the real XLA compile (the one cost this subsystem
        # exists to delete on every later start).
        # (mx_compile_seconds is observed by the compile log, from the
        # build event this compile raises.)
        _misses_total.labels(site=self.site).inc()
        t0 = time.perf_counter()
        with _trace.span("compile_cache::compile", site=self.site):
            compiled = lowered.compile()
        dt = time.perf_counter() - t0
        self.num_compiles += 1
        _record_cost(self.site, key, compiled)
        if store is not None:
            self._commit(store, key, compiled, dt)
        return compiled

    def _try_load(self, store, key, source, meta_payload=None):
        """Deserialize one entry (from disk, or from ``meta_payload``
        pulled off a peer); None on any failure, counted."""
        rec = meta_payload if meta_payload is not None else store.get(key)
        if rec is None:
            return None
        _meta, payload = rec
        try:
            t0 = time.perf_counter()
            with _trace.span("compile_cache::load", site=self.site,
                             source=source):
                compiled = _deserialize(payload)
            _load_seconds.labels(site=self.site).observe(
                time.perf_counter() - t0)
        except Exception as exc:
            _errors_total.labels(site=self.site, kind="deserialize").inc()
            _log.warn_rate_limited(
                _logger, "cc_deser:%d" % id(self), 60.0,
                "cached executable failed to load at site %s (key %s, "
                "recompiling): %s", self.site, key, exc)
            if meta_payload is None:
                store._quarantine(store.path_for(key))
            return None
        self.num_hits += 1
        _hits_total.labels(site=self.site, source=source).inc()
        _record_cost(self.site, key, compiled)
        return compiled

    def _try_remote(self, store, key):
        """Local miss: ask the pod (rank-0-compiles-peers-pull). A
        fetched entry is committed locally first, so the NEXT restart
        hits disk without the pod."""
        distributor = _active_distributor()
        if distributor is None or not distributor.pulls:
            return None
        try:
            rec = distributor.fetch(key)
        except Exception as exc:
            _errors_total.labels(site=self.site, kind="distribute").inc()
            _log.warn_rate_limited(
                _logger, "cc_fetch:%d" % id(self), 60.0,
                "peer compile-cache fetch failed at site %s (compiling "
                "locally): %s", self.site, exc)
            return None
        if rec is None:
            return None
        meta, payload = rec
        try:
            store.put(key, payload, meta)
        except OSError as exc:
            _errors_total.labels(site=self.site, kind="io").inc()
            _log.warn_rate_limited(
                _logger, "cc_put:%d" % id(self), 60.0,
                "compile cache commit failed at site %s (entry stays "
                "memory-only): %s", self.site, exc)
        return self._try_load(store, key, source="remote",
                              meta_payload=(meta, payload))

    def _commit(self, store, key, compiled, compile_seconds):
        """Serialize + atomically commit a freshly compiled executable;
        publish to the pod when this rank is the publisher."""
        try:
            payload = _serialize(compiled)
        except Exception as exc:
            # Backend cannot serialize this executable: it still runs,
            # the cache just stays cold.
            _errors_total.labels(site=self.site,
                                 kind="serialize_unsupported").inc()
            _log.warn_rate_limited(
                _logger, "cc_ser:%d" % id(self), 300.0,
                "backend cannot serialize executables at site %s (the "
                "persistent cache stays cold here): %s", self.site, exc)
            return
        meta = {"site": self.site, "key_parts": repr(self.key_parts),
                "backend": backend_fingerprint(),
                "compile_seconds": round(compile_seconds, 3),
                "created": time.time(), "payload_bytes": len(payload)}
        try:
            store.put(key, payload, meta)
        except OSError as exc:
            _errors_total.labels(site=self.site, kind="io").inc()
            _log.warn_rate_limited(
                _logger, "cc_put:%d" % id(self), 60.0,
                "compile cache commit failed at site %s (will recompile "
                "next start): %s", self.site, exc)
            return
        distributor = _active_distributor()
        publish = distributor is not None and \
            (distributor.publishes if self._publish is None
             else self._publish)
        if publish:
            try:
                distributor.publish(key, meta, payload)
            except Exception as exc:
                _errors_total.labels(site=self.site,
                                     kind="distribute").inc()
                _log.warn_rate_limited(
                    _logger, "cc_pub:%d" % id(self), 60.0,
                    "compile cache publish failed at site %s (peers "
                    "will compile locally): %s", self.site, exc)


def _record_cost(site, key, compiled):
    """Report the executable's cost_analysis() flops/bytes to the
    attribution plane (mx_executable_flops{site}) — achieved-FLOPs
    accounting. Advisory: a backend/deserialized executable without
    cost analysis records nothing."""
    try:
        from ..telemetry import attribution as _attr

        _attr.record_executable_cost(site, compiled, key=key)
    except Exception:
        pass


# -- serialization backend -----------------------------------------------------

def _fingerprint_text(lowered):
    """Canonical text of the lowered computation — the content half of
    the cache key. StableHLO when available, else the default text."""
    try:
        return lowered.as_text()
    except Exception:
        # Some lowerings can't render every dialect; the compiler IR
        # repr is still content-addressed.
        return repr(lowered.compiler_ir())


def _serialize(compiled):
    """Executable -> bytes (pickled payload + in/out trees + the ids of
    the devices it was compiled for, in assignment order)."""
    import jax
    from jax.experimental import serialize_executable as _sx

    payload, in_tree, out_tree = _sx.serialize(compiled)
    sharding = jax.tree_util.tree_leaves(
        (compiled.input_shardings, compiled.output_shardings))[0]
    device_ids = [d.id for d in sharding._device_assignment]
    return pickle.dumps((payload, in_tree, out_tree, device_ids),
                        protocol=4)


def _deserialize(blob):
    """Bytes -> loaded executable ready to call, on the devices it was
    compiled for: left to its default, the loader assigns EVERY device
    of the backend, and a one-device executable then dies at its first
    call on any host with more than one."""
    import jax
    from jax.experimental import serialize_executable as _sx

    payload, in_tree, out_tree, device_ids = pickle.loads(blob)
    by_id = {d.id: d for d in jax.devices()}
    return _sx.deserialize_and_load(
        payload, in_tree, out_tree,
        execution_devices=[by_id[i] for i in device_ids])


# -- the seam API --------------------------------------------------------------

def cached_compile(fn, site, key_parts=(), **jit_kwargs):
    """Wrap ``fn`` in a :class:`CachedFunction` against the active
    store (the store may be attached later; a disabled cache just means
    every signature compiles, exactly like ``jax.jit``)."""
    return CachedFunction(fn, site, key_parts=key_parts, **jit_kwargs)


def maybe_cached_jit(fn, site, key_parts=(), **jit_kwargs):
    """The three compile seams' entry point: a :class:`CachedFunction`
    when the cache is enabled, else a plain ``jax.jit`` — zero behavior
    (and zero overhead) change while disabled."""
    if enabled():
        return cached_compile(fn, site, key_parts=key_parts, **jit_kwargs)
    import jax

    return jax.jit(fn, **jit_kwargs)
