"""Device context.

Reference: include/mxnet/base.h (Context with dev types cpu/gpu/
cpu_pinned/cpu_shared) and python/mxnet/context.py. TPU-native rebuild:
a Context names a JAX device — ``cpu(i)`` a host device, ``tpu(i)`` /
``gpu(i)`` (alias kept for API parity) an accelerator chip. There is no
pinned/shared distinction: host staging buffers and cross-process
sharing are handled by the PJRT runtime and jax.Array itself.
"""
from __future__ import annotations

import threading

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus", "num_tpus"]

_context_stack = threading.local()


def _devices_by_type():
    import jax

    out = {"cpu": [], "tpu": []}
    # local_devices, not devices: in a multi-process SPMD group
    # (parallel.dist.initialize) the global list includes other hosts'
    # chips, which this process cannot address — imperative work is
    # per-process, exactly as each reference worker computes on its own
    # GPUs and only kvstore/collectives cross hosts.
    for d in jax.local_devices():
        kind = "cpu" if d.platform == "cpu" else "tpu"
        out[kind].append(d)
    # When running on an accelerator backend, host CPU devices are still
    # reachable for host-resident arrays.
    if not out["cpu"]:
        try:
            out["cpu"] = [d for d in jax.devices("cpu")
                          if d.process_index == jax.process_index()]
        except RuntimeError:
            out["cpu"] = []
    return out


class Context:
    """A device on which NDArrays live and ops execute.

    ``device_type`` is one of ``'cpu'``, ``'tpu'`` (``'gpu'`` is accepted
    as an alias for the accelerator so reference scripts run unchanged).
    """

    devtype2mask = {"cpu": 1, "gpu": 2, "tpu": 2, "cpu_pinned": 3, "cpu_shared": 5}
    devmask2type = {1: "cpu", 2: "tpu", 3: "cpu_pinned", 5: "cpu_shared"}
    _default_ctx = None

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_type = device_type.device_type
            self.device_id = device_type.device_id
        else:
            if device_type == "gpu":
                device_type = "tpu"
            if device_type in ("cpu_pinned", "cpu_shared"):
                device_type = "cpu"
            if device_type not in ("cpu", "tpu"):
                raise ValueError("unknown device type %s" % device_type)
            self.device_type = device_type
            self.device_id = device_id

    @property
    def device_typeid(self):
        return self.devtype2mask[self.device_type]

    @property
    def jax_device(self):
        devs = _devices_by_type()[self.device_type]
        if not devs:
            raise RuntimeError("no %s device available" % self.device_type)
        if self.device_type == "cpu":
            # Host ids wrap, as in the reference, where every cpu(i)
            # names the one host: scripts and tests written for N
            # devices still run where fewer host devices exist.
            return devs[self.device_id % len(devs)]
        if not 0 <= self.device_id < len(devs):
            # A chip id past the end is an error, never chip 0: a run
            # that asked for four chips must not pile onto the first.
            raise RuntimeError(
                "%r: this process has %d tpu device(s)"
                % (self, len(devs)))
        return devs[self.device_id]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __str__(self):
        return self.__repr__()

    def __enter__(self):
        if not hasattr(_context_stack, "stack"):
            _context_stack.stack = []
        _context_stack.stack.append(self)
        return self

    def __exit__(self, *args):
        _context_stack.stack.pop()

    def empty_cache(self):
        """Release cached device memory (reference: Context::empty_cache →
        storage pool ReleaseAll). XLA/PJRT owns the HBM pool; we clear
        the framework-level executable/donation caches instead."""
        import gc

        gc.collect()

    @classmethod
    def default_ctx(cls):
        stack = getattr(_context_stack, "stack", None)
        if stack:
            return stack[-1]
        if cls._default_ctx is None:
            import jax

            cls._default_ctx = (
                Context("cpu", 0)
                if jax.default_backend() == "cpu"
                else Context("tpu", 0)
            )
        return cls._default_ctx


def cpu(device_id=0):
    return Context("cpu", device_id)


def gpu(device_id=0):
    """Alias for :func:`tpu` — keeps reference scripts (`mx.gpu(0)`) working."""
    return Context("tpu", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def current_context():
    return Context.default_ctx()


def num_gpus():
    return num_tpus()


def num_tpus():
    return len(_devices_by_type()["tpu"])
