"""Misc utilities (reference: python/mxnet/util.py)."""
from __future__ import annotations

import functools
import os

__all__ = ["makedirs", "get_gpu_count", "get_gpu_memory", "use_np_shape",
           "is_np_shape", "set_np_shape", "pin_platform"]


def pin_platform(choice):
    """Honor a device choice IN-PROCESS, before the first backend touch.

    A driver's ``--device`` flag is read after Python has started, so
    the choice is made through ``jax.config`` (which overrides a
    ``JAX_PLATFORMS`` already in the environment) and holds as long as
    it runs before a backend initializes. The pin is exclusive: with
    "tpu", the first backend touch raises where no TPU can be
    initialized — it never falls through to the CPU.

    choice: "auto" (no-op), "cpu", or "tpu". Anything else raises —
    including values arriving via the MXNET_DEVICE env var, which
    bypasses argparse `choices=` validation in the example drivers."""
    if choice in (None, "auto"):
        return
    if choice not in ("cpu", "tpu"):
        raise ValueError("pin_platform: unknown device %r "
                         "(expected auto/cpu/tpu)" % (choice,))
    import jax

    jax.config.update("jax_platforms", choice)


_np_shape = [True]  # numpy-style zero-size shapes are native on jax


def makedirs(d):
    """mkdir -p (reference util.py:makedirs)."""
    os.makedirs(os.path.expanduser(d), exist_ok=True)


def get_gpu_count():
    from .context import num_tpus

    return num_tpus()


def get_gpu_memory(gpu_dev_id=0):
    """Per-device memory stats from the PJRT client (free, total) in
    bytes; (-1, -1) when the backend does not expose them."""
    import jax

    try:
        dev = jax.local_devices()[gpu_dev_id]
        stats = dev.memory_stats()
        total = stats.get("bytes_limit", -1)
        used = stats.get("bytes_in_use", 0)
        return (total - used if total > 0 else -1, total)
    except Exception:
        return (-1, -1)


def set_np_shape(active):
    """Zero-dim/zero-size shape semantics toggle (reference
    util.py:set_np_shape). XLA shapes are numpy-semantic natively, so
    this records-and-returns; nothing needs switching."""
    prev = _np_shape[0]
    _np_shape[0] = bool(active)
    return prev


def is_np_shape():
    return _np_shape[0]


def use_np_shape(func):
    """Decorator form (reference util.py:use_np_shape)."""

    @functools.wraps(func)
    def wrapper(*args, **kwargs):
        prev = set_np_shape(True)
        try:
            return func(*args, **kwargs)
        finally:
            set_np_shape(prev)

    return wrapper
