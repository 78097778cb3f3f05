"""mxnet_tpu.compile — the one compile cache and the compile log.

Every program of this framework is built by a plain ``jax.jit``: the
whole-step ``TrainStep`` executable, ``CachedOp`` graphs, the fused
optimizer chunks, ``Executor`` graphs, the serving bucket ladder and
the decode loop's step, place and prefill programs. What makes a
second start warm is JAX's own persistent compilation cache, keyed by
XLA on the compile request and serving every ``jax.jit`` in the
process:

* :func:`enable_jax_cache` places it (``JAX_COMPILATION_CACHE_DIR``, or
  ``<checkout>/.jax_cache``) and has it keep every program, however
  quickly it compiled. The entry points call it before their first
  compile. A pod that wants one cache for all its hosts points
  ``JAX_COMPILATION_CACHE_DIR`` at a shared directory. A cache
  directory that is absent, unwritable or holds a damaged entry costs
  a compile and a warning from JAX, never a wrong executable.

* :func:`build_log` / :func:`step_done` (:mod:`.buildlog`) are the
  compile log: every trace, lowering and backend build (a real XLA
  compile or a load from the cache, told apart by its outcome) from
  JAX's own events, with the framework's executables named for their
  seam (``jit_mx_train_step``, ``jit_mx_cached_fwd``, ...).
"""
from __future__ import annotations

import os

from .buildlog import build_log, step_done, install as _install_log

__all__ = ["enable_jax_cache", "build_log", "step_done"]

# The compile log (buildlog.py) listens to JAX's compile events from
# here on: every program traced, lowered or built after this import is
# in compile.build_log().
_install_log()

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
