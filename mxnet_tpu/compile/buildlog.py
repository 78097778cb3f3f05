"""The compile log: what JAX traced, lowered and built, when, for how
long and during which training step — fed by JAX's own events.

``jax.monitoring`` raises a duration event around every jaxpr trace,
every lowering to an MLIR module and every backend compile (a real XLA
compile or a load from JAX's persistent cache), each with the
function's or module's name, from every ``jax.jit`` in the process: the
framework's seams (whose executables are called ``mx_…``,
``ops.registry.named_fn``) and the raw ``jnp`` calls that go round them.
:func:`install` registers the listeners; each duration event becomes one
:class:`Record` in a bounded in-memory log, read with
:func:`build_log`, and one retroactive span in the trace rings
(``xla::trace`` / ``xla::lower`` / ``xla::build``), so a compile inside
a traced window labels its own idle gap.

This is also the compile accounting: every ``build`` that was not a
persistent-cache hit is observed into ``mx_compile_seconds{site}``
(``telemetry.memstats``), with the site read off the executable's
name. The seconds are XLA's alone; tracing and lowering are in the
log, not in the histogram.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import NamedTuple

from ..telemetry import memstats as _ms
from ..telemetry import trace as _trace

__all__ = ["Record", "build_log", "step_done", "clear", "install"]

_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "build",
}
# JAX raises these plain events on the compiling thread inside the
# backend compile, before the duration event that closes it: the request
# consults the persistent cache; it was found there; it was compiled
# and written there. A build that saw neither of the last two was
# compiled and not stored (cache off, or under JAX's size and
# compile-time thresholds): "uncached".
_OUTCOMES = {
    "/jax/compilation_cache/compile_requests_use_cache": None,
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
# mx_compile_seconds{site} by the executable's name (named_fn).
_SITES = (("mx_cached_", "cached_op"), ("mx_fused_", "fused_apply"),
          ("mx_train_step", "train_step"))
_CAPACITY = 16384


class Record(NamedTuple):
    kind: str        # "trace" | "lower" | "build"
    fun_name: str    # JAX's name: the function's for a trace,
    #                  "jit(<function>)" for lower and build
    outcome: str     # build: "hit" | "miss" | "uncached"; else ""
    start: float     # time.perf_counter() seconds, the trace rings' clock
    seconds: float
    step: int        # training steps this process had completed
    inner: bool      # a trace made while another trace was open on the
    #                  same thread (a jitted jnp function inside a
    #                  step's trace raises its own event): its seconds
    #                  are part of the outer one's


_log = collections.deque(maxlen=_CAPACITY)
_steps = [0]
_tls = threading.local()
_installed = False


def step_done():
    """One training step of this process has returned
    (``TrainStep.__call__``, ``Trainer.step``)."""
    _steps[0] += 1


def _site(fun_name):
    for prefix, site in _SITES:
        if prefix in fun_name:       # "jit(mx_train_step)"
            return site
    return "other"


def _on_event(event, **_kw):
    if event in _OUTCOMES:
        _tls.outcome = _OUTCOMES[event]


def _on_enter(event, _start, **_kw):
    # JAX raises a scalar under the event's name as it opens the
    # interval whose duration event closes it.
    if _KINDS.get(event) == "trace":
        _tls.open_traces = getattr(_tls, "open_traces", 0) + 1


def _on_duration(event, seconds, fun_name="", **_kw):
    kind = _KINDS.get(event)
    if kind is None:
        return
    end = time.perf_counter()
    outcome, inner = "", False
    if kind == "trace":
        _tls.open_traces = max(getattr(_tls, "open_traces", 1) - 1, 0)
        inner = _tls.open_traces > 0
    elif kind == "build":
        outcome = getattr(_tls, "outcome", None) or "uncached"
        _tls.outcome = None
        if outcome != "hit":
            _ms.observe_compile(_site(fun_name), seconds)
    _log.append(Record(kind, fun_name, outcome, end - seconds, seconds,
                       _steps[0], inner))
    args = {"fun": fun_name}
    if outcome:
        args["outcome"] = outcome
    _trace.complete("xla::" + kind, end - seconds, end, **args)


def install():
    """Register the listeners, once a process (``import mxnet_tpu`` does)."""
    global _installed
    if not _installed:
        import jax.monitoring as monitoring

        _installed = True
        monitoring.register_event_listener(_on_event)
        monitoring.register_scalar_listener(_on_enter)
        monitoring.register_event_duration_secs_listener(_on_duration)


def build_log():
    """The records, oldest first (the last ``16384``). Sum seconds over
    the records that are not ``inner`` and every second is counted
    once."""
    return list(_log)


def clear():
    """Forget the records and the step count (tests)."""
    _log.clear()
    _steps[0] = 0
