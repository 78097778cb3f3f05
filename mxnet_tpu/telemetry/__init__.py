"""mxnet_tpu.telemetry — the framework-wide observability subsystem.

Three pillars (ISSUE 3; reference identity: src/profiler/profiler.h's
chrome-trace spans + aggregate tables, grown to production scope):

1. **Metrics registry** (:mod:`.metrics`) — typed Counter / Gauge /
   Histogram families with labels, lock-sharded for the step hot path,
   exposed via ``render_prometheus()`` and the stdlib
   ``start_http_server()`` ``/metrics`` endpoint. ``profiler.dumps()``,
   ``serving`` stats and ``checkpoint`` counters are all views over the
   single process-wide ``REGISTRY``.
2. **Structured tracing** (:mod:`.trace`) — thread-aware span recording
   (``with trace.span("step", step=i):``) into bounded per-thread
   rings, flushed to chrome://tracing JSON (``trace.dump()``) loadable
   in Perfetto. A dump is on ``time.perf_counter()``'s clock, a
   jax.profiler capture on the profiler's own: to see the program's
   names against the device lines, load the capture, which carries
   every ``span()`` opened while it ran as a ``TraceAnnotation`` of
   the same name on its ``/host:CPU`` plane. Spans are emitted at
   every layer seam: CachedOp trace/execute, autograd
   backward/vjp/commit, Trainer step/allreduce/update, TrainStep
   step/dispatch, serving enqueue→device→reply, checkpoint
   snapshot/write/commit, and XLA trace/lower/build from the compile
   log (``mxnet_tpu.compile.build_log()``).
3. **Step-health monitor** (:mod:`.health`) — rolling step-time EWMA
   with slow-step outlier detection, recompile detection via the
   ``CachedOp.on_trace`` hook, and checkpoint-writer backlog watching,
   emitting rate-limited warnings and the ``mx_anomalies_total``
   counter.

Quick start::

    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import trace

    telemetry.start_http_server(9090)         # curl :9090/metrics
    monitor = telemetry.StepMonitor()
    for i in range(num_steps):
        with monitor.step(i):
            loss = train_step(x, y)
    trace.dump("chrome_trace.json")           # perf_counter's clock
    print(telemetry.render_prometheus())

``telemetry.set_enabled(False)`` pauses both metric recording and span
capture (the bench.py ``telemetry_step_overhead_pct`` contract measures
the difference: <= 2% on the step path).

Pod scale (ISSUE 5) adds four more modules on the same registry/rings:

* :mod:`.aggregate` — per-rank registry snapshots pushed over the
  kvstore command channel and merged by rank 0 into one fleet registry
  (every series labeled by ``rank``, silent ranks marked stale, and a
  ``sum without (rank)`` merged series per histogram family), so ONE
  scrape shows the whole pod.
* :mod:`.export` — streaming span export: the rings are drained on a
  size/age rotation budget into immutable, atomically committed
  ``trace.rank<R>.<SEQ>.jsonl`` segments; ``tools/trace_merge.py``
  stitches per-rank segments into one Perfetto timeline.
* :mod:`.slo` — multi-window error-budget burn rates over the latency
  histogram families, ``mx_slo_burn_rate{slo,window}`` gauges and
  rate-limited alerts.
* :mod:`.flamegraph` — pprof-style top-K self-time table
  (``profiler.dumps(format="top")``), collapsed-stack output for
  standard flamegraph tooling, and capture diffing
  (``diff_top``/``tools/flame_diff.py``).

Failure forensics (ISSUE 7) turns detection into evidence:

* :mod:`.recorder` — the flight recorder: anomaly-triggered, atomically
  committed ``diag.rank<R>.<SEQ>.json`` bundles (thread stacks, last-N
  spans, registry snapshot + exemplars, anomaly history, data batch
  provenance, watchdog lanes, device memory, compile accounting, env);
  ``tools/diagnose.py`` summarizes and merges them.
* :mod:`.watchdog` — heartbeat lanes in training / serving / the
  checkpoint writer plus a :class:`HangWatchdog` that turns in-flight
  work past ``max(deadline, K×EWMA)`` into ``*_hang`` anomalies (and
  bundles).
* :mod:`.numerics` — opt-in cadence-gated ``isfinite`` guards on the
  loss and on the fused update's flat buckets (O(buckets) device-side
  reductions); violations raise ``nonfinite`` anomalies carrying
  step/batch-id provenance, optionally halting the job.
* :mod:`.memstats` — ``mx_device_live_bytes``/``_buffers``/peak gauges
  sampled from the backend, and ``mx_compile_seconds{site}`` fed by the
  compile log from JAX's own compile events (one observation per XLA
  compile, none for a persistent-cache hit).

The fleet health plane (ISSUE 8) makes the pod operable from outside:

* :mod:`.healthplane` — ``GET /healthz``/``/readyz`` liveness and
  readiness probes plus ``/debug/*`` JSON views mounted on the same
  ``/metrics`` server (``start_http_server(..., health=HealthPlane())``),
  a process-wide component readiness registry the TrainStep / serving /
  data-pipeline warmup paths feed, and :class:`DiagCollector` — flight-
  recorder bundles shipped to rank 0 over the kvstore ``diag_push``
  channel plus the ``request_bundle`` pod-snapshot fan-out.
* :class:`.export.PushExporter` — periodic push-gateway export of any
  registry (rank 0 passes its Aggregator so one push describes the
  pod), bounded retry buffer + exponential backoff.
* Fleet SLOs — ``Aggregator.fleet_slo(...)`` scopes a
  :class:`.slo.ServiceLevelObjective` to the merged ``rank="all"``
  histograms so ONE rank-0 ``BurnRateMonitor`` alerts for the pod.

Continuous profiling & step attribution (ISSUE 12) answer "where does
wall-clock go" on a HEALTHY pod:

* :mod:`.profiling` — :class:`ContinuousProfiler`: an always-on
  ~67 Hz stack sampler folding every thread into windowed
  collapsed-stack profiles (lane-tagged roots, file:line frame keys,
  retention ring, ≤1% self-accounted overhead) with a
  rolling-baseline ``profile_regression`` sentinel; pulled via
  ``GET /debug/pprof``, flight-recorder ``profile`` sections, or
  pod-wide over the kvstore diag channel.
* :mod:`.attribution` — :class:`StepAttribution`:
  ``mx_step_phase_seconds{phase}`` per-step decomposition (data_wait /
  h2d / dispatch / device_compute / allreduce / checkpoint / other),
  and the one-hot ``mx_step_bound{cause}`` classifier + ``input_bound``
  anomaly.
* :mod:`.remote_write` — the Prometheus remote-write wire format
  (pure-python protobuf ``WriteRequest`` + snappy framing) as
  ``PushExporter(wire_format="remote_write")``.

The goodput ledger (ISSUE 20) folds all of the above into the run-level
answer — "how much of the wall-clock was useful work":

* :mod:`.goodput` — :class:`GoodputLedger`: a mutually-exclusive,
  collectively-exhaustive goodput/badput category set (device_compute vs.
  compile / input_stall / h2d / exposed_comm / checkpoint /
  restart_replay / hang_recovery / idle / other) whose categories sum
  to wall-clock within a closure tolerance; durable per-rank
  ``goodput.rank<R>.json`` (atomic commits, resumed after a crash with
  replayed steps booked as ``restart_replay``), fleet-aggregated
  ``mx_goodput_seconds_total{category}`` counters, ``GET
  /debug/goodput``, bundle sections, and ``tools/goodput_report.py``.
"""
from __future__ import annotations

from . import metrics
from . import xtrace
from . import trace
from . import aggregate
from . import export
from . import flamegraph
from . import slo
from . import memstats
from . import watchdog
from . import recorder
from . import numerics
from . import healthplane
from . import profiling
from . import attribution
from . import goodput
from . import remote_write
from .metrics import (Registry, REGISTRY, counter, gauge, histogram,
                      render_prometheus, start_http_server,
                      default_buckets, set_exemplars)
from .health import StepMonitor
from .aggregate import Aggregator, LocalBus
from .export import StreamingTraceWriter, PushExporter
from .slo import BurnRateMonitor, ServiceLevelObjective
from .recorder import FlightRecorder
from .watchdog import HangWatchdog
from .numerics import NumericGuard, NonFiniteError
from .memstats import DeviceMemoryMonitor
from .healthplane import HealthPlane, DiagCollector
from .profiling import ContinuousProfiler
from .attribution import StepAttribution
from .goodput import GoodputLedger

__all__ = ["metrics", "xtrace", "trace", "aggregate", "export",
           "flamegraph",
           "slo", "memstats", "watchdog", "recorder", "numerics",
           "healthplane", "profiling", "attribution", "goodput",
           "remote_write",
           "Registry", "REGISTRY", "counter", "gauge",
           "histogram", "render_prometheus", "start_http_server",
           "default_buckets", "set_exemplars", "StepMonitor",
           "Aggregator", "LocalBus", "StreamingTraceWriter",
           "PushExporter", "BurnRateMonitor", "ServiceLevelObjective",
           "FlightRecorder", "HangWatchdog", "NumericGuard",
           "NonFiniteError", "DeviceMemoryMonitor", "HealthPlane",
           "DiagCollector", "ContinuousProfiler", "StepAttribution",
           "GoodputLedger", "set_enabled", "enabled"]


def set_enabled(on):
    """Master switch for the whole subsystem: gates metric recording AND
    span capture. Returns the previous combined state."""
    prev = metrics.enabled() and trace.enabled()
    metrics.set_enabled(on)
    trace.set_enabled(on)
    return prev


def enabled():
    return metrics.enabled() and trace.enabled()
