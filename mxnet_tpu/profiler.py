"""mx.profiler — profiling over jax.profiler plus framework-level
aggregate statistics.

Reference: python/mxnet/profiler.py:29-257 (set_config/set_state/pause/
resume/dump/dumps + user-defined Domain/Task/Frame/Counter/Marker) over
src/profiler/profiler.h:256 (chrome://tracing JSON spans, aggregate
summary tables from aggregate_stats.cc).

TPU rebuild, two layers:

1. Device/XLA level — captured by `jax.profiler`, reduced here:
   `set_state('run')` starts a trace capture whose output
   (TensorBoard/XPlane format, the modern chrome-trace equivalent;
   profiler.h:87 wrote chrome JSON) lands in the configured directory
   with per-HLO device timing, and after `set_state('stop')`
   :func:`device_table` reads that capture back
   (`telemetry/device_table.py`): device ms a step by executable, by
   phase of the step (`forward`, `loss`, `backward`,
   `optimizer_update`, and a combined row for a fusion that mixes two),
   by scope (Gluon block names and op scopes), by kernel, and the
   longest idle gaps, each split by what the host did through it.
2. Framework level — a thin VIEW over `mxnet_tpu.telemetry.REGISTRY`:
   the dispatch path records per-op wall-time spans into the
   ``mx_dispatch_seconds`` histogram family (exact count/total/min/max
   per op), user-defined Counters live in the ``mx_profiler_counter``
   gauge family, and Task/Frame/Marker events go to the bounded
   ``telemetry.trace`` rings (no unbounded event log; ``dump()`` flushes
   them to ``chrome_trace.json``). `dumps()` renders the same aggregate
   tables as before — but serving, checkpoint and training metrics now
   share the registry, so one `telemetry.render_prometheus()` (or the
   /metrics endpoint) exposes everything this module shows and more.

On an async backend the op spans measure *dispatch* cost, not device
cost. `dumps()` prints both, under their names: the dispatch table; a
"Device" section, the table above, once a capture has been stopped (key
``"device"`` of ``format='json'``; the capture itself stays in the trace
directory for TensorBoard or Perfetto); and a "Host" section, with or
without a capture: the longest intervals between successive
``train_step::dispatch`` ends against their median, each with the
collector's ms (``host::gc``), the stepping thread's CPU share and its
involuntary context switches — what to print after a window that read
low.

Reset semantics (pinned by tests/test_profiler.py): ``dumps(reset=True)``
clears the per-op dispatch statistics only. User-defined Counters are
live process-global gauges (`checkpoint::pending`, `serving::requests`)
shared across subsystems — they survive reset by design.
"""
from __future__ import annotations

import os
import time
import threading

from .telemetry import metrics as _tm
from .telemetry import trace as _trace

__all__ = ["set_config", "profiler_set_config", "set_state",
           "profiler_set_state", "pause", "resume", "dump", "dumps",
           "device_table",
           "set_kvstore_handle", "server_dumps",
           "Domain", "Task", "Frame", "Counter", "Marker"]

_state = {
    "running": False,
    "paused": False,
    "config": {"filename": "profile_output", "profile_all": False,
               "profile_symbolic": True, "profile_imperative": True,
               "profile_api": True, "aggregate_stats": True},
    "trace_active": False,
    # the capture the last set_state('stop') closed: whether there is
    # one, and its device lines once device_table() has read them
    "capture_ready": False,
    "capture": None,
    "table": None,                 # dumps()'s reduction of it
}
# Kept for back-compat with callers that serialized on the profiler
# lock; the registry families shard their own locks now.
_lock = threading.Lock()

# THE registry families behind this module's tables. Op spans keep
# exact min/max (the histogram tracks extrema beside its exponential
# buckets), so the aggregate table is bit-identical to the old one.
_dispatch = _tm.REGISTRY.histogram(
    "mx_dispatch_seconds",
    "Framework-level dispatch spans per op (dispatch cost, not device "
    "cost — device timing lives in the jax.profiler trace)",
    labels=("op",))
_user_counters = _tm.REGISTRY.gauge(
    "mx_profiler_counter",
    "User-defined profiler counters (profiler.Domain/Counter), named "
    "domain::counter",
    labels=("name",))


_kv_handle = [None]


def set_kvstore_handle(kv):
    """Attach a dist kvstore so profile_process='server' calls reach the
    remote servers (reference profiler.py:set_kvstore_handle — required
    before server-side profiling commands)."""
    _kv_handle[0] = kv


def _server_cmd(sub, arg=None):
    kv = _kv_handle[0]
    if kv is None or not hasattr(kv, "server_profiler_command"):
        raise RuntimeError(
            "profile_process='server' needs a dist kvstore: call "
            "profiler.set_kvstore_handle(kv) with a dist_* store first")
    return kv.server_profiler_command(sub, arg)


def set_config(profile_process="worker", **kwargs):
    """(reference profiler.py:set_config). Accepts the reference's knobs;
    `filename` names the trace output directory for jax.profiler. With
    ``profile_process='server'`` the config is forwarded to every
    kvstore server (reference KVStoreServerProfilerCommand kSetConfig)."""
    if profile_process == "server":
        _server_cmd("set_config", kwargs)
        return
    _state["config"].update(kwargs)


profiler_set_config = set_config


def _trace_dir():
    base = _state["config"].get("filename", "profile_output")
    # reference writes one json file; jax.profiler writes a directory.
    if base.endswith(".json"):
        base = base[:-5]
    return base


def set_state(state="stop", profile_process="worker"):
    """'run' starts device tracing + op-span recording; 'stop' ends it
    (reference profiler.py:set_state). ``profile_process='server'``
    toggles the profiler on every kvstore server instead."""
    if state not in ("run", "stop"):
        raise ValueError("state must be 'run' or 'stop'")
    if profile_process == "server":
        _server_cmd("set_state", state)
        return
    if state == "run" and not _state["running"]:
        _state["running"] = True
        _state["paused"] = False
        try:
            import jax

            os.makedirs(_trace_dir(), exist_ok=True)
            jax.profiler.start_trace(_trace_dir())
            _state["trace_active"] = True
            _state["capture_ready"] = False
            _state["capture"] = _state["table"] = None
            _trace.mark_capture_clock()
        except Exception:
            _state["trace_active"] = False  # framework-level only
    elif state == "stop" and _state["running"]:
        _state["running"] = False
        if _state["trace_active"]:
            import jax

            jax.profiler.stop_trace()
            _state["trace_active"] = False
            _state["capture_ready"] = True


profiler_set_state = set_state


def pause(profile_process="worker"):
    """Suspend op-span recording (reference profiler.py:pause)."""
    if profile_process == "server":
        _server_cmd("pause")
        return
    _state["paused"] = True


def resume(profile_process="worker"):
    if profile_process == "server":
        _server_cmd("resume")
        return
    _state["paused"] = False


def is_recording():
    return _state["running"] and not _state["paused"]


def record_op_span(name, seconds):
    """Called from the dispatch path for each op while profiling."""
    _dispatch.labels(op=name).observe(seconds)


def dump(finished=True, profile_process="worker"):
    """Flush profile output (reference profiler.py:dump): writes the
    framework span rings to ``<trace_dir>/chrome_trace.json`` and, when
    ``finished`` (the default, reference semantics), stops the device
    trace too — the profiler is done. ``finished=False`` flushes a
    snapshot but keeps the profiler running and usable, so a long job
    can dump mid-flight. A no-op when profiling was never started
    (historical behavior — defensive teardown dumps leave no files)."""
    if profile_process == "server":
        _server_cmd("dump")
        return
    if not _state["running"]:
        return      # nothing captured — keep the historical no-op
    try:
        os.makedirs(_trace_dir(), exist_ok=True)
        _trace.dump(os.path.join(_trace_dir(), "chrome_trace.json"))
    except OSError:
        pass    # trace flush is best-effort; the device trace matters more
    if finished:
        set_state("stop")


def server_dumps():
    """Aggregate span tables from every kvstore server (beyond the
    reference, whose servers only write local files). Returns a list of
    per-server tables."""
    return _server_cmd("dumps")


def device_table(depth=3, steps=None, skip=0):
    """Where the device's time went in the capture that the last
    ``set_state('stop')`` closed (``telemetry.device_table.reduce_capture``
    has the details): ms a step ``by_executable``, ``by_phase``,
    ``by_scope`` (paths cut to `depth`), ``by_kernel``; ``busy_ms``,
    ``idle_ms`` and the longest ``idle_gaps``, each split by the host's
    spans. Steps are the launches of the step executable after the first
    `skip` (or `steps`, as the caller says). ``names`` is None where the
    phases and scopes are the capture's own, else why they may not be (an
    executable loaded from a compile-cache entry that an older build
    wrote, more than one live TrainStep, none). None where no capture has
    been made, while one is running, and where it holds no device line."""
    return _device_table(_trace.chrome_trace()["traceEvents"], depth=depth,
                         steps=steps, skip=skip)


def _device_table(ring_events, **how):
    from .telemetry import device_table as _dt

    if not _state["capture_ready"]:
        return None
    if _state["capture"] is None:
        path = _dt.find_capture(_trace_dir())
        if path is None:
            return None
        _state["capture"] = _dt.load_capture(path)
    if not _state["capture"]["ops"]:
        return None                    # a capture with no device line (CPU)
    from .parallel import train_step as _ts

    return _dt.reduce_capture(
        _state["capture"], ring_events,
        [ts.program_text() for ts in list(_ts._live_steps)], **how)


def _op_table(reset=False):
    """{op: (calls, total_s, min_s, max_s, p50_s, p99_s)} from the
    dispatch family (quantiles interpolated from the histogram buckets,
    clamped to the exact extrema). With ``reset`` the family is drained
    (swap under the family lock) before reading; at most one in-flight
    span per recorder thread can fall between the snapshot and the
    fresh generation — the price of not serializing every
    dispatch-path observe behind a global lock."""
    items = _dispatch.drain() if reset else _dispatch.collect()
    out = {}
    for (name,), child in items:
        snap = child.snapshot()
        if snap["count"]:
            out[name] = (snap["count"], snap["sum"], snap["min"],
                         snap["max"], child.quantile(0.5),
                         child.quantile(0.99))
    return out


def _counter_table():
    """{'domain::name': value} from the user-counter family."""
    return {name: child.value
            for (name,), child in _user_counters.collect()}


def dumps(reset=False, format="table"):
    """Aggregate statistics (reference profiler.py:dumps over
    aggregate_stats.cc). ``format='table'`` renders the human-readable
    table (reference behavior); ``format='json'`` returns the same data
    machine-readable — {"trace_dir", "ops": {name: {calls, total_ms,
    min_ms, max_ms, p50_ms, p99_ms}}, "counters": {"domain::name":
    value}} — for the bench harness and serving dashboards (the
    histogram-derived p50/p99 the table shows ride the JSON payload
    too, pinned by tests/test_profiler.py). ``format='top'`` renders
    the pprof-style top-K self-time view
    (:func:`mxnet_tpu.telemetry.flamegraph.render_top`) — the
    flamegraph entry of the dispatch table.

    ``reset=True`` clears the per-op dispatch statistics. User-defined
    Counters are NOT reset: they are live gauges shared process-wide
    (checkpoint::pending, serving::requests) and zeroing them here would
    corrupt other subsystems' telemetry (behavior pinned by
    tests/test_profiler.py::test_dumps_reset_keeps_counters)."""
    if format not in ("table", "json", "top"):
        raise ValueError("format must be 'table', 'json' or 'top', "
                         "got %r" % (format,))
    if format == "top":
        from .telemetry import flamegraph as _fg

        text = _fg.render_top()
        if reset:
            _dispatch.drain()
        return text
    from .telemetry import device_table as _dt

    ops = _op_table(reset=reset)
    counters = _counter_table()
    ring_events = _trace.chrome_trace()["traceEvents"]
    # reduced once a capture: a dashboard may call dumps() every second
    if _state["capture_ready"] and _state["table"] is None:
        _state["table"] = _device_table(ring_events)
    device = _state["table"] if _state["capture_ready"] else None
    host = _dt.host_table(ring_events)
    if format == "json":
        import json

        return json.dumps({
            "trace_dir": _trace_dir(),
            "ops": {name: {"calls": st[0], "total_ms": st[1] * 1e3,
                           "min_ms": st[2] * 1e3, "max_ms": st[3] * 1e3,
                           "p50_ms": st[4] * 1e3, "p99_ms": st[5] * 1e3}
                    for name, st in ops.items()},
            "counters": counters,
            "device": device,
            "host": host,
        })
    lines = [
        "Profile Statistics (framework dispatch spans; device timing: "
        "the Device section below, from the capture in %r)" % _trace_dir(),
        "%-40s %10s %14s %14s %14s %14s %14s"
        % ("Name", "Calls", "Total(ms)", "Min(ms)", "Max(ms)",
           "P50(ms)", "P99(ms)"),
    ]
    for name in sorted(ops):
        cnt, tot, mn, mx, p50, p99 = ops[name]
        lines.append("%-40s %10d %14.3f %14.3f %14.3f %14.3f %14.3f"
                     % (name, cnt, tot * 1e3, mn * 1e3, mx * 1e3,
                        p50 * 1e3, p99 * 1e3))
    for name in sorted(counters):
        lines.append("%-40s %10s %14s" % (name, "counter",
                                          counters[name]))
    if device is not None:
        lines.append(_dt.render(device))
    if host is not None:
        lines.append(_dt.render_host(host))
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# user-defined profiling objects (reference profiler.py:Domain/Task/...)
# ---------------------------------------------------------------------------

class Domain:
    def __init__(self, name):
        self.name = name

    def new_counter(self, name, value=None):
        return Counter(self, name, value)

    def new_task(self, name):
        return Task(self, name)

    def new_frame(self, name):
        return Frame(self, name)

    def new_marker(self, name):
        return Marker(self, name)

    def __repr__(self):
        return "Domain('%s')" % self.name


class _Span:
    """Task/Frame base: start/stop records one bounded trace-ring span
    (flushed to chrome_trace.json by dump()) and, while profiling, an
    aggregate dispatch row. No unbounded event log — the old module-wide
    `_events` list grew forever and was appended without a lock."""

    def __init__(self, domain, name):
        self.domain = domain
        self.name = name
        self._t0 = None

    def start(self):
        self._t0 = time.perf_counter()

    def stop(self):
        t1 = time.perf_counter()
        if self._t0 is not None:
            _trace.complete(self._qual(), self._t0, t1)
            if is_recording():
                record_op_span(self._qual(), t1 - self._t0)
            self._t0 = None

    def _qual(self):
        return "%s::%s" % (self.domain.name, self.name)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()


class Task(_Span):
    pass


class Frame(_Span):
    pass


class Counter:
    """A named value in the unified registry (gauge semantics: set or
    increment). Visible in dumps() as 'domain::name' AND in
    telemetry.render_prometheus() as
    mx_profiler_counter{name="domain::name"}."""

    def __init__(self, domain, name, value=None):
        self.domain = domain
        self.name = name
        self._child = _user_counters.labels(
            name="%s::%s" % (domain.name, name))
        if value is not None:
            self.set_value(value)

    def _key(self):
        return (self.domain.name, self.name)

    def set_value(self, value):
        self._child.set(value)

    def increment(self, delta=1):
        # The registry child carries its own lock: serving worker/client
        # threads increment while dumps() snapshots, and an unlocked
        # read-modify-write would lose concurrent increments.
        self._child.inc(delta)

    def decrement(self, delta=1):
        self._child.inc(-delta)

    def __iadd__(self, v):
        self.increment(v)
        return self

    def __isub__(self, v):
        self.decrement(v)
        return self


class Marker:
    def __init__(self, domain, name):
        self.domain = domain
        self.name = name

    def mark(self, scope="process"):
        # Bounded trace-ring instant, not an unbounded list append.
        _trace.instant("%s::%s" % (self.domain.name, self.name),
                       scope=scope)


# Reference env_var.md MXNET_PROFILER_AUTOSTART: begin profiling at import.
if os.environ.get("MXNET_PROFILER_AUTOSTART", "0") in ("1", "true"):
    set_state("run")
