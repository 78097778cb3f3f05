"""mxnet_tpu.telemetry.memstats — device memory and compile accounting.

Two measurement substrates the rest of the diagnostics layer (and
ROADMAP direction 2's persistent compile cache) are judged against:

* **Device memory.** ``mx_device_live_buffers{device}`` /
  ``mx_device_live_bytes{device}`` gauges plus a host-maintained
  ``mx_device_peak_bytes{device}`` watermark, sampled from the backend:
  PJRT ``device.memory_stats()`` where the backend implements it (TPU),
  falling back to walking ``jax.live_arrays()`` and attributing each
  addressable shard to its device (the CPU backend). ``sample()`` is a
  point read — call it on a step cadence, run a
  :class:`DeviceMemoryMonitor` for a background cadence, or let a
  flight-recorder bundle capture one at the moment of failure.

* **Compile time.** ``mx_compile_seconds{site}`` histogram, observed by
  the compile log (``mxnet_tpu.compile.buildlog``) from JAX's own
  backend-compile events: one observation per XLA compile, with XLA's
  seconds alone (tracing and lowering are in ``compile.build_log()``),
  none for a program loaded from JAX's persistent cache or from the
  repo's executable store. ``site`` is read off the executable's name —
  ``cached_op`` (``mx_cached_fwd``/``mx_cached_vjp``), ``fused_apply``
  (``mx_fused_<optimizer>``), ``train_step`` (``mx_train_step``), else
  ``other`` — so its cardinality is bounded. This is the cold-start
  cost a persistent compile cache deletes.
"""
from __future__ import annotations

import threading
import time

from . import metrics as _metrics
from .. import log as _log

__all__ = ["DeviceMemoryMonitor", "sample_device_memory",
           "observe_compile", "compile_stats"]

_live_buffers = _metrics.REGISTRY.gauge(
    "mx_device_live_buffers",
    "Live device buffers (PJRT memory_stats where available, else "
    "addressable shards of jax.live_arrays)", labels=("device",))
_live_bytes = _metrics.REGISTRY.gauge(
    "mx_device_live_bytes",
    "Bytes held by live device buffers", labels=("device",))
_peak_bytes = _metrics.REGISTRY.gauge(
    "mx_device_peak_bytes",
    "Peak of mx_device_live_bytes seen so far (backend peak counter "
    "where available, else a high-watermark over samples)",
    labels=("device",))
_compile_seconds = _metrics.REGISTRY.histogram(
    "mx_compile_seconds",
    "XLA compile seconds per compile site (from JAX's compile events; "
    "persistent-cache hits are not counted)", labels=("site",))

# Host-side peak watermark per device (backends without a native peak
# counter): survives across samples, reset via reset_peak().
_peaks = {}
_peaks_lock = threading.Lock()


def observe_compile(site, seconds):
    """Record one compile into ``mx_compile_seconds{site=...}``.
    Called by the compile log for every XLA compile JAX reports;
    available for compiles made outside JAX too."""
    _compile_seconds.labels(site=site).observe(float(seconds))


def compile_stats():
    """``{site: {count, total_s, p50_s, p99_s}}`` summary of every
    compile site observed so far (the recorder-bundle / REPL view)."""
    out = {}
    for (site,), child in _compile_seconds.collect():
        snap = child.snapshot()
        if not snap["count"]:
            continue
        out[site] = {"count": snap["count"], "total_s": snap["sum"],
                     "p50_s": child.quantile(0.5),
                     "p99_s": child.quantile(0.99)}
    return out


def _stats_sample():
    """Per-device (buffers, bytes, backend_peak) via PJRT memory_stats;
    devices whose backend lacks the counters are returned for the
    live-array fallback."""
    import jax

    out, missing = {}, []
    for dev in jax.local_devices():
        stats = None
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if stats and "bytes_in_use" in stats:
            out[str(dev)] = (
                int(stats.get("num_allocs", 0)) or None,
                int(stats["bytes_in_use"]),
                int(stats.get("peak_bytes_in_use", 0)) or None)
        else:
            missing.append(dev)
    return out, missing


def _live_array_sample(devices):
    """Fallback accounting: walk jax.live_arrays() and attribute each
    addressable shard's nbytes to its device. O(live arrays) — fine on
    a sampling cadence, and the only truth the CPU backend offers."""
    import jax

    wanted = {str(d) for d in devices}
    counts = {d: 0 for d in wanted}
    nbytes = {d: 0 for d in wanted}
    for arr in jax.live_arrays():
        try:
            for shard in arr.addressable_shards:
                dev = str(shard.device)
                if dev in wanted:
                    counts[dev] += 1
                    nbytes[dev] += int(getattr(shard.data, "nbytes", 0))
        except Exception:
            continue        # deleted/donated mid-walk: skip, not fatal
    return counts, nbytes


def sample_device_memory(update_gauges=True):
    """One point-in-time device-memory sample. Returns
    ``{device: {"buffers", "bytes", "peak_bytes"}}`` and (by default)
    writes the three gauges. The peak is the max of the backend's own
    peak counter (when it has one) and the high-watermark of samples
    taken so far."""
    stats, missing = _stats_sample()
    if missing:
        counts, nbytes = _live_array_sample(missing)
        for dev in counts:
            stats[dev] = (counts[dev], nbytes[dev], None)
    out = {}
    with _peaks_lock:
        for dev, (buffers, in_use, backend_peak) in stats.items():
            peak = max(_peaks.get(dev, 0), in_use, backend_peak or 0)
            _peaks[dev] = peak
            out[dev] = {"buffers": buffers, "bytes": in_use,
                        "peak_bytes": peak}
    if update_gauges:
        for dev, rec in out.items():
            if rec["buffers"] is not None:
                _live_buffers.labels(device=dev).set(rec["buffers"])
            _live_bytes.labels(device=dev).set(rec["bytes"])
            _peak_bytes.labels(device=dev).set(rec["peak_bytes"])
    return out


def reset_peak():
    """Forget the host-side peak watermark (tests, phase boundaries)."""
    with _peaks_lock:
        _peaks.clear()


class DeviceMemoryMonitor:
    """Background device-memory sampling on a fixed cadence.

    ``tick()`` from the step loop (samples at most once per
    ``interval_s``) or ``start()`` a daemon thread; either way the
    gauges and the peak watermark stay current so an anomaly bundle or
    a scrape always has a recent memory picture. Sampling failures are
    warned rate-limited and retried — accounting never takes down the
    loop."""

    def __init__(self, interval_s=10.0, clock=time.monotonic):
        self.interval_s = float(interval_s)
        self._clock = clock
        self._last = None
        self._stop = threading.Event()
        self._thread = None
        self.last_sample = None

    def sample(self):
        self.last_sample = sample_device_memory()
        return self.last_sample

    def tick(self):
        now = self._clock()
        if self._last is not None and now - self._last < self.interval_s:
            return None
        self._last = now
        try:
            return self.sample()
        except Exception as exc:
            _log.warn_rate_limited(
                _log.get_logger("mxnet_tpu.telemetry"),
                "memstats:%d" % id(self), 60.0,
                "device memory sample failed (will retry): %s", exc)
            return None

    def start(self):
        if self._thread is None:
            self._stop.clear()

            def loop():
                while not self._stop.wait(self.interval_s):
                    self.tick()

            self._thread = threading.Thread(
                target=loop, name="mx-telemetry-memstats", daemon=True)
            self._thread.start()
        return self

    def close(self, timeout=5.0):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
