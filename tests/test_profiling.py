"""Continuous profiling & step-time attribution (ISSUE 12): the
always-on stack sampler (windows, retention, lane tagging, regression
sentinel), /debug/pprof + /debug/attribution endpoints, step-phase
attribution and the bound-cause classifier, executable-cost accounting,
decode-pool autoscaling, the Prometheus remote-write wire format, the
flamegraph frame-key fix, pod-profile collection and tools/profile_tool.
"""
import importlib.util
import json
import os
import socket
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.telemetry import aggregate, attribution, export
from mxnet_tpu.telemetry import flamegraph
from mxnet_tpu.telemetry import healthplane as hp
from mxnet_tpu.telemetry import metrics as tmetrics
from mxnet_tpu.telemetry import profiling, remote_write
from mxnet_tpu.telemetry import trace as ttrace
from mxnet_tpu.telemetry import watchdog as twd

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
from launch import launch_local  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_ROOT, "tools", "%s.py" % name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


@pytest.fixture(autouse=True)
def _clean_state():
    twd.reset()
    hp.reset()
    attribution.set_device_spans(False)
    yield
    if profiling.active_profiler() is not None:
        profiling.active_profiler().close()
    twd.reset()
    hp.reset()
    attribution.set_device_spans(False)


def _can_bind_localhost():
    try:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        s.close()
        return True
    except OSError:
        return False


def _http(url, accept=None):
    headers = {"Accept": accept} if accept else {}
    req = urllib.request.Request(url, headers=headers)
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, resp.read(), \
                resp.headers.get("Content-Type", "")
    except urllib.error.HTTPError as e:
        return e.code, e.read(), e.headers.get("Content-Type", "")


def _busy_thread(name="prof_busy"):
    stop = threading.Event()

    def loop():
        while not stop.is_set():
            time.sleep(0.001)

    thread = threading.Thread(target=loop, name=name, daemon=True)
    thread.start()
    return stop, thread


# -- sampler mechanics --------------------------------------------------------

def test_fake_clock_window_rotation_and_retention_ring():
    clock = _FakeClock()
    profiler = telemetry.ContinuousProfiler(hz=100.0, window_s=10.0,
                                            retain=3, clock=clock)
    try:
        profiler._folded["root;f (x.py:1)"] = 100.0
        profiler._samples_in_window = 5
        assert profiler.maybe_rotate() is None       # t=0: not yet
        clock.t = 9.9
        assert profiler.maybe_rotate() is None
        clock.t = 10.0
        window = profiler.maybe_rotate()
        assert window is not None and window.seq == 1
        assert window.samples == 5
        assert window.folded == {"root;f (x.py:1)": 100.0}
        # Empty windows rotate silently (no blank ring entries).
        clock.t = 20.0
        assert profiler.maybe_rotate() is None
        assert len(profiler.windows) == 1
        # Retention ring: only the newest `retain` windows survive.
        for i in range(5):
            profiler._folded["root;g (y.py:2)"] = 10.0 * (i + 1)
            profiler._samples_in_window = 1
            profiler.rotate()
        assert len(profiler.windows) == 3
        assert [w.seq for w in profiler.windows] == [4, 5, 6]
    finally:
        profiler.close()


def test_sampler_counts_samples_and_overhead():
    before_samples = tmetrics.REGISTRY.get(
        "mx_profile_samples_total").value
    before_overhead = tmetrics.REGISTRY.get(
        "mx_profile_overhead_seconds").value
    stop, thread = _busy_thread()
    profiler = telemetry.ContinuousProfiler(hz=100.0, window_s=3600.0)
    try:
        for _ in range(10):
            profiler.sample()
        assert tmetrics.REGISTRY.get(
            "mx_profile_samples_total").value == before_samples + 10
        assert tmetrics.REGISTRY.get(
            "mx_profile_overhead_seconds").value > before_overhead
        window = profiler.rotate()
        assert window.samples == 10
        assert window.overhead_s > 0.0
        # Each sample charges one period (10 ms at 100 Hz) to the leaf.
        total_us = sum(window.folded.values())
        assert total_us >= 10 * 1e4     # >= 10 samples x 1 thread
    finally:
        stop.set()
        profiler.close()
        thread.join()


def test_lane_tagging_roots_threads_by_watchdog_lane():
    stop = threading.Event()

    def worker():
        twd.begin("step")       # this thread owns the step lane
        try:
            while not stop.is_set():
                time.sleep(0.001)
        finally:
            twd.end("step")

    thread = threading.Thread(target=worker, name="raw_thread_name",
                              daemon=True)
    thread.start()
    time.sleep(0.02)
    profiler = telemetry.ContinuousProfiler(hz=100.0, window_s=3600.0)
    try:
        for _ in range(5):
            profiler.sample()
        text = profiler.collapsed()
        assert any(line.startswith("step;") for line in
                   text.splitlines()), text
        assert "raw_thread_name" not in text
    finally:
        stop.set()
        thread.join()
        profiler.close()


def _spin_a(stop):
    def spin():
        while not stop.is_set():
            time.sleep(0.001)
    spin()


def _spin_b(stop):
    def spin():
        while not stop.is_set():
            time.sleep(0.001)
    spin()


def test_frame_keys_carry_file_lineno_so_same_names_stay_distinct():
    """ISSUE 12 satellite: two same-named functions (every worker loop
    is called `spin`/`run`) must fold into DISTINCT frames."""
    stop = threading.Event()
    threads = [threading.Thread(target=fn, args=(stop,), daemon=True)
               for fn in (_spin_a, _spin_b)]
    for t in threads:
        t.start()
    time.sleep(0.02)
    profiler = telemetry.ContinuousProfiler(hz=100.0, window_s=3600.0)
    try:
        for _ in range(5):
            profiler.sample()
        text = profiler.collapsed()
        spins = set()
        for line in text.splitlines():
            path = line.rsplit(" ", 1)[0]
            for frame in path.split(";"):
                if frame.startswith("spin ("):
                    spins.add(frame)
        assert len(spins) == 2, text    # merged pre-fix
    finally:
        stop.set()
        for t in threads:
            t.join()
        profiler.close()


def test_diff_top_keeps_located_frames_distinct_and_old_captures_diffable():
    # New-vs-new: same-named frames at different locations stay apart.
    before = "t;run (a.py:10) 100\nt;run (b.py:20) 100\n"
    after = "t;run (a.py:10) 50\nt;run (b.py:20) 150\n"
    rows = {r["op"]: r for r in flamegraph.diff_top(before, after)}
    assert "run (a.py:10)" in rows and "run (b.py:20)" in rows
    assert rows["run (b.py:20)"]["delta_pp"] == pytest.approx(25.0)
    # Old-vs-new (one side has no locations): fold both to bare names
    # instead of reading every frame as a 100% add/remove pair.
    old = "t;run 100\n"
    rows = flamegraph.diff_top(old, after)
    assert [r["op"] for r in rows] == ["run"]
    assert rows[0]["delta_pp"] == pytest.approx(0.0)


# -- regression sentinel + bundle section -------------------------------------

def test_profile_regression_anomaly_and_bundle_profile_section(tmp_path):
    monitor = telemetry.StepMonitor(warn_interval_s=1e9)
    recorder = telemetry.FlightRecorder(str(tmp_path), rank=0,
                                        rate_limit_s=0.0)
    recorder.attach(monitor)
    profiler = telemetry.ContinuousProfiler(
        hz=100.0, window_s=3600.0, monitor=monitor, regress_pp=10.0,
        min_samples=10)
    try:
        # Window 1 seeds the baseline: all self time in frame_x.
        profiler._folded = {"step;frame_x (a.py:1)": 1000.0}
        profiler._samples_in_window = 50
        profiler.rotate()
        assert monitor.anomaly_counts.get("profile_regression", 0) == 0
        # Window 2: the time moved to frame_y (+100pp share) -> anomaly
        # -> flight-recorder bundle whose profile section holds the
        # offending capture.
        profiler._folded = {"step;frame_y (a.py:9)": 1000.0}
        profiler._samples_in_window = 50
        profiler.rotate()
        assert monitor.anomaly_counts["profile_regression"] == 1
        assert len(recorder.bundles) == 1
        with open(recorder.bundles[0]) as f:
            bundle = json.load(f)
        assert bundle["meta"]["kind"] == "profile_regression"
        assert "frame_y (a.py:9)" in bundle["profile"]["collapsed"]
        assert bundle["profile"]["hz"] == 100.0
        # Below min_samples: shares are noise, the sentinel stays put.
        profiler._folded = {"step;frame_z (a.py:33)": 1000.0}
        profiler._samples_in_window = 3
        profiler.rotate()
        assert monitor.anomaly_counts["profile_regression"] == 1
    finally:
        profiler.close()


# -- /debug/pprof + /debug/attribution ----------------------------------------

@pytest.mark.skipif(not _can_bind_localhost(),
                    reason="localhost sockets unavailable")
def test_debug_pprof_endpoint_serves_collapsed_and_json(tmp_path):
    stop, thread = _busy_thread("pprof_busy")
    start_count = tmetrics.REGISTRY.get("mx_profile_samples_total").value
    profiler = telemetry.ContinuousProfiler(hz=200.0,
                                            window_s=3600.0).start()
    attr = telemetry.StepAttribution(interval_s=0.0,
                                     device_spans=False)
    plane = hp.HealthPlane(attribution=attr)
    server = tmetrics.start_http_server(0, health=plane)
    try:
        # Wait on THIS profiler's samples (the counter is global and
        # earlier tests may have advanced it).
        deadline = time.time() + 10.0
        while tmetrics.REGISTRY.get(
                "mx_profile_samples_total").value < start_count + 5 \
                and time.time() < deadline:
            time.sleep(0.01)
        before = tmetrics.REGISTRY.get("mx_profile_samples_total").value
        assert before >= start_count + 5, "sampler thread never ran"
        base = "http://%s:%d" % server.server_address
        status, body, ctype = _http(base + "/debug/pprof?seconds=60")
        assert status == 200
        assert ctype.startswith("text/plain")
        assert b"pprof_busy;" in body
        # format=json carries window metadata + the capture.
        status, body, ctype = _http(
            base + "/debug/pprof?seconds=60&format=json")
        assert status == 200 and ctype.startswith("application/json")
        state = json.loads(body)
        assert state["hz"] == 200.0
        assert "pprof_busy;" in state["collapsed"]
        assert state["captured_samples"] > 0
        # Bad params are 400s, not stack traces.
        assert _http(base + "/debug/pprof?seconds=nope")[0] == 400
        assert _http(base + "/debug/pprof?format=xml")[0] == 400
        # Overhead self-accounting keeps running WHILE captures are
        # served: the sampler thread advanced its counters across the
        # requests above.
        time.sleep(0.05)
        assert tmetrics.REGISTRY.get(
            "mx_profile_samples_total").value > before
        assert tmetrics.REGISTRY.get(
            "mx_profile_overhead_seconds").value > 0.0
        # /debug/attribution: the attributor's snapshot.
        status, body, _ = _http(base + "/debug/attribution")
        assert status == 200
        snap = json.loads(body)
        assert set(snap["phases"]) == set(attribution.PHASES)
    finally:
        server.close()
        profiler.close()
        attr.close()
        stop.set()
        thread.join()
    # No profiler running -> 404 with a hint, not a 500.
    plane2 = hp.HealthPlane()
    status, body = plane2.handle("GET", "/debug/pprof")
    assert status == 404


def test_healthplane_routes_strip_query_strings():
    plane = hp.HealthPlane()
    status, body = plane.handle("GET", "/healthz?verbose=1")
    assert status in (200, 503) and "lanes" in body


# -- step attribution ---------------------------------------------------------

def _span_events(*spans):
    """[(name, start_s, dur_s)] -> chrome events (µs)."""
    return [{"ph": "X", "name": name, "ts": start * 1e6,
             "dur": dur * 1e6} for name, start, dur in spans]


def test_attribution_phases_and_counters():
    attr = telemetry.StepAttribution(interval_s=0.0, device_spans=False)
    events = _span_events(
        ("data::wait", 0.0, 0.10),
        ("train_step::step", 0.10, 0.90),
        ("train_step::data_put", 0.10, 0.05),
        ("train_step::dispatch", 0.15, 0.20),
        ("train_step::device", 0.35, 0.60),
        ("checkpoint::snapshot", 0.95, 0.02),
    )
    sums = attr.update(events=events)
    assert sums["data_wait"] == pytest.approx(0.10)
    assert sums["h2d"] == pytest.approx(0.05)
    assert sums["dispatch"] == pytest.approx(0.20)
    assert sums["device_compute"] == pytest.approx(0.60)
    assert sums["checkpoint"] == pytest.approx(0.02)
    # other = step(0.90) - accounted-inside-step(0.87)
    assert sums["other"] == pytest.approx(0.03)
    assert attr.bound_cause == "compute-bound"
    shares = attr.last_shares
    assert shares["device_compute"] == pytest.approx(0.6, abs=0.01)
    snap = attr.snapshot()
    assert snap["bound_cause"] == "compute-bound"
    assert snap["phases"]["device_compute"] == pytest.approx(0.60)
    attr.close()


def test_attribution_watermark_consumes_each_span_once():
    attr = telemetry.StepAttribution(interval_s=0.0, device_spans=False)
    events = _span_events(("data::wait", 0.0, 0.5),
                          ("train_step::step", 0.5, 0.5))
    attr.update(events=events)
    first = attr.cumulative["data_wait"]
    attr.update(events=events)      # same events: nothing re-counted
    assert attr.cumulative["data_wait"] == first
    attr.close()


def test_attribution_input_bound_classifier_and_anomaly():
    monitor = telemetry.StepMonitor(warn_interval_s=1e9)
    attr = telemetry.StepAttribution(
        monitor=monitor, interval_s=0.0, input_bound_share=0.3,
        input_bound_windows=3, device_spans=False)
    t = [0.0]

    def window():
        events = _span_events(("data::wait", t[0], 0.6),
                              ("train_step::step", t[0] + 0.6, 0.4))
        t[0] += 1.0
        return events

    attr.update(events=window())
    attr.update(events=window())
    assert monitor.anomaly_counts.get("input_bound", 0) == 0
    attr.update(events=window())    # third consecutive window: fire
    assert monitor.anomaly_counts["input_bound"] == 1
    assert attr.bound_cause == "input-bound"
    gauge = tmetrics.REGISTRY.get("mx_step_bound")
    assert gauge.labels(cause="input-bound").value == 1
    assert gauge.labels(cause="compute-bound").value == 0
    # A healthy window resets the streak AND the cause.
    events = _span_events(("data::wait", t[0], 0.01),
                          ("train_step::step", t[0] + 0.01, 0.99),
                          ("train_step::device", t[0] + 0.01, 0.9))
    attr.update(events=events)
    assert attr.bound_cause == "compute-bound"
    assert attr._streak == 0
    attr.close()


def test_attribution_trainer_path_without_step_envelope():
    """Review regression: the imperative Trainer path emits
    trainer::allreduce but no train_step::step envelope — shares must
    stay <= 1 and a comm-dominated window must NOT page input-bound."""
    monitor = telemetry.StepMonitor(warn_interval_s=1e9)
    attr = telemetry.StepAttribution(
        monitor=monitor, interval_s=0.0, input_bound_windows=1,
        device_spans=False)
    attr.update(events=_span_events(("data::wait", 0.0, 0.5),
                                    ("trainer::allreduce", 0.5, 5.0)))
    shares = attr.last_shares
    assert all(0.0 <= s <= 1.0 for s in shares.values()), shares
    assert shares["allreduce"] == pytest.approx(5.0 / 5.5)
    assert attr.bound_cause == "comm-bound"
    assert monitor.anomaly_counts.get("input_bound", 0) == 0
    attr.close()


def test_constructed_profiler_does_not_hijack_active_slot():
    """Review regression: a built-but-never-started profiler must not
    steal /debug/pprof + bundle captures from the producing one."""
    live = telemetry.ContinuousProfiler(hz=100.0, window_s=3600.0)
    live.sample()
    assert profiling.active_profiler() is live
    idle = telemetry.ContinuousProfiler(hz=100.0, window_s=3600.0)
    assert profiling.active_profiler() is live
    idle.close()                    # closing the idle one: no stomp
    assert profiling.active_profiler() is live
    live.close()
    assert profiling.active_profiler() is None


def test_attribution_comm_and_host_bound_causes():
    attr = telemetry.StepAttribution(interval_s=0.0, device_spans=False)
    attr.update(events=_span_events(
        ("train_step::step", 0.0, 1.0),
        ("trainer::allreduce", 0.0, 0.8)))
    assert attr.bound_cause == "comm-bound"
    attr.update(events=_span_events(("train_step::step", 2.0, 1.0),
                                    ("train_step::device", 2.0, 0.1)))
    assert attr.bound_cause == "host-bound"
    attr.close()


def test_train_step_device_span_gated_by_attribution():
    import numpy as np

    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import TrainStep, make_mesh

    import jax

    mx.random.seed(7)
    net = gluon.nn.Dense(4, in_units=8, prefix="attr_fc_")
    net.initialize(mx.init.Xavier())
    step = TrainStep(net, gluon.loss.L2Loss(), optimizer="sgd",
                     optimizer_params={"learning_rate": 0.01},
                     mesh=make_mesh())
    batch = 4 * jax.device_count()
    x = np.random.rand(batch, 8).astype(np.float32)
    y = np.random.rand(batch, 4).astype(np.float32)
    ttrace.clear()
    step(x, y)          # device spans off: no bracket
    names = {e["name"] for e in ttrace.chrome_trace()["traceEvents"]}
    assert "train_step::device" not in names
    with telemetry.StepAttribution(interval_s=0.0) as attr:
        assert attribution.device_spans_enabled()
        step(x, y)
        names = {e["name"]
                 for e in ttrace.chrome_trace()["traceEvents"]}
        assert "train_step::device" in names
        sums = attr.update()
        assert sums["device_compute"] >= 0.0
    assert not attribution.device_spans_enabled()   # restored


# -- decode-pool autoscaling --------------------------------------------------

class _FakePool:
    def __init__(self, num_threads=2):
        self.num_threads = num_threads
        self.calls = []

    def resize(self, n):
        self.calls.append(n)
        self.num_threads = n
        return n


def test_autoscaler_hysteresis_grow_and_shrink():
    from mxnet_tpu.data.autoscale import DecodeAutoscaler

    pool = _FakePool(num_threads=2)
    scaler = DecodeAutoscaler(pool, min_workers=1, max_workers=4,
                              grow_share=0.25, shrink_share=0.05,
                              interval_s=10.0)
    # Input-bound windows grow one worker at a time, capped at max.
    assert scaler.observe(0.5, 0.5) == 3     # share 0.5 >= 0.25
    assert scaler.observe(0.5, 0.5) == 4
    assert scaler.observe(0.9, 0.1) == 4     # at the ceiling
    # The hysteresis band holds steady.
    assert scaler.observe(0.1, 0.9) == 4     # 0.05 < 0.1 < 0.25
    # Idle input shrinks back to the floor, one at a time.
    assert scaler.observe(0.01, 0.99) == 3
    assert scaler.observe(0.0, 1.0) == 2
    assert scaler.observe(0.0, 1.0) == 1
    assert scaler.observe(0.0, 1.0) == 1     # at the floor
    assert scaler.observe(0.0, 0.0) == 1     # idle window: no signal
    assert pool.calls == [3, 4, 3, 2, 1]


def test_autoscaler_tick_fake_clock_over_registry_deltas():
    from mxnet_tpu.data.autoscale import DecodeAutoscaler

    reg = tmetrics.Registry()
    wait = reg.histogram("mx_data_wait_seconds")
    step = reg.histogram("mx_train_step_seconds")
    pool = _FakePool(num_threads=1)
    clock = _FakeClock()
    scaler = DecodeAutoscaler(pool, max_workers=3, interval_s=10.0,
                              registry=reg, clock=clock)
    wait.observe(3.0)
    step.observe(1.0)
    assert scaler.tick() is None            # first window anchors
    clock.t = 5.0
    assert scaler.tick() is None            # inside the interval
    clock.t = 10.0
    wait.observe(3.0)                       # delta: wait 3, step 1
    step.observe(1.0)
    assert scaler.tick() == 2               # 0.75 share -> grow
    clock.t = 20.0
    step.observe(10.0)                      # delta: wait 0, step 10
    assert scaler.tick() == 1               # 0.0 share -> shrink
    assert pool.calls == [2, 1]


def test_decode_pool_resize_grows_live_pool():
    from mxnet_tpu.data.decode import DecodePool

    pool = DecodePool(lambda i: i * 2, num_threads=1)
    try:
        assert pool.resize(3) == 3
        assert pool.num_threads == 3 and pool.inflight == 6
        assert pool._pool._max_workers == 3
        assert list(pool.run(range(10))) == [i * 2 for i in range(10)]
        assert pool.resize(0) == 1          # floor at one worker
    finally:
        pool.close()


def test_autoscaler_default_ceiling_reads_env(monkeypatch):
    from mxnet_tpu.data.autoscale import DecodeAutoscaler

    scaler = DecodeAutoscaler(_FakePool())
    assert scaler.max_workers == 16         # catalogue default
    monkeypatch.setenv("MXNET_DATA_MAX_WORKERS", "5")
    scaler = DecodeAutoscaler(_FakePool())
    assert scaler.max_workers == 5


def test_data_pipeline_autoscale_wiring(tmp_path):
    from mxnet_tpu import recordio
    from mxnet_tpu.data.pipeline import DataPipeline

    rec = str(tmp_path / "t.rec")
    writer = recordio.MXRecordIO(rec, "w")
    for i in range(16):
        writer.write(("payload-%03d" % i).encode())
    writer.close()

    import numpy as np

    def decode(record):
        return (np.float32(float(record[-3:].decode())),
                np.zeros(2, np.float32))

    pipe = DataPipeline(
        [rec], decode,
        batch_size=4, shuffle=False, num_shards=1, shard_index=0,
        decode_threads=2, prefetch=0, place=False,
        autoscale={"interval_s": 0.0, "max_workers": 3})
    with pipe:
        next(pipe)
        next(pipe)
        assert pipe._autoscaler is not None
        assert pipe._autoscaler.pool is pipe._pool
        assert pipe._autoscaler.max_workers == 3


# -- Prometheus remote write --------------------------------------------------

def test_remote_write_protobuf_golden_bytes():
    """The WriteRequest encoding pinned byte-for-byte against the
    prompb schema (field numbers/wire types hand-assembled)."""
    reg = tmetrics.Registry()
    reg.counter("rw_total").inc(3)
    body = remote_write.encode_write_request(reg, 1700000000000,
                                             compress=False)
    golden = bytes.fromhex(
        "0a28"                              # WriteRequest.timeseries
        "0a14"                              # TimeSeries.labels[0]
        "0a085f5f6e616d655f5f"              # Label.name  "__name__"
        "120872775f746f74616c"              # Label.value "rw_total"
        "1210"                              # TimeSeries.samples[0]
        "090000000000000840"                # Sample.value double 3.0
        "1080d095ffbc31")                   # Sample.timestamp int64
    assert body == golden


def test_remote_write_labels_sorted_and_histograms_expanded():
    reg = tmetrics.Registry()
    h = reg.histogram("rw_lat_seconds", labels=("server",),
                      buckets=(0.1, 1.0))
    h.labels(server="s0").observe(0.05)
    h.labels(server="s0").observe(5.0)
    series = list(remote_write.registry_series(
        reg, extra_labels={"job": "aaa_job"}))
    names = [dict(labels)["__name__"] for labels, _ in series]
    assert names == ["rw_lat_seconds_bucket"] * 3 + \
        ["rw_lat_seconds_sum", "rw_lat_seconds_count"]
    labels, value = series[0]
    # __name__ first, the rest sorted by label name.
    assert [n for n, _ in labels] == ["__name__", "job", "le", "server"]
    assert value == 1                       # cumulative le=0.1
    assert dict(series[2][0])["le"] == "+Inf"
    assert series[2][1] == 2
    assert series[3][1] == pytest.approx(5.05)


def test_snappy_pure_python_literal_framing():
    try:
        import snappy  # noqa: F401

        pytest.skip("real snappy installed; literal framing unused")
    except ImportError:
        pass
    data = b"hello world"
    assert remote_write.snappy_compress(data) == b"\x0b\x28" + data
    # >60 bytes: 1-byte extended length (tag 60<<2, len-1).
    data = bytes(100)
    assert remote_write.snappy_compress(data) == \
        b"\x64" + bytes([60 << 2, 99]) + data
    assert remote_write.snappy_compress(b"") == b"\x00"


def test_push_exporter_remote_write_format_and_fallback():
    reg = tmetrics.Registry()
    reg.counter("rw_push_total").inc(9)
    sent = []
    exporter = export.PushExporter(
        "http://mimir:9009/api/v1/push", registry=reg, job="trainer",
        instance="r0", wire_format="remote_write",
        transport=lambda url, body: sent.append((url, body)))
    assert exporter.push() is True
    url, body = sent[0]
    assert url == "http://mimir:9009/api/v1/push"   # verbatim endpoint
    # Snappy literal framing leaves the protobuf readable: the series
    # carries __name__ + the job/instance labels.
    for needle in (b"rw_push_total", b"__name__", b"trainer", b"r0"):
        assert needle in body
    assert b"# HELP" not in body            # not the text format

    # A broken encode degrades to ONE classic-text snapshot, counted.
    class BadCollect:
        def collect(self):
            raise RuntimeError("no proto for you")

        def render_prometheus(self, openmetrics=False):
            return "fallback_metric 1\n"

    fails = tmetrics.REGISTRY.get("mx_export_failures_total").value
    exporter = export.PushExporter(
        "http://mimir:9009/api/v1/push", registry=BadCollect(),
        wire_format="remote_write",
        transport=lambda url, body: sent.append((url, body)))
    assert exporter.push() is True
    assert sent[-1][1] == b"fallback_metric 1\n"
    assert tmetrics.REGISTRY.get(
        "mx_export_failures_total").value == fails + 1

    with pytest.raises(ValueError):
        export.PushExporter("http://x", wire_format="msgpack")


# -- pod profiles over the diag channel ---------------------------------------

def _profiler_with(folded):
    profiler = telemetry.ContinuousProfiler(hz=100.0, window_s=3600.0)
    profiler._folded = dict(folded)
    profiler._samples_in_window = 50
    profiler.rotate()
    return profiler


def test_pod_profile_collection_over_local_bus(tmp_path):
    bus = aggregate.LocalBus(num_workers=2)
    profilers = [
        _profiler_with({"step;rank0_frame (a.py:1)": 2000.0}),
        _profiler_with({"data#2;rank1_frame (b.py:2)": 1000.0}),
    ]
    collectors = []
    for rank in (0, 1):
        rec = telemetry.FlightRecorder(
            str(tmp_path / ("local%d" % rank)), rank=rank,
            rate_limit_s=0.0)
        collectors.append(hp.DiagCollector(
            bus.endpoint(rank), rec, interval_s=0.0,
            profiler=profilers[rank],
            directory=str(tmp_path / "collected") if rank == 0
            else None))
    c0, c1 = collectors
    try:
        assert c0.request_pod_profile(seconds=600.0) == 1
        assert c1.poll_request() == "profile.rank1.000001.collapsed"
        assert c0.poll_request() == "profile.rank0.000001.collapsed"
        c0.collect()
        names = sorted(os.path.basename(p) for p in c0.collected)
        assert names == ["profile.rank0.000001.collapsed",
                         "profile.rank1.000001.collapsed"]
        merged = c0.merged_pod_profile()
        assert "rank0;step;rank0_frame (a.py:1)" in merged
        assert "rank1;data#2;rank1_frame (b.py:2)" in merged
        # A repeated poll without a new request pushes nothing.
        assert c1.poll_request() is None
    finally:
        for p in profilers:
            p.close()


def test_collector_gc_keeps_newest_per_kind(tmp_path):
    bus = aggregate.LocalBus(num_workers=1)
    rec = telemetry.FlightRecorder(str(tmp_path / "local"), rank=0,
                                   rate_limit_s=0.0)
    collector = hp.DiagCollector(bus.endpoint(0), rec, interval_s=0.0,
                                 keep_last=1,
                                 directory=str(tmp_path / "collected"))
    rank_dir = tmp_path / "collected" / "rank0"
    rank_dir.mkdir(parents=True)
    for name in ("diag.rank0.000001.json", "diag.rank0.000002.json",
                 "profile.rank0.000001.collapsed",
                 "profile.rank0.000002.collapsed"):
        (rank_dir / name).write_text("{}")
    removed = collector.gc()
    assert sorted(os.path.basename(p) for p in removed) == \
        ["diag.rank0.000001.json", "profile.rank0.000001.collapsed"]
    assert sorted(os.listdir(rank_dir)) == \
        ["diag.rank0.000002.json", "profile.rank0.000002.collapsed"]


# -- tools/profile_tool.py ----------------------------------------------------

def test_trace_exemplars_split_markers_from_hot_frames():
    """ISSUE 20 satellite: ``trace:<id>`` leaf markers become per-frame
    exemplars — the real hot frame keeps its self time instead of the
    marker swallowing it as the leaf."""
    folded = {
        "main;hot (x.py:1);trace:abc": 500.0,
        "main;hot (x.py:1);trace:def": 200.0,
        "main;hot (x.py:1)": 100.0,
        "main;cold (y.py:2)": 50.0,
    }
    clean, exemplars = flamegraph.trace_exemplars(folded)
    assert clean == {"main;hot (x.py:1)": 800.0,
                     "main;cold (y.py:2)": 50.0}
    assert exemplars == {"hot (x.py:1)": {"abc": 500.0, "def": 200.0}}
    leaf = flamegraph._by_leaf(clean)
    assert leaf["hot (x.py:1)"] == 800.0
    assert not any(f.startswith("trace:") for f in leaf)


def test_sampled_context_surfaces_as_exemplar_in_debug_state():
    from mxnet_tpu.telemetry import xtrace

    ctx = xtrace.new_root(sampled=True)
    stop = threading.Event()

    def traced_loop():
        with xtrace.activate(ctx):
            while not stop.is_set():
                time.sleep(0.001)

    thread = threading.Thread(target=traced_loop,
                              name="gp_exemplar", daemon=True)
    thread.start()
    profiler = telemetry.ContinuousProfiler(hz=200.0, window_s=3600.0)
    try:
        time.sleep(0.02)              # the loop is inside activate()
        for _ in range(10):
            profiler.sample()
        state = profiler.debug_state()
        hits = [frame for frame, ids in state["exemplars"].items()
                if any(e["trace_id"] == ctx.trace_id for e in ids)]
        assert hits, state["exemplars"]
        # the marker is exemplar metadata now, not a collapsed leaf
        assert "trace:%s" % ctx.trace_id not in state["collapsed"]
        # and each exemplar row carries its sampled self time
        for ids in state["exemplars"].values():
            assert all(e["self_us"] > 0 for e in ids)
    finally:
        profiler.close()
        stop.set()
        thread.join()


def test_profile_tool_top_prints_exemplars(tmp_path, capsys):
    tool = _tool("profile_tool")
    cap = tmp_path / "c.collapsed"
    cap.write_text("main;hot (x.py:1);trace:abc 900\n"
                   "main;hot (x.py:1);trace:ffe 300\n"
                   "main;cold (y.py:2) 100\n")
    assert tool.main(["top", str(cap), "-k", "5"]) == 0
    out = capsys.readouterr().out
    assert "hot (x.py:1)" in out
    assert "exemplars: trace:abc, trace:ffe" in out
    assert "trace:abc" not in out.splitlines()[2]  # not ranked as frame


def test_profile_tool_top_diff_merge(tmp_path, capsys):
    tool = _tool("profile_tool")
    a = tmp_path / "a.collapsed"
    b = tmp_path / "b.collapsed"
    a.write_text("main;fast (x.py:1) 900\nmain;slow (y.py:2) 100\n")
    b.write_text("main;fast (x.py:1) 100\nmain;slow (y.py:2) 900\n")

    assert tool.main(["top", str(a), "-k", "5"]) == 0
    out = capsys.readouterr().out
    assert "fast (x.py:1)" in out and "90.0%" in out

    assert tool.main(["diff", str(a), str(b)]) == 0
    out = capsys.readouterr().out
    assert "slow (y.py:2)" in out and "REGRESSED" in out

    merged = tmp_path / "merged.collapsed"
    assert tool.main(["merge", "-o", str(merged), str(a),
                      str(b)]) == 0
    folded = flamegraph._parse_collapsed(merged.read_text())
    assert folded == {"main;fast (x.py:1)": 1000.0,
                      "main;slow (y.py:2)": 1000.0}


# -- the "why is my step slow" loop, endpoints only ---------------------------

def _slow_decode(record):
    """The acceptance scenario's artificially slow decode."""
    import numpy as np

    time.sleep(0.02)
    return (np.float32(0.0), np.zeros(4, np.float32))


@pytest.mark.skipif(not _can_bind_localhost(),
                    reason="localhost sockets unavailable")
def test_acceptance_slow_decode_diagnosed_from_endpoints_alone(tmp_path):
    """ISSUE 12 acceptance: with an artificially slowed decode, (a)
    data_wait is the dominant phase, (b) mx_step_bound says
    input-bound, (c) /debug/pprof's top frames point into the decode
    path — all read from the HTTP endpoints, no local state."""
    from mxnet_tpu import recordio
    from mxnet_tpu.data.pipeline import DataPipeline

    rec = str(tmp_path / "slow.rec")
    writer = recordio.MXRecordIO(rec, "w")
    for i in range(64):
        writer.write(b"r%03d" % i)
    writer.close()

    profiler = telemetry.ContinuousProfiler(hz=200.0,
                                            window_s=3600.0).start()
    attr = telemetry.StepAttribution(interval_s=0.0,
                                     device_spans=False)
    plane = hp.HealthPlane(attribution=attr)
    server = tmetrics.start_http_server(0, health=plane)
    pipe = DataPipeline([rec], _slow_decode, batch_size=8,
                        shuffle=False, num_shards=1, shard_index=0,
                        decode_threads=2, prefetch=2, place=False)
    try:
        attr.update()                   # drain unrelated span backlog
        for _ in range(8):
            next(pipe)                  # data::wait recorded here
            with ttrace.span("train_step::step"):
                time.sleep(0.001)       # the "fast step"
        attr.update()
        base = "http://%s:%d" % server.server_address
        status, body, _ = _http(base + "/debug/attribution")
        assert status == 200
        snap = json.loads(body)
        shares = snap["last_shares"]
        assert shares["data_wait"] == max(shares.values())  # dominant
        assert snap["bound_cause"] == "input-bound"
        status, body, _ = _http(base + "/debug/pprof?seconds=60")
        assert status == 200
        assert b"_slow_decode (" in body        # the culprit, by name
    finally:
        pipe.close()
        server.close()
        profiler.close()
        attr.close()


# -- 2-process acceptance -----------------------------------------------------

_PROG = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "profiling_prog.py")
_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
}


def test_two_process_pod_profile_over_kvstore(tmp_path):
    """ISSUE 12 acceptance: rank 0's request_pod_profile fan-out pulls
    both ranks' profiler windows over the kvstore diag channel — one
    collected capture per rank, merged into a single pod profile whose
    stacks keep per-rank roots and rank-distinct frames."""
    if not _can_bind_localhost():
        pytest.skip("localhost sockets unavailable (multi-process "
                    "kvstore needs them)")
    codes = launch_local(2, 1, [sys.executable, _PROG, str(tmp_path)],
                         env_extra=_ENV, timeout=300)
    assert codes == [0, 0], codes
    result = json.loads((tmp_path / "result.json").read_text())
    names = sorted(os.path.basename(p) for p in result["collected"])
    assert names == ["profile.rank0.000001.collapsed",
                     "profile.rank1.000001.collapsed"]
    merged = result["merged"]
    assert "rank0;" in merged and "rank1;" in merged
    assert "rank_marker_0 (" in merged      # rank-distinct leaf frames
    assert "rank_marker_1 (" in merged
    for line in merged.splitlines():        # roots stay per-rank
        assert line.startswith(("rank0;", "rank1;"))


def test_adaptive_sampling_backs_off_and_recovers():
    """PR 12 follow-up: when the sampler's self-accounted overhead
    share exceeds its <=1% budget, the rate halves (down to min_hz);
    once the share falls well under budget it doubles back toward the
    configured rate. Driven entirely by the fake clock + fake perf
    counter, no thread."""
    clock = _FakeClock()
    profiler = telemetry.ContinuousProfiler(
        hz=64.0, window_s=10.0, retain=3, clock=clock,
        overhead_budget=0.01, min_hz=4.0)
    hz_gauge = tmetrics.REGISTRY.get("mx_profile_hz")
    adjusts = tmetrics.REGISTRY.get("mx_profile_rate_adjustments_total")
    down0 = adjusts.labels(direction="down").value
    try:
        # Window 1: overhead 5% of 10s wall — way over the 1% budget.
        profiler._samples_in_window = 20
        profiler._overhead_in_window = 0.5
        clock.t = 10.0
        profiler.rotate()
        assert profiler.hz == 32.0
        assert hz_gauge.value == 32.0
        assert adjusts.labels(direction="down").value == down0 + 1
        # Still over budget: halves again.
        profiler._samples_in_window = 20
        profiler._overhead_in_window = 0.5
        clock.t = 20.0
        profiler.rotate()
        assert profiler.hz == 16.0
        # Repeatedly over budget: never below min_hz.
        for i in range(6):
            profiler._samples_in_window = 20
            profiler._overhead_in_window = 0.5
            clock.t = 30.0 + 10.0 * i
            profiler.rotate()
        assert profiler.hz == 4.0
        # Healthy windows (share << budget/4): doubles back, capped at
        # the configured base rate.
        for i in range(8):
            profiler._samples_in_window = 20
            profiler._overhead_in_window = 0.0001
            clock.t = 100.0 + 10.0 * i
            profiler.rotate()
        assert profiler.hz == 64.0
        assert profiler.base_hz == 64.0
        # In the dead band (between budget/4 and budget): no change.
        profiler._samples_in_window = 20
        profiler._overhead_in_window = 0.05      # 0.5% of wall
        clock.t = 200.0
        profiler.rotate()
        assert profiler.hz == 64.0
    finally:
        profiler.close()


def test_adaptive_sampling_disabled_keeps_rate():
    clock = _FakeClock()
    profiler = telemetry.ContinuousProfiler(
        hz=64.0, window_s=10.0, retain=3, clock=clock, adaptive=False)
    try:
        profiler._samples_in_window = 20
        profiler._overhead_in_window = 5.0       # 50% overhead share
        clock.t = 10.0
        profiler.rotate()
        assert profiler.hz == 64.0
    finally:
        profiler.close()
