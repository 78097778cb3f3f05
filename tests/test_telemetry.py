"""mxnet_tpu.telemetry — unified metrics registry, chrome-trace span
export, and the step-health monitor (ISSUE 3)."""
import json
import math
import threading
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.telemetry import metrics as tmetrics
from mxnet_tpu.telemetry import trace


# -- metrics registry ---------------------------------------------------------

def test_counter_hammer_no_lost_increments():
    """Multi-threaded hammer: concurrent labeled increments are never
    lost, and exposition snapshots taken mid-hammer stay parseable."""
    reg = tmetrics.Registry()
    c = reg.counter("hammer_total", "hammered", labels=("worker",))
    n_threads, n_incs = 8, 5000
    renders = []

    def hit(i):
        child = c.labels(worker="w%d" % (i % 2))
        for _ in range(n_incs):
            child.inc()

    def scrape():
        for _ in range(50):
            renders.append(reg.render_prometheus())

    threads = [threading.Thread(target=hit, args=(i,))
               for i in range(n_threads)]
    threads.append(threading.Thread(target=scrape))
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.labels(worker="w0").value == 4 * n_incs
    assert c.labels(worker="w1").value == 4 * n_incs
    for text in renders:
        for line in text.splitlines():
            assert line.startswith("#") or " " in line


def test_histogram_exact_aggregates_and_quantiles():
    reg = tmetrics.Registry()
    h = reg.histogram("lat_seconds", "latencies")
    values = [0.0005, 0.001, 0.002, 0.004, 0.1]
    for v in values:
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(sum(values))
    assert snap["min"] == pytest.approx(min(values))
    assert snap["max"] == pytest.approx(max(values))
    # cumulative bucket counts are monotone and end at count
    cums = [c for _, c in snap["buckets"]]
    assert cums == sorted(cums) and cums[-1] == 5
    assert math.isinf(snap["buckets"][-1][0])
    # quantiles: monotone in q, clamped to observed [min, max]
    qs = [h.quantile(q) for q in (0.0, 0.25, 0.5, 0.75, 0.99, 1.0)]
    assert qs == sorted(qs)
    assert snap["min"] <= qs[0] and qs[-1] <= snap["max"]
    assert qs[0] > 0


def test_histogram_empty_and_custom_buckets():
    reg = tmetrics.Registry()
    h = reg.histogram("x_seconds", buckets=(1.0, 2.0, 4.0))
    assert h.quantile(0.5) == 0.0
    h.observe(100.0)            # overflow bucket
    assert h.quantile(0.5) == pytest.approx(100.0)
    with pytest.raises(ValueError):
        reg.histogram("x_seconds", buckets=(1.0, 8.0))


def test_gauge_and_nonblocking_inc():
    reg = tmetrics.Registry()
    g = reg.gauge("pending", "in flight")
    g.set(5)
    g.dec(2)
    assert g.value == 3
    assert g.inc_try(4) is True
    assert g.value == 7
    # inc_try drops the tick (returns False) when the lock is held
    child = g.labels()
    child._lock.acquire()
    try:
        assert g.inc_try(1) is False
    finally:
        child._lock.release()
    assert g.value == 7


def test_registry_type_and_name_validation():
    reg = tmetrics.Registry()
    reg.counter("a_total", labels=("x",))
    with pytest.raises(ValueError):
        reg.gauge("a_total")                    # type conflict
    with pytest.raises(ValueError):
        reg.counter("a_total", labels=("y",))   # label conflict
    with pytest.raises(ValueError):
        reg.counter("bad name")
    with pytest.raises(ValueError):
        reg.counter("ok_total", labels=("bad-label",))
    with pytest.raises(ValueError):
        reg.counter("neg_total").inc(-1)        # counters are monotonic


def test_render_prometheus_format():
    reg = tmetrics.Registry()
    reg.counter("req_total", "requests served",
                labels=("route",)).labels(route='a"b\\c').inc(2)
    reg.histogram("dur_seconds", "durations",
                  buckets=(0.1, 1.0)).observe(0.5)
    text = reg.render_prometheus()
    assert '# TYPE req_total counter' in text
    assert 'req_total{route="a\\"b\\\\c"} 2' in text
    assert '# TYPE dur_seconds histogram' in text
    assert 'dur_seconds_bucket{le="0.1"} 0' in text
    assert 'dur_seconds_bucket{le="1"} 1' in text
    assert 'dur_seconds_bucket{le="+Inf"} 1' in text
    assert 'dur_seconds_sum 0.5' in text
    assert 'dur_seconds_count 1' in text


def test_metrics_http_endpoint():
    reg = tmetrics.Registry()
    reg.counter("served_total").inc(9)
    try:
        server = tmetrics.start_http_server(0, registry=reg)
    except OSError as exc:         # sandboxed CI without localhost bind
        pytest.skip("cannot bind localhost: %s" % exc)
    try:
        host, port = server.server_address[:2]
        with urllib.request.urlopen(
                "http://%s:%d/metrics" % (host, port), timeout=10) as r:
            assert r.status == 200
            body = r.read().decode("utf-8")
        assert "served_total 9" in body
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                "http://%s:%d/nope" % (host, port), timeout=10)
    finally:
        server.shutdown()


def test_set_enabled_pauses_recording():
    reg = tmetrics.Registry()
    c = reg.counter("gated_total")
    prev = telemetry.set_enabled(False)
    try:
        c.inc(5)
        with trace.span("gated::span"):
            pass
        trace.instant("gated::instant")
    finally:
        telemetry.set_enabled(prev)
    assert c.value == 0
    names = [e["name"] for e in trace.chrome_trace()["traceEvents"]]
    assert "gated::span" not in names and "gated::instant" not in names
    c.inc(1)
    assert c.value == 1


# -- trace --------------------------------------------------------------------

def test_chrome_trace_schema():
    trace.clear()
    with trace.span("t::outer", step=3):
        with trace.span("t::inner"):
            pass
        trace.instant("t::mark", kind="x")
    trace.complete("t::retro", 1.0, 1.5, rows=2)
    data = trace.chrome_trace()
    text = json.dumps(data)
    data = json.loads(text)                 # round-trips as valid JSON
    events = data["traceEvents"]
    assert events, "no events captured"
    for event in events:
        for key in ("ph", "ts", "pid", "tid", "name"):
            assert key in event, event
        if event["ph"] == "X":
            assert "dur" in event and event["dur"] >= 0
    by_name = {e["name"]: e for e in events}
    assert by_name["t::outer"]["args"] == {"step": 3}
    assert by_name["t::retro"]["dur"] == pytest.approx(0.5e6)
    assert by_name["t::mark"]["ph"] == "i"
    # nesting: inner span lies within outer on the same track
    outer, inner = by_name["t::outer"], by_name["t::inner"]
    assert outer["tid"] == inner["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1


def test_trace_ring_is_bounded():
    trace.clear()
    cap = trace.capacity()
    for i in range(cap + 500):
        trace.instant("bound::mark", i=i)
    assert trace.event_count() <= cap
    trace.clear()
    assert trace.event_count() == 0


def test_trace_dump_loads_in_perfetto_format(tmp_path):
    trace.clear()
    with trace.span("dumped::span"):
        pass
    path = trace.dump(str(tmp_path / "chrome_trace.json"))
    with open(path) as f:
        data = json.load(f)
    assert isinstance(data["traceEvents"], list)
    assert any(e["name"] == "dumped::span" and e["ph"] == "X"
               for e in data["traceEvents"])


def test_trace_dead_thread_rings_pruned():
    """Thread churn must not grow the ring registry without bound:
    dead threads' rings are pruned past a small retained tail."""
    trace.clear()

    def emit():
        trace.instant("churn::mark")

    for _ in range(64):                   # 64 short-lived threads
        t = threading.Thread(target=emit)
        t.start()
        t.join()
    # force a prune by registering one more ring from a fresh thread
    t = threading.Thread(target=emit)
    t.start()
    t.join()
    with trace._registry_lock:
        dead = sum(1 for th, _, _ in trace._rings if not th.is_alive())
    assert dead <= trace._MAX_DEAD_RINGS + 1
    # recent dead threads' events are still flushable
    assert any(e["name"] == "churn::mark"
               for e in trace.chrome_trace()["traceEvents"])
    trace.clear()


def test_serving_metrics_close_unregisters_series():
    from mxnet_tpu.serving.metrics import ServingMetrics

    m = ServingMetrics()
    m.record_batch(4, rows=3, n_requests=2, seconds=0.01)
    m.record_shed("queue_full")
    fam = telemetry.REGISTRY.get("mx_serving_requests_total")
    assert any(v[0] == m.server_id for v, _ in fam.collect())
    m.close()
    for name in ("mx_serving_requests_total", "mx_serving_batches_total",
                 "mx_serving_rows_total",
                 "mx_serving_request_latency_seconds",
                 "mx_serving_shed_total"):
        fam = telemetry.REGISTRY.get(name)
        assert not any(v[0] == m.server_id for v, _ in fam.collect()), name


# -- step-health monitor ------------------------------------------------------

class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_step_monitor_slow_step_detection():
    clock = _FakeClock()
    mon = telemetry.StepMonitor(slow_factor=2.0, alpha=0.5,
                                warmup_steps=3, warn_interval_s=100.0,
                                clock=clock)
    for _ in range(5):
        assert mon.observe_step(0.1) == []
    before = mon._anomalies.labels(kind="slow_step").value
    assert mon.observe_step(0.5) == ["slow_step"]
    assert mon.anomaly_counts["slow_step"] == 1
    assert mon._anomalies.labels(kind="slow_step").value == before + 1
    # the outlier re-baselines the EWMA: a second same-size step is fine
    assert mon.observe_step(0.5) == []
    # legacy mirror rides profiler.dumps
    payload = json.loads(mx.profiler.dumps(format="json"))
    assert payload["counters"]["telemetry::anomalies"] >= 1


def test_step_monitor_warmup_suppresses():
    mon = telemetry.StepMonitor(slow_factor=2.0, warmup_steps=10,
                                clock=_FakeClock())
    assert mon.observe_step(0.001) == []
    assert mon.observe_step(10.0) == []      # still warming up
    assert mon.anomaly_counts == {}


def test_step_monitor_warning_rate_limited(caplog):
    clock = _FakeClock()
    mon = telemetry.StepMonitor(slow_factor=2.0, alpha=0.0,
                                warmup_steps=0, warn_interval_s=60.0,
                                clock=clock)
    mon.observe_step(0.1)
    with caplog.at_level("WARNING", logger="mxnet_tpu.telemetry"):
        for _ in range(5):
            mon.observe_step(1.0)        # alpha=0: EWMA stays 0.1
        assert mon.anomaly_counts["slow_step"] == 5
        emitted = [r for r in caplog.records if "slow step" in r.message]
        assert len(emitted) == 1         # rate-limited to one per window
        clock.t += 61.0
        mon.observe_step(1.0)
        emitted = [r for r in caplog.records if "slow step" in r.message]
        assert len(emitted) == 2
        assert "suppressed" in emitted[-1].getMessage()


def test_warn_rate_limited_concurrent_exactly_once(caplog):
    """ISSUE 5 satellite: N threads racing the same key inside one
    window emit EXACTLY one warning; every suppressed call is still
    counted and reported on the next window's line."""
    import logging

    from mxnet_tpu import log as mxlog

    logger = logging.getLogger("rate_limit_hammer")
    key = "hammer:%d" % id(object())
    n_threads, n_calls = 8, 200
    results = []
    barrier = threading.Barrier(n_threads)

    def hammer():
        barrier.wait()
        mine = []
        for _ in range(n_calls):
            mine.append(mxlog.warn_rate_limited(
                logger, key, 60.0, "storm warning", now=10.0))
        results.append(mine)

    with caplog.at_level("WARNING", logger="rate_limit_hammer"):
        threads = [threading.Thread(target=hammer)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        emitted = [r for r in caplog.records
                   if "storm warning" in r.getMessage()]
        assert len(emitted) == 1                 # exactly once
        flat = [r for rs in results for r in rs]
        assert flat.count(True) == 1             # one caller won
        # next window: the one emission reports every suppressed call
        assert mxlog.warn_rate_limited(
            logger, key, 60.0, "storm warning", now=80.0) is True
        tail = [r for r in caplog.records
                if "storm warning" in r.getMessage()][-1].getMessage()
        assert "+%d suppressed" % (n_threads * n_calls - 1) in tail


def test_step_monitor_recompile_detection():
    class FakeOp:
        on_trace = None
        _op = None

    op = FakeOp()
    hits = []
    op.on_trace = lambda o: hits.append(o)   # pre-existing hook chains
    mon = telemetry.StepMonitor(expected_traces=1, clock=_FakeClock())
    mon.attach(op)
    op.on_trace(op)                          # warmup compile: expected
    assert mon.anomaly_counts.get("recompile", 0) == 0
    op.on_trace(op)                          # retrace: anomaly
    op.on_trace(op)
    assert mon.anomaly_counts["recompile"] == 2
    assert len(hits) == 3                    # original hook kept firing


def test_step_monitor_recompile_on_real_cached_op():
    from mxnet_tpu.cached_op import CachedOp

    op = CachedOp(lambda x: x * 2.0)
    mon = telemetry.StepMonitor(expected_traces=1, clock=_FakeClock())
    mon.attach(op)
    a = op(mx.nd.ones((2, 2)))
    a.wait_to_read()
    assert mon.anomaly_counts.get("recompile", 0) == 0
    b = op(mx.nd.ones((3, 3)))               # new shape → retrace
    b.wait_to_read()
    assert mon.anomaly_counts["recompile"] == 1


def test_step_monitor_checkpoint_backlog():
    class FakeManager:
        pending = 0

    mgr = FakeManager()
    mon = telemetry.StepMonitor(checkpoint_backlog=2, warmup_steps=0,
                                clock=_FakeClock())
    mon.watch_checkpoint(mgr)
    assert mon.observe_step(0.1) == []
    mgr.pending = 3
    assert "checkpoint_backlog" in mon.observe_step(0.1)
    assert mon.anomaly_counts["checkpoint_backlog"] == 1
    snap = mon.snapshot()
    assert snap["steps"] == 2 and snap["ewma_ms"] > 0


def test_step_monitor_step_context_manager():
    clock = _FakeClock()
    mon = telemetry.StepMonitor(clock=clock)
    with mon.step(0):
        clock.t += 0.25
    assert mon.ewma_seconds == pytest.approx(0.25)
    assert mon.steps == 1


# -- cross-subsystem integration ---------------------------------------------

def test_serving_and_checkpoint_share_registry(tmp_path):
    """Acceptance: serving stats and checkpoint counters all read
    through the one telemetry registry."""
    from mxnet_tpu import serving
    from mxnet_tpu.checkpoint import CheckpointManager

    w = mx.nd.array(np.eye(4, dtype=np.float32))
    srv = serving.InferenceServer(lambda wp, x: mx.nd.dot(x, wp), [w],
                                  item_shape=(4,), buckets=(2,),
                                  max_delay_ms=0)
    try:
        srv.predict(np.ones((2, 4), np.float32))
    finally:
        srv.shutdown()
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"a": np.ones((4, 4), np.float32)}, sync=True)
    mgr.close()

    text = telemetry.render_prometheus()
    assert "mx_serving_requests_total" in text
    assert "mx_serving_request_latency_seconds_bucket" in text
    assert 'mx_profiler_counter{name="checkpoint::bytes"}' in text
    assert "mx_cachedop_compiles_total" in text
    payload = json.loads(mx.profiler.dumps(format="json"))
    assert payload["counters"]["serving::requests"] >= 1
    assert payload["counters"]["checkpoint::bytes"] > 0
    # srv.stats() is a view over the same registry children
    sid = srv.metrics.server_id
    fam = telemetry.REGISTRY.get("mx_serving_requests_total")
    mine = {v: c for v, c in fam.collect() if v[0] == sid}
    assert sum(c.value for c in mine.values()) \
        == sum(b["requests"] for b in srv.stats()["buckets"].values())


def test_chrome_trace_spans_all_three_layers(tmp_path):
    """Acceptance: one captured chrome_trace.json holds spans from the
    train-step, serving, and checkpoint layers, and parses as
    trace-event JSON."""
    from mxnet_tpu import gluon, serving
    from mxnet_tpu.checkpoint import CheckpointManager
    from mxnet_tpu.parallel import TrainStep, make_mesh

    trace.clear()
    mx.random.seed(7)
    net = gluon.nn.HybridSequential(prefix="ttel_")
    net.add(gluon.nn.Dense(8, in_units=4, prefix="d1_"))
    net.add(gluon.nn.Dense(2, in_units=8, prefix="d2_"))
    net.initialize()
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                     optimizer="sgd",
                     optimizer_params={"learning_rate": 0.1},
                     mesh=make_mesh())
    x = np.random.RandomState(0).rand(8, 4).astype(np.float32)
    y = np.array([0, 1, 1, 0, 1, 0, 0, 1])
    float(np.asarray(step(x, y)))

    w = mx.nd.array(np.eye(4, dtype=np.float32))
    srv = serving.InferenceServer(lambda wp, xb: mx.nd.dot(xb, wp), [w],
                                  item_shape=(4,), buckets=(1,),
                                  max_delay_ms=0)
    try:
        srv.predict(np.ones((1, 4), np.float32))
    finally:
        srv.shutdown()

    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    mgr.save(1, step.state_dict(), sync=True)
    mgr.close()

    path = trace.dump(str(tmp_path / "chrome_trace.json"))
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    names = {e["name"] for e in events}
    assert any(n.startswith("train_step::") for n in names), names
    assert any(n.startswith("serving::") for n in names), names
    assert any(n.startswith("checkpoint::") for n in names), names
    for event in events:
        for key in ("ph", "ts", "pid", "tid"):
            assert key in event
