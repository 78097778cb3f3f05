"""Profiler, Monitor, visualization, util, name — SURVEY §5.1/§5.5
subsystems (reference tests: test_profiler.py, monitor usage in
test_monitor-ish flows)."""
import glob
import os
import tempfile

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import profiler


def test_profiler_trace_and_aggregate():
    with tempfile.TemporaryDirectory() as d:
        trace_dir = os.path.join(d, "prof")
        profiler.set_config(filename=trace_dir, aggregate_stats=True)
        profiler.set_state("run")
        a = mx.nd.ones((32, 32))
        for _ in range(3):
            a = mx.nd.dot(a, a) * 0.01
        a.wait_to_read()
        profiler.set_state("stop")
        stats = profiler.dumps()
        assert "dot" in stats and "Calls" in stats
        # device trace written (xplane/tensorboard layout)
        produced = glob.glob(os.path.join(trace_dir, "**", "*"),
                             recursive=True)
        assert produced, "no trace output in %s" % trace_dir


def test_profiler_pause_resume():
    profiler.dumps(reset=True)
    profiler.set_state("run")
    profiler.pause()
    b = mx.nd.ones((4, 4)).exp()
    b.wait_to_read()
    profiler.resume()
    c = mx.nd.ones((4, 4)).tanh()
    c.wait_to_read()
    profiler.set_state("stop")
    stats = profiler.dumps(reset=True)
    assert "tanh" in stats
    assert "exp" not in stats


def test_profiler_domains_counters():
    dom = profiler.Domain("test_domain")
    counter = dom.new_counter("ops_done", 0)
    counter.increment(5)
    task = dom.new_task("phase1")
    profiler.set_state("run")
    with task:
        mx.nd.ones((2, 2)).sum().wait_to_read()
    profiler.set_state("stop")
    stats = profiler.dumps()
    assert "test_domain::ops_done" in stats


def test_profiler_dumps_json_format():
    """dumps(format='json') returns the aggregate stats machine-readable
    (the bench harness and serving dashboards consume this)."""
    import json

    import pytest

    profiler.dumps(reset=True)
    dom = profiler.Domain("jsontest")
    dom.new_counter("widgets", 7)
    profiler.set_state("run")
    x = mx.nd.ones((8, 8)).tanh()
    x.wait_to_read()
    profiler.set_state("stop")

    payload = json.loads(profiler.dumps(format="json"))
    assert set(payload) == {"trace_dir", "ops", "counters", "device",
                            "host"}
    assert payload["device"] is None      # the CPU's capture: no device line
    tanh_keys = [k for k in payload["ops"] if "tanh" in k]
    assert tanh_keys, sorted(payload["ops"])
    st = payload["ops"][tanh_keys[0]]
    assert st["calls"] >= 1
    assert 0 <= st["min_ms"] <= st["max_ms"] <= st["total_ms"] + 1e-9
    assert payload["counters"]["jsontest::widgets"] == 7

    # reset through the json path clears op stats like the table path
    json.loads(profiler.dumps(format="json", reset=True))
    assert not json.loads(profiler.dumps(format="json"))["ops"]
    with pytest.raises(ValueError):
        profiler.dumps(format="xml")


def test_dumps_json_includes_histogram_percentiles():
    """ISSUE 5 satellite schema regression: the histogram-derived
    p50/p99 the table shows must ride the JSON payload too."""
    import json

    profiler.dumps(reset=True)
    for ms in (1, 1, 1, 1, 50):
        profiler.record_op_span("pctl_op", ms / 1e3)
    payload = json.loads(profiler.dumps(format="json"))
    st = payload["ops"]["pctl_op"]
    assert set(st) == {"calls", "total_ms", "min_ms", "max_ms",
                       "p50_ms", "p99_ms"}
    assert st["min_ms"] <= st["p50_ms"] <= st["p99_ms"] <= st["max_ms"]
    assert st["p99_ms"] > st["p50_ms"]      # the outlier shows up
    # the table renders the same columns
    table = profiler.dumps()
    header = table.splitlines()[1]
    assert "P50(ms)" in header and "P99(ms)" in header
    profiler.dumps(reset=True)


def test_dumps_reset_keeps_counters():
    """Pinned behavior (ISSUE 3 satellite): dumps(reset=True) clears the
    per-op dispatch stats but NOT user-defined Counters — they are live
    process-global gauges (checkpoint::pending, serving::requests)
    shared across subsystems."""
    import json

    dom = profiler.Domain("resetpin")
    dom.new_counter("kept", 11)
    profiler.record_op_span("resetpin_op", 0.001)
    payload = json.loads(profiler.dumps(format="json", reset=True))
    assert payload["ops"]["resetpin_op"]["calls"] == 1
    after = json.loads(profiler.dumps(format="json"))
    assert "resetpin_op" not in after["ops"]
    assert after["counters"]["resetpin::kept"] == 11
    # the table path resets identically
    profiler.record_op_span("resetpin_op", 0.001)
    profiler.dumps(reset=True)
    table = profiler.dumps()
    assert "resetpin_op" not in table
    assert "resetpin::kept" in table


def test_dump_finished_false_keeps_profiler_usable():
    """dump(finished=False) flushes a chrome-trace snapshot but leaves
    the profiler running (reference semantics: the `finished` argument
    was previously accepted and ignored); dump() with the default
    finished=True stops it."""
    import json

    with tempfile.TemporaryDirectory() as d:
        trace_dir = os.path.join(d, "prof")
        profiler.set_config(filename=trace_dir)
        profiler.set_state("run")
        try:
            mx.nd.ones((4, 4)).tanh().wait_to_read()
            profiler.dump(finished=False)
            assert profiler.is_recording()          # still usable
            path = os.path.join(trace_dir, "chrome_trace.json")
            assert os.path.isfile(path)
            with open(path) as f:
                data = json.load(f)
            assert isinstance(data["traceEvents"], list)
            mx.nd.ones((4, 4)).exp().wait_to_read() # records after dump
            assert "exp" in profiler.dumps()
            profiler.dump()                         # finished=True
            assert not profiler.is_recording()
        finally:
            profiler.set_state("stop")
        profiler.set_config(filename="profile_output")


def test_profiler_events_bounded():
    """Task/Frame/Marker events land in the bounded telemetry trace
    rings — the old module-level `_events` list (appended without a lock
    and never drained: a leak in any long-running server) is gone."""
    from mxnet_tpu.telemetry import trace

    assert not hasattr(profiler, "_events")
    trace.clear()        # other suites' worker threads left events
    dom = profiler.Domain("bounded")
    marker = dom.new_marker("tick")
    cap = trace.capacity()
    for _ in range(cap + 500):
        marker.mark()
    # this thread's ring is full at cap; other registered (now idle)
    # thread rings were cleared above, so the global count stays bounded
    assert trace.event_count() <= cap
    with dom.new_task("work"):
        pass
    names = [e["name"] for e in trace.chrome_trace()["traceEvents"]]
    assert "bounded::tick" in names and "bounded::work" in names
    trace.clear()


def test_monitor_collects_stats():
    from mxnet_tpu.monitor import Monitor

    data = mx.sym.var("data")
    fc = mx.sym.FullyConnected(data, num_hidden=4, name="fc1")
    out = mx.sym.softmax(fc, name="sm")
    ex = out.bind(mx.cpu(), {"data": mx.nd.ones((2, 3)),
                             "fc1_weight": mx.nd.ones((4, 3)),
                             "fc1_bias": mx.nd.zeros((4,))})
    mon = Monitor(interval=1, pattern=".*")
    mon.install(ex)
    mon.tic()
    ex.forward()
    res = mon.toc()
    assert res, "monitor collected nothing"
    names = [r[1] for r in res]
    assert any("output" in n for n in names)


def test_print_summary_and_plot(capsys):
    data = mx.sym.var("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
    total = mx.viz.print_summary(net, shape={"data": (1, 16)})
    cap = capsys.readouterr().out
    assert "fc1" in cap and "Total params" in cap
    # 16*8+8 + 8*2+2 = 154
    assert total == 154
    dot = mx.viz.plot_network(net)
    src = dot if isinstance(dot, str) else dot.source
    assert "fc1" in src and "->" in src


def test_util_and_name():
    from mxnet_tpu import util

    assert util.get_gpu_count() >= 0
    with mx.name.Prefix("scope_"):
        s = mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=2)
        assert s.name.startswith("scope_")
    s2 = mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=2)
    assert not s2.name.startswith("scope_")
