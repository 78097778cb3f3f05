"""What the host did, always on (ISSUE 39 (d)): `host::gc` spans from the
collector's callback, the stepping thread's CPU time and involuntary
switches on `train_step::step`, and the profiler's "Host" section."""
import gc
import json
import threading

import jax
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.parallel import TrainStep, make_mesh
from mxnet_tpu.parallel import train_step as ts_mod
from mxnet_tpu.telemetry import device_table as dt
from mxnet_tpu.telemetry import trace


def _events(name):
    return [e for e in trace.chrome_trace()["traceEvents"]
            if e["name"] == name]


def _collect(monkeypatch, seconds, generation, collected=7):
    """One collection of `seconds` by an injected clock."""
    clock = iter([100.0, 100.0 + seconds])
    monkeypatch.setattr(trace, "_gc_clock", lambda: next(clock))
    trace._on_gc("start", {"generation": generation})
    trace._on_gc("stop", {"generation": generation,
                          "collected": collected, "uncollectable": 0})


def test_gc_spans_by_generation_and_length(monkeypatch):
    trace.clear()
    trace.instant("ring::exists")         # this thread has a ring now
    child = {g: trace._gc_children[g] for g in range(3)}
    before = {g: child[g].value for g in range(3)}
    _collect(monkeypatch, 0.0002, 0)      # short and young: no span
    _collect(monkeypatch, 0.0002, 1)
    assert _events("host::gc") == []
    _collect(monkeypatch, 0.0002, 2)      # every full collection
    _collect(monkeypatch, 0.0040, 0, collected=11)    # any long one
    spans = _events("host::gc")
    assert [(e["args"]["generation"], e["args"]["collected"])
            for e in spans] == [(2, 7), (0, 11)]
    assert [round(e["dur"]) for e in spans] == [200, 4000]
    assert all(e["ts"] == 100.0 * 1e6 for e in spans)
    got = {g: child[g].value - before[g] for g in range(3)}
    assert abs(got[2] - 0.0002) < 1e-9 and abs(got[0] - 0.004) < 1e-9
    assert got[1] == 0
    # a "stop" with no "start" (the callback registered mid-collection)
    trace._on_gc("stop", {"generation": 2, "collected": 0})
    assert len(_events("host::gc")) == 2


def test_gc_on_a_thread_without_a_ring_counts_and_takes_no_lock(
        monkeypatch):
    before = trace._gc_children[2].value
    n = len(_events("host::gc"))
    done = []

    def run():
        with trace._registry_lock:        # as under chrome_trace()
            _collect(monkeypatch, 0.5, 2)
        done.append(True)

    worker = threading.Thread(target=run)
    worker.start()
    worker.join(timeout=10)
    assert done == [True] and not worker.is_alive()
    assert abs(trace._gc_children[2].value - before - 0.5) < 1e-9
    assert len(_events("host::gc")) == n


def test_a_real_full_collection_is_in_the_ring():
    assert trace._on_gc in gc.callbacks
    trace.clear()
    trace.instant("ring::exists")
    gc.collect()
    spans = _events("host::gc")
    assert spans and spans[-1]["args"]["generation"] == 2
    assert spans[-1]["dur"] > 0


def _step():
    net = gluon.nn.Dense(4, in_units=12)
    net.initialize()
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                     optimizer="sgd", optimizer_params={"learning_rate": .1},
                     mesh=make_mesh({"dp": -1}, devices=jax.devices()[:1]))
    x = np.random.RandomState(0).rand(8, 12).astype("float32")
    y = np.arange(8, dtype="float32") % 4
    return step, x, y


def test_step_event_carries_cpu_time_and_switches(monkeypatch):
    step, x, y = _step()
    step(x, y)
    # an injected clock: each reading 2 ms of CPU and 3 switches later
    state = {"cpu": 10.0, "sw": 100}

    def usage():
        state["cpu"] += 0.002
        state["sw"] += 3
        return state["cpu"], state["sw"]

    monkeypatch.setattr(ts_mod, "_host_usage", usage)
    step._host_mark = None                # first reading: the call's start
    trace.clear()
    total = ts_mod._switches_total.value
    step(x, y)
    step(x, y)
    events = _events("train_step::step")
    assert [e["args"]["switches"] for e in events] == [3, 3]
    assert all(abs(e["args"]["cpu_ms"] - 2.0) < 1e-6 for e in events)
    assert ts_mod._switches_total.value == total + 6
    # another thread's readings are not this thread's: the mark starts anew
    other = threading.Thread(target=step, args=(x, y))
    other.start()
    other.join(timeout=60)
    assert not other.is_alive()
    assert _events("train_step::step")[-1]["args"]["switches"] == 3


def test_the_real_readings_are_there_and_sane():
    step, x, y = _step()
    trace.clear()
    for _ in range(3):
        step(x, y)
    for e in _events("train_step::step"):
        assert e["args"]["cpu_ms"] >= 0 and e["args"]["switches"] >= 0
        assert isinstance(e["args"]["switches"], int)


def _x(name, start_ms, dur_ms, **args):
    return {"ph": "X", "name": name, "ts": start_ms * 1e3,
            "dur": dur_ms * 1e3, "args": args}


def test_host_table_names_the_long_interval():
    ring = []
    t = 0.0
    for i in range(1, 9):
        gap = 60.0 if i == 5 else 10.0    # step 5 came 60 ms after step 4
        t += gap
        ring.append(_x("train_step::dispatch", t - 2.0, 2.0, step=i))
        ring.append(_x("train_step::step", t - 3.0, 3.05, step=i,
                       cpu_ms=gap * (0.1 if i == 5 else 0.9),
                       switches=4 if i == 5 else 0))
    ring.append(_x("host::gc", 42.0, 45.0, generation=2, collected=9))
    ring.append({"ph": "i", "name": "marker", "ts": 1.0})
    host = dt.host_table(ring)
    assert host["intervals"] == 7 and host["median_ms"] == 10.0
    worst = host["longest"][0]
    assert worst["step"] == 5 and abs(worst["ms"] - 60.0) < 1e-9
    assert abs(worst["gc_ms"] - 45.0) < 1e-9
    assert abs(worst["cpu_pct"] - 10.0) < 1e-9 and worst["switches"] == 4
    assert [i["gc_ms"] for i in host["longest"][1:]] == [0.0] * 4
    assert len(host["most_over_peers"]) == dt._LONGEST_INTERVALS == 5
    assert host["gc"] == {"collections": 1, "ms": 45.0, "longest_ms": 45.0}
    late = host["most_over_peers"][0]
    assert late["step"] == 5 and abs(late["over_peers_ms"] - 50.0) < 1e-9
    text = dt.render_host(host)
    assert "median 10.000 ms" in text and "gc   45.000 ms" in text
    assert dt.host_table(ring[:2]) is None          # one dispatch: nothing


def test_host_table_ranks_an_interval_against_its_own_kind():
    """A loop that reads its loss every fourth step: short intervals
    between dispatches, long ones across a read. One read came back 19 ms
    late: not the longest by much, but the one most over its peers."""
    ring, t = [], 0.0
    for i in range(1, 18):
        gap = 3.0 if i % 4 else (1286.0 if i == 12 else 1267.0)
        t += gap
        ring.append(_x("train_step::dispatch", t - 2.0, 2.0, step=i))
        ring.append(_x("train_step::step", t - 2.5, 2.55, step=i,
                       cpu_ms=1.0, switches=0))
    host = dt.host_table(ring)
    assert host["median_ms"] == 3.0
    assert [i["step"] for i in host["longest"]][:2] == [12, 4]
    late = host["most_over_peers"][0]
    assert late["step"] == 12 and abs(late["over_peers_ms"] - 19.0) < 1e-9
    assert abs(host["most_over_peers"][1]["over_peers_ms"]) < 1e-9
    assert host["gc"]["collections"] == 0
    assert "most over their peers" in dt.render_host(host)


def test_dumps_has_a_host_section_without_a_capture():
    step, x, y = _step()
    trace.clear()
    for _ in range(4):
        step(x, y)
    text = mx.profiler.dumps()
    assert "Host (3 intervals between train_step::dispatch ends" in text
    assert "involuntary switches" in text
    data = json.loads(mx.profiler.dumps(format="json"))
    assert data["host"]["intervals"] == 3
    assert len(data["host"]["longest"]) == 3
    assert data["device"] is None                   # no capture was made
