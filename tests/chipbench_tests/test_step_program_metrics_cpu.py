"""The four metrics of ISSUE 39 that read what the program says of
itself: the step executable's memory (`step_program_gb.train`,
`step_program_temp_gb.train`), the collector's pauses
(`gc_pause_ms.train`) and the stepping thread's involuntary switches
(`host_switches_per_step.train`). Their files against `BENCHMARK.json`,
each reader on a program that lacks its family (the parent commit), and
a traced CPU run of two cells that prints all four. Fixture and helpers
are test_harness_cpu.py's; no import here touches the TPU library.
"""
import json
import os
import sys

import pytest

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)
from test_harness_cpu import _ROOT, _load, _run, root  # noqa: E402,F401

from chipbench import harness  # noqa: E402

_METRICS = {
    "step_program_gb.train": (
        "device", "GB", "program_counter", "program_metric",
        {"name": "mx_step_program_bytes", "scale": 1e-9}),
    "step_program_temp_gb.train": (
        "device", "GB", "program_counter", "program_metric",
        {"name": "mx_step_program_temp_bytes", "scale": 1e-9}),
    "gc_pause_ms.train": (
        "whole-step training", "ms", "program_span", "program_pause_ms",
        {"span": "host::gc", "family": "mx_gc_pause_seconds_total"}),
    "host_switches_per_step.train": (
        "whole-step training", "count", "program_counter", "program_metric",
        {"name": "mx_train_step_involuntary_switches_total",
         "over": "mx_train_steps_total"}),
}


def _spec(name):
    return _load(os.path.join(_ROOT, "chipbench", "layer_metrics",
                              name + ".json"))


@pytest.mark.parametrize("name", sorted(_METRICS))
def test_metric_file_agrees_with_benchmark_json(name):
    layer, unit, source, reader, args = _METRICS[name]
    spec = _spec(name)
    declared = [m for m in harness.load_bench(_ROOT)["per_layer"]
                if m["name"] == name]
    assert len(declared) == 1
    entry = declared[0]
    for key, want in (("layer", layer), ("unit", unit), ("source", source),
                      ("better", "lower"), ("moves", "train_rate")):
        assert spec[key] == want == entry[key], key
    assert spec["reader"] == reader and spec["args"] == args
    # no list of cells: a later cell reports them with no edit
    assert "cells" not in spec and "workloads" not in entry
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves"}
    assert os.path.exists(os.path.join(_ROOT, "chipbench", "readers",
                                       reader + ".py"))


@pytest.mark.parametrize("name", sorted(_METRICS))
def test_reader_returns_none_on_a_program_without_its_family(
        name, monkeypatch):
    """The parent's program has none of the four families: the reader
    finds nothing, says so, and does not raise."""
    from mxnet_tpu.telemetry import metrics

    bare = metrics.Registry()
    bare.counter("mx_train_steps_total").inc(30)
    monkeypatch.setattr(metrics, "REGISTRY", bare)
    spec = _spec(name)
    read = harness.load_module(_ROOT, "readers", spec["reader"]).read
    run = {"program_spans_ms": {"host::gc": [3.0]}, "trace": {"steps": 30}}
    assert read(run, **spec["args"]) is None


def test_pause_reader_reads_zero_only_where_pauses_are_recorded(monkeypatch):
    from mxnet_tpu.telemetry import metrics

    read = harness.load_module(_ROOT, "readers", "program_pause_ms").read
    args = _spec("gc_pause_ms.train")["args"]
    has = metrics.Registry()
    has.counter("mx_gc_pause_seconds_total", labels=("generation",))
    monkeypatch.setattr(metrics, "REGISTRY", has)
    run = {"program_spans_ms": {}, "trace": {"steps": 30}}
    assert read(run, **args) == 0.0       # no pause fell in the window
    run["program_spans_ms"]["host::gc"] = [45.0, 15.0]
    assert read(run, **args) == 2.0       # 60 ms over 30 steps
    assert read(dict(run, trace=None), **args) is None
    assert read(dict(run, trace={"steps": 0}), **args) is None


@pytest.mark.parametrize("cell", ["resnet50-train-b256",
                                  "kanana2-30b-a3b-train-s4096"])
def test_traced_cell_prints_the_four(root, cell, capsys):  # noqa: F811
    """All four are in the traced line; the program's bytes are the
    executable's own (arguments at least, temporaries inside the whole),
    and asking for them built no program and compiled nothing inside the
    window."""
    from mxnet_tpu.compile import build_log, buildlog
    from mxnet_tpu.parallel import train_step as ts_mod

    buildlog.clear()
    recompiled = ts_mod._program_recompiled.value
    result, lines = _run(root, cell, trace=1)
    assert result["correct"] is True, lines[-2:]
    assert lines[-2]["compiles_in_window"] == 0
    got = {k: v["value"] for k, v in result["metrics"].items()}
    with capsys.disabled():
        print("\n%s: %s" % (cell, json.dumps(
            {k: got.get(k) for k in sorted(_METRICS)})))
    assert set(_METRICS) <= set(got)
    assert got["step_program_gb.train"] > got["step_program_temp_gb.train"] \
        > 0
    assert got["gc_pause_ms.train"] >= 0.0
    assert got["host_switches_per_step.train"] >= 0.0
    assert ts_mod._program_recompiled.value == recompiled
    # the readers' demand came after the third step and built nothing
    late = [r for r in build_log() if r.step >= 3 and not r.inner
            and "mx_train_step" in r.fun_name]
    assert [r.kind for r in late] in ([], ["trace"]), late
