"""The metrics that read the program's own instrumentation (ISSUE 25):
the two readers on hand-made input, and a traced CPU run of all three
cells that prints every such metric. Fixture and helpers are those of
test_harness_cpu.py (tiny sizes in a temporary copy); no import here
touches the TPU library.
"""
import json
import os
import sys

import pytest

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)
from test_harness_cpu import _ROOT, _load, _run, root  # noqa: E402,F401

from chipbench import harness  # noqa: E402

_GLUON = "resnet50-gluon-b32"
_SPAN_METRICS = {
    "forward_host_ms.train": "cached_op::execute",
    "backward_host_ms.train": "autograd::backward",
    "backward_vjp_ms.train": "autograd::vjp",
    "backward_commit_ms.train": "autograd::commit",
    "update_host_ms.train": "trainer::step",
}
_LOG_METRICS = ["trace_lower_s.setup", "xla_build_s.setup",
                "programs_built.setup", "programs_compiled.setup"]


def _reader(name):
    return harness.load_module(_ROOT, "readers", name)


def test_program_span_per_step_sums_over_the_windows_steps():
    read = _reader("program_span_per_step").read
    run = {"program_spans_ms": {"a::b": [1.0, 2.0, 3.0, 6.0]},
           "trace": {"steps": 2}}
    assert read(run, span="a::b") == 6.0            # 12 ms over 2 steps
    assert read(run, span="not::there") is None     # the parent has none
    assert read(dict(run, trace=None), span="a::b") is None
    assert read(dict(run, trace={"steps": 0}), span="a::b") is None


def test_compile_log_reader_filters_and_sums(monkeypatch):
    from mxnet_tpu import compile as cc
    from mxnet_tpu.compile.buildlog import Record

    def rec(kind, seconds, step, outcome="", inner=False):
        return Record(kind, "f", outcome, 0.0, seconds, step, inner)

    log = [rec("trace", 1.0, 0), rec("trace", 0.25, 0, inner=True),
           rec("lower", 0.5, 1), rec("build", 2.0, 2, "hit"),
           rec("build", 4.0, 2, "uncached"), rec("build", 8.0, 2, "miss"),
           rec("trace", 16.0, 3), rec("build", 32.0, 5, "uncached")]
    monkeypatch.setattr(cc, "build_log", lambda: log)
    read = _reader("compile_log").read
    assert read({}, kind=["trace", "lower"], what="seconds",
                before_step=3) == 1.5
    assert read({}, kind="build", what="seconds", before_step=3) == 14.0
    assert read({}, kind="build", what="count", before_step=3) == 3.0
    assert read({}, kind="build", what="count", before_step=3,
                outcome_not="hit") == 2.0
    assert read({}, kind="build", what="count", before_step=99) == 4.0
    # a program without the log (the parent commit): nothing, no raise
    monkeypatch.delattr(cc, "build_log")
    assert read({}, kind="build", what="count", before_step=3) is None


@pytest.mark.parametrize("cell", ["resnet50-train-b256",
                                  "ptb-lstm-train-b1024", _GLUON])
def test_traced_cell_prints_the_programs_metrics(root, cell, capsys):  # noqa: F811
    """Every metric of ISSUE 25's table that applies to the cell is in
    the traced line with a value, and the relations between them hold:
    the parts of backward() do not exceed it, compiles do not exceed
    builds, and the compile log's seconds fit inside set-up."""
    from mxnet_tpu.compile import buildlog

    buildlog.clear()            # the log and its step count are the process's
    result, lines = _run(root, cell, trace=1)
    assert result["correct"] is True, lines[-2:]
    got = {k: v["value"] for k, v in result["metrics"].items()}
    setup_s = lines[0]["setup_s"]
    mine = sorted(set(_SPAN_METRICS) | set(_LOG_METRICS)
                  | {"dispatch_ms.train", "imperative_step_ms.train"})
    with capsys.disabled():
        print("\n%s: setup_s %.3f %s" % (cell, setup_s, json.dumps(
            {k: round(got[k], 4) for k in mine if k in got})))

    assert set(_LOG_METRICS) <= set(got)
    assert got["programs_built.setup"] >= 1
    assert got["programs_compiled.setup"] <= got["programs_built.setup"]
    assert got["trace_lower_s.setup"] > 0 and got["xla_build_s.setup"] > 0
    assert got["trace_lower_s.setup"] + got["xla_build_s.setup"] < setup_s
    spans = lines[-1]["program_spans_mean_ms"]
    if cell == _GLUON:
        assert set(_SPAN_METRICS) <= set(got)
        assert "dispatch_ms.train" not in got
        assert got["backward_vjp_ms.train"] \
            + got["backward_commit_ms.train"] \
            <= got["backward_host_ms.train"]
        parts = got["forward_host_ms.train"] \
            + got["backward_host_ms.train"] + got["update_host_ms.train"]
        # what is left is the loss's eager ops, a large share of a step
        # this small; on the chip the three are held to 5 %
        assert 0.5 * got["imperative_step_ms.train"] < parts \
            <= got["imperative_step_ms.train"]
        assert {"trainer::update", "trainer::fused_apply"} <= set(spans)
    else:
        assert got["dispatch_ms.train"] > 0
        assert not set(_SPAN_METRICS) & set(got)
        assert got["dispatch_ms.train"] <= got["host_step_ms.train"]
    # a compile inside set-up is in the ring under its executable's name
    assert "xla::build" not in spans        # none inside the window


def test_new_metric_files_agree_with_their_readers():
    """Each new metric's file names a reader that exists and the span or
    log arguments ISSUE 25's table gives."""
    for name, span in _SPAN_METRICS.items():
        spec = _load(os.path.join(_ROOT, "chipbench", "layer_metrics",
                                  name + ".json"))
        assert spec["reader"] == "program_span_per_step"
        assert spec["args"] == {"span": span} and spec["cells"] == [_GLUON]
    for name in _LOG_METRICS:
        spec = _load(os.path.join(_ROOT, "chipbench", "layer_metrics",
                                  name + ".json"))
        assert spec["reader"] == "compile_log" and "cells" not in spec
        assert spec["args"]["before_step"] == 3
        assert spec["moves"] == "setup_s" and spec["layer"] == "compile"
