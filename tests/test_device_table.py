"""`telemetry/device_table.py` (ISSUE 39 (c)) on a recorded capture:
`device_capture_cell3.json.gz` is a cut of one chip capture of
`kanana2-30b-a3b-train-s4096` (TPU v5e, PR 39, `tools/device_profile.py
--cut`): two launches of the step executable around a loss read, every
`XLA Ops` event with its instruction, the trace ring's events of that
stretch on the capture's clock, and the step executable's HLO text cut to
names (every `op_name` whole). Beside it, hand-made captures for what one
recording cannot hold."""
import gzip
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.parallel import TrainStep
from mxnet_tpu.parallel import train_step as ts_mod
from mxnet_tpu.telemetry import device_table as dt
from mxnet_tpu.telemetry import trace

_HERE = os.path.dirname(os.path.abspath(__file__))
_PLANE = "/device:TPU:0"


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(_HERE, "device_capture_cell3.json.gz"),
                   "rt") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def table(recorded):
    return dt.reduce_capture(recorded, recorded["ring"],
                             [recorded["program_text"]], depth=5)


def _ms(rows, key, name):
    found = [r for r in rows if r[key] == name]
    return found[0]["ms"] if found else 0.0


def test_the_sums_close(table):
    assert table["steps"] == 2 and table["devices"] == 1
    total = table["op_ms"]
    assert 110 < total < 130                     # cell 3: 119 ms a step
    for key in ("by_phase", "by_scope", "by_kernel"):
        assert abs(sum(r["ms"] for r in table[key]) - total) \
            < 1e-3 * total, key
        assert abs(sum(r["pct"] for r in table[key]) - 100.0) < 0.1
    assert abs(table["busy_ms"] + table["idle_ms"] - table["window_ms"]) \
        < 1e-9
    # ops that enclose others count their self time: no sum passes busy
    # by more than the asynchronous copies' overlap
    assert table["busy_ms"] <= total < 1.02 * table["busy_ms"]


def test_phases_and_the_combined_rows(table):
    phases = {r["phase"]: r["ms"] for r in table["by_phase"]}
    assert set(phases) <= {
        "+".join(p for p in dt.PHASES if p in combo)
        for combo in _subsets(dt.PHASES)}
    for phase in ("forward", "backward", "optimizer_update", "loss"):
        assert phases[phase] > 1.0, phase
    # Adam's update with a weight's gradient product fused into it
    assert phases["backward+optimizer_update"] > 10.0
    assert phases.get("unscoped", 0.0) < 0.02 * table["op_ms"]
    # every ms that touches the optimizer, beside Adam's fusions by kind
    touching = sum(ms for p, ms in phases.items()
                   if "optimizer_update" in p)
    adam = _ms(table["by_kernel"], "kernel", "divide_subtract_fusion")
    assert 27.0 < adam < 28.0 and touching >= adam


def _subsets(items):
    out = [[]]
    for item in items:
        out += [s + [item] for s in out]
    return [s for s in out if s]


def test_kernels_by_kind_with_the_pallas_names(table):
    kernels = {r["kernel"]: r for r in table["by_kernel"]}
    # PERF.md section 5, "after PR 38": 19.03, 9.86, 6.62 ms a step
    assert abs(kernels["mx_flash_bwd"]["ms"] - 19.03) < 0.03 * 19.03
    assert abs(kernels["mx_flash_fwd"]["ms"] - 9.86) < 0.03 * 9.86
    grouped = sum(kernels[k]["ms"] for k in ("mx_gmm", "mx_gmm_t",
                                             "mx_tgmm"))
    assert abs(grouped - 6.62) < 0.03 * 6.62
    assert kernels["mx_flash_bwd"]["calls"] == 5
    assert kernels["mx_tgmm"]["calls"] == 12
    # `.N` and `.clone` are no kinds of their own
    assert not [k for k in kernels if k[-1].isdigit() and "." in k]
    assert not [k for k in kernels if "clone" in k]


def test_scopes_keep_forward_and_backward_apart(table):
    scopes = {r["scope"]: r["ms"] for r in table["by_scope"]}
    attn = [s for s in scopes if s.endswith("self_attn/mla_attention")]
    fwd = sum(scopes[s] for s in attn if s.startswith("forward/"))
    bwd = sum(scopes[s] for s in attn if s.startswith("backward/"))
    assert len(attn) == 10 and 20 < fwd < 23 and 35 < bwd < 39
    assert all(len(s.split("/")) <= 6 for s in scopes)      # depth 5
    assert "optimizer_update" in scopes


def test_depth_cuts_the_paths(recorded):
    table = dt.reduce_capture(recorded, recorded["ring"],
                              [recorded["program_text"]], depth=1)
    scopes = {r["scope"] for r in table["by_scope"]}
    assert "forward/deepseekv30" in scopes
    assert all(len(s.split("/")) <= 2 for s in scopes)


def test_executables_by_name(table):
    rows = {r["executable"]: r for r in table["by_executable"]}
    assert rows["jit_mx_train_step"]["launches"] == 1.0
    assert 119 < rows["jit_mx_train_step"]["ms"] < 120
    assert "jit_convert_element_type" in rows


def test_the_long_gap_is_split_by_what_the_host_did(table):
    gap = table["idle_gaps"][0]
    assert 5.0 < gap["ms"] < 5.5
    host = gap["host_ms"]
    # it began inside the loss read and ran on through the next call's
    # data_put and dispatch, and the stretch of the call between them
    for name in ("profile::read_loss", "train_step::data_put",
                 "train_step::dispatch", "train_step::step"):
        assert host[name] > 0.3, name
    assert abs(sum(host.values()) - gap["ms"]) < 1e-6
    assert host.get(dt.NO_SPAN, 0.0) < 0.1 * gap["ms"]
    assert gap["ended_by"].startswith("slice-start")
    assert [g["ms"] for g in table["idle_gaps"]] == sorted(
        (g["ms"] for g in table["idle_gaps"]), reverse=True)


def test_steps_and_skip(recorded):
    one = dt.reduce_capture(recorded, recorded["ring"],
                            [recorded["program_text"]], skip=1)
    assert one["steps"] == 1 and 118 < one["busy_ms"] < 120
    assert one["idle_ms"] < 0.5              # the gap lay before it
    said = dt.reduce_capture(recorded, (), (), steps=4)
    assert said["steps"] == 4 and 55 < said["op_ms"] < 65
    # without the program's text nothing has a phase, and without the
    # ring a gap is nobody's
    assert [r["phase"] for r in said["by_phase"]] == ["unscoped"]
    assert set(said["idle_gaps"][0]["host_ms"]) == {dt.NO_SPAN}
    assert one["names"] is None and said["names"].startswith("none: ")
    assert len(said["idle_gaps"]) == dt._LONGEST_GAPS


# -- hand-made captures ---------------------------------------------------------

_TEXT = """HloModule jit_mx_train_step

%fused_computation.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8] parameter(0)
  %dot.1 = f32[8] dot(%p0, %p0), metadata={op_name="jit(mx_train_step)/transpose(jvp(forward))/net0/dense0/dot_general"}
  ROOT %sub.1 = f32[8] subtract(%p0, %dot.1), metadata={op_name="jit(mx_train_step)/optimizer_update/sub"}
}

%fused_computation.2 (p0: f32[8]) -> f32[8] {
  %p0.1 = f32[8] parameter(0)
  ROOT %tanh.1 = f32[8] tanh(%p0.1), metadata={op_name="jit(mx_train_step)/jvp(forward)/net0/dense0/tanh"}
}

%branch (p: f32[8]) -> f32[8] {
  %p = f32[8] parameter(0)
  %constant.1 = f32[] constant(0)
  ROOT %broadcast.9 = f32[8] broadcast(%constant.1), dimensions={}
}

ENTRY %main (w: f32[8]) -> f32[8] {
  %w = f32[8] parameter(0), metadata={op_name="pvals['w']"}
  %copy-start.1 = (f32[8], f32[8], u32[]) copy-start(%w)
  %copy-done.1 = f32[8] copy-done(%copy-start.1)
  %fusion.2 = f32[8] fusion(%copy-done.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(mx_train_step)/jvp(forward)/net0/dense0/tanh"}
  %copy.3 = f32[8] copy(%fusion.2)
  %cond.1 = f32[8] conditional(%copy.3), branch_computations={%branch, %branch}, metadata={op_name="jit(mx_train_step)/jvp(forward)/net0/moe0/cond"}
  ROOT %divide_subtract_fusion.7 = f32[8] fusion(%cond.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(mx_train_step)/optimizer_update/sub"}
}
"""


def _capture(events, modules=(("jit_mx_train_step(1)", 0, 1000),),
             sync=None):
    names = sorted({e[0] for e in events})
    return {"names": names,
            "ops": {_PLANE: [[names.index(n), s, d] for n, s, d in events]},
            "modules": {_PLANE: [list(m) for m in modules]}, "sync": sync}


def test_program_index_names_what_the_compiler_left_nameless():
    names, fused = dt.program_index(_TEXT)
    assert fused["fused_computation.1"] == {"backward", "optimizer_update"}
    assert fused["fused_computation.2"] == {"forward"}
    # a prefetched weight goes with what reads it, a copy with what made
    # its operand, a branch's untouched zeros with the conditional
    assert names["copy-done.1"].endswith("dense0/tanh")
    assert names["copy-start.1"].endswith("dense0/tanh")
    assert names["copy.3"].endswith("dense0/tanh")
    assert names["broadcast.9"].endswith("moe0/cond")


def test_a_two_phase_fusion_lands_in_the_combined_row():
    capture = _capture([
        ("%copy-done.1 = f32[8] copy-done(...)", 0, 100),
        ("%fusion.2 = f32[8] fusion(...), kind=kLoop, "
         "calls=%fused_computation.2", 100, 200),
        ("%divide_subtract_fusion.7 = f32[8] fusion(...), kind=kLoop, "
         "calls=%fused_computation.1", 300, 600)])
    table = dt.reduce_capture(capture, (), [_TEXT])
    assert {r["phase"]: r["ms"] for r in table["by_phase"]} == {
        "backward+optimizer_update": 600e-6, "forward": 300e-6}
    assert {r["scope"]: r["ms"] for r in table["by_scope"]} == {
        "optimizer_update": 600e-6, "forward/net0/dense0": 300e-6}
    assert [(r["kernel"], r["calls"]) for r in table["by_kernel"]] == [
        ("divide_subtract_fusion", 1.0), ("fusion", 1.0),
        ("copy-done", 1.0)]
    # the text belongs to the step executable: the same names inside
    # another executable's launch are not looked up
    other = dict(capture, modules={_PLANE: [["jit_other(2)", 0, 1000]]})
    assert [r["phase"] for r in dt.reduce_capture(other, (), [_TEXT])[
        "by_phase"]] == ["unscoped"]


_FUSION = ("%fusion.2 = f32[8] fusion(...), kind=kLoop, "
           "calls=%fused_computation.2")


def test_a_text_without_the_phases_is_called_stale():
    """A step loaded from a compile-cache entry that an older build wrote
    carries that build's names: none of the phases. The table says so
    instead of reading 100 % unscoped with no word of why."""
    old = _TEXT
    for scope in ("jvp(forward)/", "transpose(jvp(forward))/",
                  "optimizer_update/", "jvp(loss)/", "transpose(jvp(loss))/"):
        old = old.replace(scope, "")
    assert old != _TEXT
    table = dt.reduce_capture(_capture([(_FUSION, 100, 200)]), (), [old])
    assert [r["phase"] for r in table["by_phase"]] == ["unscoped"]
    assert table["names"].startswith("stale: ")
    assert "clear the compile cache" in table["names"]
    assert "  names: stale: " in dt.render(table)
    fresh = dt.reduce_capture(_capture([(_FUSION, 100, 200)]), (), [_TEXT])
    assert fresh["names"] is None and "names:" not in dt.render(fresh)


def test_two_live_steps_are_indexed_apart_and_called_ambiguous():
    """Both executables are `jit_mx_train_step` and both have a
    `fusion.2`: in one index the later text would overwrite the earlier.
    The text that holds most of the capture's instructions is read,
    whichever comes first, and the table says that it had to choose."""
    other = (_TEXT.replace("jvp(forward)/net0/dense0", "jvp(loss)/loss9")
             .replace("divide_subtract_fusion.7", "divide_subtract_fusion.8")
             .replace("copy-done.1", "copy-done.5"))
    capture = _capture([
        ("%copy-done.1 = f32[8] copy-done(...)", 0, 100),
        (_FUSION, 100, 200),
        ("%divide_subtract_fusion.7 = f32[8] fusion(...), kind=kLoop, "
         "calls=%fused_computation.1", 300, 600)])
    for texts in ([_TEXT, other], [other, _TEXT]):
        table = dt.reduce_capture(capture, (), texts)
        assert {r["phase"]: r["ms"] for r in table["by_phase"]} == {
            "backward+optimizer_update": 600e-6, "forward": 300e-6}
        assert table["names"].startswith("ambiguous: 2 live TrainSteps")
    # one program asked for twice is one program
    assert dt.reduce_capture(capture, (), [_TEXT, None, _TEXT])[
        "names"] is None


def test_an_enclosing_op_counts_its_self_time():
    capture = _capture([
        ("%cond.1 = f32[8] conditional(...)", 100, 500),
        ("%broadcast.9 = f32[8] broadcast(...)", 150, 100),
        ("%broadcast.9 = f32[8] broadcast(...)", 300, 100),
        ("%fusion.2 = f32[8] fusion(...), calls=%fused_computation.2",
         700, 100)])
    table = dt.reduce_capture(capture, (), [_TEXT])
    kernels = {r["kernel"]: r["ms"] for r in table["by_kernel"]}
    assert kernels == {"cond": 300e-6, "broadcast": 200e-6,
                       "fusion": 100e-6}
    assert table["op_ms"] == table["busy_ms"] == 600e-6
    assert abs(table["idle_ms"] - 400e-6) < 1e-12


def test_a_gap_through_three_spans_is_split_across_all_three():
    """The device waits from 200 to 800 ns; the host was reading the loss
    when it began, then put the data, then dispatched. The ring is on
    perf_counter's clock, 5 us ahead of the capture's."""
    capture = _capture(
        [("%fusion.2 = f32[8] fusion(...)", 0, 200),
         ("%fusion.2 = f32[8] fusion(...)", 800, 200)],
        sync=[1000, 6000])

    def span(name, start_ns, end_ns):
        return {"ph": "X", "name": name, "ts": (start_ns + 5000) / 1e3,
                "dur": (end_ns - start_ns) / 1e3}

    ring = [span("user::read_loss", -900, 350),
            span("train_step::step", 400, 900),
            span("train_step::data_put", 450, 550),
            span("train_step::dispatch", 600, 900),
            span("host::gc", 620, 700),
            {"ph": "i", "name": "marker", "ts": 5.5}]
    gap = dt.reduce_capture(capture, ring)["idle_gaps"][0]
    assert gap["ms"] == 600e-6 and gap["ended_by"] == "fusion.2"
    want = {"user::read_loss": 150, dt.NO_SPAN: 50,
            "train_step::step": 50 + 50, "train_step::data_put": 100,
            "train_step::dispatch": 20 + 100, "host::gc": 80}
    assert {k: round(v * 1e6) for k, v in gap["host_ms"].items()} == want


@pytest.mark.parametrize("op_name,phase,path", [
    ("jit(mx_train_step)/jvp(forward)/net0/dense0/dot_general",
     "forward", ("net0", "dense0")),
    ("jit(mx_train_step)/transpose(jvp(forward))/net0/dense0/mul",
     "backward", ("net0", "dense0")),
    ("jit(mx_train_step)/jvp(loss)/loss0/jit(log_softmax)/exp",
     "loss", ("loss0", "jit(log_softmax)")),
    ("jit(mx_train_step)/transpose(jvp(loss))/loss0/neg", "backward",
     ("loss0",)),
    ("jit(mx_train_step)/optimizer_update/sqrt", "optimizer_update", ()),
    ("jit(mx_train_step)/shard_map/backward/all_gather", "backward", ()),
    ("jit(mx_train_step)/forward/net0/relu", "forward", ("net0",)),
    ("jit(mx_train_step)/jvp(forward)/a/b;jit(mx_train_step)/jvp(loss)/c",
     "forward", ("a",)),
    ("pvals['w']", "unscoped", ()),
    ("jit(convert_element_type)/convert_element_type", "unscoped", ()),
])
def test_classify(op_name, phase, path):
    assert dt.classify(op_name) == (phase, path)


def test_dumps_has_a_device_section_once_a_capture_is_stopped(
        recorded, monkeypatch):
    class Step:
        def program_text(self):
            return recorded["program_text"]

    state = mx.profiler._state
    monkeypatch.setitem(state, "capture_ready", True)
    monkeypatch.setitem(state, "capture", recorded)
    monkeypatch.setitem(state, "table", None)
    monkeypatch.setattr(ts_mod, "_live_steps", [Step()])
    monkeypatch.setattr(
        trace, "chrome_trace", lambda: {"traceEvents": recorded["ring"]})
    table = mx.profiler.device_table(depth=2, skip=1)
    assert table["steps"] == 1 and len(table["by_phase"]) >= 6
    text = mx.profiler.dumps()
    assert "Device (2 steps on 1 device(s); ms a step)" in text
    for word in ("by executable", "by phase", "by scope", "by kernel",
                 "backward+optimizer_update", "mx_flash_bwd",
                 "longest idle gaps", "train_step::data_put", "Host ("):
        assert word in text, word
    payload = json.loads(mx.profiler.dumps(format="json"))
    assert payload["device"]["steps"] == 2
    assert payload["device"]["by_kernel"][0]["kernel"] == "fusion"
    assert payload["host"]["intervals"] == 1
    # a new capture forgets the one before
    monkeypatch.setitem(state, "capture_ready", False)
    assert mx.profiler.device_table() is None


def test_a_capture_on_the_cpu_carries_the_clock_mark(tmp_path):
    """No device line here, so no table; but the one annotation that
    ties perf_counter to the capture's clock is written and found."""
    net = gluon.nn.Dense(2, in_units=3)
    net.initialize()
    step = TrainStep(net, gluon.loss.L2Loss(), optimizer="sgd")
    x = np.ones((8, 3), "float32")
    y = np.ones((8, 2), "float32")
    step(x, y)
    mx.profiler.set_config(filename=str(tmp_path / "capture"))
    mx.profiler.set_state("run")
    try:
        float(step(x, y))
    finally:
        mx.profiler.set_state("stop")
    assert mx.profiler.device_table() is None
    capture = dt.load_capture(dt.find_capture(str(tmp_path / "capture")))
    assert capture["ops"] == {} and capture["names"] == []
    at_ns, perf_ns = capture["sync"]
    assert perf_ns > 0 and at_ns >= 0
    # the step's spans lie after the mark on both clocks
    spans = dt._host_spans(trace.chrome_trace()["traceEvents"],
                           capture["sync"])
    assert any(name == "train_step::dispatch" and start > at_ns
               for start, _, name in spans)
    mx.profiler.set_config(filename="profile_output")
