"""chipbench/trace_reduce.py on hand-made rows and on a small recorded
trace (trace_r50_8steps.json beside this file; its `note` says how it was
cut): busy union, idle share, per-op sums with shortened names, gap labels
and programs per step."""
import json
import os
import sys

import pytest

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(_HERE)))
from chipbench import trace_reduce as tr  # noqa: E402
from chipbench.readers import (device_idle_pct, device_mfu_pct,  # noqa: E402
                               device_step_ms, host_span_ms,
                               programs_per_step)

DEV, HOST = "/device:TPU:0", "/host:CPU"


def _op(name, start, dur, plane=DEV):
    return [plane, "XLA Ops", tr.short_name(name), start, dur]


def _hand_made():
    """A window of 1000 ns with two steps. The device runs a [100, 300),
    b [250, 400) (overlapping a), then waits until c [600, 900); a starts
    before the window in no case, d lies outside it."""
    return [
        [HOST, "main/1", "chipbench::window", 1000, 1000],
        [HOST, "main/1", "chipbench::step", 1000, 210],
        [HOST, "main/1", "chipbench::step", 1500, 300],
        [HOST, "main/1", "chipbench::forward", 1510, 100],
        [DEV, "XLA Modules", "jit_step(1)", 1100, 300],
        [DEV, "XLA Modules", "jit_convert_element_type(2)", 1590, 5],
        [DEV, "XLA Modules", "jit_step(1)", 1600, 300],
        [DEV, "XLA Modules", "jit_step(1)", 2500, 300],
        _op("%fusion.1 = f32[8]{0} fusion(%p0), kind=kLoop", 1100, 200),
        _op("%copy.2 = f32[8]{0} copy(%fusion.1)", 1250, 150),
        _op("%fusion.1 = f32[8]{0} fusion(%p0), kind=kLoop", 1600, 300),
        _op("%late.9 = f32[8]{0} add(%a, %b)", 2500, 100),
    ]


def test_short_name():
    assert tr.short_name("%fusion.1 = f32[8]{0} fusion(%p0)") == "fusion.1"
    assert tr.short_name("while.18") == "while.18"


def test_busy_union_merges_clips_and_drops():
    assert tr.busy_union([(5, 9), (0, 3), (2, 4), (20, 30), (8, 12)],
                         1, 25) == [[1, 4], [5, 12], [20, 25]]
    assert tr.busy_union([(0, 1), (30, 40)], 1, 25) == []


def test_hand_made_window():
    got = tr.reduce_trace(_hand_made())
    assert got["window_ns"] == 1000 and got["steps"] == 2
    assert got["devices"] == 1
    assert got["busy_ns"] == 300 + 300          # [1100,1400) + [1600,1900)
    assert got["programs"] == 3                 # the fourth is outside
    assert got["device_ops"] == [["fusion.1", 500e-9], ["copy.2", 150e-9]]
    # Gaps: [1000,1100) inside the first step span; [1400,1600) starts
    # with no span open; [1900,2000) runs to the window's end.
    assert got["idle_gaps"][0] == ["before fusion.1", 200e-9]
    assert sorted(got["idle_gaps"][1:]) == [
        ["before window end", 100e-9], ["chipbench::step", 100e-9]]
    assert got["host_ms"]["chipbench::step"] == [210e-6, 300e-6]
    assert got["host_ms"]["chipbench::forward"] == [100e-6]


def test_innermost_span_labels_a_gap():
    rows = _hand_made()
    spans = [("train_step::dispatch", 1390, 1450),
             ("train_step::step", 1380, 1700)]
    got = tr.reduce_trace(rows, host_spans=spans)
    assert got["idle_gaps"][0] == ["train_step::dispatch", 200e-9]


def test_two_devices_are_averaged():
    rows = _hand_made() + [
        _op("%fusion.1 = f32[8]{0} fusion(%p0)", 1100, 100,
            "/device:TPU:1"),
        ["/device:TPU:1", "XLA Modules", "jit_step(1)", 1100, 100]]
    got = tr.reduce_trace(rows)
    assert got["devices"] == 2
    assert got["busy_ns"] == (600 + 100) / 2
    assert got["programs"] == (3 + 1) / 2
    assert got["device_ops"][0] == ["fusion.1", (500 + 100) / 2 / 1e9]


def test_no_window_and_no_device():
    assert tr.reduce_trace([r for r in _hand_made()
                            if r[2] != "chipbench::window"]) is None
    got = tr.reduce_trace([r for r in _hand_made() if r[0] == HOST])
    assert got["devices"] == 0 and got["busy_ns"] == 0
    run = {"trace": got}
    assert device_step_ms.read(run) is None
    assert device_idle_pct.read(run) is None
    assert programs_per_step.read(run) is None
    assert host_span_ms.read(run, "chipbench::step") == pytest.approx(255e-6)


def test_readers_on_the_hand_made_window():
    run = {"trace": tr.reduce_trace(_hand_made()), "flops_per_item": 3e3,
           "items_per_step": 10, "chips": 1,
           "peak": {"bf16_flops_per_s": 1e12}}
    assert device_step_ms.read(run) == pytest.approx(300e-6)
    assert device_idle_pct.read(run) == pytest.approx(40.0)
    assert programs_per_step.read(run) == pytest.approx(1.5)
    # 3e4 operations in 300 ns are 1e11 a second: 10 % of the peak.
    assert device_mfu_pct.read(run) == pytest.approx(10.0)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(_HERE, "trace_r50_8steps.json")) as f:
        return json.load(f)["rows"]


def _brute_busy(rows, lo, hi):
    """Busy time by elementary segments: every stretch between two
    neighbouring event edges is busy where any op covers it."""
    ops = [(r[3], r[3] + r[4]) for r in rows
           if r[0] == DEV and r[1] == "XLA Ops"]
    edges = sorted({lo, hi} | {min(max(t, lo), hi) for op in ops
                               for t in op})
    return sum(b - a for a, b in zip(edges, edges[1:])
               if any(s <= a and b <= e for s, e in ops))


def test_recorded_trace(recorded):
    got = tr.reduce_trace(recorded)
    win = [r for r in recorded if r[2] == "chipbench::window"][0]
    lo, hi = win[3], win[3] + win[4]
    assert got["window_ns"] == win[4] and got["steps"] == 8
    assert got["devices"] == 1
    assert got["busy_ns"] == _brute_busy(recorded, lo, hi)
    assert 0 < got["busy_ns"] < got["window_ns"]
    # Each TrainStep call launches three programs: the step and the two
    # scalar conversions of lr and t.
    assert programs_per_step.read({"trace": got}) == 3.0
    mods = [r[2] for r in recorded if r[1] == "XLA Modules"]
    assert sum(m.startswith("jit_step(") for m in mods) == 8
    # Per-op sums carry shortened names, in falling order, and equal a
    # plain sum over the rows.
    names = [n for n, _ in got["device_ops"]]
    assert len(names) == 8 and all(" = " not in n and not n.startswith("%")
                                   for n in names)
    secs = [s for _, s in got["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    top = names[0]
    assert secs[0] == pytest.approx(sum(
        r[4] for r in recorded if r[1] == "XLA Ops"
        and r[2] == top) / 1e9)
    # Gaps add up to the idle time, the longest first, each with a label.
    idle = got["window_ns"] - got["busy_ns"]
    assert sum(s for _, s in got["idle_gaps"]) <= idle / 1e9 + 1e-12
    assert got["idle_gaps"][0][1] == max(s for _, s in got["idle_gaps"])
    assert all(label == "chipbench::step" or label.startswith("before ")
               for label, _ in got["idle_gaps"])
    assert device_idle_pct.read({"trace": got}) == pytest.approx(
        100.0 * idle / got["window_ns"])
