"""chipbench end to end on the CPU at tiny sizes: every runner and reader,
the contract's last line, the refusal of bad names, and a cell, a runner
and a layer metric added as new files with no edit to what is there. The
command itself has no CPU mode (it fails without a TPU), so the tests call
`harness.run_cell` on a temporary copy whose files hold tiny sizes. No
import here touches the TPU library.
"""
import collections
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, _ROOT)
from chipbench import harness  # noqa: E402

_TINY_CFG = {
    "resnet50_v1": {"layers": [1, 1], "channels": [8, 16, 32],
                    "classes": 10, "image": [3, 32, 32]},
    "ptb_lstm_large": {"vocab": 50, "embed": 16, "hidden": 16,
                       "num_layers": 2, "dropout": 0.0, "bptt": 5},
}
_TINY_WL = {"batch": 8, "pool_batches": 3, "read_every": 2,
            "trace_steps": 4}


def _dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


def _load(path):
    with open(path) as f:
        return json.load(f)


@pytest.fixture()
def root(tmp_path):
    """A copy of the benchmark whose configurations and cells are tiny,
    with a peak for the CPU so that the readers have one to divide by."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(_ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = _load(os.path.join(_ROOT, "BENCHMARK.json"))
    # Every cell and metric that has its files is rehearsed, declared in
    # BENCHMARK.json or kept beside it for a later PR.
    cb = os.path.join(root, "chipbench")
    have = {c["name"] for c in bench["workloads"]}
    for name in sorted(os.listdir(os.path.join(cb, "workloads"))):
        wl = _load(os.path.join(cb, "workloads", name))
        if name[:-5] not in have:
            bench["workloads"].append({
                "name": name[:-5], "config": wl["config"],
                "traffic": wl["traffic"], "chips": wl["chips"],
                "why": wl["why"]})
    have = {m["name"] for m in bench["per_layer"]}
    for name in sorted(os.listdir(os.path.join(cb, "layer_metrics"))):
        spec = _load(os.path.join(cb, "layer_metrics", name))
        if name[:-5] not in have:
            entry = {k: spec[k] for k in ("unit", "better", "source",
                                          "layer", "moves")}
            if "cells" in spec:
                entry["workloads"] = spec["cells"]
            bench["per_layer"].append(dict(entry, name=name[:-5]))
    _dump(os.path.join(root, "BENCHMARK.json"), bench)
    for conf in bench["configs"]:
        path = os.path.join(root, conf["file"])
        cfg = _load(path)
        cfg.update(_TINY_CFG[conf["name"]])
        cfg["check"]["classes"] = cfg.get("classes") or cfg["vocab"]
        _dump(path, cfg)
    for name in os.listdir(os.path.join(root, "chipbench", "workloads")):
        path = os.path.join(root, "chipbench", "workloads", name)
        _dump(path, dict(_load(path), **_TINY_WL))
    peaks = os.path.join(root, "chipbench", "peaks.json")
    _dump(peaks, dict(_load(peaks), cpu={"bf16_flops_per_s": 1e12}))
    return root


def _run(root, cell, trace, devices=None, seed=2 ** 31 + 11, seconds=0.2):
    lines = []
    bench = harness.load_bench(root)
    result = harness.run_cell(root, bench, cell, seed, seconds, trace,
                              devices or jax.devices()[:1],
                              time.perf_counter(), say=lines.append)
    return result, [json.loads(line) for line in lines]


_CELLS = ["resnet50-train-b256", "ptb-lstm-train-b1024",
          "resnet50-gluon-b32"]


@pytest.mark.parametrize("cell", _CELLS)
def test_cell_untraced_line_holds_the_contracts_keys(root, cell):
    result, lines = _run(root, cell, trace=0)
    assert lines[-1]["problems"] == [], lines[-1]
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device", "checks"}
    # every compared number beside its limits, as the line's last key
    assert list(result)[-1] == "checks"
    assert {"compiles_in_window", "masters_moved_share",
            "loss_gap_to_reference", "first_loss_over_ln_classes",
            "same_batch_loss_fall"} <= set(result["checks"])
    # the logits by one measure: bf16 cells whose configuration states
    # it, against the reference's own rounding; else the largest error
    assert len({"logits_rel_err", "logits_rms_over_bf16_operands"}
               & set(result["checks"])) == 1
    # the loss fell on a pool batch read twice, in every cell
    assert result["checks"]["same_batch_loss_fall"]["value"] > 0
    for entry in result["checks"].values():
        assert {"value"} < set(entry) <= {"value", "above", "below"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2 and result["attempted"] % 2 == 0
    assert set(result["metrics"]) == {"train_rate", "setup_s"}
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["device"]["platform"] == "cpu"
    assert lines[-1]["compiles_in_window"] == 0
    json.dumps(result)


@pytest.mark.parametrize("cell", _CELLS)
def test_cell_traced_reports_its_per_layer_metrics(root, cell):
    result, lines = _run(root, cell, trace=1)
    assert result["correct"] is True, lines[-2:]
    assert result["attempted"] == 4
    got = set(result["metrics"])
    # The CPU has no device plane: the device_trace metrics stay out of
    # the line (a reader that finds nothing returns nothing), the host
    # and program ones are read.
    assert {"import_s.setup", "first_step_s.setup"} <= got
    if cell == "resnet50-gluon-b32":
        assert "imperative_step_ms.train" in got
        assert "host_step_ms.train" not in got
    else:
        assert {"host_step_ms.train", "data_put_ms.train"} <= got
        assert "imperative_step_ms.train" not in got
    assert not got & {"device_step_ms.train", "device_mfu_pct.train",
                      "device_programs_per_step.train",
                      "device_idle_pct.train"}
    bench = harness.load_bench(root)
    units = {m["name"]: m["unit"] for m in bench["per_layer"]}
    for name, m in result["metrics"].items():
        assert m["unit"] == units[name] and m["value"] >= 0
    assert "busy_s" in result["device"] and "window_s" in result["device"]
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_four_virtual_chips(root):
    """dp over four devices through the same runner: `chips` 4, a batch
    that divides by 4, and a cell added as data alone."""
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    cell = dict(bench["workloads"][0], name="resnet50-train-dp4-b1024",
                traffic="train-dp4-b1024", chips=4)
    bench["workloads"].append(cell)
    _dump(os.path.join(root, "BENCHMARK.json"), bench)
    wl = _load(os.path.join(root, "chipbench", "workloads",
                            "resnet50-train-b256.json"))
    _dump(os.path.join(root, "chipbench", "workloads",
                       cell["name"] + ".json"),
          dict(wl, chips=4, traffic=cell["traffic"]))
    result, lines = _run(root, cell["name"], trace=0,
                         devices=jax.devices()[:4])
    assert result["correct"] is True, lines[-1]
    with pytest.raises(ValueError, match="does not divide"):
        _run(root, cell["name"], trace=0, devices=jax.devices()[:3])


def test_new_cell_runner_and_metric_are_found_as_new_files(root):
    """A later PR adds files and entries and edits none: a runner, a
    reader, a layer metric and a cell that uses them."""
    bench_path = os.path.join(root, "BENCHMARK.json")
    cb = os.path.join(root, "chipbench")
    before = {os.path.join(dp, p): open(os.path.join(dp, p), "rb").read()
              for dp, _, files in os.walk(cb) for p in files}
    with open(os.path.join(cb, "runners", "counting.py"), "w") as f:
        f.write("from chipbench.runners import train_step\n"
                "class Runner(train_step.Runner):\n"
                "    def step(self):\n"
                "        self.model_calls = getattr(self, 'model_calls', 0) + 1\n"
                "        return super().step()\n"
                "setup = Runner\n")
    with open(os.path.join(cb, "readers", "constant.py"), "w") as f:
        f.write("def read(run, value):\n    return value\n")
    _dump(os.path.join(cb, "layer_metrics", "answer.train.json"),
          {"layer": "device", "unit": "count", "better": "higher",
           "source": "program_counter", "moves": "train_rate",
           "reader": "constant", "args": {"value": 42.0},
           "cells": ["lstm-counting"]})
    wl = _load(os.path.join(cb, "workloads", "ptb-lstm-train-b1024.json"))
    _dump(os.path.join(cb, "workloads", "lstm-counting.json"),
          dict(wl, runner="counting", traffic="counting"))
    bench = _load(bench_path)
    bench["workloads"].append({"name": "lstm-counting",
                               "config": "ptb_lstm_large",
                               "traffic": "counting", "chips": 1,
                               "why": "a test's cell"})
    bench["per_layer"].append({"name": "answer.train", "unit": "count",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "device", "moves": "train_rate",
                               "workloads": ["lstm-counting"]})
    _dump(bench_path, bench)
    result, _ = _run(root, "lstm-counting", trace=1)
    assert result["metrics"]["answer.train"] == {"value": 42.0,
                                                 "unit": "count"}
    other, _ = _run(root, "ptb-lstm-train-b1024", trace=1)
    assert "answer.train" not in other["metrics"]
    for path, data in before.items():
        assert open(path, "rb").read() == data


@pytest.mark.parametrize("field,value", [
    ("name", "has space"), ("name", "slash/name"), ("name", "x" * 65),
    ("name", "-leading"), ("unit", "tokens per second"), ("unit", ""),
    ("unit", "µs"),
])
def test_bad_name_or_unit_is_refused(root, field, value):
    path = os.path.join(root, "BENCHMARK.json")
    bench = _load(path)
    bench["per_layer"][0][field] = value
    _dump(path, bench)
    with pytest.raises(harness.BenchError, match="bad"):
        harness.load_bench(root)


def test_unknown_cell_module_and_device_kind_are_errors(root):
    bench = harness.load_bench(root)
    with pytest.raises(harness.BenchError, match="no cell"):
        harness.cell_files(root, bench, "nope")
    with pytest.raises(harness.BenchError, match="bad"):
        harness.load_module(root, "runners", "../harness")
    peaks = os.path.join(root, "chipbench", "peaks.json")
    _dump(peaks, {"TPU v5 lite": _load(peaks)["TPU v5 lite"]})
    with pytest.raises(harness.BenchError, match="no peaks"):
        _run(root, "ptb-lstm-train-b1024", trace=0)


def test_a_wrong_result_is_reported_not_hidden(root):
    """The comparison with the reference decides `correct`: a tolerance
    the system cannot meet makes the run incorrect, with the reason on
    the line before the last."""
    path = os.path.join(root, "chipbench", "configs", "ptb_lstm_large.json")
    cfg = _load(path)
    cfg["check"]["tolerance"]["float32"] = 0.0
    _dump(path, cfg)
    result, lines = _run(root, "ptb-lstm-train-b1024", trace=0)
    assert result["correct"] is False
    assert any("differ from the reference" in p
               for p in lines[-1]["problems"])


_NAN = float("nan")


@pytest.mark.parametrize("reads,classes,problem", [
    ([(0, 2.3), (2, 2.0), (3, 1.9)], 1000, "not near ln"),
    ([(0, 2.3), (2, 2.0), (3, 2.4)], 10, "did not fall"),
    ([(0, 2.3), (3, _NAN)], 10, "non-finite"),
    ([(0, 2.3), (3, 2.0)], 10, None),
    # the window's last read above its first is no fault: its batches
    # differ by as much as the loss falls; the batch read twice decides
    ([(0, 2.3), (2, 2.0), (3, 2.29), (4, 2.4)], 10, None),
    # of the batches read twice, the one read furthest apart decides
    ([(0, 2.3), (2, 2.2), (3, 2.35), (8, 2.1)], 10, None),
    ([(0, 2.3), (2, 2.2), (3, 2.25), (8, 2.21)], 10, "did not fall"),
    # a fall within the limit is no fall: what moves the loss without an
    # update (dropout's masks, a router's selection bias) moves it so far
    ([(0, 2.3), (3, 2.2995)], 10, "did not fall"),
    ([(0, 2.3), (1, 2.0), (2, 1.9)], 10, "no pool batch was read twice"),
])
def test_loss_checks(reads, classes, problem):
    """`check_losses` over (call, loss) reads of a pool of 3 batches."""
    checks = {}
    chk = {"classes": classes, "loss_fall_min": 0.004}
    problems = harness.check_losses(reads, 3, chk, checks)
    if problem is None:
        assert problems == []
        assert checks["same_batch_loss_fall"]["above"] == 0.004
        assert checks["same_batch_loss_fall"]["value"] > 0.004
    else:
        assert len(problems) == 1 and problem in problems[0]


class _CountingRunner:
    """A runner of three pool batches whose loss is its call's number,
    three calls in, as set-up leaves one."""
    pool = [None] * 3

    def __init__(self):
        self.calls = 3

    def step(self):
        self.calls += 1
        return float(self.calls - 1)

    def read_loss(self, loss):
        return loss


@pytest.mark.parametrize("seen,closes_at", [
    ({0, 2}, 4),        # set-up read batches 0 and 2: call 6 reads 0 again
    (set(), 8),         # calls 4, 6, 8, 10 read batches 1, 0, 2, 1
])
def test_plain_window_closes_once_a_batch_is_read_twice(seen, closes_at):
    win = harness._plain_window(_CountingRunner(), 0.0, 2, seen)
    assert win["attempted"] == closes_at
    assert [c for c, _ in win["reads"]] == list(range(4, 4 + closes_at, 2))


def test_no_tpu_no_result():
    """The command exits non-zero and prints no result line where JAX
    finds no TPU, and for a cell that BENCHMARK.json does not hold."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for cell in ("resnet50-train-b256", "no-such-cell"):
        proc = subprocess.run(
            [sys.executable, "chipbench/run.py", "--workload", cell,
             "--seed", "1", "--seconds", "1", "--trace", "0"],
            cwd=_ROOT, env=env, capture_output=True, text=True, timeout=240)
        assert proc.returncode != 0
        assert "correct" not in proc.stdout


# -- the plain references ---------------------------------------------------

def _reference_sgd_steps(model, cfg, params, batches, opt):
    """Three steps of the plain reference: `jax.grad` of the reference's
    training forward and MXNet's SGD (rescale 1; clip each element, then
    add wd * weight; momentum buffer `m = mu * m - lr * g`, `w += m`)."""
    lr, mu = opt["learning_rate"], opt.get("momentum", 0.0)
    wd, clip = opt.get("wd", 0.0), opt.get("clip_gradient")
    frozen = {k: v for k, v in params.items() if "running_" in k}
    train = {k: v for k, v in params.items() if "running_" not in k}
    mom = {k: jnp.zeros_like(v) for k, v in train.items()}

    def loss_of(tr, x, y):
        full = type(params)((k, tr.get(k, frozen.get(k))) for k in params)
        return model.reference_loss(
            model.reference_forward(cfg, full, x, train=True), y)

    losses = []
    for x, y in batches:
        loss, grads = jax.value_and_grad(loss_of)(train, x, y)
        losses.append(float(loss))
        for k in train:
            g = grads[k]
            if clip:
                g = jnp.clip(g, -clip, clip)
            g = g + wd * train[k]
            mom[k] = mu * mom[k] - lr * g
            train[k] = train[k] + mom[k]
    return losses


@pytest.mark.parametrize("cell", ["resnet50-train-b256",
                                  "ptb-lstm-train-b1024"])
def test_three_train_steps_equal_the_plain_reference(root, cell):
    """Three `TrainStep` steps against three steps of the reference, from
    the same seeded weights on the same pool, in fp32 (`dtype` null in the
    ResNet cell here) and at dropout 0 (the reference has no dropout).
    Tolerance 2e-4 relative on each loss: both sides are fp32 on the CPU
    and differ by the order of their reductions only, which three steps of
    momentum SGD through BatchNorm carry to about 1e-5; a wrong gate order,
    a missed wd or clip, or batch statistics taken wrongly move the second
    loss by far more."""
    bench = harness.load_bench(root)
    wl_path = os.path.join(root, "chipbench", "workloads", cell + ".json")
    _dump(wl_path, dict(_load(wl_path), dtype=None))
    _, wl, cfg = harness.cell_files(root, bench, cell)
    model = harness.load_module(root, "models", cfg["model"])
    runner = harness.load_module(root, "runners", wl["runner"]).setup(
        cfg, wl, 7, jax.devices()[:1], model)
    # Deferred shapes are settled by a forward; the values are the seed's.
    from mxnet_tpu import autograd
    from mxnet_tpu.ndarray import NDArray
    with autograd.pause(train_mode=False):
        runner.net(NDArray(runner.pool[0][0]))
    # Host copies: the step donates the device buffers it starts from.
    start = collections.OrderedDict(
        (name, np.asarray(p.data()._data))
        for name, p in runner.net.collect_params().items())
    got = [runner.read_loss(runner.step()) for _ in range(3)]
    want = _reference_sgd_steps(model, cfg, start, runner.pool[:3],
                                cfg["optimizer"]["params"])
    np.testing.assert_allclose(got, want, rtol=2e-4)
    assert got[0] != got[1]


def test_resnet50_operations_and_structure():
    """The FLOPs come from the layers' shapes: a 224x224 forward of the
    published configuration is 2 x 4.09e9 operations within 3 %, and a
    training step three times that. The configuration's sizes give the
    zoo's resnet50_v1, parameter for parameter."""
    from chipbench.models import resnet
    from mxnet_tpu.gluon.model_zoo import vision

    cfg = _load(os.path.join(_ROOT, "chipbench", "configs",
                             "resnet50_v1.json"))
    assert abs(2 * resnet.forward_macs(cfg) / (2 * 4.09e9) - 1) < 0.03
    assert resnet.flops_per_item(cfg) == 6 * resnet.forward_macs(cfg)
    ours = vision.ResNetV1(vision.BottleneckV1, cfg["layers"],
                           cfg["channels"], classes=cfg["classes"])
    zoo = vision.resnet50_v1(classes=1000)
    assert [p.shape for p in ours.collect_params().values()] == \
        [p.shape for p in zoo.collect_params().values()]


def test_lstm_operations():
    """306 MFLOP a token at the published sizes: 2 layers of 4 x 1500 x
    3000 gate products and the 1500 x 10000 decoder, two operations per
    multiply-accumulate, forward once and backward twice."""
    from chipbench.models import word_lm

    cfg = _load(os.path.join(_ROOT, "chipbench", "configs",
                             "ptb_lstm_large.json"))
    assert word_lm.flops_per_item(cfg) == 6 * (2 * 4 * 1500 * 3000
                                               + 1500 * 10000)


def test_benchmark_json_agrees_with_the_data_files():
    """Every per-layer metric has its file with the same layer, unit,
    source and arrow; every cell its workload file; every configuration
    its file with the listed `reduced`."""
    bench = harness.load_bench(_ROOT)
    for m in bench["per_layer"]:
        spec = _load(os.path.join(_ROOT, "chipbench", "layer_metrics",
                                  m["name"] + ".json"))
        for key in ("layer", "unit", "better", "source", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert spec.get("cells") == m.get("workloads")
        assert os.path.exists(os.path.join(_ROOT, "chipbench", "readers",
                                           spec["reader"] + ".py"))
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
    for cell in bench["workloads"]:
        _, wl, cfg = harness.cell_files(_ROOT, bench, cell["name"])
        assert wl["traffic"] == cell["traffic"] and wl["why"] == cell["why"]
    for conf in bench["configs"]:
        assert _load(os.path.join(_ROOT, conf["file"]))["reduced"] == \
            conf["reduced"]
